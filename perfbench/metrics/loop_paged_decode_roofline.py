"""The paged-decode kernel's share of its roofline on a loop region's
stacked caches in the traced slice: the pool is every step's strip of every
slot, `bf16[slots x steps x max_len / page, page size, key-value heads x
head size]`, which `paged_decode_roofline` (one strip a slot) cannot read,
and a call reads ONE step's live keys and values of one layer through its
page table. The least time the chip could take for that (`paged_need`: bytes
bound it) over the mean device time of the kernel's calls on the stacked
pool under the scope `ff.loop` on chip 0; the live positions are those of
the slice's own iterations. Silent where the family has no loop or
no Mosaic call on that layout ran under that scope."""
import re
import statistics

from perfbench.harness import program_spans, spec, trace

_paged = spec.module("metrics", "paged_decode_roofline.py")
_decode = spec.module("metrics", "loop_decode_roofline.py")


def stacked_layout(z, serving):
    """The HLO spelling of the stacked pool."""
    page = serving["page_size"]
    return "bf16[%d,%d,%d]" % (
        serving["slots"] * z["steps"] * serving["max_len"] // page, page,
        z["kv_heads"] * z["head_dim"])


def read(facts):
    found, traced = _decode.family(facts), facts["traced"]
    spans = program_spans.of(facts)
    if found is None or spans is None or not traced \
            or not any(traced["positions"]):
        return None
    ref, cfg = found
    z = ref.sizes(cfg)
    layout = stacked_layout(z, facts["serving"])
    # the kernel's calls on chip 0, under the loop's scope
    calls = [e - s for name, s, e in spans._under(_decode.SCOPE)
             if layout in name and re.search(trace.MOSAIC_CALL, name)]
    if not calls:
        return None
    live = statistics.mean(sum(p + 1 for p in ps)
                           for ps in traced["positions"] if ps)
    flops, moved = _paged.paged_need(live, z["kv_heads"] * z["head_dim"])
    peaks = facts["peaks"]
    least = max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * len(calls) * least / (sum(calls) * 1e-9)
