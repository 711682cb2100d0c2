"""The paged-decode kernel's share of its roofline on the WINDOW layers'
rings in the traced slice: the least time the chip could take to read the
live keys and values of one sliding layer, over the mean device time of the
kernel's calls on the ring pool. The kernel is known by the pool it reads,
`bf16[slots x ring pages, page size, key-value heads x head size]` (a window
layer's cache is a ring of the window rounded up to whole pages) among a
Mosaic call's operands; a slot at position p has min(p + 1, window) live
positions there. The count is of the work, not of the kernel: live keys and
values once a layer at 2 bytes, and 4 operations a live position, query head
and channel (q k^T and p v); bytes bound it. Silent when no Mosaic call ran
on that layout: the program keeps no ring, or the kernel no longer reads it."""
import statistics

from perfbench.harness import spec


def gqa_need(live_positions, heads, kv_heads, head_dim, bytes_per_value=2):
    """(operations, bytes) of one grouped-query layer's decode attention
    over `live_positions` (all occupied slots'): keys and values read once
    at the key-value heads' width, q k^T and p v for every query head."""
    return (4 * heads * head_dim * live_positions,
            2 * kv_heads * head_dim * live_positions * bytes_per_value)


def pool_layout(z, serving, positions):
    """The HLO spelling of the pool of `positions` a slot."""
    page = serving["page_size"]
    return "bf16[%d,%d,%d]" % (serving["slots"] * positions // page, page,
                               z["kv_heads"] * z["head_dim"])


def heads_of(z, attn_kind):
    """Query heads of the layers of the kind (they agree), or None."""
    heads = {k[2] for k in z.get("layer_types", ())
             if isinstance(k, tuple) and k[0] == attn_kind}
    return heads.pop() if len(heads) == 1 else None


def read_kind(facts, attn_kind, positions_of, live_of):
    """The roofline share of the kernel's calls on the pool of the layers of
    `attn_kind`: `positions_of(z, serving)` a slot's positions in that pool,
    `live_of(z, p)` the live ones of a slot at position p."""
    cell, traced = facts["cell"], facts["traced"]
    _, ref = spec.family(cell.config)
    z = ref.sizes(cell.config)
    heads = heads_of(z, attn_kind)  # None: another family's configuration
    if heads is None or not traced or not traced["positions"]:
        return None
    serving = facts["serving"]
    kernel = facts["trace"].kernel(
        pool_layout(z, serving, positions_of(z, serving)))
    if kernel is None:
        return None
    seconds, calls, _ = kernel
    live = statistics.mean(sum(live_of(z, p) for p in ps)
                           for ps in traced["positions"] if ps)
    flops, moved = gqa_need(live, heads, z["kv_heads"], z["head_dim"])
    peaks = facts["peaks"]
    least = max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds


def ring(z, serving):
    page = serving["page_size"]
    return min(serving["max_len"], -(-z["window"] // page) * page)


def read(facts):
    return read_kind(facts, "sliding_attention", ring,
                     lambda z, p: min(p + 1, z["window"]))
