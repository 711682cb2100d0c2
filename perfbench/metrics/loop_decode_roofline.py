"""A loop region's share of its roofline in the batched decode step: the
least time the chip could take for the region's part of one decode step
(every step's pass of the layers' matrices once, and every step's live keys
and values of every layer, the family's `loop_decode_bytes`; the operations,
its `forward_flops` without the head, where they bound it instead) over chip
0's device time under the scope `ff.loop` inside the slice's
`ff.serve.decode` spans, a step. The live positions are those of the slice's
own iterations. A loop that ran fewer steps, or read a copy of its caches,
reads low. Silent where the family has no loop or the slice no `ff.loop`
scope inside a decode span (the parent's program, another family)."""
import statistics

from perfbench.harness import program_spans, spec, trace

SCOPE = "ff.loop"


def loop_seconds(spans, span_name):
    """[(span, seconds of chip 0 busy under `ff.loop` inside it)] for the
    spans of `span_name` that hold any such operation."""
    ops = [(s, e) for _, s, e in spans._under(SCOPE)]
    out = []
    for span in (s for s in spans.spans if s.name == span_name):
        inside = trace._union([s, e] for s, e in ops
                              if span.start_ns <= s and e <= span.end_ns)
        if inside:
            out.append((span, sum(e - s for s, e in inside) * 1e-9))
    return out


def family(facts):
    """(reference module, configuration) of a looped family, or None."""
    cell = facts["cell"]
    _, ref = spec.family(cell.config)
    if not hasattr(ref, "loop_decode_bytes"):
        return None
    return ref, cell.config


def read(facts):
    found, traced = family(facts), facts["traced"]
    spans = program_spans.of(facts)
    if found is None or spans is None or not traced \
            or not any(traced["positions"]):
        return None
    ref, cfg = found
    steps = loop_seconds(spans, "ff.serve.decode")
    if not steps:
        return None
    peaks = facts["peaks"]
    least = statistics.mean(
        max(ref.forward_flops(cfg, ps, 0) / peaks["flops_bf16"],
            ref.loop_decode_bytes(cfg, [p + 1 for p in ps])
            / peaks["hbm_bytes_per_s"])
        for ps in traced["positions"] if ps)
    seconds = statistics.mean(s for _, s in steps)
    return 100.0 * least / seconds
