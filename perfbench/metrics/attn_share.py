"""How much of the decode step the marked attention layers are: chip 0's busy
time with the operations under any `ff.attn.` scope (the rotation, the
attention proper of window and full layers, the per-head gate; not the
projections) inside the slice's `ff.serve.decode` spans, over its busy time
under those spans. Silent where no such operation ran in a decode step."""
from perfbench.harness import program_spans, spec

SCOPE = "ff.attn."


def read(facts):
    spans = program_spans.of(facts)
    busy = spans.busy_under("ff.serve.decode") if spans is not None else None
    if not busy:
        return None
    ops = [(s, e) for _, s, e in spans._under(SCOPE)]
    seconds = spec.module("metrics", "moe_decode_roofline.py").seconds_in(
        spans, ops, "ff.serve.decode")
    return 100.0 * seconds / busy if seconds else None
