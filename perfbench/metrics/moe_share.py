"""How much of the decode step the expert layers are: chip 0's busy time with
the expert layers' operations (under the scope `ff.moe`: `moe_decode_roofline`
finds them) inside the slice's `ff.serve.decode` spans, over its busy time
under those spans. Silent where no such operation ran in a decode step."""
from perfbench.harness import program_spans, spec


def read(facts):
    spans = program_spans.of(facts)
    busy = spans.busy_under("ff.serve.decode") if spans is not None else None
    if not busy:
        return None
    roofline = spec.module("metrics", "moe_decode_roofline.py")
    seconds = roofline.seconds_in(spans, roofline.moe_ops(spans),
                                  "ff.serve.decode")
    return 100.0 * seconds / busy if seconds else None
