"""How much of the cell the linear-attention layers' mixer is: chip 0's busy
time under any `ff.linear_attn.` scope (projections, convolution, the
chunked scan or the one-token step, the gated norm) over its busy time in
the traced slice. Silent where no operation lies under such a scope."""
from perfbench.harness import program_spans


def read(facts):
    spans = program_spans.of(facts)
    if spans is None or not spans.busy_s:
        return None
    seconds = spans.scope_seconds("ff.linear_attn.")
    return 100.0 * seconds / spans.busy_s if seconds else None
