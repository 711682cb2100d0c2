"""Device seconds of one training step's backward pass in the traced
slice: chip 0's operations whose scope path is under
`transpose(jvp(ff.fwd))`, over the executions of the step's program."""
from perfbench.harness import spec


def read(facts):
    return spec.module("metrics", "fwd_ms.py").step_scope_ms(
        facts, "transpose(jvp(ff.fwd))")
