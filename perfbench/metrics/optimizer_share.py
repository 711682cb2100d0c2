"""The optimizer update's share of the device's busy time in the traced
slice: chip 0's operations whose scope path is under `ff.opt`, over its
busy time."""
from perfbench.harness import program_spans


def read(facts):
    spans = program_spans.of(facts)
    if spans is None or not spans.busy_s:
        return None
    seconds = spans.scope_seconds("ff.opt")
    return None if seconds is None else 100.0 * seconds / spans.busy_s
