"""Seconds the device sat idle at the boundary between two `fit()` calls
of the traced slice: chip 0's idle time under the `ff.fit.fold` (the fetch
of the epoch's partials), `ff.fit.sync` (block_until_ready on the new
parameters) and `ff.fit.feed` (the batch onto the device) spans, over the
`fit()` calls of the slice (one `ff.fit.sync` each). Prints the slice's
table of busy and idle seconds by `ff.` span."""
from perfbench.harness import program_spans

PHASES = ("ff.fit.fold", "ff.fit.sync", "ff.fit.feed")


def read(facts):
    spans = program_spans.of(facts)
    if spans is None or not spans.count("ff.fit.sync"):
        return None
    spans.report()
    return 1e3 * sum(spans.idle_under(p) or 0.0 for p in PHASES) \
        / spans.count("ff.fit.sync")
