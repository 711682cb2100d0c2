"""Device-busy seconds of chip 0 inside one `ff.serve.admit` span of the
traced slice (the prefill program and what of the cache insert ran before
the span ended; the insert is dispatched without a sync, so its tail runs
under the next `ff.serve.decode`). None with no admission in the slice."""
from perfbench.harness import program_spans


def read(facts):
    spans = program_spans.of(facts)
    if spans is None or not spans.count("ff.serve.admit"):
        return None
    return 1e3 * spans.busy_under("ff.serve.admit") \
        / spans.count("ff.serve.admit")
