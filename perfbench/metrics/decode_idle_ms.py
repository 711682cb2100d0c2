"""Seconds the device sat idle inside one decode iteration of the traced
slice: chip 0's idle time under the union of the `ff.serve.decode` spans
over their count. Prints the slice's table of busy and idle seconds by
`ff.` span (PERF.md section 5 is written from it)."""
import sys

from perfbench.harness import program_spans


def read(facts):
    spans = program_spans.of(facts)
    if spans is None or not spans.count("ff.serve.decode"):
        return None
    spans.report()
    lag = spans.lag_after_device("ff.serve.decode.wait")
    if lag:
        print(f"ff.serve.decode.wait ends {1e3 * sorted(lag)[len(lag) // 2]:.3f}"
              f" ms (median of {len(lag)}, worst {1e3 * max(lag):.3f}) after "
              "the device operation before it", file=sys.stderr)
    return 1e3 * spans.idle_under("ff.serve.decode") \
        / spans.count("ff.serve.decode")
