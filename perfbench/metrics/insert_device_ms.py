"""Chip 0's busy milliseconds in the cache insert's programs
(`program_runs.INSERT`, known by their names on the device's modules line)
per `ff.serve.insert` span of the traced slice, wherever the programs ran:
the insert is dispatched without a sync, so what of its work the host's
dispatch does not wait out runs under the next admission's
`ff.serve.prefill` or the next `ff.serve.decode`. Runs before the slice's
first insert span are left out: their insert began before the profiler did
and has no span to count it by. None with no insert span or no insert
program in the slice."""
from perfbench.harness import program_runs, program_spans


def read(facts):
    spans, runs = program_spans.of(facts), program_runs.of(facts)
    if spans is None or runs is None or not spans.count("ff.serve.insert"):
        return None
    first = min(s.start_ns for s in spans.spans if s.name == "ff.serve.insert")
    runs = [r for r in runs if r[1] >= first]
    if not program_runs.inserts(runs):
        return None
    return 1e3 * program_runs.insert_seconds(spans, runs) \
        / spans.count("ff.serve.insert")
