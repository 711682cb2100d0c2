"""Chip 0's busy milliseconds under one `ff.serve.prefill` span of the
traced slice (one computed prefill: the batch-1 cache, the step and the
pick of its first id), less the operations of the cache insert's programs
that ran there: the prefill syncs inside its span, so what else runs under
it is an earlier admission's insert, which `insert_device_ms` counts. None
with no prefill span in the slice."""
from perfbench.harness import program_runs, program_spans


def read(facts):
    spans, runs = program_spans.of(facts), program_runs.of(facts)
    if spans is None or runs is None or not spans.count("ff.serve.prefill"):
        return None
    return 1e3 * program_runs.busy_under_less_inserts(
        spans, runs, "ff.serve.prefill") / spans.count("ff.serve.prefill")
