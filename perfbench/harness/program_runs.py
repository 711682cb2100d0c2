"""Chip 0's programs in the profiler's slice, run by run: the "XLA Modules"
line of the device plane holds one event an execution of one program, on
the clock the host's `ff.` spans share. A program is known by its name, the
part before the "(<fingerprint>)" the trace appends to it.

The serve loop's cache insert (runtime/serving.py `_insert_slot` ->
parallel/decode.py `insert_row`) is dispatched without a sync: what of its
programs the host's dispatch does not wait out runs under whatever span
follows it, the next admission's prefill or the next decode iteration. They
are found here by their names, wherever they ran, never by the host span
they overlap.
"""
import bisect
import os

from . import program_spans, runctx, trace

# the insert's programs: one eager `jax.lax.dynamic_update_slice` a per-slot
# leaf, its `copy` of the whole leaf inside it (read off a slice recorded on
# a v5e: perfbench/fixtures/ff_admit_slice_v5e.xplane.txt.gz)
INSERT = ("jit_dynamic_update_slice",)


def base_name(program):
    return program.split("(")[0]


def read(path):
    """[(program name, start_ns, end_ns)] of the first chip's runs, by start;
    [] where the slice has no TPU plane."""
    data = trace.load(path)
    devices = sorted((p for p in data.planes
                      if trace.DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    if not devices:
        return []
    return sorted(((name, s, s + d) for name, s, d in
                   trace._line_events(devices[0], trace.MODULES_LINE)),
                  key=lambda r: r[1])


def of(facts):
    """The run's program runs, read once from the slice the tracer wrote and
    kept in `facts`; None where the run has no slice."""
    if "program_runs" not in facts:
        try:
            path = trace.find_xplane(os.path.join(runctx.OUT_DIR, "trace"))
        except FileNotFoundError:
            path = None
        facts["program_runs"] = read(path) if path else None
    return facts["program_runs"]


def inserts(runs):
    """[start_ns, end_ns] of the insert's runs."""
    return [[s, e] for name, s, e in runs if base_name(name) in INSERT]


def split_ops(spans, runs):
    """Chip 0's busy intervals, merged, split in two: those of the insert's
    operations (an operation is the insert's where it starts inside one of
    the insert's runs) and those of every other."""
    mine = inserts(runs)
    starts = [s for s, _ in mine]
    ins, rest = [], []
    for _, s, e in spans.ops:
        j = bisect.bisect_right(starts, s) - 1
        (ins if j >= 0 and s < mine[j][1] else rest).append([s, e])
    return trace._union(ins), trace._union(rest)


def insert_seconds(spans, runs):
    """Seconds chip 0 was busy with the insert's operations, wherever they
    ran."""
    return sum(e - s for s, e in split_ops(spans, runs)[0]) * 1e-9


def busy_under_less_inserts(spans, runs, name):
    """Seconds chip 0 was busy under the spans called `name`, less the
    insert's operations that ran there."""
    under = trace._union([sp.start_ns, sp.end_ns] for sp in spans.spans
                         if sp.name == name)
    return program_spans._overlap(under, split_ops(spans, runs)[1]) * 1e-9
