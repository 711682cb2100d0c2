"""Reduce the profiler's .xplane.pb to what the metric readers use: device
busy time, the device's operations by name, the programs (XLA modules) by
name, and the idle gaps with what the host was doing in them. Read with
jax.profiler.ProfileData alone.

A TPU's plane is `/device:TPU:<n>`; its line "XLA Ops" holds one event per
device operation and "XLA Modules" one per executed program. Host spans the
benchmark wrote (jax.profiler.TraceAnnotation, names starting `perfbench.`)
are on the host plane, on the same clock.
"""
import dataclasses
import glob
import gzip
import bisect
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "perfbench."
# how a Pallas (Mosaic) kernel shows in an operation's HLO text
MOSAIC_CALL = r'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Summary:
    chips: int
    busy_s: float      # union of op intervals, mean over the chips
    first_ns: float    # first and last device event, any chip
    last_ns: float
    ops: dict          # op name -> [seconds (mean over chips), count]
    modules: dict      # program name -> [seconds, count], first chip
    gaps: list         # (host activity, seconds), longest first
    op_programs: dict  # op name -> names of the programs it ran in

    def op_seconds(self, pattern):
        """(seconds, count) of the device operations whose name matches."""
        rx = re.compile(pattern)
        hit = [v for k, v in self.ops.items() if rx.search(k)]
        return sum(v[0] for v in hit), sum(v[1] for v in hit)

    def kernel(self, operand):
        """(seconds, calls, programs) of the Mosaic calls that have `operand`
        (a shape as the HLO text spells it) among their operands or results:
        a kernel is known by the layout it works on, not by being the only
        Mosaic call there is. None where no Mosaic call ran at all; where
        some ran and none has the operand, None too, and it says so: the
        kernel's count of operations and bytes no longer fits what runs."""
        mosaic = [k for k in self.ops if re.search(MOSAIC_CALL, k)]
        mine = [k for k in mosaic if operand in k]
        if not mine:
            if mosaic:
                calls = sum(self.ops[k][1] for k in mosaic)
                print(f"perfbench: {calls} Mosaic call(s) in the trace, "
                      f"none on {operand}: kernel changed or absent",
                      file=sys.stderr)
            return None
        programs = set().union(*(self.op_programs.get(k, ()) for k in mine))
        return (sum(self.ops[k][0] for k in mine),
                sum(self.ops[k][1] for k in mine), programs)

    def program(self, names=None, executions=None):
        """(seconds, executions) of one program of the slice: among `names`
        (all, if None), and among those executed about `executions` times
        where that is given (a tenth either way, two at the least: the
        slice's ends cut iterations), the one that took most of the device's
        time. None without any."""
        hit = [v for k, v in self.modules.items()
               if (names is None or k in names)
               and (executions is None or abs(v[1] - executions)
                   <= max(2, 0.1 * executions))]
        return tuple(max(hit, key=lambda v: v[0])) if hit else None

    def top_ops(self, n=10, width=100):
        """The operations that took most device time, the instances of one
        kind (the same instruction but for its number, such as one kernel in
        24 layers) summed: [[head of the name, seconds], ...]."""
        kinds = {}
        for name, (seconds, _) in self.ops.items():
            kind = re.sub(r"^(%[^.\s=]+)[.\d]*(?= = )", r"\1", name)[:width]
            kinds[kind] = kinds.get(kind, 0.0) + seconds
        return sorted(([k, v] for k, v in kinds.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n=10):
        by = {}
        for name, s in self.gaps:
            by[name] = by.get(name, 0.0) + s
        return sorted(([k, v] for k, v in by.items()),
                      key=lambda kv: -kv[1])[:n]


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _line_events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    return []


def load(path):
    """The profile at `path`: a recorded .xplane.pb, the same gzipped, or an
    XSpace written out as a text proto (the tests' fixtures)."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    if path.endswith((".txt", ".txt.gz")):
        return ProfileData.from_text_proto(data.decode())
    return ProfileData.from_serialized_xspace(data)


def reduce(path, min_gap_s=50e-6):
    data = load(path)
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    ops, busy, first, last, gaps = {}, 0.0, None, None, []
    op_programs = {}
    for i, plane in enumerate(devices):
        events = _line_events(plane, OPS_LINE)
        merged = _union((s, s + d) for _, s, d in events)
        busy += sum(e - s for s, e in merged) * 1e-9
        if merged:
            first = merged[0][0] if first is None else min(first, merged[0][0])
            last = merged[-1][1] if last is None else max(last, merged[-1][1])
        for name, _, d in events:
            rec = ops.setdefault(name, [0.0, 0])
            rec[0] += d * 1e-9 / len(devices)
            rec[1] += 1
        if i == 0:  # gaps and programs on the first chip stand for all
            for (_, e0), (s1, _) in zip(merged, merged[1:]):
                if (s1 - e0) * 1e-9 >= min_gap_s:
                    gaps.append((_host_activity(spans, e0, s1),
                                 (s1 - e0) * 1e-9))
            runs = sorted((s, s + d, name) for name, s, d in
                          _line_events(plane, MODULES_LINE))
            starts = [r[0] for r in runs]
            for name, s, _ in events:  # the program running when it started
                j = bisect.bisect_right(starts, s) - 1
                if j >= 0 and s < runs[j][1]:
                    op_programs.setdefault(name, set()).add(runs[j][2])
    modules = {}
    for name, _, d in _line_events(devices[0], MODULES_LINE):
        rec = modules.setdefault(name, [0.0, 0])
        rec[0] += d * 1e-9
        rec[1] += 1
    gaps.sort(key=lambda g: -g[1])
    return Summary(chips=len(devices), busy_s=busy / len(devices),
                   first_ns=first or 0.0, last_ns=last or 0.0, ops=ops,
                   modules=modules, gaps=gaps, op_programs=op_programs)


def _host_activity(spans, t0, t1):
    """The benchmark's own host span that covers most of [t0, t1]; time no
    span of the benchmark covers belongs to the program."""
    best, cover = "program", 0.0
    for name, s, e in spans:
        c = min(e, t1) - max(s, t0)
        if c > cover:
            best, cover = name[len(SPAN_PREFIX):], c
    return best if cover >= 0.5 * (t1 - t0) else "program"
