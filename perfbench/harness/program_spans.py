"""What the program itself wrote into the profiler's slice: its `ff.` host
spans (flexflow_tpu.obs.mark, a jax.profiler.TraceAnnotation each) with
their arguments, the device's busy and idle time under each of them, and
the scope path (`jit(step)/ff.fwd/h3_attn/...`) of every device operation.

Host spans are on the lines of the `/host:CPU` plane, one line a thread, on
the clock of `/device:TPU:0`. A v5e trace holds an operation's scope path
(the `op_name` of its HLO metadata) as the stat `tf_op` of the event's
METADATA, which jax.profiler.ProfileData does not hand out (it gives an
event's own stats only); the few fields needed are read from the XSpace's
wire format here (tsl/profiler/protobuf/xplane.proto; importing the
generated module would import TensorFlow, 25 s).

An older program writes no `ff.` span and no `ff.` scope: `of()` then holds
empty tables and every reader built on it returns None.
"""
import bisect
import dataclasses
import os
import re
import sys

from . import runctx, trace

SPAN_PREFIX = "ff."
SCOPE_STAT = "tf_op"
FF_SCOPE = re.compile(r"(?:^|[/(])ff\.")


# -- the XSpace's wire format, as far as needed -------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            i += {1: 8, 5: 4}[kind]
            continue
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def op_scopes(xspace, plane=trace.DEVICE_PLANE):
    """{operation name (its HLO text): scope path} of the first plane whose
    name matches `plane`, from the `tf_op` stat of each event metadata."""
    for f, raw in _fields(memoryview(xspace)):
        if f != 1:  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, value in _fields(raw):
            if pf == 2:  # XPlane.name
                name = _text(value)
            elif pf == 4:  # event_metadata: map<int64, XEventMetadata>
                metas += [v for k, v in _fields(value) if k == 2]
            elif pf == 5:  # stat_metadata: map<int64, XStatMetadata>
                for k, v in _fields(value):
                    if k == 2:
                        meta = dict(_fields(v))
                        stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not plane.match(name):
            continue
        want = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        out = {}
        for raw_meta in metas:
            op, scope = None, None
            for mf, value in _fields(raw_meta):
                if mf == 2:  # XEventMetadata.name
                    op = _text(value)
                elif mf == 5:  # XEventMetadata.stats
                    stat = dict(_fields(value))
                    if stat.get(1) not in want:
                        continue
                    if 5 in stat:  # str_value
                        scope = _text(stat[5])
                    elif 7 in stat:  # ref_value -> a stat metadata's name
                        scope = stat_names.get(stat[7])
            if op is not None and scope:
                out[op] = scope.rstrip(":")
        return out
    return {}


# -- intervals ------------------------------------------------------------------
def _overlap(a, b):
    """Nanoseconds the merged sorted interval lists `a` and `b` share."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def _self_intervals(spans):
    """Per span of one thread (they nest), what is left of it once its
    children are taken out: the innermost span wins."""
    out, stack = [], []  # stack: [span index, cursor]
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
    pieces = {i: [] for i in order}

    def close(upto):
        while stack and spans[stack[-1][0]].end_ns <= upto:
            i, cursor = stack.pop()
            if spans[i].end_ns > cursor:
                pieces[i].append([cursor, spans[i].end_ns])
            if stack:
                stack[-1][1] = max(stack[-1][1], spans[i].end_ns)

    for i in order:
        close(spans[i].start_ns)
        if stack and spans[i].start_ns > stack[-1][1]:
            pieces[stack[-1][0]].append([stack[-1][1], spans[i].start_ns])
        stack.append([i, spans[i].start_ns])
    close(float("inf"))
    for i in order:
        out.append((spans[i], pieces[i]))
    return out


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: dict
    thread: str


@dataclasses.dataclass
class ProgramSpans:
    spans: list    # every `ff.` host span of every thread, by start
    under: dict    # span name -> {count, busy_under, idle_under (seconds of
    #                chip 0 inside the union of that name's spans),
    #                busy_self, idle_self (the same, innermost span winning)}
    busy_s: float  # chip 0: union of its operations' intervals
    idle_s: float  # chip 0: between its first and last event, the rest
    idle_named_s: float  # of idle_s, under any `ff.` span
    scopes: dict   # device operation name -> scope path
    ops: list      # chip 0: (operation name, start_ns, end_ns), by start
    reported: bool = False

    def count(self, name):
        return self.under.get(name, {}).get("count", 0)

    def busy_under(self, name):
        return self.under[name]["busy_under"] if name in self.under else None

    def idle_under(self, name):
        return self.under[name]["idle_under"] if name in self.under else None

    def _under(self, inside, outside=None):
        """(name, start, end) of chip 0's operations whose scope path has
        `inside` and not `outside` (plain substrings)."""
        for name, s, e in self.ops:
            scope = self.scopes.get(name, "")
            if inside in scope and not (outside and outside in scope):
                yield name, s, e

    def scope_seconds(self, inside, outside=None):
        """Seconds chip 0 was busy with the operations under the scope: the
        union of their intervals, so an operation running inside another
        is counted once. None when no operation of the slice has a `ff.`
        scope at all."""
        if not self.has_scopes():
            return None
        hit = [[s, e] for _, s, e in self._under(inside, outside)]
        return sum(e - s for s, e in trace._union(hit)) * 1e-9

    def scope_kinds(self, inside, outside=None, n=3):
        """[(instruction kind, seconds)] of the operations `scope_seconds`
        counts, the instances of one kind (an instruction's name less its
        number) summed, the longest first: says what a scope's time is
        made of where one fusion holds operations of two scopes and
        carries the name of one."""
        kinds = {}
        for name, s, e in self._under(inside, outside):
            kind = re.sub(r"[.\d]*$", "", name.split(" = ")[0])
            kinds[kind] = kinds.get(kind, 0.0) + (e - s) * 1e-9
        return sorted(kinds.items(), key=lambda kv: -kv[1])[:n]

    def has_scopes(self):
        return any(FF_SCOPE.search(v) for v in self.scopes.values())

    def unscoped_seconds(self):
        """Device-busy seconds under no `ff.` scope."""
        scoped = [[s, e] for name, s, e in self.ops
                  if FF_SCOPE.search(self.scopes.get(name, ""))]
        return self.busy_s - sum(e - s for s, e in trace._union(scoped)) * 1e-9

    def lag_after_device(self, name):
        """Per span named `name`, the seconds from the end of the last device
        operation that ended before the span did to the span's own end: a
        span that waits for the device ends right behind it where host and
        device share a clock."""
        ends = sorted(e for _, _, e in self.ops)
        out = []
        for s in self.spans:
            j = bisect.bisect_right(ends, s.end_ns) - 1
            if s.name == name and j >= 0:
                out.append((s.end_ns - ends[j]) * 1e-9)
        return out

    def table(self):
        """Rows (span name, count, busy and idle seconds under it, the same
        with the innermost span winning), most idle first."""
        return sorted(
            ([n, u["count"], u["busy_under"], u["idle_under"],
              u["busy_self"], u["idle_self"]] for n, u in self.under.items()),
            key=lambda r: -r[5])

    def report(self):
        """The table, in milliseconds, on standard error, once per run."""
        if self.reported:
            return
        self.reported, out = True, sys.stderr
        print("ff. span                    count  busy_ms  idle_ms | "
              "innermost: busy_ms  idle_ms", file=out)
        for n, c, b, i, bs, is_ in self.table():
            print(f"{n:27s} {c:5d} {1e3 * b:8.2f} {1e3 * i:8.2f} | "
                  f"{1e3 * bs:18.2f} {1e3 * is_:8.2f}", file=out)
        named = 100.0 * self.idle_named_s / self.idle_s if self.idle_s else 0.0
        print(f"device busy {1e3 * self.busy_s:.2f} ms, idle "
              f"{1e3 * self.idle_s:.2f} ms, {named:.1f}% of the idle time "
              "under a named ff. span", file=out)


def read(path):
    """The ProgramSpans of the profile at `path` (.xplane.pb, the same
    gzipped, or a text proto)."""
    import gzip

    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read()
    if path.endswith((".txt", ".txt.gz")):
        raw = ProfileData.text_proto_to_serialized_xspace(raw.decode())
    data = ProfileData.from_serialized_xspace(raw)
    spans, ops = [], []
    devices = sorted((p for p in data.planes
                      if trace.DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats), line.name))
    if devices:
        ops = sorted((s, s + d, name) for name, s, d in
                     trace._line_events(devices[0], trace.OPS_LINE))
        ops = [(name, s, e) for s, e, name in ops]
    spans.sort(key=lambda s: s.start_ns)
    busy = trace._union([s, e] for _, s, e in ops)
    edges = [s.start_ns for s in spans] + [s.end_ns for s in spans] \
        + ([busy[0][0], busy[-1][1]] if busy else [])
    idle = []
    if edges:
        lo, hi = min(edges), max(edges)
        cursor = lo
        for s, e in busy:
            if s > cursor:
                idle.append([cursor, s])
            cursor = max(cursor, e)
        if hi > cursor:
            idle.append([cursor, hi])
    under = {}
    by_thread = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    own = {}
    for thread_spans in by_thread.values():
        for span, pieces in _self_intervals(thread_spans):
            own.setdefault(span.name, []).extend(pieces)
    for name in {s.name for s in spans}:
        whole = trace._union([s.start_ns, s.end_ns] for s in spans
                             if s.name == name)
        mine = trace._union(own.get(name, []))
        under[name] = {
            "count": sum(1 for s in spans if s.name == name),
            "busy_under": _overlap(whole, busy) * 1e-9,
            "idle_under": _overlap(whole, idle) * 1e-9,
            "busy_self": _overlap(mine, busy) * 1e-9,
            "idle_self": _overlap(mine, idle) * 1e-9}
    named = trace._union([s.start_ns, s.end_ns] for s in spans)
    return ProgramSpans(
        spans=spans, under=under,
        busy_s=sum(e - s for s, e in busy) * 1e-9,
        idle_s=sum(e - s for s, e in idle) * 1e-9,
        idle_named_s=_overlap(named, idle) * 1e-9,
        scopes=op_scopes(raw) if devices else {}, ops=ops)


def of(facts):
    """The run's ProgramSpans, read once from the slice the tracer wrote and
    kept in `facts`; None where the run has no slice."""
    if "program_spans" not in facts:
        try:
            path = trace.find_xplane(os.path.join(runctx.OUT_DIR, "trace"))
        except FileNotFoundError:
            path = None
        facts["program_spans"] = read(path) if path else None
    return facts["program_spans"]


def session_file(name):
    """A file of the program's telemetry session that the run held open
    through set-up (perfbench/.out/telemetry), or None."""
    path = os.path.join(runctx.OUT_DIR, "telemetry", name)
    return path if os.path.exists(path) else None
