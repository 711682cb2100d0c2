"""What one run carries from set-up to its last line: host-clock spans, the
profiler's slice, the compile counter, the device and its memory peak."""
import contextlib
import json
import os
import shutil
import sys
import time

from . import spec, trace

TRACE_SLICE_S = 4.0  # the profiler runs over the last seconds of the window
OUT_DIR = os.path.join(spec.BENCH_DIR, ".out")


class Spans:
    """Seconds by name, host clock, summed over the spans of one name."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) \
                + time.perf_counter() - t0


class Tracer:
    """Profiles the last TRACE_SLICE_S seconds of the window into
    perfbench/.out/trace, which it empties first: one trace is kept."""

    def __init__(self, on):
        self.on, self.t_start, self.window_s = on, None, None
        self.dir = os.path.join(OUT_DIR, "trace")

    def maybe_start(self, elapsed, seconds):
        """Start the profiler once the slice is due; returns the seconds the
        start itself took."""
        if not self.on or self.t_start is not None \
                or elapsed < seconds - TRACE_SLICE_S:
            return 0.0
        import jax

        t0 = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from TraceAnnotation
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_start = time.perf_counter()
        return self.t_start - t0

    def stop(self):
        if self.t_start is None:
            return
        import jax

        self.window_s = time.perf_counter() - self.t_start
        jax.profiler.stop_trace()

    def summary(self):
        if self.t_start is None:
            return None
        return trace.reduce(trace.find_xplane(self.dir))


class CompileCounter:
    """Counts programs JAX had to build or fetch while the window was open:
    a compile, or a load from the persistent cache, each means a shape the
    set-up did not warm."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.open, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_args, **_kw):
        if self.open and name in self.EVENTS:
            self.count += 1


class RunCtx:
    def __init__(self, t_process, trace_on):
        self.t_process = t_process
        self.spans, self.tracer = Spans(), Tracer(trace_on)
        self.compiles = CompileCounter()
        self.facts, self.end_to_end = {}, {}
        self.attempted = self.failed = 0
        self.memory_peak_bytes = None
        self.before_window = lambda: None  # run.py closes its session here

    def open_window(self):
        """Set-up ends here; from here on nothing may compile."""
        self.end_to_end["setup_s"] = time.perf_counter() - self.t_process
        self.compiles.open = True

    def close_window(self):
        self.compiles.open = False
        self.facts["compiles_in_window"] = self.compiles.count

    def read_memory(self):
        import jax

        # the CPU of a rehearsal reports no memory statistics
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices())


def program_tree(names, flat):
    """The benchmark's flat weights under the program's (op, weight) names."""
    tree = {}
    for k, (op, w) in names.items():
        tree.setdefault(op, {})[w] = flat[k]
    return tree


def free(tree):
    """Give the device arrays of `tree` back now."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if not leaf.is_deleted():
            leaf.delete()


def cache_entries(path):
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except FileNotFoundError:
        return 0


def result_line(cell, run_ctx, device, checks, per_layer, breakdown):
    """The run's last line: the contract's keys, then what was compared."""
    declared = cell.end_to_end + (cell.per_layer if per_layer is not None else [])
    values = dict(run_ctx.end_to_end, **(per_layer or {}))
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in declared if values.get(m["name"]) is not None}
    line = {"correct": checks.correct, "attempted": run_ctx.attempted,
            "failed": run_ctx.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks.rows
    return json.dumps(line)


def say(*a):
    print(*a, file=sys.stderr, flush=True)
