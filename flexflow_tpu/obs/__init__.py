"""Unified telemetry: structured event tracing, metrics export, and
strategy-search explainability.

The reference surfaces runtime behaviour through `-lg:prof` profiles,
per-op event timing prints and the simulator's timeline export (SURVEY
§5); this package unifies the TPU-native equivalents behind one API:

  * `obs.tracer` — low-overhead span tracer -> structured JSONL event
    log, exportable to Chrome-trace/Perfetto (spans around compile,
    every search decision, per-step execution, checkpoints, elastic
    re-search, guard/canary/watchdog firings);
  * `obs.metrics` — counter/gauge/histogram registry -> Prometheus text
    file + JSONL (step wall time, samples/s/chip, grad norm, loss
    scale, skip/retry counts, serving latency percentiles, PCG-derived
    static gauges);
  * `obs.explain_strategy(model)` — joins the recorded search
    trajectory with on-device `profile_ops` measurements to rank ops by
    |simulated − measured| cost and feed the miscalibration back into
    the next search.

Wire-up: ``model.fit(..., telemetry=TelemetryConfig(dir=...))`` runs one
session end to end; ``python -m flexflow_tpu.obs`` converts/summarizes
the artifacts. The hot path (serve loop, fit(), compile) marks its phases
with `obs.mark("ff....")`, which needs no session: the spans are
jax.profiler TraceAnnotations, visible to any profiler trace. With no
session active every other helper here is a cheap no-op — `tracer()` returns the shared NULL_TRACER (no per-call
allocation) and the counter/gauge helpers return after one global read.
"""
from __future__ import annotations

import contextlib
import sys
from typing import Optional

from .anomaly import Anomaly, AnomalySentinel  # noqa: F401
from .calibration import CalibrationStore, resolve_calibration  # noqa: F401
from .fleet import FleetAggregator, MetricSpool  # noqa: F401
from .metrics import (  # noqa: F401
    MetricsRegistry,
    merge_histogram_states,
    parse_prometheus,
    parse_prometheus_labeled,
)
from .request_trace import (  # noqa: F401
    NULL_REQUEST_TRACE,
    RequestTrace,
    SLOMonitor,
    mint_request_trace,
    record_request_stages,
)
from .telemetry import Telemetry, TelemetryConfig  # noqa: F401
from .tracer import (  # noqa: F401
    NULL_TRACER,
    Mark,
    Tracer,
    _NULL_SPAN,
    read_events_jsonl,
    to_chrome_trace,
    validate_event,
)
from .trajectory import SearchTrajectory  # noqa: F401

_ACTIVE: Optional[Telemetry] = None


# ----------------------------------------------------------------------
# session lifecycle
# ----------------------------------------------------------------------
def start(config: TelemetryConfig) -> Telemetry:
    """Start (and globally register) a telemetry session. One session is
    active per process; starting over a live one finishes it first."""
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.finish()
    _ACTIVE = Telemetry(config)
    return _ACTIVE


def finish() -> None:
    """Finish the active session: flush events.jsonl, write metrics.prom
    / metrics.jsonl and the Perfetto trace.json."""
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.finish()
        _ACTIVE = None


def active() -> Optional[Telemetry]:
    return _ACTIVE


@contextlib.contextmanager
def session(config: TelemetryConfig):
    tel = start(config)
    try:
        yield tel
    finally:
        if _ACTIVE is tel:
            finish()
        else:  # someone else already rotated the session
            tel.finish()


# ----------------------------------------------------------------------
# cheap emission helpers (no-ops when no session is active)
# ----------------------------------------------------------------------
def tracer():
    """The active session's tracer, or the shared no-op NULL_TRACER."""
    t = _ACTIVE
    return t.tracer if t is not None else NULL_TRACER


def span(name: str, cat: str = "runtime", **args):
    """Context manager timing a span; a shared no-op when inactive."""
    t = _ACTIVE
    if t is None:
        return _NULL_SPAN
    return t.tracer.span(name, cat, **args)


def mark(name: str, cat: str = "runtime", *, into=None, step_num=None,
         session: bool = True, **args) -> Mark:
    """THE way the program marks time on its hot path (names start
    `ff.`): a context manager that always enters a jax.profiler
    TraceAnnotation, so a profiler's trace shows the span on the calling
    thread's host line, on the device's clock, and that also records the
    tracer's "X" event while a session is open. `into=(counters, key)`
    adds the span's seconds to an always-on counter; `step_num` makes the
    annotation the profiler's step marker; `session=False` keeps a span
    that repeats with no work done (the serve loop's idle sleep) out of
    the session's event log. See tracer.Mark. `span()` stays the
    session-only timer of everything off the hot path."""
    t = _ACTIVE if session else None
    return Mark(t.tracer if t is not None else None, name, cat, into,
                step_num, args)


def event(name: str, cat: str = "runtime", **args) -> None:
    """Instant event; dropped when inactive."""
    t = _ACTIVE
    if t is not None:
        t.tracer.instant(name, cat, **args)


def count(name: str, n: float = 1.0, help: str = "", **labels) -> None:
    t = _ACTIVE
    if t is not None:
        t.metrics.counter(name, help, **labels).inc(n)


def gauge_set(name: str, value: float, help: str = "", **labels) -> None:
    t = _ACTIVE
    if t is not None:
        t.metrics.gauge(name, help, **labels).set(value)


def observe(name: str, value: float, help: str = "", **labels) -> None:
    t = _ACTIVE
    if t is not None:
        t.metrics.histogram(name, help, **labels).observe(value)


def forensics_dump(reason: str, error: Optional[BaseException] = None,
                   **extra) -> Optional[str]:
    """Dump a flight-recorder forensics bundle (obs/flight_recorder.py);
    None when no recorder is installed."""
    from . import flight_recorder as _fr

    return _fr.dump(reason=reason, error=error, **extra)


def record_failure(exc: BaseException, **extra) -> Optional[str]:
    """Dump a forensics bundle iff `exc` is a typed runtime failure (at
    most once per exception instance); None otherwise."""
    from . import flight_recorder as _fr

    return _fr.maybe_dump_failure(exc, **extra)


# ----------------------------------------------------------------------
# structured progress logger (the fit/eval print() replacement)
# ----------------------------------------------------------------------
def progress(msg: str, *, verbose: bool = True, name: str = "log",
             cat: str = "train", **fields) -> None:
    """Human-readable progress line + structured telemetry event.

    This is THE sink for library progress output (fflint FFL201 forbids
    bare print() elsewhere in flexflow_tpu/): at default verbosity the
    line prints exactly as before, and when a telemetry session is
    active the same information lands in the event log as structured
    fields."""
    if verbose:
        print(msg, file=sys.stdout)  # fflint: disable=FFL201
    t = _ACTIVE
    if t is not None:
        t.tracer.instant(name, cat, message=msg, **fields)


def explain_strategy(model, x=None, **kw):
    """See obs/explain.py (imported lazily: it pulls in jax)."""
    from .explain import explain_strategy as _impl

    return _impl(model, x, **kw)


def capture_step_profile(model, x, y, **kw):
    """See obs/step_profile.py (imported lazily: it pulls in jax)."""
    from .step_profile import capture_step_profile as _impl

    return _impl(model, x, y, **kw)
