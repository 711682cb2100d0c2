"""In-situ step observatory: measured timelines of the REAL jitted
training step, overlaid on the simulator's schedule.

The calibration loop (obs/explain.py) times ops *in isolation* via
separately-jitted programs, so it cannot see what the fused step does:
whether the overlap discount (docs/performance.md, FFA501) actually
hides weight-grad collectives at runtime, where exposed sync time
lives, or what HBM the step really peaks at. This module is the
in-situ instrument:

  * ``capture_step_profile(model, x, y)`` — a measured per-op /
    per-collective timeline of the real step. On TPU/GPU it parses a
    ``jax.profiler`` trace capture (``runtime/profiler.py::trace``);
    everywhere (and as the deterministic CPU fallback) it runs a
    chunked instrumented execution attributed to PCG op guids
    (``runtime/profiler.py::measured_timeline_events``) plus a wall
    clock of the REAL fused jitted step
    (``PCGExecutor.time_train_step``).
  * **overlap realization** — the fused step is timed with the
    overlapped gradient sync on AND off, and each weight-grad
    collective is timed in isolation over the live mesh's ``data``
    axis; the hidden-vs-exposed split per collective is checked
    against the FFA501 discount assumption and exported as
    ``ff_overlap_realized_ratio``. ``write_calibration`` pushes the
    measured ``overlap_efficiency`` + per-kind collective bandwidths
    through ``CalibrationStore.record_globals`` so the next
    ``compile(calibration=...)`` prices overlap from reality.
  * **HBM reconciliation** — ``HbmSampler`` reads per-device live
    watermarks (``device.memory_stats()`` on TPU/GPU, a
    ``jax.live_arrays()`` allocator estimate on CPU), emits them as
    Perfetto counter tracks (``ph="C"``) and
    ``ff_hbm_peak_bytes{device}``, and reconciles them against
    ``analysis/memory.py``'s static FFA301 prediction
    (``ff_hbm_static_accuracy``). ``dump_oom_forensics`` writes the
    static report + live stats + top allocations when a step dies
    with RESOURCE_EXHAUSTED.
  * **overlay export** — ``export_overlay`` merges the measured events
    with ``runtime/profiler.py::simulated_timeline_events`` into ONE
    Perfetto file: "simulated" and "measured" process groups on a
    shared rebased timebase.

Wire-up: ``fit(telemetry=TelemetryConfig(dir=..., step_profile=True))``
captures after the training loop (the step is warm) and writes
``step_timeline.json`` next to the session's other artifacts.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import logging
import math
import os
import re
import time
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

MEASURED_CAT = "measured"
OVERLAY_FILE = "step_timeline.json"
OOM_FORENSICS_FILE = "oom_forensics.json"
# floor written to the calibration store: validate_calibration rejects
# efficiencies outside (0, 1], and a literal 0.0 would price overlap as
# impossible forever on the strength of one noisy capture
_MIN_RECORDED_EFFICIENCY = 0.05


# ----------------------------------------------------------------------
# HBM watermarks
# ----------------------------------------------------------------------
class HbmSampler:
    """Per-device live-memory watermark sampler.

    Prefers ``device.memory_stats()`` (TPU/GPU allocator truth, with
    peak tracking); falls back to summing ``jax.live_arrays()`` shard
    bytes per device (CPU — an allocator *estimate*: it sees live jax
    buffers, not XLA scratch). ``source`` says which oracle answered,
    and rides into the reconciliation metric so a CPU-estimated
    accuracy ratio is never mistaken for allocator truth."""

    def __init__(self, devices=None):
        import jax

        self.devices = list(devices) if devices is not None \
            else list(jax.local_devices())
        self.source = "memory_stats"
        stats = None
        try:
            stats = self.devices[0].memory_stats() if self.devices else None
        except Exception as e:  # fflint: disable=FFL002 — probe only
            logger.debug("hbm sampler: memory_stats probe failed (%s)", e)
        if not stats:
            self.source = "live_arrays"
        self.peak: Dict[int, int] = {}

    def _sample_memory_stats(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for d in self.devices:
            stats = d.memory_stats() or {}
            b = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
            if b is not None:
                out[d.id] = int(b)
        return out

    def _sample_live_arrays(self) -> Dict[int, int]:
        import jax

        out: Dict[int, int] = {d.id: 0 for d in self.devices}
        for arr in jax.live_arrays():
            try:
                for sh in arr.addressable_shards:
                    if sh.device.id in out:
                        out[sh.device.id] += int(sh.data.nbytes)
            except Exception as e:  # fflint: disable=FFL002 — deleted buffers race
                logger.debug("hbm sampler: shard walk failed (%s)", e)
        return out

    def sample(self) -> Dict[int, int]:
        """One watermark per device id; also folds into ``self.peak``."""
        try:
            out = (self._sample_memory_stats()
                   if self.source == "memory_stats"
                   else self._sample_live_arrays())
        except Exception as e:  # fflint: disable=FFL002 — sampling must not kill training
            logger.debug("hbm sampler: sample failed (%s)", e)
            out = {}
        for d, b in out.items():
            if b > self.peak.get(d, 0):
                self.peak[d] = b
        return out


@dataclasses.dataclass
class HbmReport:
    """Measured-vs-static HBM reconciliation for one capture."""

    peak_bytes: Dict[int, int]          # device id -> measured watermark
    static_bytes: Dict[int, int]        # device id -> FFA301 estimate
    source: str                         # "memory_stats" | "live_arrays"
    samples: int = 0

    @property
    def measured_peak(self) -> int:
        return max(self.peak_bytes.values(), default=0)

    @property
    def static_peak(self) -> int:
        return max(self.static_bytes.values(), default=0)

    @property
    def static_accuracy(self) -> Optional[float]:
        """static peak / measured peak. >1 = the static model
        over-provisions (safe); <1 = it under-predicts (the direction
        that OOMs)."""
        if self.measured_peak <= 0 or self.static_peak <= 0:
            return None
        return self.static_peak / self.measured_peak


@dataclasses.dataclass
class CollectiveRealization:
    """One weight-grad collective's measured hidden/exposed split."""

    op: str
    guid: int
    kind: str                 # "all_reduce" | "reduce_scatter+all_gather"
    wire_bytes: int
    sync_s: float             # isolated measured collective seconds
    hidden_s: float
    bytes_per_s: float = 0.0
    overlappable: bool = True

    @property
    def exposed_s(self) -> float:
        return max(0.0, self.sync_s - self.hidden_s)


@dataclasses.dataclass
class StepProfile:
    """The capture result: a measured timeline + the derived overlap /
    HBM reconciliations. All times in seconds (the schema every obs
    component shares)."""

    events: List[dict]                       # cat "measured" events
    step_wall_s: float                       # fused jitted step (as compiled)
    serial_step_wall_s: float                # overlap path forced off
    collectives: List[CollectiveRealization]
    hbm: Optional[HbmReport]
    mode: str                                # "instrumented" | "xla_trace"
    backend: str
    assumed_efficiency: float = 1.0          # FFA501 discount assumption
    data_degree: int = 1

    @property
    def total_sync_s(self) -> float:
        return sum(c.sync_s for c in self.collectives)

    @property
    def hidden_sync_s(self) -> float:
        return sum(c.hidden_s for c in self.collectives)

    @property
    def realized_ratio(self) -> Optional[float]:
        """Measured fraction of overlappable collective time the real
        fused step hides behind compute — the in-situ counterpart of
        the FFA501 ``overlap_efficiency`` assumption. None when the
        strategy has no weight-grad collectives to hide."""
        s = self.total_sync_s
        if s <= 0:
            return None
        return min(1.0, max(0.0, self.hidden_sync_s / s))

    def collective_bandwidths(self) -> Dict[str, float]:
        """Measured effective bytes/s per collective kind (wire bytes /
        isolated measured seconds), aggregated over the capture's
        collectives — the in-situ values record_globals persists."""
        by_kind: Dict[str, List[Tuple[int, float]]] = {}
        for c in self.collectives:
            if c.sync_s > 0 and c.wire_bytes > 0:
                by_kind.setdefault(c.kind, []).append((c.wire_bytes, c.sync_s))
        return {
            k: sum(b for b, _ in v) / sum(s for _, s in v)
            for k, v in by_kind.items()
        }

    def write_calibration(self, store) -> bool:
        """Push the measured overlap efficiency + per-kind collective
        bandwidths through ``CalibrationStore.record_globals`` so the
        next ``compile(calibration=...)`` prices overlap from this
        capture. Returns False when there was nothing measured."""
        ratio = self.realized_ratio
        bw = self.collective_bandwidths()
        if ratio is None and not bw:
            return False
        eff = None
        if ratio is not None:
            eff = max(_MIN_RECORDED_EFFICIENCY, min(1.0, ratio))
        store.record_globals(overlap_efficiency=eff, collectives=bw)
        return True

    def summary(self) -> dict:
        return {
            "mode": self.mode,
            "backend": self.backend,
            "step_wall_s": self.step_wall_s,
            "serial_step_wall_s": self.serial_step_wall_s,
            "data_degree": self.data_degree,
            "collectives": len(self.collectives),
            "total_sync_s": self.total_sync_s,
            "hidden_sync_s": self.hidden_sync_s,
            "realized_ratio": self.realized_ratio,
            "assumed_efficiency": self.assumed_efficiency,
            "collective_bytes_per_s": self.collective_bandwidths(),
            "hbm_peak_bytes": self.hbm.measured_peak if self.hbm else None,
            "hbm_static_accuracy": (self.hbm.static_accuracy
                                    if self.hbm else None),
            "hbm_source": self.hbm.source if self.hbm else None,
            "events": len(self.events),
        }


# ----------------------------------------------------------------------
# collective measurement (the real mesh, the real axis)
# ----------------------------------------------------------------------
def _grad_sync_plan(model) -> List[Tuple]:
    """(op, wire_bytes, kind, weight_elems, overlappable) per
    weight-carrying compute op whose implicit data-parallel gradient
    sync the step executes. Wire bytes follow the ring formulas
    estimate_collective_bytes uses (all-reduce moves 2(p-1)/p of the
    buffer; the overlapped reduce-scatter + all-gather decomposition
    moves the same)."""
    from ..analysis.collectives import overlappable_grad_syncs
    from ..search.cost_model import op_weight_bytes

    ex = model.executor
    d = ex.mesh.shape.get("data", 1) if ex is not None and ex.mesh else 1
    if d <= 1:
        return []
    overlappable = overlappable_grad_syncs(model.graph)
    omap = ex._overlap_specs() if ex is not None else {}
    out = []
    for op in model.graph.topo_order():
        if not op.weights or op.is_parallel_op:
            continue
        wb = op_weight_bytes(op)
        if wb <= 0:
            continue
        wire = int(wb * 2 * (d - 1) / d)
        decomposed = any(name == op.name for name, _ in omap)
        kind = "reduce_scatter+all_gather" if decomposed else "all_reduce"
        elems = sum(
            int(math.prod(w.material_shape())) for w in op.weights
        )
        out.append((op, wire, kind, elems, op.guid in overlappable))
    return out


def _measure_collectives(model, *, repeats: int = 3,
                         warmup: int = 1) -> List[CollectiveRealization]:
    """Time each weight-grad collective in isolation on the LIVE mesh:
    a jitted shard_map psum over the ``data`` axis of a buffer shaped
    like the op's (replicated) gradient — the same wire pattern the
    step's all-reduce (or its RS+AG decomposition, byte-identical)
    moves. hidden_s is attributed afterwards by the caller."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    plan = _grad_sync_plan(model)
    if not plan:
        return []
    mesh = model.executor.mesh
    rep_sharding = NamedSharding(mesh, PartitionSpec())

    def psum_data(a):
        return jax.lax.psum(a, "data")

    fn = jax.jit(jax.shard_map(psum_data, mesh=mesh,
                           in_specs=PartitionSpec(),
                           out_specs=PartitionSpec()))
    out: List[CollectiveRealization] = []
    for op, wire, kind, elems, overlappable in plan:
        buf = jax.device_put(np.zeros((max(1, elems),), np.float32),
                             rep_sharding)
        try:
            jax.block_until_ready(fn(buf))
            for _ in range(max(0, warmup - 1)):
                jax.block_until_ready(fn(buf))
            t0 = time.perf_counter()
            r = None
            for _ in range(max(1, repeats)):
                r = fn(buf)
            jax.block_until_ready(r)
            sync_s = (time.perf_counter() - t0) / max(1, repeats)
        except Exception as e:  # fflint: disable=FFL002 — measurement must not kill capture
            logger.debug("collective measure failed for %s (%s)",
                         op.name, e)
            continue
        out.append(CollectiveRealization(
            op=op.name, guid=op.guid, kind=kind, wire_bytes=wire,
            sync_s=sync_s, hidden_s=0.0,
            bytes_per_s=(wire / sync_s) if sync_s > 0 else 0.0,
            overlappable=overlappable,
        ))
    return out


def _attribute_hidden(collectives: List[CollectiveRealization],
                      hidden_total: float) -> None:
    """Distribute the step-level measured hidden time across the
    overlappable collectives, proportional to each one's isolated sync
    time and capped at it (a collective cannot hide more than itself).
    This is attribution, not per-collective ground truth — the step
    only exposes the aggregate."""
    pool = [c for c in collectives if c.overlappable and c.sync_s > 0]
    remaining = max(0.0, hidden_total)
    total = sum(c.sync_s for c in pool)
    if total <= 0 or remaining <= 0:
        return
    for c in pool:
        c.hidden_s = min(c.sync_s, remaining * (c.sync_s / total))


# ----------------------------------------------------------------------
# capture
# ----------------------------------------------------------------------
def _first_batch(model, x, y, batch_size: int):
    """(cast input arrays, labels) for one batch, the way fit feeds the
    step (core/model.py fast path)."""
    import numpy as np

    xs = x if isinstance(x, (list, tuple)) else [x]
    batch = next(model._batches(list(xs) + [y], batch_size))
    in_pts = model.executor.input_pts
    cast = [np.asarray(a, pt.data_type.np_dtype)
            for pt, a in zip(in_pts, batch[:-1])]
    return cast, np.asarray(batch[-1])


def _fused_step_args(model, cast, labels):
    import jax

    ex = model.executor
    bx = [ex.shard_batch(pt, a) for pt, a in zip(ex.input_pts, cast)]
    by = ex.put_replicated(
        labels.astype(model.label_tensor.data_type.jnp_dtype)
    )
    rng = ex.put_replicated(jax.random.PRNGKey(0))
    return bx, by, rng


_HLO_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bop_name="([^"]*)"', re.M)
_EVENT_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def _instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled program's text: the
    scope path the lowering gave each instruction (`jit(step)/
    jvp(ff.fwd)/<PCG op>/dot_general`)."""
    return dict(_HLO_INSTRUCTION.findall(hlo_text))


def _xplane_op_events(path: str, scopes: Dict[str, str],
                      pcg_names) -> List[dict]:
    """The device operations of the profile at `path` (the "XLA Ops"
    line of the first `/device:` plane), each under the PCG op whose
    per-op scope (jax.named_scope(op.name), PCGExecutor.apply) is a
    component of its instruction's op_name. Operations under no PCG
    op's scope (the optimizer update, compiler-made copies) are left
    out."""
    from jax.profiler import ProfileData

    pcg_names = set(pcg_names)
    plane = next((p for p in sorted(ProfileData.from_file(path).planes,
                                    key=lambda p: p.name)
                  if p.name.startswith("/device:")), None)
    if plane is None:
        return []
    raw = [e for line in plane.lines if line.name == "XLA Ops"
           for e in line.events]
    if not raw:
        return []
    min_ns = min(e.start_ns for e in raw)
    out: List[dict] = []
    for e in raw:
        m = _EVENT_INSTRUCTION.match(e.name)
        scope = scopes.get(m.group(1), "") if m else ""
        op = next((c for c in scope.split("/") if c in pcg_names), None)
        if op is None:
            continue
        out.append({
            "ts": (e.start_ns - min_ns) * 1e-9,
            "ph": "X", "name": op, "cat": MEASURED_CAT,
            "dur": e.duration_ns * 1e-9, "tid": 0,
            "args": {"source": "xla_trace", "xla_op": e.name[:200],
                     "scope": scope},
        })
    return out


def _xla_trace_events(model, step_args, logdir: str) -> List[dict]:
    """TPU/GPU path: run one real fused step under jax.profiler, read
    the newest `*.xplane.pb` with jax.profiler.ProfileData and map each
    device operation to its PCG op by the per-op scope in its op_name
    (taken from the text of the very executable that ran: the trace
    names an operation by its instruction). Best-effort by construction
    — callers fall back to the instrumented path when nothing maps."""
    import jax

    from ..runtime.profiler import trace

    step = model.executor.build_train_step(donate=False)
    bx, by, rng = step_args
    compiled = step.lower(model.state, bx, by, rng).compile()
    _, parts = compiled(model.state, bx, by, rng)  # warm outside the trace
    jax.block_until_ready(parts["loss"])
    with trace(logdir):
        _, parts = compiled(model.state, bx, by, rng)
        jax.block_until_ready(parts["loss"])
    paths = sorted(glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return []
    return _xplane_op_events(
        paths[-1], _instruction_scopes(compiled.as_text()),
        (op.name for op in model.graph.topo_order()))


def capture_step_profile(model, x, y, *, batch_size: Optional[int] = None,
                         repeats: int = 2, warmup: int = 1,
                         mode: str = "auto",
                         sample_hbm: bool = True) -> StepProfile:
    """Capture a measured timeline + overlap/HBM reconciliation of the
    real training step. ``mode``: "instrumented" (deterministic chunked
    per-op execution, the CPU fallback and the default off-TPU),
    "xla_trace" (jax.profiler parse — TPU/GPU), or "auto"."""
    import jax

    from ..analysis.memory import estimate_per_device_bytes
    from ..runtime.profiler import measured_timeline_events

    if model.executor is None:
        from ..runtime.verify import NotCompiledError

        raise NotCompiledError("capture_step_profile: call compile() first")
    backend = jax.default_backend()
    if mode == "auto":
        mode = "xla_trace" if backend in ("tpu", "gpu") else "instrumented"
    ex = model.executor
    bs = batch_size or model.config.batch_size
    cast, labels = _first_batch(model, x, y, bs)
    step_args = _fused_step_args(model, cast, labels)

    sampler = HbmSampler() if sample_hbm else None
    samples = 0
    if sampler is not None:
        sampler.sample()
        samples += 1

    # -- the real fused step, as compiled ------------------------------
    step_wall = ex.time_train_step(model.state, *step_args,
                                   repeats=repeats, warmup=warmup)
    if sampler is not None:
        sampler.sample()
        samples += 1

    # -- overlap realization: the same step with the overlapped
    #    gradient-sync decomposition forced off ------------------------
    serial_wall = step_wall
    had_overlap = ex.overlap_grad_sync and bool(ex._overlap_specs())
    if had_overlap:
        ex.set_overlap_grad_sync(False)
        try:
            serial_wall = ex.time_train_step(model.state, *step_args,
                                             repeats=repeats, warmup=warmup)
        finally:
            ex.set_overlap_grad_sync(True)
    collectives = _measure_collectives(model, repeats=max(2, repeats))
    _attribute_hidden(collectives, max(0.0, serial_wall - step_wall))

    # -- the per-op timeline -------------------------------------------
    events: List[dict] = []
    if mode == "xla_trace":
        import tempfile

        try:
            with tempfile.TemporaryDirectory() as td:
                events = _xla_trace_events(model, step_args, td)
        except Exception as e:  # fflint: disable=FFL002 — profiler capture is best-effort
            logger.warning("xla trace capture failed (%s); falling back "
                           "to instrumented execution", e)
            events = []
        if not events:
            mode = "instrumented"
    if mode == "instrumented":
        events = measured_timeline_events(model, cast, repeats=repeats,
                                          warmup=warmup)
    # lay the measured collectives on a comm lane after the compute
    # timeline, mirroring the simulated overlap schedule's layout
    t_end = max((e["ts"] + e.get("dur", 0.0) for e in events), default=0.0)
    comm_tid = max((int(e.get("tid", 0)) for e in events), default=0) + 1
    cursor = t_end
    for c in collectives:
        events.append({
            "ts": cursor, "ph": "X", "name": f"{c.op}.grad_sync",
            "cat": MEASURED_CAT, "dur": c.sync_s, "tid": comm_tid,
            "args": {"collective": c.kind, "wire_bytes": c.wire_bytes,
                     "hidden_s": c.hidden_s, "exposed_s": c.exposed_s,
                     "bytes_per_s": c.bytes_per_s,
                     "overlappable": c.overlappable,
                     "source": "measured_isolated"},
        })
        cursor += c.sync_s
    if sampler is not None:
        sampler.sample()
        samples += 1

    hbm = None
    if sampler is not None:
        views = getattr(model, "searched_views", None) or {}
        ndev = max(1, len(list(ex.mesh.devices.flat)))
        static = estimate_per_device_bytes(
            model.graph, views, ndev,
            train=model._is_training_compile(),
            optimizer=model.optimizer,
            grad_bytes_ratio=model._grad_bytes_ratio(),
        )
        hbm = HbmReport(peak_bytes=dict(sampler.peak),
                        static_bytes=static, source=sampler.source,
                        samples=samples)

    cm = model._build_cost_model()
    d = ex.mesh.shape.get("data", 1) if ex.mesh is not None else 1
    return StepProfile(
        events=events, step_wall_s=step_wall,
        serial_step_wall_s=serial_wall, collectives=collectives,
        hbm=hbm, mode=mode, backend=backend,
        assumed_efficiency=float(getattr(cm, "overlap_efficiency", 1.0)),
        data_degree=int(d),
    )


# ----------------------------------------------------------------------
# overlay export
# ----------------------------------------------------------------------
def overlay_events(profile: StepProfile, model) -> List[dict]:
    """Measured + simulated events on one shared timebase (both start
    at 0; to_chrome_trace rebases the merged min to 0 and keys the
    process groups off the cats)."""
    from ..pcg.machine_view import make_1d_view
    from ..runtime.profiler import simulated_timeline_events

    searched = getattr(model, "searched_views", None) or {}
    ex = getattr(model, "executor", None)
    ndev = ex.mesh.size if ex is not None and ex.mesh is not None else 1
    full = make_1d_view(0, max(1, int(ndev)))
    # simulated_timeline_events indexes views[guid] strictly; a manually
    # lowered model (no search) has no searched_views, so complete the
    # map from per-op placement with the whole mesh as the SPMD default
    views = {op.guid: (searched.get(op.guid) or op.machine_view or full)
             for op in model.graph.ops}
    sim = simulated_timeline_events(model.graph, views,
                                    model._build_cost_model(),
                                    overlap_sync=True)
    base = min((float(e["ts"]) for e in profile.events), default=0.0)
    measured = [dict(e, ts=float(e["ts"]) - base) for e in profile.events]
    return sim + measured


def export_overlay(profile: StepProfile, model, path: str,
                   extra_events: Optional[List[dict]] = None) -> str:
    """ONE Perfetto file with "simulated" and "measured" process
    groups (plus any session counter events passed in)."""
    from .tracer import to_chrome_trace

    events = overlay_events(profile, model) + list(extra_events or [])
    with open(path, "w") as f:
        json.dump(to_chrome_trace(events), f)
    return path


# ----------------------------------------------------------------------
# session publishing
# ----------------------------------------------------------------------
def publish_step_profile(tel, model, profile: StepProfile) -> None:
    """Feed one capture into a live telemetry session: measured events
    + HBM counter tracks into the tracer, the realization/HBM gauges
    into the metrics registry, the calibration write-through into the
    session store, and the overlay trace file next to the session's
    other artifacts."""
    for e in profile.events:
        tel.tracer.emit(dict(e))
    if profile.hbm is not None:
        for dev, b in sorted(profile.hbm.peak_bytes.items()):
            tel.tracer.counter("hbm_bytes", cat=MEASURED_CAT, tid=int(dev),
                               **{f"device{dev}": float(b)})
            tel.metrics.gauge(
                "ff_hbm_peak_bytes",
                "measured per-device HBM watermark "
                "(memory_stats, or a live-arrays estimate on CPU)",
                device=str(dev),
            ).set(float(b))
        acc = profile.hbm.static_accuracy
        if acc is not None:
            tel.metrics.gauge(
                "ff_hbm_static_accuracy",
                "static FFA301 peak estimate / measured peak watermark "
                "(>1 over-provisions, <1 under-predicts)",
            ).set(acc)
    ratio = profile.realized_ratio
    if ratio is not None:
        tel.metrics.gauge(
            "ff_overlap_realized_ratio",
            "measured fraction of weight-grad collective time the fused "
            "step hides behind compute (FFA501's in-situ counterpart)",
        ).set(ratio)
    tel.metrics.gauge(
        "ff_step_wall_measured_seconds",
        "fused jitted step wall time from the step-profile capture",
    ).set(profile.step_wall_s)
    tel.tracer.instant("step_profile", cat=MEASURED_CAT,
                       **{k: v for k, v in profile.summary().items()
                          if not isinstance(v, dict)})
    if tel.calibration is not None:
        profile.write_calibration(tel.calibration)
    out = os.path.join(tel.config.dir, OVERLAY_FILE)
    try:
        # strategy-swap boundary instants (runtime/tuner.py): global
        # (s="g") markers drawn across the whole overlay. Their wall-clock
        # timestamps share no base with the profiler events, so they are
        # rebased to the overlay origin in commit order — the marker (and
        # its step/fingerprint args) is the signal, not its offset.
        swaps = list(getattr(model, "_strategy_swap_overlay_events",
                             None) or [])
        swaps = [dict(e, ts=float(i)) for i, e in enumerate(swaps)]
        export_overlay(profile, model, out, extra_events=swaps)
    except Exception as e:  # fflint: disable=FFL002 — export must not kill training
        logger.warning("step-profile overlay export failed: %s", e)


def capture_into_session(model, tel, x, y, batch_size: int) -> StepProfile:
    """fit()'s hook: capture with the session's knobs and publish."""
    prof = capture_step_profile(
        model, x, y, batch_size=batch_size,
        repeats=getattr(tel.config, "step_profile_repeats", 2),
    )
    publish_step_profile(tel, model, prof)
    return prof


# ----------------------------------------------------------------------
# OOM forensics
# ----------------------------------------------------------------------
def dump_oom_forensics(model, out_dir: str, *, error: str = "",
                       top_n: int = 20) -> str:
    """RESOURCE_EXHAUSTED post-mortem: the static FFA301 per-device
    estimate, the live allocator stats, and the top-N largest live
    allocations — everything needed to answer "what ate the HBM"
    without re-running the workload."""
    import jax

    from ..analysis.memory import estimate_per_device_bytes

    doc: dict = {"error": error[:2000], "unixtime": time.time(),
                 "backend": jax.default_backend()}
    try:
        views = getattr(model, "searched_views", None) or {}
        ndev = 1
        if model.executor is not None and model.executor.mesh is not None:
            ndev = max(1, len(list(model.executor.mesh.devices.flat)))
        doc["static_per_device_bytes"] = {
            str(k): v for k, v in estimate_per_device_bytes(
                model.graph, views, ndev,
                train=model._is_training_compile(),
                optimizer=model.optimizer,
                grad_bytes_ratio=model._grad_bytes_ratio(),
            ).items()
        }
    except Exception as e:  # fflint: disable=FFL002 — forensics are best-effort
        doc["static_per_device_bytes_error"] = str(e)
    try:
        doc["device_memory_stats"] = {
            str(d.id): (d.memory_stats() or {}) for d in jax.local_devices()
        }
    except Exception as e:  # fflint: disable=FFL002 — forensics are best-effort
        doc["device_memory_stats_error"] = str(e)
    try:
        allocs = []
        for arr in jax.live_arrays():
            allocs.append({
                "shape": list(getattr(arr, "shape", ())),
                "dtype": str(getattr(arr, "dtype", "?")),
                "nbytes": int(getattr(arr, "nbytes", 0)),
                "devices": sorted(
                    sh.device.id for sh in arr.addressable_shards
                ),
            })
        allocs.sort(key=lambda a: -a["nbytes"])
        doc["top_live_allocations"] = allocs[:top_n]
        doc["live_arrays_total_bytes"] = sum(a["nbytes"] for a in allocs)
    except Exception as e:  # fflint: disable=FFL002 — forensics are best-effort
        doc["top_live_allocations_error"] = str(e)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, OOM_FORENSICS_FILE)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return path
