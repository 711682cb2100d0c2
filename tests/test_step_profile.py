"""Step-observatory tests (flexflow_tpu/obs/step_profile.py): in-situ
capture of the real jitted training step (instrumented CPU fallback),
the simulated/measured overlay, overlap-realization measurement + its
calibration write-through, HBM watermark reconciliation, counter-event
round-trip, and the BENCH-history regression attribution."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
    TelemetryConfig,
)
import flexflow_tpu.obs as obs
from flexflow_tpu.obs.step_profile import (
    MEASURED_CAT,
    OVERLAY_FILE,
    HbmSampler,
    capture_step_profile,
)
from flexflow_tpu.obs.tracer import (
    Tracer,
    read_events_jsonl,
    to_chrome_trace,
    validate_event,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_session():
    obs.finish()
    yield
    obs.finish()


def small_model():
    """Default config (no search) -> manual lowering -> data degree =
    ndev, so the capture actually measures grad-sync collectives."""
    cfg = FFConfig()
    cfg.batch_size = 8
    m = FFModel(cfg)
    x = m.create_tensor((8, 4), DataType.DT_FLOAT)
    t = m.dense(x, 16, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 3)
    t = m.softmax(t)
    m.compile(SGDOptimizer(lr=0.1),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


def data(n=32, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, 4).astype(np.float32),
            rng.randint(0, 3, (n, 1)).astype(np.int32))


@pytest.fixture(scope="module")
def captured():
    """One capture shared by the read-only assertions (the capture jits
    the fused + serial steps and every isolated collective — too slow to
    repeat per test)."""
    m = small_model()
    x, y = data()
    prof = capture_step_profile(m, x, y, batch_size=8, repeats=1, warmup=1)
    return m, prof


# ----------------------------------------------------------------------
# capture: CPU fallback, event schema, realization bounds
# ----------------------------------------------------------------------
def test_cpu_capture_falls_back_to_instrumented(captured):
    _, prof = captured
    assert prof.mode == "instrumented"
    assert prof.backend == "cpu"
    assert prof.step_wall_s > 0
    assert prof.serial_step_wall_s > 0


def test_capture_events_are_schema_valid(captured):
    _, prof = captured
    assert prof.events, "capture produced no timeline events"
    for e in prof.events:
        assert validate_event(e) == [], e
        assert e["cat"] == MEASURED_CAT
    names = {e["name"] for e in prof.events}
    # forward, backward, and grad-sync spans of the two dense layers
    assert "op_linear_0" in names
    assert "op_linear_0.bwd" in names
    assert "op_linear_0.grad_sync" in names


def test_collectives_measured_on_data_parallel_mesh(captured):
    m, prof = captured
    assert prof.data_degree == m.executor.mesh.shape["data"] > 1
    assert prof.collectives, "no grad-sync collectives measured"
    for c in prof.collectives:
        assert c.sync_s > 0
        assert c.wire_bytes > 0
        assert 0.0 <= c.hidden_s <= c.sync_s + 1e-12
        assert c.exposed_s >= 0.0
    bw = prof.collective_bandwidths()
    assert bw and all(v > 0 for v in bw.values())


def test_realized_ratio_bounds(captured):
    _, prof = captured
    r = prof.realized_ratio
    assert r is not None
    assert 0.0 <= r <= 1.0


def test_grad_sync_spans_carry_attribution_args(captured):
    _, prof = captured
    syncs = [e for e in prof.events if e["name"].endswith(".grad_sync")]
    assert len(syncs) == len(prof.collectives)
    for e in syncs:
        a = e["args"]
        assert a["source"] == "measured_isolated"
        assert a["hidden_s"] + a["exposed_s"] == pytest.approx(e["dur"])
        assert a["bytes_per_s"] > 0


# ----------------------------------------------------------------------
# overlay: one file, two process groups, shared timebase
# ----------------------------------------------------------------------
def test_overlay_has_both_process_groups(tmp_path, captured):
    from flexflow_tpu.obs.step_profile import export_overlay

    m, prof = captured
    path = export_overlay(prof, m, str(tmp_path / "overlay.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    groups = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
    assert {"simulated", "measured"} <= groups
    spans = [e for e in evs if e.get("ph") == "X"]
    assert min(e["ts"] for e in spans) == 0.0  # rebased shared timebase
    pid_names = {e["pid"]: e["args"]["name"] for e in evs
                 if e.get("ph") == "M"}
    by_group = {g: 0 for g in ("simulated", "measured")}
    for e in spans:
        g = pid_names.get(e["pid"])
        if g in by_group:
            by_group[g] += 1
    assert by_group["simulated"] > 0 and by_group["measured"] > 0


# ----------------------------------------------------------------------
# HBM: sampler fallback + reconciliation ratio
# ----------------------------------------------------------------------
def test_hbm_sampler_cpu_fallback(captured):
    _, prof = captured
    assert prof.hbm is not None
    # CPU devices have no memory_stats -> live_arrays allocator estimate
    assert prof.hbm.source == "live_arrays"
    assert prof.hbm.measured_peak > 0
    assert prof.hbm.peak_bytes  # per-device watermarks


def test_hbm_static_accuracy_ratio(captured):
    _, prof = captured
    acc = prof.hbm.static_accuracy
    assert acc is not None and acc > 0
    assert acc == pytest.approx(
        prof.hbm.static_peak / prof.hbm.measured_peak)


def test_hbm_sampler_direct():
    import jax

    s = HbmSampler(jax.local_devices())
    s.sample()
    assert s.source in ("memory_stats", "live_arrays")
    assert s.peak and all(v >= 0 for v in s.peak.values())


def test_memory_reconciliation_diagnostics():
    from flexflow_tpu.analysis.memory import (
        memory_reconciliation_diagnostics,
    )

    rep, ratio = memory_reconciliation_diagnostics(
        {0: 800}, {0: 1000}, source="live_arrays")
    assert ratio == pytest.approx(0.8)
    assert any(d.severity.name == "WARNING" for d in rep)  # under-predicts
    rep2, ratio2 = memory_reconciliation_diagnostics({}, {0: 1000})
    assert ratio2 is None
    assert not rep2.warnings


# ----------------------------------------------------------------------
# telemetry session: publish + calibration write-through
# ----------------------------------------------------------------------
def test_fit_step_profile_session_artifacts(tmp_path):
    m = small_model()
    x, y = data()
    teldir = str(tmp_path / "tel")
    calib = str(tmp_path / "calib.json")
    m.fit(x, y, batch_size=8, epochs=1, verbose=False,
          telemetry=TelemetryConfig(dir=teldir, step_profile=True,
                                    step_profile_repeats=1,
                                    calibration_path=calib))
    events, problems = read_events_jsonl(os.path.join(teldir,
                                                      "events.jsonl"))
    assert not problems
    counters = [e for e in events if e["ph"] == "C"]
    assert counters, "no hbm_bytes counter tracks"
    assert any(e["name"] == "step_profile" for e in events)
    overlay = json.load(open(os.path.join(teldir, OVERLAY_FILE)))
    groups = {e["args"]["name"] for e in overlay["traceEvents"]
              if e.get("ph") == "M"}
    assert {"simulated", "measured"} <= groups
    prom = open(os.path.join(teldir, "metrics.prom")).read()
    assert "ff_overlap_realized_ratio" in prom
    assert "ff_hbm_peak_bytes" in prom
    assert "ff_hbm_static_accuracy" in prom
    glb = json.load(open(calib))["globals"]
    assert 0 < glb["overlap_efficiency"] <= 1.0
    assert glb["collective_bytes_per_s"]


def test_calibration_write_through_to_fresh_process(tmp_path):
    """The acceptance loop: a session capture writes the measured
    overlap efficiency + collective bandwidths, and a FRESH process's
    compile(calibration=...) prices overlap from them (reported in the
    cost model's provenance)."""
    m = small_model()
    x, y = data()
    calib = str(tmp_path / "calib.json")
    m.fit(x, y, batch_size=8, epochs=1, verbose=False,
          telemetry=TelemetryConfig(dir=str(tmp_path / "tel"),
                                    step_profile=True,
                                    step_profile_repeats=1,
                                    calibration_path=calib))
    code = f"""
import json
from flexflow_tpu import (ActiMode, DataType, FFConfig, FFModel, LossType,
                          MetricsType, SGDOptimizer)
cfg = FFConfig()
cfg.batch_size = 8
m = FFModel(cfg)
x = m.create_tensor((8, 4), DataType.DT_FLOAT)
t = m.dense(x, 16, ActiMode.AC_MODE_RELU)
t = m.softmax(m.dense(t, 3))
m.compile(SGDOptimizer(lr=0.1),
          LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
          [MetricsType.METRICS_ACCURACY], calibration={calib!r})
print(json.dumps(m._build_cost_model().provenance()))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=os.environ.copy(), timeout=300)
    assert r.returncode == 0, r.stderr
    prov = json.loads(r.stdout.strip().splitlines()[-1])
    assert prov["overlap_efficiency_source"] == "calibration_store"
    assert 0 < prov["overlap_efficiency"] <= 1.0
    assert prov["collective_bytes_per_s"]


# ----------------------------------------------------------------------
# counter events (satellite: tracer ph="C")
# ----------------------------------------------------------------------
def test_counter_event_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    tr = Tracer(path)
    tr.counter("hbm_bytes", cat="measured", tid=3, device3=123.0)
    tr.flush()
    events, problems = read_events_jsonl(path)
    assert not problems
    [e] = events
    assert e["ph"] == "C"
    assert e["args"] == {"device3": 123.0}
    chrome = to_chrome_trace(events)
    entry = next(c for c in chrome["traceEvents"] if c.get("ph") == "C")
    assert entry["args"] == {"device3": 123.0}  # series pass through
    assert "s" not in entry  # instant-scope key must not leak onto C


def test_counter_event_validation():
    ok = {"ts": 0.0, "ph": "C", "name": "n", "cat": "c",
          "tid": 0, "args": {"v": 1.0}}
    assert validate_event(ok) == []
    bad_empty = dict(ok, args={})
    assert validate_event(bad_empty)
    bad_value = dict(ok, args={"v": "high"})
    assert validate_event(bad_value)
    bad_bool = dict(ok, args={"v": True})
    assert validate_event(bad_bool)


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------
def test_cli_summary_reports_step_observatory(tmp_path):
    m = small_model()
    x, y = data()
    teldir = str(tmp_path / "tel")
    m.fit(x, y, batch_size=8, epochs=1, verbose=False,
          telemetry=TelemetryConfig(dir=teldir, step_profile=True,
                                    step_profile_repeats=1))
    r = subprocess.run(
        [sys.executable, "-m", "flexflow_tpu.obs", "summary",
         os.path.join(teldir, "events.jsonl")],
        capture_output=True, text=True, env=os.environ.copy(), timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "step observatory" in r.stdout
    assert "overlap realization" in r.stdout
    assert "measured-vs-simulated drift" in r.stdout


# ----------------------------------------------------------------------
# explain: in-situ join
# ----------------------------------------------------------------------
def test_explain_joins_in_situ_measurements(captured):
    m, prof = captured
    exp = obs.explain_strategy(m, repeats=1, warmup=1, step_profile=prof)
    rows = [r for r in exp.rows if r.get("insitu_total_s") is not None]
    assert rows, "no explain row joined an in-situ measurement"
    for r in rows:
        assert r["insitu_total_s"] > 0
        assert r["insitu_source"] == "instrumented"
    assert "insitu ms" in exp.summary(5)


# ----------------------------------------------------------------------
# overlap-realization analysis (FFA506)
# ----------------------------------------------------------------------
def test_overlap_realization_diagnostics(captured):
    from flexflow_tpu.analysis.perf import overlap_realization_diagnostics

    _, prof = captured
    rep = overlap_realization_diagnostics(prof)
    assert any(d.code == "FFA506" for d in rep)
    # realized on CPU is far below the assumed discount -> must warn
    if prof.realized_ratio is not None and \
            prof.realized_ratio < prof.assumed_efficiency - 0.1:
        assert rep.warnings
