"""Elastic runtime tests (runtime/elastic.py): topology fingerprinting,
checkpoint resharding onto a shrunk mesh with strategy re-search, the
health watchdog, and host-loss fault injection.

Everything runs on the CPU mesh (8 virtual devices, conftest.py);
`shrunk_devices` simulates host loss by shrinking what jax.devices()
reports. The multi-topology chaos sweep is @pytest.mark.slow and runs
standalone via scripts/elastic_check.sh."""
import os
import time

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
)
from flexflow_tpu.runtime.elastic import (
    ElasticRestoreError,
    FileHeartbeat,
    HealthMonitor,
    restore_elastic,
    shrunk_devices,
    topology_fingerprint,
    topology_matches,
    validate_machine_views,
)
from flexflow_tpu.runtime.resilience import (
    CheckpointManager,
    CollectiveTimeout,
    FaultInjector,
    HostLossError,
)

# scripts/elastic_check.sh re-runs this suite on 8/4/2-device process
# meshes (JAX_NUM_CPU_DEVICES, conftest.py); cases that encode the
# 8-device tier-1 topology (or shrink to 4 inside the process) skip on
# smaller meshes instead of asserting a device count that isn't there
import jax  # noqa: E402  (conftest configured the platform already)

NDEV = len(jax.devices())
needs8 = pytest.mark.skipif(
    NDEV != 8, reason="encodes the 8-device tier-1 mesh"
)
needs4 = pytest.mark.skipif(NDEV < 4, reason="needs >= 4 devices")


def small_model(hidden=16, batch=32, machine_file=None, search_budget=None):
    cfg = FFConfig()
    cfg.batch_size = batch
    if machine_file is not None:
        cfg.machine_model_file = machine_file
    if search_budget is not None:
        cfg.search_budget = search_budget
    m = FFModel(cfg)
    x = m.create_tensor((batch, 4), DataType.DT_FLOAT)
    t = m.dense(x, hidden, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 3)
    t = m.softmax(t)
    m.compile(SGDOptimizer(lr=0.1, momentum=0.9),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


def dataset(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    y = rng.randint(0, 3, (n, 1)).astype(np.int32)
    return x, y


def params_of(m):
    # copy=True: np.asarray(jax_array) can be a zero-copy view on CPU,
    # which the donated train step overwrites on the next fit (see
    # tests/test_resilience.py params_of)
    return {
        name: {k: np.array(v, copy=True) for k, v in wd.items()}
        for name, wd in m.state.params.items()
    }


def assert_params_close(a, b, atol=1e-6):
    for name, wd in a.items():
        for k, v in wd.items():
            np.testing.assert_allclose(b[name][k], v, atol=atol,
                                       err_msg=f"{name}/{k}")


def slow_chip_machine(tmp_path, workers=8):
    """A machine file whose chips are slow and links fast, so the
    strategy search actually spreads work (the TPU-spec defaults make a
    toy model's compute free relative to any collective, and the search
    rightly picks a single device)."""
    p = str(tmp_path / "slow_machine.cfg")
    with open(p, "w") as f:
        f.write(f"num_nodes = 1\nworkers_per_node = {workers}\n"
                "peak_flops_bf16 = 1e9\nhbm_bandwidth = 1e9\n"
                "ici_bandwidth = 1e12\nici_latency = 1e-9\n")
    return p


# ----------------------------------------------------------------------
# topology fingerprinting
# ----------------------------------------------------------------------
def test_topology_fingerprint_shape_and_match():
    m = small_model()
    fp = topology_fingerprint(m.executor.mesh)
    assert fp["num_devices"] == int(m.executor.mesh.devices.size)
    assert fp["platform"] == "cpu"
    assert fp["mesh_axes"]  # named axis -> size
    assert topology_matches(fp, dict(fp))
    changed = dict(fp, num_devices=fp["num_devices"] + 1)
    assert not topology_matches(fp, changed)
    # pre-v3 sidecars carry no fingerprint: treated as unchanged
    assert topology_matches(None, fp)
    assert topology_matches(fp, None)


@needs4
def test_fingerprint_without_mesh_uses_process_devices():
    import jax

    fp = topology_fingerprint()
    assert fp["num_devices"] == len(jax.devices())
    with shrunk_devices(4):
        assert topology_fingerprint()["num_devices"] == 4
    assert topology_fingerprint()["num_devices"] == fp["num_devices"]


def test_validate_machine_views_flags_dead_devices():
    from flexflow_tpu.pcg.machine_view import MachineView

    ok = MachineView(start_device_id=0, dim=(4,), stride=(1,))
    bad = MachineView(start_device_id=4, dim=(4,), stride=(1,))
    assert validate_machine_views({1: ok, 2: None}, 4) == []
    violations = validate_machine_views({1: ok, 2: bad}, 4)
    assert len(violations) == 1 and "op 2" in violations[0]


def test_checkpoint_sidecar_records_topology_and_views(tmp_path):
    from flexflow_tpu.runtime.checkpoint import load_checkpoint_meta

    m = small_model()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(m, step=1)
    meta = load_checkpoint_meta(mgr.step_path(1))
    assert meta["version"] >= 3
    topo = meta["topology"]
    assert topo["num_devices"] == int(m.executor.mesh.devices.size)
    # every op record carries the strategy fields an elastic restore reads
    for rec in meta["ops"]:
        assert {"name", "op_type", "machine_view", "output_degrees",
                "weight_degrees"} <= set(rec)


# ----------------------------------------------------------------------
# elastic resume across a topology change (the acceptance demo)
# ----------------------------------------------------------------------
@needs8
def test_restore_elastic_8_to_4_params_identical(tmp_path):
    """Checkpoint written on the 8-device mesh restores onto a 4-device
    survivor: strategy re-planned, params bit-identical after gather."""
    x, y = dataset(64)
    m8 = small_model()
    assert int(m8.executor.mesh.devices.size) == 8
    m8.fit(x, y, epochs=1, verbose=False, checkpoint_dir=str(tmp_path),
           checkpoint_every_n_steps=1)
    ref = params_of(m8)

    with shrunk_devices(4):
        m4, info = restore_elastic(small_model, str(tmp_path))
        assert int(m4.executor.mesh.devices.size) == 4
        assert info.step == m8.state.step
        saved_topo = info.meta["topology"]
        live_topo = topology_fingerprint(m4.executor.mesh)
        assert saved_topo["num_devices"] == 8
        assert live_topo["num_devices"] == 4
        assert not topology_matches(saved_topo, live_topo)
        assert_params_close(ref, params_of(m4), atol=0)  # bit-identical


@needs8
def test_elastic_resume_matches_uninterrupted_4dev_run(tmp_path):
    """8-device run killed after epoch 1 resumes on 4 devices and lands
    on the same params as a 4-device run that was never interrupted."""
    x, y = dataset(64)
    # reference: uninterrupted 2-epoch run entirely on 4 devices
    with shrunk_devices(4):
        mref = small_model()
        mref.fit(x, y, epochs=2, verbose=False)
        ref = params_of(mref)

    # elastic run: epoch 1 on 8 devices (same init: same seed), then the
    # pod shrinks and the run resumes on 4
    m8 = small_model()
    m8.fit(x, y, epochs=1, verbose=False, checkpoint_dir=str(tmp_path))
    with shrunk_devices(4):
        m4, info = restore_elastic(small_model, str(tmp_path))
        m4.fit(x, y, epochs=2, verbose=False, checkpoint_dir=str(tmp_path),
               elastic=True)
        # deterministic data order + SGD: only collective reduction order
        # differs between the 8- and 4-way epoch-1 sums
        assert_params_close(ref, params_of(m4), atol=1e-5)


@needs8
def test_fit_elastic_true_recompiles_after_shrink(tmp_path):
    """fit(elastic=True) itself notices the stale mesh (mesh_is_live
    False after a shrink) and re-plans before resuming."""
    x, y = dataset(64)
    m = small_model()
    m.fit(x, y, epochs=1, verbose=False, checkpoint_dir=str(tmp_path))
    ref = params_of(m)
    with shrunk_devices(4):
        assert not m.executor.mesh_is_live()
        m.fit(x, y, epochs=2, verbose=False, checkpoint_dir=str(tmp_path),
              elastic=True)
        assert int(m.executor.mesh.devices.size) == 4
        assert m.executor.mesh_is_live()
    # epoch 1 state was restored (not re-initialized) before epoch 2 ran
    assert m.state.step > 0


@needs8
def test_searched_strategy_researched_for_shrunk_machine(tmp_path):
    """With a machine file that makes the search spread (slow chips), the
    8-device searched strategy is re-searched for 4 survivors: new
    MachineViews are valid for (and the mesh spans exactly) the live
    device set."""
    mf = slow_chip_machine(tmp_path)
    x, y = dataset(64)

    def model_fn():
        return small_model(machine_file=mf, search_budget=4)

    m8 = model_fn()
    assert int(m8.executor.mesh.devices.size) == 8
    assert validate_machine_views(m8.searched_views, 8) == []
    # the 8-wide plan is NOT valid for a 4-device survivor
    assert validate_machine_views(m8.searched_views, 4) != []
    m8.fit(x, y, epochs=1, verbose=False, checkpoint_dir=str(tmp_path))
    ref = params_of(m8)

    with shrunk_devices(4):
        m4, info = restore_elastic(model_fn, str(tmp_path))
        assert int(m4.executor.mesh.devices.size) == 4
        assert validate_machine_views(m4.searched_views, 4) == []
        assert_params_close(ref, params_of(m4), atol=0)
        # the sidecar still remembers the 8-device plan it was saved under
        assert info.meta["topology"]["num_devices"] == 8


def test_restore_elastic_no_checkpoint_raises(tmp_path):
    with pytest.raises(ElasticRestoreError, match="no restorable"):
        restore_elastic(small_model, str(tmp_path / "empty"))


@needs4
def test_research_views_and_for_device_count(tmp_path):
    """The search-layer elastic entries: for_device_count re-targets a
    machine at the survivor count keeping chip constants; research_views
    reassigns valid views for it without a full substitution search."""
    from flexflow_tpu.search import (
        CostModel,
        MachineModel,
        for_device_count,
        research_views,
    )

    base = MachineModel(num_nodes=2, workers_per_node=4)
    m4 = for_device_count(4, like=base)
    assert m4.num_workers == 4 and m4.workers_per_node == 4
    assert m4.chip is base.chip or m4.chip == base.chip
    m6 = for_device_count(6, like=base)
    assert m6.num_workers == 6  # 4 doesn't divide 6: falls back to 3x2
    assert for_device_count(1, like=base).num_workers == 1

    # a graph searched for 4 devices (degree-4 structure) re-views onto a
    # GROWN 8-device machine without a full substitution search...
    with shrunk_devices(4):
        model = small_model(machine_file=slow_chip_machine(tmp_path, 4),
                            search_budget=4)
        assert int(model.executor.mesh.devices.size) == 4
    machine8 = for_device_count(8, like=model._build_cost_model().machine)
    result = research_views(model.graph, CostModel(machine8))
    assert result.cost != float("inf")
    assert validate_machine_views(result.views, 8) == []
    # ...but its degree-4 STRUCTURE cannot be re-viewed onto 2 devices:
    # infinity tells the elastic layer a full re-compile must re-search
    machine2 = for_device_count(2, like=model._build_cost_model().machine)
    assert research_views(model.graph, CostModel(machine2)).cost \
        == float("inf")


# ----------------------------------------------------------------------
# health watchdog
# ----------------------------------------------------------------------
def test_watchdog_detects_hung_step_and_flushes_checkpoint(tmp_path):
    """Acceptance: an injected hung step is detected within the timeout
    and escalates through checkpoint-and-raise (CollectiveTimeout)."""
    x, y = dataset(64)
    m = small_model()
    fi = FaultInjector().inject("hung_step", at_step=3)
    mon = HealthMonitor(timeout_s=0.5)
    t0 = time.monotonic()
    try:
        with pytest.raises(CollectiveTimeout) as ei:
            m.fit(x, y, epochs=2, verbose=False,
                  checkpoint_dir=str(tmp_path), checkpoint_every_n_steps=2,
                  fault_injector=fi, health_monitor=mon)
    finally:
        mon.stop()
    elapsed = time.monotonic() - t0
    assert ei.value.info["kind"] == "hung_step"
    assert ei.value.step == 3
    # detection bounded by the timeout (+ slack for the poll interval,
    # jit compile of the steps before the hang, and a slow CI host)
    assert elapsed < 30.0
    assert fi.fired["hung_step"] == 1
    # the last good state was flushed on the way out...
    assert ei.value.checkpoint_path is not None
    assert os.path.isdir(ei.value.checkpoint_path)
    # ...and a fresh process resumes from it
    m2 = small_model()
    m2.fit(x, y, epochs=2, verbose=False, checkpoint_dir=str(tmp_path))
    assert m2.state.step == 4  # 2 epochs x (64/32) steps, resumed


def test_watchdog_quiet_on_healthy_run(tmp_path):
    x, y = dataset(64)
    m = small_model()
    mon = HealthMonitor(timeout_s=30.0)
    try:
        m.fit(x, y, epochs=1, verbose=False, health_monitor=mon)
        assert not mon.hang_detected
    finally:
        mon.stop()


def test_file_heartbeat_detects_straggler(tmp_path):
    hb_dir = str(tmp_path / "hb")
    me = FileHeartbeat(hb_dir, "host0", stale_after_s=0.2)
    peer = FileHeartbeat(hb_dir, "host1", stale_after_s=0.2)
    peer.beat()
    assert me() == []  # fresh peer: healthy
    mon = HealthMonitor(timeout_s=5.0, heartbeat_fn=me,
                        heartbeat_interval_s=0.05).start()
    try:
        deadline = time.monotonic() + 5.0
        while not mon.hang_detected and time.monotonic() < deadline:
            time.sleep(0.05)  # host1 never beats again -> goes stale
        assert mon.hang_detected
        assert mon.hang_info["kind"] == "straggler"
        assert mon.hang_info["peers"] == ["host1"]
    finally:
        mon.stop()


def test_file_heartbeat_missing_expected_peer():
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        me = FileHeartbeat(d, "host0", stale_after_s=30.0,
                           expected_peers=["host0", "host1"])
        assert me() == ["host1"]  # expected but never appeared


def test_heartbeat_error_escalates():
    def broken():
        raise RuntimeError("transport down")

    mon = HealthMonitor(timeout_s=5.0, heartbeat_fn=broken,
                        heartbeat_interval_s=0.05).start()
    try:
        deadline = time.monotonic() + 5.0
        while not mon.hang_detected and time.monotonic() < deadline:
            time.sleep(0.02)
        assert mon.hang_detected
        assert mon.hang_info["kind"] == "heartbeat_error"
    finally:
        mon.stop()


def test_on_hang_callback_fires_once():
    calls = []
    mon = HealthMonitor(timeout_s=0.1, poll_interval_s=0.02,
                        on_hang=calls.append, compile_grace_s=0.0)
    mon.start()
    try:
        mon.step_started(7)
        deadline = time.monotonic() + 5.0
        # the flag is set before the callback runs: wait for the callback
        while not calls and time.monotonic() < deadline:
            time.sleep(0.02)
        assert mon.hang_detected
        time.sleep(0.3)  # several more polls: it does not fire again
        assert len(calls) == 1 and calls[0]["step"] == 7
    finally:
        mon.stop()


def test_first_step_gets_compile_grace():
    """The first step of a run is usually inside XLA compilation — which
    takes minutes at scale, not timeout_s — so the hung-step check gives
    it compile_grace_s of extra slack; steady-state steps get the tight
    timeout (flaked as a spurious step-0 'hang' on cold-cache CI before
    the grace window existed)."""
    mon = HealthMonitor(timeout_s=0.1, poll_interval_s=0.02,
                        compile_grace_s=30.0)
    mon.start()
    try:
        mon.step_started(0)        # "compiling": outlives timeout_s...
        time.sleep(0.5)
        assert not mon.hang_detected   # ...but sits inside the grace
        mon.step_finished(0)
        mon.step_started(1)        # steady state: tight timeout applies
        deadline = time.monotonic() + 5.0
        while not mon.hang_detected and time.monotonic() < deadline:
            time.sleep(0.02)
        assert mon.hang_detected
        assert mon.hang_info["kind"] == "hung_step"
        assert mon.hang_info["step"] == 1
    finally:
        mon.stop()


# ----------------------------------------------------------------------
# host-loss fault injection -> elastic restart
# ----------------------------------------------------------------------
@needs4
def test_host_loss_flushes_then_elastic_restart(tmp_path):
    """The orchestrator-eye view: HostLossError carries the survivor
    count, the final checkpoint is flushed, and the restarted run picks
    up on the shrunk machine."""
    x, y = dataset(64)
    m = small_model()
    fi = FaultInjector().inject("host_loss", at_step=1, surviving_devices=4)
    with pytest.raises(HostLossError) as ei:
        m.fit(x, y, epochs=2, verbose=False, checkpoint_dir=str(tmp_path),
              fault_injector=fi)
    assert ei.value.surviving_devices == 4
    assert ei.value.checkpoint_path is not None  # graceful: state flushed

    with shrunk_devices(ei.value.surviving_devices):
        m2, info = restore_elastic(small_model, str(tmp_path))
        assert info.step == 1
        m2.fit(x, y, epochs=2, verbose=False, checkpoint_dir=str(tmp_path),
               elastic=True)
        assert m2.state.step == 4  # 2 epochs x 2 steps, resumed mid-run


# ----------------------------------------------------------------------
# slow chaos sweep (scripts/elastic_check.sh)
# ----------------------------------------------------------------------
@pytest.mark.slow
@needs8
def test_elastic_shrink_sweep_8_4_2(tmp_path):
    """8 -> 4 -> 2 device shrink chain: each resume restores the previous
    topology's checkpoint bit-identically and keeps training."""
    x, y = dataset(64)
    m = small_model()
    m.fit(x, y, epochs=1, verbose=False, checkpoint_dir=str(tmp_path))
    prev = params_of(m)
    expected_step = m.state.step
    for n, epochs in ((4, 2), (2, 3)):
        with shrunk_devices(n):
            mn, info = restore_elastic(small_model, str(tmp_path))
            assert int(mn.executor.mesh.devices.size) == n
            assert info.step == expected_step
            assert_params_close(prev, params_of(mn), atol=0)
            mn.fit(x, y, epochs=epochs, verbose=False,
                   checkpoint_dir=str(tmp_path), elastic=True)
            prev = params_of(mn)
            expected_step = mn.state.step
    assert expected_step == 3 * 2  # 3 epochs total, 2 steps each


@pytest.mark.slow
@needs4
def test_hung_step_then_elastic_restart_on_survivors(tmp_path):
    """The full production story in one test: a collective hangs (host
    died mid-psum), the watchdog checkpoints-and-raises, the orchestrator
    restarts on the survivors, training continues elastically."""
    x, y = dataset(64)
    m = small_model()
    fi = FaultInjector().inject("hung_step", at_step=2)
    mon = HealthMonitor(timeout_s=0.5)
    try:
        with pytest.raises(CollectiveTimeout) as ei:
            m.fit(x, y, epochs=3, verbose=False,
                  checkpoint_dir=str(tmp_path), checkpoint_every_n_steps=1,
                  fault_injector=fi, health_monitor=mon)
    finally:
        mon.stop()
    assert ei.value.checkpoint_path is not None
    with shrunk_devices(4):
        m2, info = restore_elastic(small_model, str(tmp_path))
        assert info.step == 2
        mon2 = HealthMonitor(timeout_s=30.0)
        try:
            m2.fit(x, y, epochs=3, verbose=False,
                   checkpoint_dir=str(tmp_path), elastic=True,
                   health_monitor=mon2)
            assert not mon2.hang_detected
        finally:
            mon2.stop()
        assert m2.state.step == 3 * 2
