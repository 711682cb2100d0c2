"""Fault-tolerance tests (runtime/resilience.py): retry/backoff policy,
deterministic fault injection, atomic checkpointing + retention,
preemption/resume equivalence, NaN-step guard, serving degraded mode.

Everything runs on the CPU mesh; the slow chaos sweep is marked
@pytest.mark.slow and runs standalone via scripts/chaos_check.sh."""
import os

import numpy as np
import pytest  # noqa: F401

from flexflow_tpu import (
    ActiMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.runtime.resilience import (
    CheckpointManager,
    FaultInjector,
    InferenceTimeout,
    NonFiniteGradientsError,
    PreemptionSignal,
    RetryPolicy,
    StepGuardConfig,
    TrainingPreempted,
    restore_latest,
    retry,
)


def small_model(hidden=16):
    cfg = FFConfig()
    cfg.batch_size = 8
    m = FFModel(cfg)
    x = m.create_tensor((8, 4), DataType.DT_FLOAT)
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
    # copy=True is load-bearing: on CPU np.asarray(jax_array) can be a
    # zero-copy VIEW of the device buffer, and the train step's
    # donate_argnums reuses that memory on the next fit — a view snapshot
    # silently morphs into later-step values (flaked whenever a warm jit
    # cache made training fast enough for the race to land)
    return {
        name: {k: np.array(v, copy=True) for k, v in wd.items()}
        for name, wd in m.state.params.items()
    }


def assert_params_close(a, b, atol=1e-6):
    for name, wd in a.items():
        for k, v in wd.items():
            np.testing.assert_allclose(b[name][k], v, atol=atol,
                                       err_msg=f"{name}/{k}")


# ----------------------------------------------------------------------
# retry / backoff
# ----------------------------------------------------------------------
def test_retry_succeeds_after_transient_failures():
    delays, calls = [], []
    policy = RetryPolicy(max_attempts=4, base_delay_s=0.1, multiplier=2.0,
                         jitter=0.0)

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    out = retry(flaky, policy, sleep=delays.append)
    assert out == "ok"
    assert len(calls) == 3
    # exponential backoff: base, base*mult
    assert delays == pytest.approx([0.1, 0.2])


def test_retry_exhaustion_raises_last_error():
    policy = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0)
    calls = []

    def always_fails():
        calls.append(1)
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        retry(always_fails, policy, sleep=lambda d: None)
    assert len(calls) == 3


def test_retry_non_retryable_propagates_immediately():
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        retry(bad, RetryPolicy(max_attempts=5), sleep=lambda d: None)
    assert len(calls) == 1  # ValueError is not in retry_on


def test_retry_policy_delay_jitter_and_cap():
    policy = RetryPolicy(base_delay_s=1.0, multiplier=10.0, max_delay_s=5.0,
                         jitter=0.5)
    # attempt 3 uncapped would be 1000s; capped at 5 then jittered +/-50%
    for r in (0.0, 0.5, 1.0):
        d = policy.delay(3, rand=lambda: r)
        assert 2.5 - 1e-9 <= d <= 7.5 + 1e-9


# ----------------------------------------------------------------------
# fault injector
# ----------------------------------------------------------------------
def test_fault_injector_step_targeting_and_shot_count():
    fi = FaultInjector()
    fi.inject("nan_grads", at_step=3, times=2)
    assert fi.fire("nan_grads", 2) is None
    assert fi.fire("nan_grads", 3) is not None
    assert fi.fire("nan_grads", 3) is not None
    assert fi.fire("nan_grads", 3) is None  # shots exhausted
    assert fi.pending("nan_grads") == 0
    assert fi.fired["nan_grads"] == 2


def test_fault_injector_raises_armed_exception():
    fi = FaultInjector()
    fi.inject("checkpoint_write", exc=IOError("disk full"), times=1)
    with pytest.raises(IOError, match="disk full"):
        fi.fire("checkpoint_write", 0)
    assert fi.fire("checkpoint_write", 1) is None  # consumed


# ----------------------------------------------------------------------
# checkpoint manager: atomicity, retention, latest, fallback
# ----------------------------------------------------------------------
def _no_partials(directory):
    return [n for n in os.listdir(directory) if ".tmp" in n]


def test_checkpoint_write_ioerror_is_retried_atomically(tmp_path):
    m = small_model()
    fi = FaultInjector()
    fi.inject("checkpoint_write", exc=IOError("injected"), times=1)
    mgr = CheckpointManager(str(tmp_path), fault_injector=fi,
                           retry_policy=RetryPolicy(max_attempts=3,
                                                    base_delay_s=0.0),
                           sleep=lambda d: None)
    path = mgr.save(m, step=5)
    assert fi.fired["checkpoint_write"] == 1
    assert os.path.isdir(path)
    assert _no_partials(str(tmp_path)) == []
    # the retried checkpoint restores cleanly
    m2 = small_model()
    info = mgr.restore_latest(m2)
    assert info is not None and info.step == 5
    assert_params_close(params_of(m), params_of(m2))


def test_checkpoint_write_failure_never_leaves_partial(tmp_path):
    m = small_model()
    fi = FaultInjector()
    fi.inject("checkpoint_write", exc=IOError("injected"), times=10)
    mgr = CheckpointManager(str(tmp_path), fault_injector=fi,
                           retry_policy=RetryPolicy(max_attempts=2,
                                                    base_delay_s=0.0),
                           sleep=lambda d: None)
    with pytest.raises(IOError):
        mgr.save(m, step=1)
    assert mgr.list_steps() == []  # no complete checkpoint...
    assert _no_partials(str(tmp_path)) == []  # ...and no debris either


def test_stale_tmp_sweep_spares_this_process_own_writers(tmp_path):
    """A manager opened while another in this process is mid-save (a
    restarted replica restoring beside ReplicaSet.start's first
    checkpoint) sweeps only other processes' debris. orbax fills a
    directory named after ours plus a suffix of its own: that one is
    ours too."""
    mgr = CheckpointManager(str(tmp_path))
    ours = f"step_0000000000.tmp-{os.getpid()}.orbax-checkpoint-tmp"
    theirs = "step_0000000000.tmp-1.orbax-checkpoint-tmp"
    for name in (ours, theirs, f"step_0000000001.tmp-{os.getpid()}"):
        os.makedirs(tmp_path / name)
    mgr.clean_stale_tmp()
    assert sorted(os.listdir(tmp_path)) == [
        ours, f"step_0000000001.tmp-{os.getpid()}"]


def test_checkpoint_retention_and_latest_pointer(tmp_path):
    m = small_model()
    mgr = CheckpointManager(str(tmp_path), keep_last_n=2)
    for s in (1, 2, 3, 4, 5):
        mgr.save(m, step=s)
    assert mgr.list_steps() == [4, 5]
    assert mgr.latest_step() == 5
    assert not os.path.exists(mgr.step_path(3) + ".meta.json")  # GC'd sidecars


def test_restore_latest_falls_back_past_corrupt_newest(tmp_path):
    import shutil

    m = small_model()
    x, y = dataset(16)
    mgr = CheckpointManager(str(tmp_path), keep_last_n=3)
    mgr.save(m, step=1)
    m.fit(x, y, batch_size=8, epochs=1, verbose=False)
    good = params_of(m)
    mgr.save(m, step=2)
    m.fit(x, y, batch_size=8, epochs=1, verbose=False)
    mgr.save(m, step=3)
    # corrupt the newest (simulates a crash torn exactly mid-directory)
    shutil.rmtree(mgr.step_path(3))
    os.makedirs(mgr.step_path(3))
    m2 = small_model()
    with pytest.warns(UserWarning, match="falling back"):
        info = mgr.restore_latest(m2)
    assert info is not None and info.step == 2
    assert_params_close(good, params_of(m2))


# ----------------------------------------------------------------------
# preemption + mid-epoch resume (the acceptance demo)
# ----------------------------------------------------------------------
def test_hard_preemption_resume_matches_uninterrupted(tmp_path):
    x, y = dataset(64)
    # reference: uninterrupted 2-epoch run (plain fit loop)
    mA = small_model()
    mA.fit(x, y, batch_size=8, epochs=2, verbose=False)
    ref = params_of(mA)

    # run B: hard-killed (no final flush) mid-epoch 1 at step 10
    mB = small_model()
    fi = FaultInjector().inject("preempt", at_step=10, graceful=False)
    with pytest.raises(TrainingPreempted) as ei:
        mB.fit(x, y, batch_size=8, epochs=2, verbose=False,
               checkpoint_dir=str(tmp_path), checkpoint_every_n_steps=3,
               fault_injector=fi)
    assert ei.value.step == 10
    assert ei.value.checkpoint_path is None  # hard kill: nothing flushed

    # fresh process resumes from the last periodic checkpoint (step 9,
    # mid-epoch cursor) and replays deterministically to the same params
    mB2 = small_model()
    mB2.fit(x, y, batch_size=8, epochs=2, verbose=False,
            checkpoint_dir=str(tmp_path), checkpoint_every_n_steps=3)
    assert_params_close(ref, params_of(mB2))


def test_graceful_preemption_flushes_final_checkpoint(tmp_path):
    x, y = dataset(64)
    mA = small_model()
    mA.fit(x, y, batch_size=8, epochs=2, verbose=False)
    ref = params_of(mA)

    mB = small_model()
    fi = FaultInjector().inject("preempt", at_step=7)  # graceful default
    with pytest.raises(TrainingPreempted) as ei:
        mB.fit(x, y, batch_size=8, epochs=2, verbose=False,
               checkpoint_dir=str(tmp_path), checkpoint_every_n_steps=100,
               fault_injector=fi)
    # SIGTERM grace period flushed the exact step-7 state
    assert ei.value.checkpoint_path is not None
    assert os.path.isdir(ei.value.checkpoint_path)

    mB2 = small_model()
    mB2.fit(x, y, batch_size=8, epochs=2, verbose=False,
            checkpoint_dir=str(tmp_path), checkpoint_every_n_steps=100)
    assert_params_close(ref, params_of(mB2))


def test_preemption_signal_flag_between_steps(tmp_path):
    x, y = dataset(32)
    sig = PreemptionSignal()
    sig.trigger(graceful=True)
    m = small_model()
    with pytest.raises(TrainingPreempted) as ei:
        m.fit(x, y, batch_size=8, epochs=1, verbose=False,
              preemption_signal=sig)
    assert ei.value.step == 0  # armed before any step ran
    sig.clear()
    m.fit(x, y, batch_size=8, epochs=1, verbose=False,
          preemption_signal=sig)  # cleared flag trains normally


def test_restore_latest_convenience_and_empty_dir(tmp_path):
    m = small_model()
    assert restore_latest(m, str(tmp_path)) is None
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(m, step=11)
    m2 = small_model()
    info = restore_latest(m2, str(tmp_path))
    assert info is not None and info.step == 11


# ----------------------------------------------------------------------
# NaN/Inf step guard
# ----------------------------------------------------------------------
def test_nan_step_skipped_without_corrupting_params():
    x, y = dataset(64)
    # reference run skipping nothing, to locate params just before step 2
    m = small_model()
    fi = FaultInjector().inject("nan_grads", at_step=2)
    m.fit(x, y, batch_size=8, epochs=1, verbose=False,
          skip_nonfinite_steps=True, fault_injector=fi)
    g = m.state.guard
    assert int(np.asarray(g.total_skips)) == 1
    assert int(np.asarray(g.consecutive_skips)) == 0  # recovered after
    # loss-scale backoff: 1.0 -> 0.5 (regrowth interval not reached)
    assert float(np.asarray(g.loss_scale)) == pytest.approx(0.5)
    for wd in m.state.params.values():
        for v in wd.values():
            assert np.isfinite(np.asarray(v)).all()


def test_skipped_step_carries_params_and_momentum_through():
    x, y = dataset(16)
    m = small_model()
    # train one good step, snapshot, then poison the next step only
    m.fit(x[:8], y[:8], batch_size=8, epochs=1, verbose=False,
          skip_nonfinite_steps=True)
    before = params_of(m)
    mom_before = {
        name: {k: np.asarray(v) for k, v in wd.items()}
        for name, wd in m.state.opt_state["v"].items()
    }
    fi = FaultInjector().inject("nan_grads", at_step=0)
    m.fit(x[8:16], y[8:16], batch_size=8, epochs=1, verbose=False,
          skip_nonfinite_steps=True, fault_injector=fi)
    assert int(np.asarray(m.state.guard.total_skips)) == 1
    assert_params_close(before, params_of(m))  # update skipped exactly
    for name, wd in mom_before.items():
        for k, v in wd.items():
            np.testing.assert_allclose(
                np.asarray(m.state.opt_state["v"][name][k]), v, atol=1e-7
            )


def test_persistent_nan_hard_fails_after_max_consecutive_skips():
    x, y = dataset(64)
    m = small_model()
    fi = FaultInjector().inject("nan_grads", times=1000)  # every step
    with pytest.raises(NonFiniteGradientsError, match="consecutive"):
        m.fit(x, y, batch_size=8, epochs=8, verbose=False,
              skip_nonfinite_steps=True, max_consecutive_skips=3,
              fault_injector=fi)
    assert int(np.asarray(m.state.guard.consecutive_skips)) == 3


def test_loss_scale_regrowth_after_backoff():
    x, y = dataset(64)
    m = small_model()
    guard = StepGuardConfig(growth_interval=3, max_consecutive_skips=5)
    fi = FaultInjector().inject("nan_grads", at_step=1)
    m.fit(x, y, batch_size=8, epochs=1, verbose=False, step_guard=guard,
          fault_injector=fi)
    # backoff at step 1 (1.0 -> 0.5), then 3 good steps regrow to the
    # cap (max defaults to init_loss_scale = 1.0, never beyond)
    assert float(np.asarray(m.state.guard.loss_scale)) == pytest.approx(1.0)
    assert int(np.asarray(m.state.guard.total_skips)) == 1


def test_guard_state_round_trips_through_checkpoint(tmp_path):
    x, y = dataset(32)
    m = small_model()
    fi = FaultInjector().inject("nan_grads", at_step=1)
    m.fit(x, y, batch_size=8, epochs=1, verbose=False,
          skip_nonfinite_steps=True, fault_injector=fi,
          checkpoint_dir=str(tmp_path), checkpoint_every_n_steps=2)
    scale = float(np.asarray(m.state.guard.loss_scale))
    assert scale == pytest.approx(0.5)
    m2 = small_model()
    info = CheckpointManager(str(tmp_path)).restore_latest(m2)
    assert info is not None  # restore attaches the saved guard state
    assert float(np.asarray(m2.state.guard.loss_scale)) == pytest.approx(scale)
    assert int(np.asarray(m2.state.guard.total_skips)) == 1


# ----------------------------------------------------------------------
# serving: typed timeout, retry, degraded mode
# ----------------------------------------------------------------------
def test_serving_unstarted_scheduler_degrades_to_direct():
    from flexflow_tpu.runtime.serving import BatchScheduler

    m = small_model()
    sched = BatchScheduler(m)  # never .start()ed
    out = sched.infer([np.zeros(4, np.float32)], timeout=1.0)
    assert out.shape == (3,)
    assert sched.stats["degraded"] == 1


def test_serving_worker_death_falls_back_unbatched():
    from flexflow_tpu.runtime.serving import BatchScheduler

    m = small_model()
    fi = FaultInjector()
    fi.inject("serving_worker", exc=RuntimeError("worker crash"), times=1)
    # max_worker_restarts=0: the operator opted out of auto-restart, so a
    # dead worker degrades traffic permanently (the pre-restart contract)
    sched = BatchScheduler(m, fault_injector=fi, max_worker_restarts=0).start()
    try:
        # first request crashes the worker; the caller still gets an
        # answer from the degraded path, and so does all later traffic
        out1 = sched.infer([np.zeros(4, np.float32)], timeout=5.0)
        out2 = sched.infer([np.ones(4, np.float32)], timeout=5.0)
        assert out1.shape == (3,) and out2.shape == (3,)
        assert not sched.worker_alive()
        assert sched.stats["degraded"] >= 2
        assert sched.stats["worker_restarts"] == 0
    finally:
        sched.stop()


def test_serving_worker_auto_restarts_after_crash():
    import time as _time

    from flexflow_tpu.runtime.serving import BatchScheduler

    m = small_model()
    fi = FaultInjector()
    fi.inject("serving_worker", exc=RuntimeError("worker crash"), times=1)
    sched = BatchScheduler(m, fault_injector=fi, max_worker_restarts=3,
                           restart_backoff_s=0.01).start()
    try:
        out1 = sched.infer([np.zeros(4, np.float32)], timeout=5.0)
        assert out1.shape == (3,)  # crash answered via degraded path
        _time.sleep(0.05)  # let the backoff window open
        out2 = sched.infer([np.ones(4, np.float32)], timeout=5.0)
        assert out2.shape == (3,)
        assert sched.stats["worker_restarts"] == 1
        assert sched.worker_alive()  # restarted worker handles traffic
    finally:
        sched.stop()


def test_serving_worker_restart_budget_then_stays_degraded():
    import time as _time

    from flexflow_tpu.runtime.serving import BatchScheduler

    m = small_model()
    fi = FaultInjector()
    # every revived worker dies again on its first batch
    fi.inject("serving_worker", exc=RuntimeError("worker crash"), times=50)
    sched = BatchScheduler(m, fault_injector=fi, max_worker_restarts=2,
                           restart_backoff_s=0.0).start()
    try:
        for i in range(6):
            out = sched.infer([np.zeros(4, np.float32)], timeout=5.0)
            assert out.shape == (3,)
            _time.sleep(0.02)
        # budget spent: exactly max_worker_restarts revivals, then the
        # scheduler stays degraded (but keeps answering) forever
        assert sched.stats["worker_restarts"] == 2
        assert not sched.worker_alive()
        assert sched.stats["degraded"] >= 1
    finally:
        sched.stop()


def test_serving_timeout_raises_typed_error(monkeypatch):
    import time as _time

    from flexflow_tpu.runtime.serving import BatchScheduler

    m = small_model()
    sched = BatchScheduler(m, retry_policy=RetryPolicy(max_attempts=1))
    slow_fwd = sched._fwd

    def stalled(*a, **kw):
        _time.sleep(0.5)
        return slow_fwd(*a, **kw)

    monkeypatch.setattr(sched, "_fwd", stalled)
    sched.start()
    try:
        with pytest.raises(InferenceTimeout, match="unanswered"):
            sched.infer([np.zeros(4, np.float32)], timeout=0.05)
        assert sched.stats["timeouts"] == 1
    finally:
        sched.stop()


def test_serving_batched_path_still_works():
    from flexflow_tpu.runtime.serving import BatchScheduler

    m = small_model()
    sched = BatchScheduler(m).start()
    try:
        out = sched.infer([np.zeros(4, np.float32)], timeout=10.0)
        assert out.shape == (3,)
        assert sched.stats["degraded"] == 0
        assert sched.stats["batches"] == 1
    finally:
        sched.stop()


# ----------------------------------------------------------------------
# distributed init retry
# ----------------------------------------------------------------------
def test_init_distributed_retries_coordinator_connect(monkeypatch):
    import jax

    from flexflow_tpu.runtime import distributed

    calls = []

    def flaky_initialize(**kw):
        calls.append(kw)
        if len(calls) < 3:
            raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", flaky_initialize)
    assert not distributed.is_initialized()
    try:
        pid, nproc, devs = distributed.init_distributed(
            coordinator_address="127.0.0.1:1234",
            num_processes=1, process_id=0,
            retry_policy=RetryPolicy(
                max_attempts=4, base_delay_s=0.0, jitter=0.0,
                retry_on=(RuntimeError,),
            ),
        )
        assert len(calls) == 3  # two failures, then success
        assert nproc == 1
    finally:
        distributed._initialized = False


def test_init_distributed_exhausted_retries_raise(monkeypatch):
    import jax

    from flexflow_tpu.runtime import distributed

    def dead_initialize(**kw):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", dead_initialize)
    with pytest.raises(RuntimeError, match="unreachable"):
        distributed.init_distributed(
            coordinator_address="127.0.0.1:1234",
            num_processes=1, process_id=0,
            retry_policy=RetryPolicy(
                max_attempts=2, base_delay_s=0.0, jitter=0.0,
                retry_on=(RuntimeError,),
            ),
        )
    assert not distributed.is_initialized()


def test_is_initialized_probes_externally_initialized_runtime(monkeypatch):
    """A launcher (or user code) that called jax.distributed.initialize
    directly never set our module flag — is_initialized() must still see
    the live multi-process runtime via the process-count probe."""
    import jax

    from flexflow_tpu.runtime import distributed

    assert not distributed._initialized
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    assert distributed.is_initialized()
    monkeypatch.setattr(jax, "process_count", lambda: 1)
    assert not distributed.is_initialized()


def test_shutdown_idempotent(monkeypatch):
    import jax

    from flexflow_tpu.runtime import distributed

    calls = []

    def fake_shutdown():
        if calls:
            raise RuntimeError("distributed runtime already shut down")
        calls.append(1)

    monkeypatch.setattr(jax.distributed, "shutdown", fake_shutdown)
    # never initialized: a no-op, not a crash
    distributed.shutdown()
    assert calls == []
    # initialized once: tears down exactly once, repeat calls are no-ops
    distributed._initialized = True
    distributed.shutdown()
    distributed.shutdown()
    distributed.shutdown()
    assert calls == [1]
    assert not distributed._initialized
    # even a racing double-teardown under the flag is swallowed
    distributed._initialized = True
    distributed.shutdown()  # fake now raises RuntimeError — absorbed
    assert not distributed._initialized


# ----------------------------------------------------------------------
# chaos sweep (slow; scripts/chaos_check.sh runs it standalone)
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_chaos_sweep_all_faults_together(tmp_path):
    """NaN batches + checkpoint IOErrors + repeated hard preemptions in
    one run: the sequence of restarts must still land on the
    uninterrupted run's loss surface (guard skips are data-free steps, so
    allow tolerance rather than exactness)."""
    x, y = dataset(64)
    mA = small_model()
    mA.fit(x, y, batch_size=8, epochs=3, verbose=False)
    ref = params_of(mA)

    ckpt = str(tmp_path)
    mB = small_model()
    fi = FaultInjector()
    fi.inject("preempt", at_step=5, graceful=False)
    fi.inject("preempt", at_step=13, graceful=False)
    fi.inject("checkpoint_write", exc=IOError("flaky disk"), times=2)
    attempts = 0
    while attempts < 10:
        attempts += 1
        try:
            mB.fit(x, y, batch_size=8, epochs=3, verbose=False,
                   checkpoint_dir=ckpt, checkpoint_every_n_steps=2,
                   fault_injector=fi)
            break
        except TrainingPreempted:
            mB = small_model()  # fresh process after each kill
    else:
        pytest.fail("chaos run never completed")
    assert _no_partials(ckpt) == []
    assert_params_close(ref, params_of(mB), atol=1e-5)
