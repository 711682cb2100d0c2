"""Overload-robust serving: continuous batching, admission control and
replica failover (runtime/serving.py, runtime/kvcache.py).

The contract under test is the Orca/vLLM-shaped one ROADMAP Open item 3
asks for: iteration-level scheduling with per-slot decode positions that
is EXACT vs the reference generator, admission decisions that are always
typed and counted (zero silent drops), KV-page accounting that
backpressures instead of over-committing, and a ReplicaSet that requeues
a dead replica's in-flight work and restores the replica elastically.
scripts/load_check.py drives the same stack under a sustained 10x ramp;
here every edge gets a deterministic unit."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    AggrMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.runtime.kvcache import (
    KVCacheAccountingError,
    KVCacheConfig,
    KVCacheExhaustedError,
    PagePool,
)
from flexflow_tpu.runtime.resilience import FaultInjector
from flexflow_tpu.runtime.serving import (
    AdmissionQueue,
    BatchScheduler,
    ContinuousBatcher,
    DeadlineExceededError,
    GenerationRequest,
    QueueFullError,
    RateLimitedError,
    ReplicaDeathError,
    ReplicaSet,
    RequestShedError,
    ServingConfig,
    TokenBucket,
    incremental_generate,
)

VOCAB, SEQ, HIDDEN, HEADS = 29, 16, 16, 2


def build_lm(batch=2, seq=SEQ, layers=1):
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.search_budget = 1
    m = FFModel(cfg)
    ids = m.create_tensor((batch, seq), DataType.DT_INT32)
    t = m.embedding(ids, VOCAB, HIDDEN, AggrMode.AGGR_MODE_NONE)
    for _ in range(layers):
        t = m.multihead_attention(t, t, t, HIDDEN, HEADS, causal=True)
        t = m.dense(t, HIDDEN, ActiMode.AC_MODE_RELU)
    t = m.softmax(m.dense(t, VOCAB))
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


@pytest.fixture(scope="module")
def lm():
    return build_lm()


# ---------------------------------------------------------------------------
# paged KV-cache allocator
# ---------------------------------------------------------------------------

def test_page_pool_reserve_touch_release_accounting():
    pool = PagePool(KVCacheConfig(num_pages=8, page_size=4))
    assert pool.pages_free == 8
    rr = pool.reserve("a", 10)  # ceil(10/4) = 3 pages
    assert rr.pages == 3 and rr.shared_pages == 0
    assert pool.pages_free == 5 and pool.pages_reserved == 3
    assert pool.pages_in_use == 0  # nothing materialized yet
    assert pool.touch("a", 4) and pool.pages_in_use == 1
    assert pool.touch("a", 5) and pool.pages_in_use == 2
    assert pool.touch("a", 5) == []  # already covered
    assert len(pool.page_table("a")) == 2
    # growth beyond the reservation is a caller bug, not an over-commit
    with pytest.raises(ValueError):
        pool.touch("a", 16)
    assert pool.release("a") == 2
    # double release is a TYPED accounting error now, not a silent no-op
    # (a failover requeue bug must surface instead of corrupting refs)
    with pytest.raises(KVCacheAccountingError):
        pool.release("a")
    assert pool.release("a", missing_ok=True) == 0  # benign-race escape
    assert pool.stats["accounting_errors"] == 1
    assert pool.pages_free == 8 and pool.pages_in_use == 0
    assert pool.audit().ok


def test_page_pool_exhaustion_typed_and_never_fits():
    pool = PagePool(KVCacheConfig(num_pages=4, page_size=4))
    pool.reserve("a", 12)  # 3 of 4 pages
    with pytest.raises(KVCacheExhaustedError) as ei:
        pool.reserve("b", 8)  # needs 2, only 1 admittable
    assert ei.value.pages_needed == 2
    assert ei.value.pages_free == 1
    assert not ei.value.never_fits  # would fit once "a" retires
    with pytest.raises(KVCacheExhaustedError) as ei2:
        pool.reserve("c", 999)
    assert ei2.value.never_fits  # bigger than the whole pool: shed
    assert pool.stats["exhaustions"] == 2


def test_page_pool_watermark_and_config_validation():
    pool = PagePool(KVCacheConfig(num_pages=10, page_size=4, watermark=0.2))
    # 2 pages held back: only 8 admittable
    pool.reserve("a", 32)  # 8 pages
    with pytest.raises(KVCacheExhaustedError):
        pool.reserve("b", 1)
    for bad in (dict(num_pages=0), dict(num_pages=4, page_size=0),
                dict(num_pages=4, watermark=1.0)):
        with pytest.raises(ValueError):
            KVCacheConfig(**bad)


def test_page_pool_watermark_rounds_up_on_tiny_pools():
    """Regression: int(num_pages * watermark) floored to 0 below 1/w
    pages, silently disabling the watermark exactly where CPU tests
    live. A positive watermark must hold back >= 1 page."""
    tiny = KVCacheConfig(num_pages=4, page_size=4, watermark=0.1)
    assert tiny.held_back_pages() == 1  # 0.4 pages rounds UP, not down
    pool = PagePool(tiny)
    pool.reserve("a", 12)  # 3 of the 3 admittable pages
    with pytest.raises(KVCacheExhaustedError):
        pool.reserve("b", 1)  # the held-back page is not admittable
    # no float-noise over-rounding: 10 * 0.2 holds exactly 2, not 3
    assert KVCacheConfig(num_pages=10, watermark=0.2).held_back_pages() == 2
    # a watermark that would hold back the whole pool is a config error
    with pytest.raises(ValueError):
        KVCacheConfig(num_pages=2, page_size=4, watermark=0.9)


def test_page_pool_kv_exhaustion_fault_site():
    fi = FaultInjector()
    fi.inject("kv_exhaustion", never_fits=True)
    pool = PagePool(KVCacheConfig(num_pages=64, page_size=4),
                    fault_injector=fi)
    with pytest.raises(KVCacheExhaustedError) as ei:
        pool.reserve("a", 4)
    assert ei.value.never_fits
    assert fi.fired["kv_exhaustion"] == 1
    pool.reserve("a", 4)  # one-shot plan consumed: pool works again


# ---------------------------------------------------------------------------
# per-slot decode positions (the continuous-batching mechanism)
# ---------------------------------------------------------------------------

def test_per_slot_positions_match_full_forward(lm):
    """Rows of one decode batch advancing at DIFFERENT positions must
    reproduce the full causal forward exactly — the cache update and the
    causality mask are per-row."""
    bs = 2
    rng = np.random.RandomState(0)
    toks = rng.randint(0, VOCAB, (bs, SEQ)).astype(np.int32)
    full = np.asarray(lm.executor.build_forward()(
        lm.state.params, [jnp.asarray(toks)]))

    init_caches, step = lm.executor.build_decode(bs, SEQ)
    caches = init_caches()
    pos = np.zeros(bs, np.int32)
    for it in range(2 * SEQ):
        feed = np.stack([toks[i, min(pos[i], SEQ - 1)]
                         for i in range(bs)])[:, None]
        logits, caches = step(lm.state.params, caches,
                              jnp.asarray(pos), [jnp.asarray(feed)])
        logits = np.asarray(logits)
        for i in range(bs):
            if pos[i] >= SEQ:
                continue
            # row 0 advances every iteration, row 1 every other one
            if i == 0 or it % 2 == 0:
                np.testing.assert_allclose(
                    logits[i, 0], full[i, pos[i]], rtol=2e-4, atol=2e-4)
                pos[i] += 1
        if (pos >= SEQ).all():
            break
    assert (pos >= SEQ).all()


# ---------------------------------------------------------------------------
# admission queue + token bucket
# ---------------------------------------------------------------------------

def test_admission_queue_full_rejection_typed():
    q = AdmissionQueue(max_depth=2)
    r1 = GenerationRequest(np.arange(3), 4, deadline_s=30)
    r2 = GenerationRequest(np.arange(3), 4, deadline_s=30)
    r3 = GenerationRequest(np.arange(3), 4, deadline_s=30)
    q.offer(r1)
    q.offer(r2)
    with pytest.raises(QueueFullError):
        q.offer(r3)
    assert isinstance(r3.error, QueueFullError)  # finished typed, not lost
    assert r3.done()
    # requeue (failover) is exempt from the bound
    q.requeue(GenerationRequest(np.arange(3), 4, deadline_s=30))
    assert len(q) == 3


def test_admission_queue_deadline_shed_enqueue_and_dequeue():
    q = AdmissionQueue(max_depth=8)
    dead = GenerationRequest(np.arange(3), 4, deadline_s=0.0)
    with pytest.raises(DeadlineExceededError) as ei:
        q.offer(dead)
    assert ei.value.stage == "enqueue"
    # expires while queued -> shed at dequeue, never returned to a worker
    r = GenerationRequest(np.arange(3), 4, deadline_s=0.05)
    q.offer(r)
    time.sleep(0.08)
    assert q.poll(timeout=0.0) is None
    assert isinstance(r.error, DeadlineExceededError)
    assert r.error.stage == "dequeue"


def test_admission_queue_drain_is_typed():
    q = AdmissionQueue(max_depth=8)
    reqs = [GenerationRequest(np.arange(2), 2, deadline_s=30)
            for _ in range(3)]
    for r in reqs:
        q.offer(r)
    n = q.drain(lambda req: RequestShedError("shutdown", reason="aborted"))
    assert n == 3
    assert all(isinstance(r.error, RequestShedError) for r in reqs)


def test_token_bucket_acquire_and_aimd_adapt():
    now = [0.0]
    bucket = TokenBucket(rate=2.0, burst=2, clock=lambda: now[0])
    assert bucket.try_acquire() and bucket.try_acquire()
    assert not bucket.try_acquire()  # burst spent
    now[0] += 0.5  # refills 1 token at 2/s
    assert bucket.try_acquire()
    r0 = bucket.rate
    assert bucket.adapt(10.0, 1.0) < r0        # over target: cut
    assert bucket.adapt(0.1, 1.0) >= r0 * 0.7  # under target: grow back
    assert bucket.adapt(float("nan"), 1.0) == bucket.rate  # no samples


def test_generation_request_finish_once_and_generation_guard():
    r = GenerationRequest(np.arange(3), 4, deadline_s=30)
    gen = r.generation
    assert r._requeue_bump() == gen + 1
    # the old owner's publish loses: stale generation
    assert not r._finish(tokens=np.arange(5), generation=gen)
    assert not r.done()
    assert r._finish(tokens=np.arange(5), generation=gen + 1)
    assert r.done()
    assert r._requeue_bump() is None  # already finished
    np.testing.assert_array_equal(r.result(0.1), np.arange(5))


# ---------------------------------------------------------------------------
# continuous batching (single replica)
# ---------------------------------------------------------------------------

def _serve_cfg(**kw):
    base = dict(max_len=SEQ, slots=2, page_size=4, precompile=False,
                default_deadline_s=60.0)
    base.update(kw)
    return ServingConfig(**base)


def test_continuous_batching_matches_incremental_generate(lm):
    q = AdmissionQueue(max_depth=16)
    b = ContinuousBatcher(lm, _serve_cfg(slots=3), q).start()
    rng = np.random.RandomState(1)
    cases = []
    try:
        for _ in range(7):  # more requests than slots: queueing + reuse
            plen = int(rng.randint(1, 6))
            new = int(rng.randint(1, 6))
            prompt = rng.randint(0, VOCAB, plen).astype(np.int32)
            req = GenerationRequest(prompt, new, deadline_s=60.0)
            q.offer(req)
            cases.append((prompt, new, req))
        for prompt, new, req in cases:
            out = req.result(timeout=120.0)
            ref = incremental_generate(lm, prompt[None], max_new_tokens=new)
            np.testing.assert_array_equal(out, ref[0])
    finally:
        b.stop()
    assert b.stats["finished"] == 7
    assert b.pool.pages_in_use == 0  # every retirement released its pages


def test_continuous_batching_admits_mid_stream(lm):
    """A request arriving while the batch is mid-decode joins without
    disturbing the running sequences — the iteration-level contract."""
    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(lm, _serve_cfg(), q).start()
    rng = np.random.RandomState(2)
    p1 = rng.randint(0, VOCAB, 3).astype(np.int32)
    p2 = rng.randint(0, VOCAB, 5).astype(np.int32)
    try:
        r1 = GenerationRequest(p1, 10, deadline_s=60.0)
        q.offer(r1)
        while b.stats["admitted"] == 0:  # r1 is decoding
            time.sleep(0.005)
        r2 = GenerationRequest(p2, 4, deadline_s=60.0)
        q.offer(r2)
        out1 = r1.result(timeout=120.0)
        out2 = r2.result(timeout=120.0)
        np.testing.assert_array_equal(
            out1, incremental_generate(lm, p1[None], max_new_tokens=10)[0])
        np.testing.assert_array_equal(
            out2, incremental_generate(lm, p2[None], max_new_tokens=4)[0])
    finally:
        b.stop()


def test_continuous_batching_kv_backpressure(lm):
    """A pool covering ~one sequence serializes admission instead of
    over-committing; everything still completes."""
    q = AdmissionQueue(max_depth=8)
    cfg = _serve_cfg(num_pages=3)  # one 10-token sequence = 3 pages
    b = ContinuousBatcher(lm, cfg, q).start()
    rng = np.random.RandomState(3)
    reqs = []
    try:
        for _ in range(4):
            req = GenerationRequest(rng.randint(0, VOCAB, 3).astype(np.int32),
                                    6, deadline_s=60.0)
            q.offer(req)
            reqs.append(req)
        outs = [r.result(timeout=120.0) for r in reqs]
    finally:
        b.stop()
    assert len(outs) == 4
    assert b.pool.stats["exhaustions"] >= 1  # backpressure really engaged
    assert b.pool.pages_in_use == 0


def test_continuous_batching_sheds_never_fits_and_too_long(lm):
    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(lm, _serve_cfg(num_pages=2), q).start()
    try:
        # 3+10 tokens -> 4 pages > the whole 2-page pool: typed shed
        never = GenerationRequest(np.zeros(3, np.int32), 10, deadline_s=60.0)
        q.offer(never)
        with pytest.raises(RequestShedError) as ei:
            never.result(timeout=60.0)
        assert ei.value.reason == "kv_exhausted"
        # prompt + max_new beyond the compiled cache width
        long = GenerationRequest(np.zeros(SEQ - 1, np.int32), SEQ,
                                 deadline_s=60.0)
        q.offer(long)
        with pytest.raises(RequestShedError) as ei2:
            long.result(timeout=60.0)
        assert ei2.value.reason == "too_long"
    finally:
        b.stop()


def test_continuous_batching_eos_early_retirement():
    m = build_lm()
    q = AdmissionQueue(max_depth=8)
    # find what token the model emits first, then declare it EOS
    probe = GenerationRequest(np.zeros(2, np.int32), 1, deadline_s=60.0)
    b = ContinuousBatcher(m, _serve_cfg(), q).start()
    try:
        q.offer(probe)
        eos = int(probe.result(timeout=120.0)[-1])
        b.stop()
        q2 = AdmissionQueue(max_depth=8)
        b2 = ContinuousBatcher(m, _serve_cfg(eos_token_id=eos), q2).start()
        try:
            req = GenerationRequest(np.zeros(2, np.int32), 10,
                                    deadline_s=60.0)
            q2.offer(req)
            out = req.result(timeout=120.0)
            assert out[-1] == eos
            assert len(out) < 2 + 10  # retired at EOS, not max_new
            assert b2.stats["retired_eos"] == 1
        finally:
            b2.stop()
    finally:
        b.stop()


def test_continuous_batcher_rejects_two_input_graphs():
    cfg = FFConfig()
    cfg.batch_size = 2
    m = FFModel(cfg)
    a = m.create_tensor((2, 4), DataType.DT_FLOAT)
    bt = m.create_tensor((2, 4), DataType.DT_FLOAT)
    t = m.softmax(m.dense(m.add(a, bt), 3))
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    from flexflow_tpu.runtime.verify import ServingConfigError

    with pytest.raises(ServingConfigError):
        ContinuousBatcher(m, _serve_cfg(max_len=4), AdmissionQueue(4))


def test_lone_batcher_death_reaches_the_caller(lm):
    """A lone batcher (no ReplicaSet) whose serve thread dies fails its
    in-flight, queued AND later-offered requests with the death cause at
    once — not with a bare timeout after the deadline (what hid a kernel
    the TPU compiler refused behind 600 s of silence)."""
    fi = FaultInjector()
    fi.inject("replica_death", at_step=1, replica="replica0")
    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(lm, _serve_cfg(slots=1), q,
                          fault_injector=fi).start()
    prompt = np.arange(3, dtype=np.int32)
    try:
        in_flight = GenerationRequest(prompt, 8, deadline_s=600.0)
        queued = GenerationRequest(prompt, 8, deadline_s=600.0)
        q.offer(in_flight)
        q.offer(queued)  # one slot: waits behind the first
        for req in (in_flight, queued):
            with pytest.raises(ReplicaDeathError) as ei:
                req.result(timeout=60.0)
            assert ei.value.__cause__ is b.death_cause
        assert b.dead and isinstance(b.death_cause, ReplicaDeathError)
        late = GenerationRequest(prompt, 8, deadline_s=600.0)
        with pytest.raises(ReplicaDeathError):
            q.offer(late)
        with pytest.raises(ReplicaDeathError):
            late.result(timeout=1.0)
    finally:
        b.stop()
    assert b.pool.pages_in_use == 0


def test_one_stalled_iteration_sheds_no_request_that_fits_its_deadline(lm):
    """The early deadline shed reads a per-token service estimate. One
    decode iteration stalled for a while (a profiler's start or stop, a
    host hiccup) is no proof that a request admitted right after it
    cannot meet its deadline: it is served."""
    fi = FaultInjector()
    fi.inject("slow_worker", at_step=3, replica="replica0", delay_s=1.5)
    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(lm, _serve_cfg(slots=2, precompile=True), q,
                          fault_injector=fi).start()
    try:
        running = GenerationRequest(np.arange(2, dtype=np.int32), 13,
                                    deadline_s=600.0)
        q.offer(running)
        t0 = time.monotonic()
        while not fi.fired.get("slow_worker") and time.monotonic() - t0 < 120:
            time.sleep(0.002)
        # offered inside the stall: admitted once it ends, 12 tokens of a
        # few ms each due within 3 s
        late = GenerationRequest(np.arange(2, dtype=np.int32) + 3, 12,
                                 deadline_s=3.0)
        q.offer(late)
        assert len(late.result(timeout=60.0)) == 14
        assert len(running.result(timeout=60.0)) == 15
    finally:
        b.stop()
    assert fi.fired["slow_worker"] == 1 and b.stats["shed_decode"] == 0


# ---------------------------------------------------------------------------
# replica failover + rate limiting
# ---------------------------------------------------------------------------

def test_replica_set_death_failover_and_elastic_restart(tmp_path):
    fi = FaultInjector()
    fi.inject("replica_death", at_step=2, replica="replica0",
              exc=ReplicaDeathError("injected"))
    cfg = _serve_cfg()
    rs = ReplicaSet(build_lm, cfg, replicas=2, ckpt_dir=str(tmp_path),
                    fault_injector=fi, health_timeout_s=60.0,
                    restart_backoff_s=0.05).start()
    rng = np.random.RandomState(4)
    try:
        reqs = [rs.submit(rng.randint(0, VOCAB, 3).astype(np.int32),
                          max_new_tokens=5, deadline_s=120.0)
                for _ in range(6)]
        outs = [r.result(timeout=180.0) for r in reqs]
        assert len(outs) == 6  # no admitted request was lost to the death
        t0 = time.monotonic()
        # _restart_replica registers the replacement BEFORE bumping the
        # restarts stat — wait on both, not just the count
        while (rs.replica_count() < 2 or rs.stats["restarts"] < 1) \
                and time.monotonic() - t0 < 120:
            time.sleep(0.05)
        assert rs.replica_count() == 2  # restored via the elastic path
        assert rs.stats["restarts"] == 1
        assert fi.fired["replica_death"] == 1
    finally:
        rs.stop()


def test_replica_set_warm_spare_activation(tmp_path):
    fi = FaultInjector()
    fi.inject("replica_death", replica="replica0",
              exc=ReplicaDeathError("injected"))
    rs = ReplicaSet(build_lm, _serve_cfg(), replicas=1,
                    ckpt_dir=str(tmp_path), fault_injector=fi,
                    health_timeout_s=60.0, restart_backoff_s=0.05,
                    warm_spares=1).start()
    rng = np.random.RandomState(5)
    try:
        reqs = [rs.submit(rng.randint(0, VOCAB, 3).astype(np.int32),
                          max_new_tokens=4, deadline_s=120.0)
                for _ in range(4)]
        outs = [r.result(timeout=180.0) for r in reqs]
        assert len(outs) == 4
        t0 = time.monotonic()
        while rs.stats["restarts"] < 1 and time.monotonic() - t0 < 120:
            time.sleep(0.05)
        assert rs.stats["spares_used"] == 1  # restart came from the spare
        assert rs.stats["restarts"] == 1
    finally:
        rs.stop()


def test_replica_set_rate_limiter_sheds_typed():
    cfg = _serve_cfg(rate_limit=1.0, rate_burst=2)
    rs = ReplicaSet(build_lm, cfg, replicas=1, health_timeout_s=60.0).start()
    try:
        ok = shed = 0
        for _ in range(6):  # burst 2, refill 1/s: most of these shed
            try:
                rs.submit(np.zeros(2, np.int32), max_new_tokens=2,
                          deadline_s=60.0)
                ok += 1
            except RateLimitedError:
                shed += 1
        assert ok >= 2 and shed >= 3
    finally:
        rs.stop()


def test_replica_set_stop_aborts_pending_typed():
    rs = ReplicaSet(build_lm, _serve_cfg(), replicas=1,
                    health_timeout_s=60.0).start()
    reqs = [rs.submit(np.zeros(2, np.int32), max_new_tokens=3,
                      deadline_s=120.0) for _ in range(5)]
    rs.stop(timeout=0.2)  # shut down before the queue can drain
    for r in reqs:
        assert r.done()
        if r.error is not None:
            assert isinstance(r.error, RequestShedError)


def test_metrics_find_does_not_create():
    from flexflow_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    assert reg.find("ff_serving_latency_seconds") is None
    assert reg.to_prometheus() == ""  # no empty series polluted the export
    h = reg.histogram("ff_serving_latency_seconds")
    h.observe(0.25)
    assert reg.find("ff_serving_latency_seconds") is h


def test_serving_metrics_export_through_obs_session(lm, tmp_path):
    """With a telemetry session active, the new serving series land in
    the session registry and export to Prometheus text (the
    docs/observability.md catalog entries)."""
    from flexflow_tpu import obs
    from flexflow_tpu.obs import TelemetryConfig
    from flexflow_tpu.obs.metrics import parse_prometheus

    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))) as tel:
        q = AdmissionQueue(max_depth=4)
        b = ContinuousBatcher(lm, _serve_cfg(), q).start()
        try:
            req = GenerationRequest(np.zeros(2, np.int32), 3,
                                    deadline_s=60.0)
            q.offer(req)
            req.result(timeout=120.0)
            # typed shed: dead-on-arrival
            with pytest.raises(DeadlineExceededError):
                q.offer(GenerationRequest(np.zeros(2, np.int32), 3,
                                          deadline_s=0.0))
        finally:
            b.stop()
        series = parse_prometheus(tel.metrics.to_prometheus())
    assert series.get("ff_serving_requests_total") == 1.0
    assert series.get('ff_serving_shed_total{reason="deadline"}') == 1.0
    assert "ff_serving_queue_depth" in series
    assert "ff_kv_pages_in_use" in series
    assert any(k.startswith("ff_serving_latency_seconds_bucket")
               for k in series)


# ---------------------------------------------------------------------------
# BatchScheduler satellite fixes
# ---------------------------------------------------------------------------

def _dense_model(batch=4):
    cfg = FFConfig()
    cfg.batch_size = batch
    m = FFModel(cfg)
    x = m.create_tensor((batch, 6), DataType.DT_FLOAT)
    t = m.softmax(m.dense(m.dense(x, 16, ActiMode.AC_MODE_RELU), 3))
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


def test_batchscheduler_sheds_expired_at_dequeue():
    """Satellite fix: a request whose deadline passed while queued must
    be shed with a typed error at dequeue, not executed on-device."""
    m = _dense_model()
    sched = BatchScheduler(m, max_delay_s=0.001)
    x = np.zeros(6, np.float32)
    expired = sched.submit([x], deadline=time.monotonic() - 1.0)
    live = sched.submit([x], deadline=time.monotonic() + 30.0)
    sched.start()
    try:
        assert live.event.wait(30.0)
        assert live.error is None and live.result is not None
        assert expired.event.wait(5.0)
        assert isinstance(expired.error, DeadlineExceededError)
        assert expired.error.stage == "dequeue"
        assert sched.stats["shed"] == 1
    finally:
        sched.stop()


def test_batchscheduler_queue_bound_typed():
    m = _dense_model()
    sched = BatchScheduler(m, max_queue_depth=2)  # worker not started
    x = np.zeros(6, np.float32)
    sched.submit([x])
    sched.submit([x])
    with pytest.raises(QueueFullError):
        sched.submit([x])
    assert sched.stats["shed"] == 1


def test_batchscheduler_worker_death_surfaces_degraded_retry():
    """Satellite fix: the in-flight request that dies with the worker is
    re-run degraded AND the retry is surfaced (stat + structured event),
    not silent."""
    m = _dense_model()
    fi = FaultInjector()
    fi.inject("serving_worker", at_step=0, exc=RuntimeError("worker crash"))
    sched = BatchScheduler(m, fault_injector=fi, max_worker_restarts=0)
    sched.start()
    try:
        out = sched.infer([np.zeros(6, np.float32)], timeout=30.0)
        assert out.shape == (3,)
        assert sched.stats["degraded_retries"] >= 1
        assert sched.stats["degraded"] >= 1
    finally:
        sched.stop()


def test_batchscheduler_restart_backoff_under_lock():
    """Satellite fix regression: concurrent infer() callers racing a
    worker crash must agree on the backoff window (no restart before
    the window the dying worker published)."""
    m = _dense_model()
    fi = FaultInjector()
    fi.inject("serving_worker", at_step=0, exc=RuntimeError("crash"),
              times=1)
    sched = BatchScheduler(m, fault_injector=fi, max_worker_restarts=2,
                           restart_backoff_s=0.05)
    sched.start()
    results = []

    def caller():
        try:
            results.append(sched.infer([np.zeros(6, np.float32)],
                                       timeout=30.0))
        except BaseException as e:  # noqa: BLE001 — collected for assert
            results.append(e)

    threads = [threading.Thread(target=caller) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    try:
        assert len(results) == 4
        for r in results:
            assert isinstance(r, np.ndarray), r
        # the restart happened at most max_worker_restarts times
        assert sched.stats["worker_restarts"] <= 2
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# slow chaos sweep over the new fault sites
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_sweep_serving_fault_sites(tmp_path):
    """Every new FaultInjector site, one sustained run each: all offered
    requests end in tokens or a typed error, and killed/hung replicas
    come back."""
    rng = np.random.RandomState(7)
    scenarios = [
        ("replica_death", dict(replica="replica0",
                               exc=ReplicaDeathError("chaos"))),
        # at_step=5: past the first-step compile-grace window, so the
        # watchdog's steady-state timeout is what catches the stall
        ("slow_worker", dict(replica="replica0", at_step=5, delay_s=2.0)),
        ("kv_exhaustion", dict(times=3)),
    ]
    for site, kw in scenarios:
        fi = FaultInjector()
        fi.inject(site, **kw)
        timeout_s = 0.4 if site == "slow_worker" else 60.0
        rs = ReplicaSet(
            build_lm, _serve_cfg(), replicas=2,
            ckpt_dir=str(tmp_path / site), fault_injector=fi,
            health_timeout_s=timeout_s, compile_grace_s=300.0,
            restart_backoff_s=0.05,
        ).start()
        try:
            reqs = [rs.submit(rng.randint(0, VOCAB, 3).astype(np.int32),
                              max_new_tokens=4, deadline_s=120.0)
                    for _ in range(10)]
            done = typed = 0
            for r in reqs:
                try:
                    r.result(timeout=180.0)
                    done += 1
                except RequestShedError:
                    typed += 1
            assert done + typed == 10, (site, done, typed)
            assert done > 0, site
            assert fi.fired.get(site, 0) >= 1, site
            if site in ("replica_death", "slow_worker"):
                t0 = time.monotonic()
                while (rs.replica_count() < 2
                       and time.monotonic() - t0 < 120):
                    time.sleep(0.05)
                assert rs.replica_count() == 2, site
                assert rs.stats["restarts"] >= 1, site
        finally:
            rs.stop()
