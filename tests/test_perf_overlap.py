"""Step hot-path perf features (ISSUE 8): comm/compute-overlapped
gradient sync (reduce-scatter + sharded update + all-gather), RNG-threaded
flash dropout, and the search's overlappable-collective discount.

All on the virtual CPU mesh: the flash kernels run in interpret mode, the
overlapped step runs on the conftest's 8-device mesh (any data degree > 1
works, so the 8/4-device perf_check.sh sweep passes too)."""
import math
import warnings as warnings_mod

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.attention import (
    attention_dropout_mask,
    dropout_seeds,
    flash_attention_folded,
)

RNG = np.random.RandomState(0)


# ---------------------------------------------------------------------------
# RNG-threaded flash dropout (kernels/attention.py, interpret mode)
# ---------------------------------------------------------------------------

def _dense_dropout_ref(qf, kf, vf, seeds, rate, causal):
    """The dense path's math with the SAME counter-based mask the flash
    kernels regenerate blockwise — the parity oracle."""
    bh, sq, d = qf.shape
    sk = kf.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) / math.sqrt(d)
    if causal:
        tri = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(tri[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    keep = attention_dropout_mask(seeds, rate, bh, sq, sk)
    p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, vf)


def _folded_qkv(bh=4, sq=32, sk=32, d=16):
    return (
        jnp.asarray(RNG.randn(bh, sq, d).astype(np.float32)),
        jnp.asarray(RNG.randn(bh, sk, d).astype(np.float32)),
        jnp.asarray(RNG.randn(bh, sk, d).astype(np.float32)),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_forward_matches_dense(causal):
    qf, kf, vf = _folded_qkv()
    seeds = dropout_seeds(jax.random.PRNGKey(42))
    rate = 0.3
    ours = flash_attention_folded(qf, kf, vf, causal, True,
                                  dropout=rate, seeds=seeds)
    ref = _dense_dropout_ref(qf, kf, vf, seeds, rate, causal)
    # same mask by construction: a single mask disagreement would shift
    # an output element by a full prob*value, far outside this atol
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_backward_matches_dense(causal):
    qf, kf, vf = _folded_qkv()
    seeds = dropout_seeds(jax.random.PRNGKey(7))
    rate = 0.25

    def ours_loss(q_, k_, v_):
        return jnp.sum(flash_attention_folded(
            q_, k_, v_, causal, True, dropout=rate, seeds=seeds) ** 2)

    def ref_loss(q_, k_, v_):
        return jnp.sum(_dense_dropout_ref(q_, k_, v_, seeds, rate,
                                          causal) ** 2)

    g1 = jax.grad(ours_loss, argnums=(0, 1, 2))(qf, kf, vf)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(qf, kf, vf)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_flash_dropout_blocked_backward_matches_dense():
    """The blocked backward must regenerate the same mask per tile —
    offsets, not materialization."""
    qf, kf, vf = _folded_qkv(bh=2, sq=16, sk=32)
    seeds = dropout_seeds(jax.random.PRNGKey(3))
    rate = 0.4
    g1 = jax.grad(lambda k_: jnp.sum(flash_attention_folded(
        qf, k_, vf, False, True, dropout=rate, seeds=seeds,
        block_k=8) ** 2))(kf)
    g2 = jax.grad(lambda k_: jnp.sum(_dense_dropout_ref(
        qf, k_, vf, seeds, rate, False) ** 2))(kf)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,bq,bk,rate", [
    (32, 32, 8, 8, 0.25),     # a query offset and a key offset in every tile
    (32, 32, 16, 8, 0.3),
    (16, 32, 8, 16, 0.4),     # seq_q != seq_k
])
def test_flash_dropout_blocked_walk_matches_dense(sq, sk, bq, bk, rate,
                                                  causal):
    """With the query axis tiled too, forward and backward regenerate
    attention_dropout_mask's bits at the absolute (row, q, k): output and
    all three gradients equal the dense path under the same mask."""
    qf, kf, vf = _folded_qkv(bh=2, sq=sq, sk=sk)
    seeds = dropout_seeds(jax.random.PRNGKey(11))

    def ours(q_, k_, v_):
        return flash_attention_folded(q_, k_, v_, causal, True, dropout=rate,
                                      seeds=seeds, block_q=bq, block_k=bk)

    def ref(q_, k_, v_):
        return _dense_dropout_ref(q_, k_, v_, seeds, rate, causal)

    np.testing.assert_allclose(np.asarray(ours(qf, kf, vf)),
                               np.asarray(ref(qf, kf, vf)), atol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(ours(*a) ** 2), (0, 1, 2))(qf, kf, vf)
    g2 = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), (0, 1, 2))(qf, kf, vf)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_flash_ignores_the_old_tile_variables(monkeypatch):
    """FF_FLASH_BWD_G / FF_FLASH_BWD_BK are read nowhere: the blocks come
    from the shape or from the keywords, so setting them changes no bit."""
    qf, kf, vf = _folded_qkv(bh=4, sq=32, sk=32)

    def grads():
        return jax.grad(lambda *a: jnp.sum(flash_attention_folded(
            *a, True, True, block_q=16, block_k=16) ** 2),
            (0, 1, 2))(qf, kf, vf)

    base = grads()
    monkeypatch.setenv("FF_FLASH_BWD_G", "1")
    monkeypatch.setenv("FF_FLASH_BWD_BK", "8")
    for a, b in zip(grads(), base):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dropout_mask_deterministic_and_rate():
    seeds = dropout_seeds(jax.random.PRNGKey(0))
    m1 = attention_dropout_mask(seeds, 0.3, 32, 64, 64)
    m2 = attention_dropout_mask(seeds, 0.3, 32, 64, 64)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    frac = float(jnp.mean(m1))
    assert 0.67 < frac < 0.73, f"keep fraction {frac} far from 0.7"
    other = attention_dropout_mask(
        dropout_seeds(jax.random.PRNGKey(1)), 0.3, 32, 64, 64)
    assert not bool(jnp.all(m1 == other)), "different keys, same mask"


def test_flash_dropout_needs_seeds():
    qf, kf, vf = _folded_qkv(bh=2, sq=8, sk=8, d=8)
    with pytest.raises(ValueError, match="seeds"):
        flash_attention_folded(qf, kf, vf, False, True, dropout=0.5)


def test_dense_path_uses_shared_mask():
    """The MHA op's dense dropout path draws the SAME counter-based mask
    (ops/attention.py) — pinned by recomputing it from the op's rng."""
    from flexflow_tpu.ff_types import DataType, OperatorType
    from flexflow_tpu.ops import attention as mha
    from flexflow_tpu.ops.registry import FwdCtx, get_op_def

    params = mha.MultiHeadAttentionParams(embed_dim=16, num_heads=2,
                                          dropout=0.5)
    opdef = get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION)
    x = jnp.asarray(RNG.randn(2, 8, 16).astype(np.float32))
    ws = opdef.weights(params, [(2, 8, 16)] * 3, [DataType.DT_FLOAT] * 3)
    key = jax.random.PRNGKey(5)
    weights = {}
    for w in ws:
        key, sub = jax.random.split(key)
        weights[w.name] = jax.random.normal(sub, w.shape, jnp.float32) * 0.1
    rng = jax.random.PRNGKey(11)
    ctx = FwdCtx(training=True, rng=rng, op_name="mha0")
    out, = opdef.forward(params, weights, [x, x, x], ctx)
    out2, = opdef.forward(params, weights, [x, x, x], ctx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # a different key must flip some mask bits -> different output
    ctx2 = FwdCtx(training=True, rng=jax.random.PRNGKey(12), op_name="mha0")
    out3, = opdef.forward(params, weights, [x, x, x], ctx2)
    assert not np.allclose(np.asarray(out), np.asarray(out3))


# ---------------------------------------------------------------------------
# dropout-fallback warn-once + metric (ops/attention.py satellite)
# ---------------------------------------------------------------------------

def test_dropout_fallback_warns_once_and_counts(monkeypatch, tmp_path):
    from flexflow_tpu import obs
    from flexflow_tpu.ff_types import DataType, OperatorType
    from flexflow_tpu.obs import TelemetryConfig
    from flexflow_tpu.ops import attention as mha
    from flexflow_tpu.ops.registry import FwdCtx, get_op_def

    monkeypatch.setenv("FF_ATTENTION_IMPL", "chunked")
    mha.reset_attention_fallback_warnings()
    params = mha.MultiHeadAttentionParams(embed_dim=16, num_heads=2,
                                          dropout=0.5)
    opdef = get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION)
    x = jnp.asarray(RNG.randn(2, 8, 16).astype(np.float32))
    ws = opdef.weights(params, [(2, 8, 16)] * 3, [DataType.DT_FLOAT] * 3)
    key = jax.random.PRNGKey(5)
    weights = {}
    for w in ws:
        key, sub = jax.random.split(key)
        weights[w.name] = jax.random.normal(sub, w.shape, jnp.float32) * 0.1

    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))):
        ctx = FwdCtx(training=True, rng=key, op_name="layer0")
        with pytest.warns(UserWarning, match="dense path"):
            opdef.forward(params, weights, [x, x, x], ctx)
        # same (impl, layer, reason): warning deduped, metric still counts
        with warnings_mod.catch_warnings():
            warnings_mod.simplefilter("error")
            opdef.forward(params, weights, [x, x, x], ctx)
        # a DIFFERENT layer warns again
        ctx1 = FwdCtx(training=True, rng=key, op_name="layer1")
        with pytest.warns(UserWarning, match="layer1"):
            opdef.forward(params, weights, [x, x, x], ctx1)
        c = obs.active().metrics.find("ff_attention_fallback_total",
                                      reason="kernel")
        assert c is not None and c.value == 3.0


# ---------------------------------------------------------------------------
# overlapped RS/update/AG step (parallel/executor.py tentpole)
# ---------------------------------------------------------------------------

def _data_degree() -> int:
    return len(jax.devices())


def _small_model(overlap: bool, optimizer):
    from flexflow_tpu import FFConfig, FFModel, LossType, MetricsType
    from flexflow_tpu.ff_types import ActiMode, DataType

    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.overlap_backward_update = overlap
    m = FFModel(cfg)
    x = m.create_tensor((8, 16), DataType.DT_FLOAT, name="x")
    t = m.dense(x, 32, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 16, ActiMode.AC_MODE_NONE)
    m.compile(
        optimizer=optimizer,
        loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[MetricsType.METRICS_MEAN_SQUARED_ERROR],
    )
    return m


def _run_steps(model, *, steps=3, guard=False):
    import dataclasses

    from flexflow_tpu.runtime.resilience import StepGuardConfig

    ex = model.executor
    if guard:
        ex.set_step_guard(StepGuardConfig())
    st = model.state
    if guard:
        st = dataclasses.replace(st, guard=ex.init_guard_state())
    step = ex.build_train_step(donate=False)
    X = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    Y = np.random.RandomState(1).randn(8, 16).astype(np.float32)
    xb = ex.shard_batch(ex.input_pts[0], X)
    yb = ex.put_replicated(Y)
    key = ex.put_replicated(jax.random.PRNGKey(7))
    partials = None
    for _ in range(steps):
        st, partials = step(st, [xb], yb, key)
    return st, partials


def _assert_states_close(s0, s1):
    for a, b in zip(jax.tree_util.tree_leaves(s0.params),
                    jax.tree_util.tree_leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-6, atol=1e-7)
    o0 = [x for x in jax.tree_util.tree_leaves(s0.opt_state)
          if x is not None]
    o1 = [x for x in jax.tree_util.tree_leaves(s1.opt_state)
          if x is not None]
    for a, b in zip(o0, o1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-6, atol=1e-7)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="overlap needs a data degree > 1")
@pytest.mark.parametrize("guard", [False, True])
def test_overlapped_step_matches_allreduce_sgd(guard):
    from flexflow_tpu import SGDOptimizer

    m0 = _small_model(False, SGDOptimizer(lr=0.05, momentum=0.9))
    s0, p0 = _run_steps(m0, guard=guard)
    m1 = _small_model(True, SGDOptimizer(lr=0.05, momentum=0.9))
    assert m1.executor._overlap_specs(), "no weights eligible for overlap"
    s1, p1 = _run_steps(m1, guard=guard)
    _assert_states_close(s0, s1)
    np.testing.assert_allclose(float(p0["loss"]), float(p1["loss"]),
                               rtol=1e-5)
    if guard:
        # the fused per-shard guard norm equals the full-tree norm
        np.testing.assert_allclose(float(p0["grad_norm"]),
                                   float(p1["grad_norm"]), rtol=1e-5)
        assert float(p1["skipped"]) == 0.0


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="overlap needs a data degree > 1")
@pytest.mark.parametrize("guard", [False, True])
def test_overlapped_step_matches_allreduce_adam(guard):
    from flexflow_tpu.core.optimizers import AdamOptimizer

    m0 = _small_model(False, AdamOptimizer(alpha=1e-3))
    s0, _ = _run_steps(m0, guard=guard)
    m1 = _small_model(True, AdamOptimizer(alpha=1e-3))
    s1, _ = _run_steps(m1, guard=guard)
    _assert_states_close(s0, s1)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="overlap needs a data degree > 1")
def test_overlap_shards_optimizer_state_zero1():
    """The sharded update never gathers m/v: optimizer state LIVES
    sharded over the data axis (ZeRO-1), before and after a step."""
    from flexflow_tpu.core.optimizers import AdamOptimizer

    m = _small_model(True, AdamOptimizer(alpha=1e-3))
    d = _data_degree()
    op_name = next(iter(m.state.params))

    def assert_sharded(leaf):
        spec = leaf.sharding.spec
        assert len(spec) >= 1 and spec[0] == "data", spec
        shard = leaf.addressable_shards[0].data.shape
        assert shard[0] == leaf.shape[0] // d

    assert_sharded(m.state.opt_state["m"][op_name]["kernel"])
    st, _ = _run_steps(m, steps=1)
    assert_sharded(st.opt_state["m"][op_name]["kernel"])
    # params stay replicated (all-gathered after the sharded update)
    p = st.params[op_name]["kernel"]
    assert p.sharding.spec == jax.sharding.PartitionSpec()


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="overlap needs a data degree > 1")
def test_overlap_scan_driver_matches_stepwise():
    """build_train_scan shares the step program, so the fused multi-step
    driver sees the same overlapped schedule."""
    from flexflow_tpu import SGDOptimizer

    m = _small_model(True, SGDOptimizer(lr=0.05))
    ex = m.executor
    X = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    Y = np.random.RandomState(1).randn(8, 16).astype(np.float32)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)

    scan = ex.build_train_scan()
    xs = [ex.shard_batch_stack(ex.input_pts[0],
                               np.broadcast_to(X, (3,) + X.shape))]
    ys = ex.put_replicated(np.broadcast_to(Y, (3,) + Y.shape))
    st_scan, _ = scan(m.state, xs, ys, ex.put_replicated(keys))

    m2 = _small_model(True, SGDOptimizer(lr=0.05))
    ex2 = m2.executor
    step = ex2.build_train_step(donate=False)
    st = m2.state
    xb = ex2.shard_batch(ex2.input_pts[0], X)
    yb = ex2.put_replicated(Y)
    for i in range(3):
        st, _ = step(st, [xb], yb, ex2.put_replicated(keys[i]))
    _assert_states_close(st_scan, st)


def test_set_overlap_grad_sync_invalidates_cache():
    from flexflow_tpu import SGDOptimizer

    m = _small_model(True, SGDOptimizer(lr=0.05))
    ex = m.executor
    f1 = ex.build_train_step()
    ex.set_overlap_grad_sync(False)
    assert ex._overlap_specs() == {}
    f2 = ex.build_train_step()
    assert f1 is not f2
    ex.set_overlap_grad_sync(False)  # no-op keeps the cache
    assert ex.build_train_step() is f2


# ---------------------------------------------------------------------------
# cost-model overlappable discount (search satellite of the tentpole)
# ---------------------------------------------------------------------------

def _linear_graph():
    """A data-parallel PCG with weight ops (non-zero sync), sharded over
    every device of the process mesh."""
    from flexflow_tpu import FFConfig, FFModel, LossType, MetricsType
    from flexflow_tpu import SGDOptimizer
    from flexflow_tpu.ff_types import ActiMode, DataType

    cfg = FFConfig()
    cfg.batch_size = 8
    m = FFModel(cfg)
    x = m.create_tensor((8, 16), DataType.DT_FLOAT, name="x")
    t = m.dense(x, 64, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 16, ActiMode.AC_MODE_NONE)
    m.compile(optimizer=SGDOptimizer(lr=0.1),
              loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
              metrics=[MetricsType.METRICS_MEAN_SQUARED_ERROR])
    return m.graph


def _machine():
    from flexflow_tpu.search.machine_model import MachineModel

    return MachineModel(num_nodes=1, workers_per_node=len(jax.devices()))


def _dp_view():
    from flexflow_tpu.pcg.machine_view import MachineView

    return MachineView(start_device_id=0, dim=(len(jax.devices()),),
                       stride=(1,))


def _dp_views(graph, machine):
    from flexflow_tpu.search.mcmc import MCMCSearch
    from flexflow_tpu.search.cost_model import CostModel

    return MCMCSearch(CostModel(machine)).data_parallel_start(graph)


def test_discount_bounded_and_never_negative():
    from flexflow_tpu.search.cost_model import CostModel

    graph = _linear_graph()
    machine = _machine()
    plain = CostModel(machine)
    disc = CostModel(machine, overlap_backward_update=True)
    view = _dp_view()
    saw_sync = False
    for op in graph.topo_order():
        if op.is_parallel_op:
            continue
        c0 = plain.measure_operator_cost(op, view)
        c1 = disc.measure_operator_cost(op, view)
        assert c1.total_time <= c0.total_time + 1e-18
        assert c1.total_time >= c1.forward_time + c1.backward_time - 1e-18
        assert c1.hidden_sync_time >= 0.0
        assert c1.hidden_sync_time <= c1.sync_time + 1e-18
        if c0.sync_time > 0:
            saw_sync = True
            assert c1.hidden_sync_time > 0.0
        if c0.sync_time == 0:
            assert c1.total_time == pytest.approx(c0.total_time)
    assert saw_sync, "graph produced no weight-grad sync to discount"


def test_discount_efficiency_scales():
    from flexflow_tpu.search.cost_model import CostModel

    graph = _linear_graph()
    machine = _machine()
    full = CostModel(machine, overlap_backward_update=True,
                     overlap_efficiency=1.0)
    half = CostModel(machine, overlap_backward_update=True,
                     overlap_efficiency=0.5)
    view = _dp_view()
    for op in graph.topo_order():
        cf = full.measure_operator_cost(op, view)
        ch = half.measure_operator_cost(op, view)
        assert ch.hidden_sync_time <= cf.hidden_sync_time + 1e-18


def test_calibration_rejects_bad_overlap_efficiency():
    from flexflow_tpu.search.cost_model import validate_calibration

    with pytest.raises(ValueError, match="overlap_efficiency"):
        validate_calibration({"overlap_efficiency": 0.0})
    validate_calibration({"overlap_efficiency": 0.9})


def test_simulate_runtime_overlap_discount():
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.mcmc import simulate_runtime

    graph = _linear_graph()
    machine = _machine()
    cm = CostModel(machine)
    views = _dp_views(graph, machine)
    serial = simulate_runtime(graph, views, cm,
                              overlap_backward_update=False)
    overlapped = simulate_runtime(graph, views, cm,
                                  overlap_backward_update=True)
    assert 0.0 < overlapped < serial
    # hiding can reclaim at most the total sync time — never more
    total_sync = sum(
        cm.measure_operator_cost(op, views[op.guid]).sync_time
        for op in graph.topo_order()
    )
    assert total_sync > 0.0
    assert overlapped >= serial - total_sync - 1e-18


def test_simulate_runtime_follows_cost_model_flag():
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.mcmc import simulate_runtime

    graph = _linear_graph()
    machine = _machine()
    views = _dp_views(graph, machine)
    serial_cm = CostModel(machine)
    # overlap flag on the cost model is picked up by default...
    ov_cm = CostModel(machine, overlap_backward_update=True)
    assert simulate_runtime(graph, views, ov_cm) <= \
        simulate_runtime(graph, views, serial_cm)
    # ...and an explicit argument overrides it
    assert simulate_runtime(
        graph, views, ov_cm, overlap_backward_update=False
    ) == pytest.approx(simulate_runtime(graph, views, serial_cm))


def test_overlappable_grad_syncs_static_proof():
    from flexflow_tpu.analysis.collectives import (
        hideable_backward_compute,
        overlappable_grad_syncs,
    )
    from flexflow_tpu.search.cost_model import CostModel

    graph = _linear_graph()
    ov = overlappable_grad_syncs(graph)
    weight_ops = [op for op in graph.topo_order()
                  if op.weights and not op.is_parallel_op]
    assert {op.guid for op in weight_ops} == ov
    for op in graph.topo_order():
        if op.is_parallel_op:
            assert op.guid not in ov
    cm = CostModel(_machine())
    hide = hideable_backward_compute(graph, None, cm)
    # later ops (reverse-topo-earlier backward) have MORE hideable compute
    guids = [op.guid for op in graph.topo_order() if op.guid in ov]
    hides = [hide[g] for g in guids]
    assert hides == sorted(hides)
    assert hides[-1] > 0.0


def test_fsdp_target_excluded_from_overlap():
    """A WeightShard-governed op's sync is FSDP's reduce-scatter, not an
    overlappable all-reduce — it must not be double-discounted."""
    from flexflow_tpu.analysis.collectives import overlappable_grad_syncs
    from flexflow_tpu.parallel.weight_sharding import insert_weight_shard

    graph = _linear_graph()
    weight_ops = [op for op in graph.topo_order()
                  if op.weights and not op.is_parallel_op]
    target = weight_ops[0]
    insert_weight_shard(graph, target, 2)
    ov = overlappable_grad_syncs(graph)
    assert target.guid not in ov
    assert all(op.guid in ov for op in weight_ops[1:])


# ---------------------------------------------------------------------------
# Perfetto overlap evidence (runtime/profiler.py)
# ---------------------------------------------------------------------------

def test_simulated_timeline_shows_collective_compute_overlap(tmp_path):
    import json

    from flexflow_tpu.obs.tracer import to_chrome_trace
    from flexflow_tpu.runtime.profiler import (
        export_simulated_timeline,
        simulated_timeline_events,
    )
    from flexflow_tpu.search.cost_model import CostModel

    graph = _linear_graph()
    machine = _machine()
    cm = CostModel(machine)
    views = _dp_views(graph, machine)
    events = simulated_timeline_events(graph, views, cm,
                                       overlap_sync=True)
    syncs = [e for e in events if e["name"].endswith(".grad_sync")
             and e["args"].get("overlapped")]
    bwds = [e for e in events if e["name"].endswith(".bwd")]
    assert syncs and bwds
    comm_tid = syncs[0]["tid"]
    assert all(e["tid"] == comm_tid for e in syncs)
    assert comm_tid not in {e["tid"] for e in bwds}
    # at least one collective span is CONCURRENT with a backward span
    overlap_found = any(
        s["ts"] < b["ts"] + b["dur"] and b["ts"] < s["ts"] + s["dur"]
        for s in syncs for b in bwds
    )
    assert overlap_found, "no collective span concurrent with backward"
    # the export round-trips through the shared Chrome-trace schema
    path = str(tmp_path / "overlap_trace.json")
    export_simulated_timeline(graph, views, cm, path, overlap_sync=True)
    trace = json.load(open(path))
    names = {e.get("name") for e in trace["traceEvents"]}
    assert any(str(n).endswith(".grad_sync") for n in names)
    # default (non-overlap) export unchanged: no comm-channel spans
    base = simulated_timeline_events(graph, views, cm)
    assert not any(e["name"].endswith(".grad_sync") for e in base)
    assert to_chrome_trace(base)["traceEvents"]


# ---------------------------------------------------------------------------
# explain worklist (obs satellite)
# ---------------------------------------------------------------------------

def test_explain_worklist_shape():
    from flexflow_tpu.obs.explain import StrategyExplanation

    rows = [
        {"name": f"op{i}", "op_type": "OP_LINEAR", "parts": 1,
         "sim_fwd_s": 1e-5, "sim_bwd_s": 2e-5, "sim_total_s": 3e-5,
         "meas_fwd_s": 1e-4, "meas_bwd_s": 2e-4, "meas_total_s": 3e-4,
         "abs_err_s": (5 - i) * 1e-4, "ratio": 10.0, "_key": ("k", i)}
        for i in range(5)
    ]
    exp = StrategyExplanation(rows, {}, None)
    wl = exp.worklist(3)
    assert [w["rank"] for w in wl] == [1, 2, 3]
    assert [w["name"] for w in wl] == ["op0", "op1", "op2"]
    assert all("_key" not in w for w in wl)


def test_obs_cli_has_explain_subcommand():
    from flexflow_tpu.obs.__main__ import main

    with pytest.raises(SystemExit):
        main(["explain", "--bogus-flag-that-does-not-exist"])
