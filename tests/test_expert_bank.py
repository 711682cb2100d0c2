"""The expert bank (ops/moe.py): one op for a layer's router, the routed
experts held here and its shared expert. No token dropped under skewed
routing, the held range, the guide's share test (the shares' partial results,
the shared expert counted once, add up to the uncut layer), the per-token
decode rule with its counters, and the prices the searches pay."""
import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu import (AggrMode, DataType, FFConfig, FFModel, LossType,
                          SGDOptimizer)
from flexflow_tpu.ff_types import ActiMode, OperatorType
from flexflow_tpu.ops.moe import (EXPERT_BANK_COUNTERS, ExpertBankParams,
                                  route)
from flexflow_tpu.ops.registry import FwdCtx, get_op_def

E, N, K, F, FS = 16, 8, 2, 12, 20


def op():
    return get_op_def(OperatorType.OP_EXPERT_BANK)


def params(lo=0, hi=N, shared=FS, **kw):
    return ExpertBankParams(experts=N, held_from=lo, held_count=hi - lo,
                            top_k=K, width=F, shared_width=shared, scale=2.5,
                            **kw)


def weights(p, seed=0):
    """The WHOLE layer's weights; `held(w, p)` cuts a bank's share."""
    rng = np.random.RandomState(seed)
    return {"router": rng.randn(E, N).astype(np.float32),
            "b_corr": np.zeros(N, np.float32),
            "w_up": 0.4 * rng.randn(N, E, F).astype(np.float32),
            "w_down": 0.4 * rng.randn(N, F, E).astype(np.float32),
            "shared_up": 0.4 * rng.randn(E, FS).astype(np.float32),
            "shared_down": 0.4 * rng.randn(FS, E).astype(np.float32)}


def held(w, p):
    lo, hi = p.held_from, p.held_from + p.held_count
    out = {k: jnp.asarray(v) for k, v in w.items()}
    out["w_up"], out["w_down"] = out["w_up"][lo:hi], out["w_down"][lo:hi]
    if not p.shared_width:
        del out["shared_up"], out["shared_down"]
    return out


def naive(w, x, experts=range(N), shared=True):
    """The layer token by token in numpy float64: sigmoid scores over all
    experts, the K largest of score + b_corr, weights normalised x 2.5,
    every chosen expert among `experts` applied, relu squared."""
    x = np.asarray(x, np.float64).reshape(-1, E)
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    out = np.zeros_like(x)
    for t, v in enumerate(x):
        s = 1.0 / (1.0 + np.exp(-(v @ w["router"])))
        chosen = np.argsort(-(s + w["b_corr"]), kind="stable")[:K]
        gate = s[chosen] / (s[chosen].sum() + 1e-20) * 2.5
        for e, g in zip(chosen, gate):
            if e in experts:
                out[t] += g * (np.maximum(v @ w["w_up"][e], 0) ** 2
                               @ w["w_down"][e])
        if shared:
            out[t] += np.maximum(v @ w["shared_up"], 0) ** 2 @ w["shared_down"]
    return out


def run(p, w, x, ctx=None):
    (y,) = op().forward(p, held(w, p), [jnp.asarray(x)],
                        ctx or FwdCtx(training=False))
    return np.asarray(y, np.float64).reshape(-1, E)


def test_the_whole_layer_is_the_naive_reference():
    p, w = params(), weights(params())
    x = np.random.RandomState(1).randn(3, 7, E).astype(np.float32)
    assert np.abs(run(p, w, x) - naive(w, x)).max() < 1e-4
    assert op().infer(p, [(3, 7, E)], [DataType.DT_FLOAT])[0] == [(3, 7, E)]


def test_no_token_is_dropped_when_every_token_goes_to_one_expert():
    """A routing skewed as far as it goes: b_corr puts expert 5 first for
    every token (40 tokens on one expert, where a capacity of ceil(k T / n)
    = 10 would keep a quarter). The correction chooses and does not weigh:
    the gate is the plain score's share."""
    p, w = params(), weights(params(), 2)
    w["b_corr"][5] = 100.0
    x = np.random.RandomState(3).randn(40, E).astype(np.float32)
    ids, gate = route(p, jnp.asarray(w["router"]), jnp.asarray(w["b_corr"]),
                      jnp.asarray(x))
    assert np.all(np.asarray(ids)[:, 0] == 5)
    assert np.all(np.asarray(gate) < 2.5) and np.allclose(
        np.asarray(gate).sum(-1), 2.5, atol=1e-5)
    ctx = FwdCtx(training=False, counters={})
    assert np.abs(run(p, w, x, ctx) - naive(w, x)).max() < 1e-4
    assert int(ctx.counters["moe_expert_load_max"]) == 40
    assert int(ctx.counters["moe_assignments_held"]) == 80
    assert int(ctx.counters["moe_assignments_elsewhere"]) == 0


@pytest.mark.parametrize("lo,hi", [(0, 4), (4, 8), (2, 3), (0, 8)])
def test_a_bank_computes_the_experts_it_holds_and_no_other(lo, hi):
    p, w = params(lo, hi), weights(params(), 4)
    x = np.random.RandomState(5).randn(2, 9, E).astype(np.float32)
    ctx = FwdCtx(training=False, counters={})
    assert np.abs(run(p, w, x, ctx)
                  - naive(w, x, experts=range(lo, hi))).max() < 1e-4
    assert set(ctx.counters) == set(EXPERT_BANK_COUNTERS)
    chosen = np.asarray(route(p, jnp.asarray(w["router"]),
                              jnp.asarray(w["b_corr"]),
                              jnp.asarray(x.reshape(-1, E)))[0])
    here = (chosen >= lo) & (chosen < hi)
    assert int(ctx.counters["moe_assignments_held"]) == here.sum()
    assert int(ctx.counters["moe_assignments_elsewhere"]) == (~here).sum()
    assert int(ctx.counters["moe_experts_touched"]) == \
        len(set(chosen[here]))
    with pytest.raises(ValueError):
        params(6, 10)


def test_a_long_block_takes_the_experts_a_group_at_a_time(monkeypatch):
    """Past the stated bytes of hidden activations the held experts are
    taken in groups that divide them (8 held: 2 at a time here, then one by
    one), and the result is the same."""
    from flexflow_tpu.ops import moe

    p, w = params(), weights(params(), 9)
    x = np.random.RandomState(10).randn(30, E).astype(np.float32)
    whole = run(p, w, x)
    for g in (3, 1):  # 3 does not divide 8: the next that does is 2
        monkeypatch.setattr(moe, "_BANK_HIDDEN_BYTES", 4 * 30 * g * F)
        assert np.abs(run(p, w, x) - whole).max() < 1e-5
    assert np.abs(whole - naive(w, x)).max() < 1e-4


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: two chips hold experts 0..3 and 4..7 of one
    layer, router and shared expert whole on both. Their partial results,
    the shared expert counted once, equal the uncut layer's reference."""
    w = weights(params(), 6)
    x = np.random.RandomState(7).randn(4, 11, E).astype(np.float32)
    first = run(params(0, 4), w, x)                   # routed half + shared
    second = run(params(4, 8, shared=0), w, x)        # routed half alone
    whole = naive(w, x)
    assert np.abs(first + second - whole).max() < 1e-4
    # each half is a part, not the whole: the cut shows
    assert np.abs(first - whole).max() > 1e-2
    # and counted twice the shared expert would not add up
    assert np.abs(first + run(params(4, 8), w, x) - whole).max() > 1e-2


def test_relu2_is_an_activation_mode():
    from flexflow_tpu.ops.common import apply_activation

    x = jnp.asarray([-2.0, 0.0, 3.0])
    assert np.array_equal(np.asarray(
        apply_activation(ActiMode.AC_MODE_RELU2, x)), [0.0, 0.0, 9.0])
    assert params().activation == ActiMode.AC_MODE_RELU2


def build_lm(slots=4, max_len=32, vocab=61):
    cfg = FFConfig()
    cfg.batch_size = slots
    m = FFModel(cfg)
    ids = m.create_tensor((slots, max_len), DataType.DT_INT32, name="ids")
    x = m.embedding(ids, vocab, E, AggrMode.AGGR_MODE_NONE, name="wte")
    a = m.multihead_attention(x, x, x, E, 4, causal=True, bias=False,
                              num_kv_heads=2, name="attn")
    x = m.add(x, a, name="r0")
    for i in range(2):
        b = m.expert_bank(m.rms_norm(x, name=f"n{i}"), N, K, F, held=(0, 4),
                          shared_width=FS, scale=2.5, act="relu2",
                          name=f"e{i}")
        x = m.add(x, b, name=f"r{i + 1}")
    m.softmax(m.dense(x, vocab, use_bias=False, name="head"), name="probs")
    m.compile(optimizer=SGDOptimizer(lr=0.0),
              loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[])
    return m


@pytest.fixture(scope="module")
def lm():
    import sys

    argv, sys.argv = sys.argv, sys.argv[:1]
    try:
        return build_lm()
    finally:
        sys.argv = argv


def test_its_decode_rule_is_per_token_and_the_step_counts(lm):
    """Prefill 9 tokens then decode 3 through the step against the full
    forward; the caches' "counters" section holds what the two expert
    layers of the LAST step counted: 4 tokens x top 2 x 2 layers."""
    init, step = lm.executor.build_decode(4, 32)
    caches = init(lm.state.params, ())
    assert set(caches["counters"]) == set(EXPERT_BANK_COUNTERS)
    assert not caches["recurrent"] and set(caches["mha"]) == {"attn"}
    k, _ = caches["mha"]["attn"]
    assert k.shape == (4, 32, 2 * 4)          # the key-value heads alone
    ids = np.random.RandomState(8).randint(0, 61, (4, 32)).astype(np.int32)
    want = np.asarray(lm.executor.build_forward()(
        lm.state.params, [jnp.asarray(ids)]))
    got, caches = step(lm.state.params, caches, jnp.int32(0),
                       [jnp.asarray(ids[:, :9])])
    outs = [np.asarray(got)]
    for t in range(9, 12):
        got, caches = step(lm.state.params, caches, jnp.full((4,), t),
                           [jnp.asarray(ids[:, t:t + 1])])
        outs.append(np.asarray(got))
    assert np.abs(np.concatenate(outs, 1) - want[:, :12]).max() < 1e-5
    c = {k: int(v) for k, v in caches["counters"].items()}
    assert c["moe_assignments_held"] + c["moe_assignments_elsewhere"] == 16
    assert 0 < c["moe_experts_touched"] <= 8
    assert 0 < c["moe_expert_load_max"] <= 4


def test_the_batcher_sums_the_steps_counters_into_its_stats(lm):
    from flexflow_tpu.runtime.serving import (AdmissionQueue,
                                              ContinuousBatcher,
                                              GenerationRequest,
                                              ServingConfig)

    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(lm, ServingConfig(max_len=32, slots=4, page_size=4,
                                            precompile=False), q).start()
    reqs = [GenerationRequest(np.arange(n, dtype=np.int32), 6)
            for n in (5, 9, 3)]
    for r in reqs:
        q.offer(r)
    for r in reqs:
        r.result(timeout=300.0)
    stats = dict(b.stats)
    b.stop(timeout=60.0)
    # every step routes all 4 rows (an empty slot's row too) in 2 layers
    assert stats["moe_assignments_held"] + stats["moe_assignments_elsewhere"] \
        == stats["iterations"] * 4 * K * 2
    assert 0 < stats["moe_experts_touched"] <= stats["iterations"] * 8
    assert 0 < stats["moe_expert_load_max"] <= 4


def test_both_searches_price_the_op(lm):
    from flexflow_tpu.search.cost_model import (op_decode_bytes, op_flops,
                                                op_weight_bytes)

    bank = next(o for o in lm.graph.topo_order()
                if o.op_type == OperatorType.OP_EXPERT_BANK)
    tokens = 4 * 32
    # router, shared expert, and each of the 4 held experts on every token
    assert op_flops(bank) == 2.0 * tokens * E * (N + 2 * FS + 2 * 4 * F)
    # a decode step reads every held expert once, and 4 tokens in and out
    assert op_decode_bytes(bank) == op_weight_bytes(bank) + 2 * 4 * E * 4
    attn = next(o for o in lm.graph.topo_order()
                if o.op_type == OperatorType.OP_MULTIHEAD_ATTENTION)
    # grouped-query: half the keys and values of an ungrouped op
    assert op_decode_bytes(attn) == op_weight_bytes(attn) \
        + 2 * (4 * 32 * E * 4) / 2 + 4 * (4 * E * 4)


# -- softmax routing and gated experts (three matrices) --------------------------
def gated_params(lo=0, hi=N, **kw):
    return params(lo, hi, router="softmax", gated=True,
                  activation=ActiMode.AC_MODE_SILU, **kw)


def gated_weights(seed=0):
    rng = np.random.RandomState(seed)
    w = weights(None, seed)
    w["w_gate"] = 0.4 * rng.randn(N, E, F).astype(np.float32)
    w["shared_gate"] = 0.4 * rng.randn(E, FS).astype(np.float32)
    return w


def gated_held(w, p):
    lo, hi = p.held_from, p.held_from + p.held_count
    out = held(w, p)
    out["w_gate"] = out["w_gate"][lo:hi]
    return out


def gated_naive(w, x, experts=range(N), shared=True):
    """A loop over tokens and experts in numpy float64: softmax over all
    experts, the K largest chosen, weights normalised x 2.5, every chosen
    expert among `experts` as down(silu(gate x) * up x)."""
    x = np.asarray(x, np.float64).reshape(-1, E)
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}

    def glu(v, gate, up, down):
        a = v @ gate
        return (a / (1.0 + np.exp(-a)) * (v @ up)) @ down

    out = np.zeros_like(x)
    for t, v in enumerate(x):
        logits = v @ w["router"]
        s = np.exp(logits - logits.max())
        s /= s.sum()
        chosen = np.argsort(-s, kind="stable")[:K]
        gate = s[chosen] / (s[chosen].sum() + 1e-20) * 2.5
        for e, g in zip(chosen, gate):
            if e in experts:
                out[t] += g * glu(v, w["w_gate"][e], w["w_up"][e],
                                  w["w_down"][e])
        if shared:
            out[t] += glu(v, w["shared_gate"], w["shared_up"],
                          w["shared_down"])
    return out


def gated_run(p, w, x):
    (y,) = op().forward(p, gated_held(w, p), [jnp.asarray(x)],
                        FwdCtx(training=False))
    return np.asarray(y, np.float64).reshape(-1, E)


def test_softmax_routing_and_gated_experts_are_the_loop_over_experts():
    p, w = gated_params(), gated_weights()
    names = [s.name for s in op().weights(p, [(2, 3, E)], [DataType.DT_FLOAT])]
    assert names == ["router", "b_corr", "w_up", "w_down", "w_gate",
                     "shared_up", "shared_down", "shared_gate"]
    x = np.random.RandomState(1).randn(3, 7, E).astype(np.float32)
    assert np.abs(gated_run(p, w, x) - gated_naive(w, x)).max() < 1e-4
    ids, gate = route(p, jnp.asarray(w["router"]), jnp.asarray(w["b_corr"]),
                      jnp.asarray(x.reshape(-1, E)))
    assert np.allclose(np.asarray(gate).sum(-1), 2.5, atol=1e-5)
    # the sigmoid router over the same logits chooses the same experts (both
    # are monotone) and weighs them otherwise
    _, sig = route(params(), jnp.asarray(w["router"]),
                   jnp.asarray(w["b_corr"]), jnp.asarray(x.reshape(-1, E)))
    assert not np.allclose(np.asarray(sig), np.asarray(gate), atol=1e-3)
    with pytest.raises(ValueError):
        params(router="tanh")


def test_a_long_gated_block_takes_the_experts_a_group_at_a_time(monkeypatch):
    from flexflow_tpu.ops import moe

    p, w = gated_params(), gated_weights()
    x = np.random.RandomState(5).randn(2, 9, E).astype(np.float32)
    whole = gated_run(p, w, x)
    monkeypatch.setattr(moe, "_BANK_HIDDEN_BYTES", 4 * 18 * 2 * F)
    assert np.abs(gated_run(p, w, x) - whole).max() < 1e-5


def test_the_gated_shares_add_up_to_the_uncut_layer():
    """The guide's share test for the softmax-routed gated layer: 4 chips
    hold 2 of the 8 experts each (the cell's 32 shares of 8 at a small
    size); their partial results, the shared expert counted once, add up to
    what the uncut layer gives."""
    w = gated_weights()
    x = np.random.RandomState(6).randn(4, 5, E).astype(np.float32)
    whole = gated_run(gated_params(), w, x)
    shares = [gated_run(gated_params(lo, lo + 2, shared=FS if lo == 0 else 0),
                        w, x) for lo in range(0, N, 2)]
    assert np.abs(sum(shares) - whole).max() < 1e-4
    assert np.abs(whole - gated_naive(w, x)).max() < 1e-4
    # one share is not the layer
    assert np.abs(shares[0] - whole).max() > 1e-2


def test_the_builder_spells_the_gated_softmax_bank():
    m = FFModel(FFConfig())
    x = m.create_tensor((2, 4, E), DataType.DT_FLOAT)
    m.expert_bank(x, N, K, F, held=(0, 4), shared_width=FS, scale=2.5,
                  act="silu_gated", router="softmax", name="g")
    m.expert_bank(x, N, K, F, held=(0, 4), shared_width=FS, scale=2.5,
                  name="u")
    g, u = (layer.params for layer in m.layers[-2:])
    assert g.gated and g.router == "softmax" \
        and g.activation == ActiMode.AC_MODE_SILU
    assert not u.gated and u.router == "sigmoid" \
        and u.activation == ActiMode.AC_MODE_RELU2
    from flexflow_tpu.search.cost_model import op_flops
    # three matrices an expert and the shared one, where the ungated has two
    m.compile(optimizer=SGDOptimizer(lr=0.0),
              loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
              metrics=[])
    ops = {o.name: o for o in m.executor.topo}
    tokens = 2 * 4
    assert op_flops(ops["g"]) == 2.0 * tokens * E * (N + 3 * FS + 3 * 4 * F)
    assert op_flops(ops["u"]) == 2.0 * tokens * E * (N + 2 * FS + 2 * 4 * F)
