"""The gated delta-rule op (ops/linear_attention.py) and what it brought
with it: the chunked form against the recurrence, the mask of valid tokens,
the decode rule and cache section, the RMS norm, SiLU and q/k norms, and the
prices the searches pay for them."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import (ActiMode, AggrMode, DataType, FFConfig, FFModel,
                          LossType, SGDOptimizer)
from flexflow_tpu.ff_types import OperatorType
from flexflow_tpu.ops.linear_attention import (CHUNK, GatedDeltaNetParams,
                                               _mix, delta_rule_chunked,
                                               delta_rule_step, init_state,
                                               state_bytes)
from flexflow_tpu.ops.registry import FwdCtx, get_op_def


def operands(seed, b, s, h, dk, dv, beta_max=2.0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, s, h, dk).astype(np.float32)
    k = rng.randn(b, s, h, dk).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(b, s, h, dv).astype(np.float32)
    beta = (beta_max * rng.rand(b, s, h)).astype(np.float32)
    g = np.log(rng.uniform(0.9, 0.999, (b, s, h))).astype(np.float32)
    S0 = 0.1 * rng.randn(b, h, dv, dk).astype(np.float32)
    return S0, q, k, v, g, beta


def recurrence(S, q, k, v, g, beta):
    outs = []
    step = jax.jit(delta_rule_step)
    for t in range(q.shape[1]):
        o, S = step(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), S


@pytest.mark.parametrize("s,chunk", [(64, 64), (192, 64), (24, 24), (40, 8)])
def test_chunked_form_is_the_recurrence(s, chunk):
    """Whole chunks, from a state that is not zero, with beta up to 2
    (negative eigenvalues). Tolerance: float32 round-off of a 64-wide
    triangular solve at key size 32 (1e-5 of outputs of size 3)."""
    S0, q, k, v, g, beta = operands(1, 2, s, 3, 32, 16)
    assert beta.max() > 1.5
    o, S = delta_rule_chunked(jnp.asarray(S0), q, k, v, g, beta, chunk)
    o_ref, S_ref = recurrence(jnp.asarray(S0), q, k, v, g, beta)
    assert np.abs(np.asarray(o - o_ref)).max() < 3e-5
    assert np.abs(np.asarray(S - S_ref)).max() < 3e-5


def tiny_params():
    return GatedDeltaNetParams(embed_dim=24, num_heads=2, head_k_dim=16,
                               head_v_dim=8)


def tiny_weights(p, seed=0):
    rng = np.random.RandomState(seed)
    spec = get_op_def(OperatorType.OP_GATED_DELTA_NET).weights(
        p, [(1, 1, p.embed_dim)], [DataType.DT_FLOAT])
    w = {s.name: jnp.asarray(0.3 * rng.randn(*s.shape), jnp.float32)
         for s in spec}
    w["norm"] = w["norm"] + 1.0
    return w


@pytest.mark.parametrize("length", [1, 5, 63, 64, 65, 100, 130])
def test_a_block_is_its_tokens_one_by_one(length):
    """The op on a block (chunked form, any length: not a multiple of the
    chunk takes the counted ragged path) gives what the op gives token by
    token (the recurrence), outputs, state and convolution tail alike."""
    p, ctx = tiny_params(), FwdCtx(training=False)
    w = tiny_weights(p)
    x = jnp.asarray(np.random.RandomState(2).randn(2, length, 24), jnp.float32)
    y, (S, tail) = _mix(p, w, x, ctx, init_state(p, 2, jnp.float32), None)
    state, ys = init_state(p, 2, jnp.float32), []
    for t in range(length):
        yt, state = _mix(p, w, x[:, t:t + 1], ctx, state, None)
        ys.append(yt)
    assert np.abs(np.asarray(y - jnp.concatenate(ys, 1))).max() < 2e-5
    assert np.abs(np.asarray(S - state[0])).max() < 2e-5
    assert np.abs(np.asarray(tail - state[1])).max() < 2e-5


@pytest.mark.parametrize("bucket", [8, 64, 128, 256])
def test_positions_beyond_the_valid_count_leave_the_state_alone(bucket):
    """Rows padded to a bucket, each with its own count of real tokens
    (0: nothing is real): state and tail are those of the real tokens
    alone, and the real positions' outputs do not see the padding."""
    p, ctx = tiny_params(), FwdCtx(training=False)
    w = tiny_weights(p, 3)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(3, bucket, 24), jnp.float32)
    valid = np.array([bucket - 3, bucket // 2 + 1, 0], np.int32)
    start = tuple(jnp.asarray(0.1 * rng.randn(*a.shape), a.dtype)
                  for a in init_state(p, 3, jnp.float32))
    y, (S, tail) = _mix(p, w, x, ctx, start, jnp.asarray(valid))
    for row, n in enumerate(valid):
        own = tuple(a[row:row + 1] for a in start)
        if n == 0:
            want_S, want_tail = own
        else:
            y1, (want_S, want_tail) = _mix(p, w, x[row:row + 1, :n], ctx,
                                           own, None)
            assert np.abs(np.asarray(y[row, :n] - y1[0])).max() < 2e-5
        assert np.abs(np.asarray(S[row] - want_S[0])).max() < 2e-5
        assert np.abs(np.asarray(tail[row] - want_tail[0])).max() < 2e-5


def test_state_bytes_are_the_leaves_bytes():
    p = GatedDeltaNetParams(embed_dim=3840, num_heads=30, head_k_dim=96,
                            head_v_dim=192)
    S, tail = jax.eval_shape(lambda: init_state(p, 1, jnp.bfloat16))
    assert state_bytes(p, 2) == S.size * 4 + tail.size * 2 \
        == 4 * 30 * 192 * 96 + 2 * 3 * 11520
    assert p.conv_channels == 11520 and CHUNK == 64


# -- through FFModel ------------------------------------------------------------
def hybrid(batch=2, seq=16, hidden=32, vocab=97):
    fc = FFConfig()
    fc.batch_size = batch
    fc.workersPerNode = 1
    fc.search_budget = -1
    m = FFModel(fc)
    ids = m.create_tensor((batch, seq), DataType.DT_INT32, name="ids")
    x = m.embedding(ids, vocab, hidden, AggrMode.AGGR_MODE_NONE, name="wte")
    for i, kind in enumerate(("linear", "full")):
        if kind == "linear":
            a = m.gated_delta_net(x, 2, 16, 32, name=f"h{i}.mixer")
        else:
            a = m.multihead_attention(x, x, x, hidden, 2, causal=True,
                                      bias=False, qk_norm=True,
                                      name=f"h{i}.mixer")
        x = m.add(x, m.rms_norm(a, name=f"h{i}.n1"), name=f"h{i}.r1")
        g = m.dense(x, 64, ActiMode.AC_MODE_SILU, use_bias=False,
                    name=f"h{i}.gate")
        u = m.silu(m.dense(x, 64, use_bias=False, name=f"h{i}.up"),
                   name=f"h{i}.act")
        d = m.dense(m.multiply(g, u, name=f"h{i}.glu"), hidden,
                    use_bias=False, name=f"h{i}.down")
        x = m.add(x, m.rms_norm(d, name=f"h{i}.n2"), name=f"h{i}.r2")
    x = m.dense(m.rms_norm(x, name="norm_f"), vocab, use_bias=False,
                name="head")
    m.softmax(x, name="probs")
    m.compile(optimizer=SGDOptimizer(lr=0.0),
              loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[])
    return m


@pytest.fixture(scope="module")
def model():
    import sys

    argv, sys.argv = sys.argv, sys.argv[:1]
    try:
        m = hybrid()
        # glorot weights, and decays and gates that are not their defaults
        rng = np.random.RandomState(5)
        mix = m.state.params["h0.mixer"]
        mix["dt_bias"] = jnp.asarray(rng.uniform(-4, -1, 2), jnp.float32)
        mix["wa"] = jnp.asarray(0.5 * rng.randn(32, 2), jnp.float32)
        mix["wb"] = jnp.asarray(0.5 * rng.randn(32, 2), jnp.float32)
        yield m
    finally:
        sys.argv = argv


def test_builder_calls_make_the_ops_and_both_searches_run(model):
    kinds = [op.op_type for op in model.executor.topo]
    assert OperatorType.OP_GATED_DELTA_NET in kinds
    assert OperatorType.OP_SILU in kinds
    weights = {n: set(w) for n, w in model.state.params.items()}
    assert weights["h0.n1"] == {"scale"}  # an RMS norm has no bias
    assert {"q_norm", "k_norm"} <= weights["h1.mixer"]
    assert weights["h0.mixer"] == {"wq", "wk", "wv", "wz", "wo", "wb", "wa",
                                   "conv", "A_log", "dt_bias", "norm"}
    assert model.compile_decode() is not None  # the decode-objective search


def test_decode_through_the_caches_is_the_full_forward(model):
    """Prefill a block, then one token at a time, rows at their own
    positions through the per-row `t`: the logits of every step are the
    full forward's at that position (float32 round-off)."""
    ex = model.executor
    init, step = ex.build_decode(2, 16)
    ids = np.random.RandomState(6).randint(0, 97, (2, 16)).astype(np.int32)
    full = np.asarray(ex.build_forward()(model.state.params,
                                         [jnp.asarray(ids)]))
    caches = init(model.state.params, ())
    assert set(caches["recurrent"]) == {"h0.mixer"}
    assert set(caches["mha"]) == {"h1.mixer"}
    S, tail = caches["recurrent"]["h0.mixer"]
    assert S.shape == (2, 2, 32, 16) and S.dtype == jnp.float32
    assert tail.shape == (2, 3, 2 * (16 + 16 + 32))
    probs, caches = step(model.state.params, caches, jnp.int32(0),
                         [jnp.asarray(ids[:, :5])])
    assert np.abs(np.asarray(probs) - full[:, :5]).max() < 1e-6
    for t in range(5, 16):
        probs, caches = step(model.state.params, caches,
                             jnp.asarray([t, t], jnp.int32),
                             [jnp.asarray(ids[:, t:t + 1])])
        assert np.abs(np.asarray(probs)[:, 0] - full[:, t]).max() < 1e-6


def test_a_padded_prefill_needs_the_valid_count(model):
    """A prompt of 5 in a bucket of 8: with the count the state is that of
    the 5 tokens; without it the padding has run through it."""
    ex = model.executor
    init, step = ex.build_decode(1, 16)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :5] = [3, 1, 4, 1, 5]
    params = model.state.params
    _, exact = step(params, init(params, ()), jnp.int32(0),
                    [jnp.asarray(ids[:, :5])])
    _, masked = step(params, init(params, ()), jnp.int32(0),
                     [jnp.asarray(ids)], jnp.int32(5))
    _, unmasked = step(params, init(params, ()), jnp.int32(0),
                       [jnp.asarray(ids)])
    for a, b in zip(exact["recurrent"]["h0.mixer"],
                    masked["recurrent"]["h0.mixer"]):
        assert np.abs(np.asarray(a - b)).max() < 1e-6
    S_exact, S_unmasked = (c["recurrent"]["h0.mixer"][0]
                           for c in (exact, unmasked))
    assert np.abs(np.asarray(S_exact - S_unmasked)).max() > 1e-3


def test_the_cost_model_prices_the_new_ops(model):
    from flexflow_tpu.search.cost_model import op_decode_bytes, op_flops

    ops = {op.name: op for op in model.executor.topo}
    gdn = ops["h0.mixer"]
    tokens, e, h, dk, dv = 2 * 16, 32, 2, 16, 32
    assert op_flops(gdn) == \
        2.0 * tokens * e * (h * (2 * dk + 3 * dv) + 2 * h) \
        + 6.0 * tokens * h * dv * dk + 2.0 * tokens * 4 * h * (2 * dk + dv)
    assert op_flops(ops["h0.n1"]) == 4.0 * tokens * e       # RMS norm
    assert op_flops(ops["h0.act"]) == 4.0 * tokens * 64     # SiLU
    # one decode step streams the op's weights, and reads and writes both
    # rows' state: more than the weights alone by exactly that
    weights = sum(int(np.prod(w.material_shape())) * w.data_type.size
                  for w in gdn.weights)
    state = 2 * 2 * state_bytes(gdn.params, 4)
    io = 2 * (2 * e) * 4  # one token's input and output, two rows
    assert op_decode_bytes(gdn) == pytest.approx(weights + state + io)


def test_beam_search_carries_the_recurrent_state(model):
    """Beam reorder gathers the recurrent section with the keys and
    values: greedy (one beam) is the incremental decode."""
    from flexflow_tpu.runtime.serving import (incremental_beam_generate,
                                              incremental_generate)

    prompt = np.array([[7, 3, 9, 2]], np.int32)
    greedy = incremental_generate(model, prompt, max_new_tokens=6, max_len=16)
    beams = incremental_beam_generate(model, prompt, num_beams=3,
                                      max_new_tokens=6, max_len=16)
    one = incremental_beam_generate(model, prompt, num_beams=1,
                                    max_new_tokens=6, max_len=16)
    assert np.array_equal(one, greedy)
    assert beams.shape == greedy.shape
    assert np.array_equal(beams[:, :4], prompt)


def test_decode_and_prefill_steps_lower_for_the_tpu(model, monkeypatch):
    """Both step programs of a model with the op export for the TPU
    platform from here (`pallas_compiled` says yes, as on the chip): the
    recurrent op has no rule the TPU lowering lacks, and a full layer of
    128-wide folded rows keeps its paged-decode kernel beside it."""
    from flexflow_tpu.kernels import attention as kattn
    from flexflow_tpu.kernels.decode import decode_block_pages

    monkeypatch.setattr(kattn, "pallas_compiled", lambda: True)
    ex = model.executor
    ex._decode_builds.clear()  # steps traced off the TPU took the jnp paths
    try:
        init, step = ex.build_decode(2, 16)
        params = model.state.params
        caches = jax.eval_shape(init, params, ())
        decode = jax.export.export(step, platforms=["tpu"])(
            params, caches, jax.ShapeDtypeStruct((2,), jnp.int32),
            [jax.ShapeDtypeStruct((2, 1), jnp.int32)])
        # the tiny full layer (2 heads of 16) cannot tile the paged kernel
        # and takes its counted dense branch: no Mosaic call here
        assert "tpu_custom_call" not in decode.mlir_module()
        init1, step1 = ex.build_decode(1, 16)
        caches1 = jax.eval_shape(init1, params, ())
        jax.export.export(step1, platforms=["tpu"])(
            params, caches1, jax.ShapeDtypeStruct((), jnp.int32),
            [jax.ShapeDtypeStruct((1, 8), jnp.int32)],
            jax.ShapeDtypeStruct((), jnp.int32))
    finally:
        ex._decode_builds.clear()
    # the hybrid cell's pool, bf16[2048,16,3840]: 30 heads of 128 tile
    assert decode_block_pages(3840, 3840, 16, jnp.bfloat16) is not None


def test_one_row_of_a_block_is_that_row_of_the_whole_output(model):
    """step(..., row): what follows the last attention runs on one
    position; its output is the whole block's at that position, the
    caches are the whole block's."""
    ex = model.executor
    init, step = ex.build_decode(2, 16)
    params = model.state.params
    ids = np.random.RandomState(8).randint(0, 97, (2, 8)).astype(np.int32)
    whole, caches = step(params, init(params, ()), jnp.int32(0),
                         [jnp.asarray(ids)], jnp.int32(6))
    one, caches1 = step(params, init(params, ()), jnp.int32(0),
                        [jnp.asarray(ids)], jnp.int32(6), jnp.int32(5))
    assert one.shape == (2, 1, 97)
    assert np.abs(np.asarray(one[:, 0] - whole[:, 5])).max() < 1e-6
    rows = jnp.asarray([2, 7], jnp.int32)  # a row of its own for each
    per_row, _ = step(params, init(params, ()), jnp.int32(0),
                      [jnp.asarray(ids)], None, rows)
    full, _ = step(params, init(params, ()), jnp.int32(0), [jnp.asarray(ids)])
    assert np.abs(np.asarray(per_row[0, 0] - full[0, 2])).max() < 1e-6
    assert np.abs(np.asarray(per_row[1, 0] - full[1, 7])).max() < 1e-6
    for a, b in zip(jax.tree_util.tree_leaves(caches),
                    jax.tree_util.tree_leaves(caches1)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
