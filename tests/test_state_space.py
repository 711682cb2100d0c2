"""The Mamba-2 mixer (ops/state_space.py): the chunked (SSD) form against the
recurrence, a ragged last chunk, the mask of valid tokens, the decode rule
and cache section through a served graph with a reused slot, and the prices
the searches pay for it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import (AggrMode, DataType, FFConfig, FFModel, LossType,
                          SGDOptimizer)
from flexflow_tpu.ff_types import OperatorType
from flexflow_tpu.ops.registry import FwdCtx, get_op_def
from flexflow_tpu.ops.state_space import (Mamba2Params, _mix, init_state,
                                          ssd_chunked, ssm_step, state_bytes)


def operands(seed, b, s, h, p, g, n):
    rng = np.random.RandomState(seed)
    x = rng.randn(b, s, h, p).astype(np.float32)
    B = rng.randn(b, s, g, n).astype(np.float32)
    C = rng.randn(b, s, g, n).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (b, s, h)).astype(np.float32)
    A = -np.exp(rng.uniform(-1.0, 1.0, h)).astype(np.float32)
    S0 = 0.1 * rng.randn(b, h, p, n).astype(np.float32)
    return S0, x, B, C, dt, A


def recurrence(S, x, B, C, dt, A):
    outs, step = [], jax.jit(ssm_step)
    for t in range(x.shape[1]):
        y, S = step(S, x[:, t], B[:, t], C[:, t], dt[:, t], A)
        outs.append(y)
    return jnp.stack(outs, 1), S


@pytest.mark.parametrize("s,chunk", [(128, 128), (192, 64), (24, 24), (40, 8)])
def test_chunked_form_is_the_recurrence(s, chunk):
    """Whole chunks from a state that is not zero, 6 heads in 2 groups.
    Tolerance: float32 round-off of sums over a chunk of terms of size 1
    (outputs of size 10; 2e-5 read)."""
    S0, x, B, C, dt, A = operands(1, 2, s, 6, 8, 2, 16)
    y, S = ssd_chunked(jnp.asarray(S0), x, B, C, dt, A, chunk)
    y_ref, S_ref = recurrence(jnp.asarray(S0), x, B, C, dt, A)
    assert np.abs(np.asarray(y_ref)).max() > 1.0
    assert np.abs(np.asarray(y - y_ref)).max() < 1e-4
    assert np.abs(np.asarray(S - S_ref)).max() < 1e-4


def tiny_params(**kw):
    return Mamba2Params(embed_dim=24, num_heads=4, head_dim=8, state_size=16,
                        n_groups=2, chunk_size=32, **kw)


def tiny_weights(p, seed=0):
    rng = np.random.RandomState(seed)
    spec = get_op_def(OperatorType.OP_MAMBA2).weights(
        p, [(1, 1, p.embed_dim)], [DataType.DT_FLOAT])
    w = {s.name: jnp.asarray(0.3 * rng.randn(*s.shape), jnp.float32)
         for s in spec}
    w["norm"], w["D"] = w["norm"] + 1.0, w["D"] + 1.0
    return w


@pytest.mark.parametrize("length", [1, 5, 31, 32, 33, 64, 70])
def test_a_block_is_its_tokens_one_by_one(length):
    """The op on a block (chunked form; a length that is no whole number of
    chunks takes the counted ragged path, one shorter than a chunk is one
    short chunk) gives what the op gives token by token (the recurrence):
    outputs, state and convolution tail alike."""
    p, ctx = tiny_params(), FwdCtx(training=False)
    w = tiny_weights(p)
    u = jnp.asarray(np.random.RandomState(2).randn(2, length, 24), jnp.float32)
    y, (S, tail) = _mix(p, w, u, ctx, init_state(p, 2, jnp.float32), None)
    state, ys = init_state(p, 2, jnp.float32), []
    for t in range(length):
        y_t, state = _mix(p, w, u[:, t:t + 1], ctx, state, None)
        ys.append(y_t)
    assert np.abs(np.asarray(y - jnp.concatenate(ys, 1))).max() < 2e-5
    assert np.abs(np.asarray(S - state[0])).max() < 2e-5
    # (a block's projection and a token's sum in another order)
    assert np.abs(np.asarray(tail - state[1])).max() < 2e-6


def test_only_a_ragged_block_is_counted(tmp_path):
    from flexflow_tpu import obs
    from flexflow_tpu.obs import TelemetryConfig

    p, ctx, w = tiny_params(), FwdCtx(training=False), tiny_weights(tiny_params())
    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))) as s:
        for length in (1, 8, 32, 64, 70):
            u = jnp.zeros((1, length, 24), jnp.float32)
            _mix(p, w, u, ctx, init_state(p, 1, jnp.float32), None)
        counted = s.metrics.counter("ff_ssm_fallback_total",
                                    reason="ragged_chunk").value
    assert counted == 1  # 70 = 2 x 32 + 6; 8 is one short chunk


def test_a_masked_tail_leaves_the_state_untouched():
    """A padded block told its rows' real lengths hands on the state and the
    tail of the real tokens alone, whatever the padding holds; told nothing,
    it does not."""
    p, ctx, w = tiny_params(), FwdCtx(training=False), tiny_weights(tiny_params())
    rng = np.random.RandomState(3)
    u = jnp.asarray(rng.randn(2, 64, 24), jnp.float32)
    valid = jnp.asarray([37, 5], jnp.int32)
    y, (S, tail) = _mix(p, w, u, ctx, init_state(p, 2, jnp.float32), valid)
    for row, n in enumerate((37, 5)):
        y1, (S1, tail1) = _mix(p, w, u[row:row + 1, :n], ctx,
                               init_state(p, 1, jnp.float32), None)
        assert np.abs(np.asarray(y[row, :n] - y1[0])).max() < 2e-5
        assert np.abs(np.asarray(S[row] - S1[0])).max() < 2e-5
        assert np.abs(np.asarray(tail[row] - tail1[0])).max() < 2e-6
    _, (S_all, _) = _mix(p, w, u, ctx, init_state(p, 2, jnp.float32), None)
    assert np.abs(np.asarray(S_all - S)).max() > 1e-3


def test_state_is_float32_whatever_the_compute_type_and_is_counted():
    p = tiny_params()
    S, tail = init_state(p, 3, jnp.bfloat16)
    assert S.dtype == jnp.float32 and S.shape == (3, 4, 8, 16)
    assert tail.dtype == jnp.bfloat16 and tail.shape == (3, 3, 32 + 2 * 32)
    assert state_bytes(p, 2) == 4 * 4 * 8 * 16 + 2 * 3 * 96
    # the published layer: 64 x 64 x 128 float32 = 2.10 MB a slot
    big = Mamba2Params(2688, 64, 64, 128, n_groups=8)
    assert big.in_width == 10304 and big.conv_channels == 6144
    assert round(state_bytes(big, 2) / 1e6, 2) == 2.13


def build_lm(slots=3, max_len=64, vocab=61, hidden=24):
    cfg = FFConfig()
    cfg.batch_size = slots
    m = FFModel(cfg)
    ids = m.create_tensor((slots, max_len), DataType.DT_INT32, name="ids")
    x = m.embedding(ids, vocab, hidden, AggrMode.AGGR_MODE_NONE, name="wte")
    for i in range(2):
        a = m.mamba2(m.rms_norm(x, name=f"n{i}"), 4, 8, 16, n_groups=2,
                     chunk_size=16, name=f"m{i}")
        x = m.add(x, a, name=f"r{i}")
    x = m.dense(x, vocab, use_bias=False, name="head")
    m.softmax(x, name="probs")
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


def test_the_decode_rule_puts_the_state_in_the_recurrent_section(lm):
    init, step = lm.executor.build_decode(3, 64)
    caches = init(lm.state.params, ())
    assert set(caches["recurrent"]) == {"m0", "m1"}
    assert not caches["mha"] and not caches["counters"]
    S, tail = caches["recurrent"]["m0"]
    assert S.shape == (3, 4, 8, 16) and tail.shape == (3, 3, 96)


def test_served_through_the_batcher_with_a_reused_slot(lm):
    """More requests than slots, prompts that are no powers of two (every
    prefill has a masked tail, one longer than a chunk): each answer is the
    greedy continuation the full forward gives, so a slot's second occupant
    starts from a fresh state."""
    from flexflow_tpu.runtime.serving import (AdmissionQueue,
                                              ContinuousBatcher,
                                              GenerationRequest,
                                              ServingConfig)

    q = AdmissionQueue(max_depth=32)
    b = ContinuousBatcher(lm, ServingConfig(max_len=64, slots=3, page_size=4,
                                            precompile=False), q).start()
    rng = np.random.RandomState(5)
    lengths, outs = [37, 5, 21, 3, 30, 9, 18], [6, 9, 4, 12, 5, 8, 7]
    reqs = [GenerationRequest(rng.randint(0, 61, n).astype(np.int32), o)
            for n, o in zip(lengths, outs)]
    for r in reqs:
        q.offer(r)
    answers = [np.asarray(r.result(timeout=300.0)) for r in reqs]
    stats = dict(b.stats)
    b.stop(timeout=60.0)
    assert stats["prefill_masked_tokens"] > 0
    mixer = next(layer.params for layer in lm.layers if layer.name == "m0")
    assert stats["recurrent_state_bytes"] == 3 * 2 * state_bytes(mixer, 4)
    fwd = lm.executor.build_forward()
    for r, toks, o in zip(reqs, answers, outs):
        assert len(toks) == len(r.prompt) + o
        buf = np.zeros((3, 64), np.int32)
        buf[0, :len(toks)] = toks
        probs = np.asarray(fwd(lm.state.params, [jnp.asarray(buf)]))[0]
        at = np.arange(len(r.prompt) - 1, len(toks) - 1)
        best = probs[at].max(-1)
        assert np.all(best - probs[at, toks[at + 1]] < 1e-5)


def test_both_searches_price_the_op(lm):
    from flexflow_tpu.search.cost_model import (op_decode_bytes, op_flops,
                                                op_weight_bytes)

    op = next(o for o in lm.graph.topo_order()
              if o.op_type == OperatorType.OP_MAMBA2)
    p = op.params
    tokens = 3 * 64
    assert op_flops(op) == 2.0 * tokens * 24 * (p.in_width + 32) \
        + 6.0 * tokens * 4 * 8 * 16 + 2.0 * tokens * 4 * 96
    # a decode step reads the weights once and each slot's state twice
    assert op_decode_bytes(op) >= op_weight_bytes(op) \
        + 2 * 3 * state_bytes(p, 4)
