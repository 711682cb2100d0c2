"""Rotary embeddings, a window and the per-head gate in the attention op
(ops/attention.py), the ring its decode cache becomes under a window, and the
band in the kernels' block walks (kernels/attention.py). Every path against
formulas written out here; an op with none of the three against the op as it
was."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.ff_types import DataType, OperatorType
from flexflow_tpu.kernels import attention as K
from flexflow_tpu.ops.attention import (AttentionConfigError,
                                        MultiHeadAttentionParams,
                                        RotaryParams, apply_rotary,
                                        rotary_table, window_ring)
from flexflow_tpu.ops.registry import FwdCtx, get_op_def
from flexflow_tpu.parallel import decode

E, H, KV, D = 24, 6, 2, 8
YARN = dict(theta=500000.0, dim=4, scaling="yarn", factor=128.0,
            original_max_position_embeddings=64, beta_fast=32.0,
            beta_slow=1.0)


def op():
    return get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION)


def params(**kw):
    return MultiHeadAttentionParams(embed_dim=E, num_heads=H, kdim=D, vdim=D,
                                    bias=False, causal=True, num_kv_heads=KV,
                                    **kw)


def weights(p, seed=0):
    rng = np.random.RandomState(seed)
    spec = op().weights(p, [(1, 1, E)] * 3, [DataType.DT_FLOAT] * 3)
    return {s.name: jnp.asarray(0.4 * rng.randn(*s.shape), jnp.float32)
            for s in spec}


# -- the formulas, written out -------------------------------------------------
def inv_freq_by_hand(rope: RotaryParams, d):
    """Per pair of channels, as `transformers` computes it."""
    dim = rope.dim or d
    out = []
    if rope.scaling == "yarn":
        def c(n):
            return dim * math.log(rope.original_max_position_embeddings
                                  / (2 * math.pi * n)) \
                / (2 * math.log(rope.theta))
        low = max(math.floor(c(rope.beta_fast)), 0)
        high = min(math.ceil(c(rope.beta_slow)), dim - 1)
    for i in range(dim // 2):
        extra = rope.theta ** (-2.0 * i / dim)
        if rope.scaling == "default":
            out.append(extra)
            continue
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extra / rope.factor * ramp + extra * (1.0 - ramp))
    return np.array(out)


def rotate_by_hand(rope, x, positions):
    """x (s, d) at `positions`: channel i of the rotated part paired with
    i + rot/2, the rest passed."""
    x = np.asarray(x, np.float64)
    d = x.shape[-1]
    inv = inv_freq_by_hand(rope, d)
    rot = 2 * len(inv)
    factor = 1.0 if rope.scaling == "default" else (
        rope.attention_factor or 0.1 * math.log(rope.factor) + 1.0)
    out = x.copy()
    for s, p in enumerate(positions):
        for i in range(rot // 2):
            a = p * inv[i]
            x1, x2 = x[s, i], x[s, i + rot // 2]
            out[s, i] = factor * (x1 * math.cos(a) - x2 * math.sin(a))
            out[s, i + rot // 2] = factor * (x2 * math.cos(a)
                                             + x1 * math.sin(a))
    return out


def naive(p, w, x):
    """The whole op head by head in numpy float64: rotary by the rows'
    positions, the band written out pair by pair, the gate."""
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    b, s, _ = x.shape
    out = np.zeros((b, s, E))
    pos = np.arange(s)
    mask = np.array([[j <= i and (not p.window or j > i - p.window)
                      for j in range(s)] for i in range(s)])
    for r in range(b):
        for h in range(p.num_heads):
            g = h // p.group
            q, k, v = (x[r] @ w[n][:, i] for n, i in
                       (("wq", h), ("wk", g), ("wv", g)))
            if p.rope is not None:
                q, k = (rotate_by_hand(p.rope, a, pos) for a in (q, k))
            sc = np.where(mask, q @ k.T / np.sqrt(D), -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            o = pr @ v
            if p.head_gate:
                o = o / (1.0 + np.exp(-(x[r] @ w["wg"][:, h])))[:, None]
            out[r] += o @ w["wo"][h]
    return out


ROPES = {"default": RotaryParams(theta=10000.0),
         "partial": RotaryParams(theta=10000.0, dim=4),
         "yarn": RotaryParams(**YARN)}


@pytest.mark.parametrize("name", sorted(ROPES))
def test_rotary_is_the_formula(name):
    rope = ROPES[name]
    inv, factor = rotary_table(rope, D)
    np.testing.assert_allclose(inv, inv_freq_by_hand(rope, D), rtol=1e-6)
    assert factor == pytest.approx(
        0.1 * math.log(128.0) + 1.0 if name == "yarn" else 1.0)
    x = np.random.RandomState(0).randn(1, 5, 3, D).astype(np.float32)
    positions = np.array([0, 1, 7, 63, 200])
    got = np.asarray(apply_rotary(rope, jnp.asarray(x),
                                  jnp.asarray(positions)))
    for h in range(3):
        np.testing.assert_allclose(
            got[0, :, h], rotate_by_hand(rope, x[0, :, h], positions),
            atol=2e-5)
    if name == "partial":  # the channels past the rotated ones pass
        assert np.array_equal(got[..., 4:], x[..., 4:])
    # per-row positions: each row by its own
    two = np.asarray(apply_rotary(
        rope, jnp.asarray(np.concatenate([x, x])),
        jnp.asarray(np.stack([positions, positions + 3]))))
    assert np.array_equal(two[0], got[0]) and not np.allclose(two[1], got[0])


def test_yarn_at_the_published_sizes_blends_between_its_two_corrections():
    rope = RotaryParams(theta=500000.0, dim=64, scaling="yarn", factor=128.0,
                        original_max_position_embeddings=8192,
                        attention_factor=1.4852030263919618)
    inv, factor = rotary_table(rope, 128)
    assert factor == pytest.approx(0.1 * math.log(128) + 1)
    extra = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    # c(32) = 9.06 -> low 9, c(1) = 17.5 -> high 18: pairs up to 9 keep
    # their frequency, pairs from 18 on are divided by the factor
    np.testing.assert_allclose(inv[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], extra[18:] / 128, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    with pytest.raises(ValueError):
        RotaryParams(scaling="ntk")
    with pytest.raises(ValueError):
        RotaryParams(scaling="yarn", factor=4.0)  # no original length


@pytest.mark.parametrize("kw", [
    dict(rope=ROPES["default"]), dict(rope=ROPES["yarn"], head_gate=True),
    dict(window=5), dict(window=4, rope=ROPES["partial"], head_gate=True),
], ids=["rope", "yarn-gate", "window", "all-three"])
def test_forward_is_the_naive_reference(kw):
    p = params(**kw)
    w = weights(p)
    assert ("wg" in w) == p.head_gate
    x = jnp.asarray(np.random.RandomState(1).randn(2, 13, E), jnp.float32)
    (y,) = op().forward(p, w, [x, x, x], FwdCtx(training=False))
    assert np.abs(np.asarray(y) - naive(p, w, x)).max() < 2e-5


@pytest.mark.parametrize("impl", ["chunked", "dense"])
def test_forward_window_by_each_path(impl, monkeypatch):
    monkeypatch.setenv("FF_ATTENTION_IMPL", impl)
    p = params(window=7, rope=ROPES["default"])
    w = weights(p)
    x = jnp.asarray(np.random.RandomState(2).randn(1, 40, E), jnp.float32)
    (y,) = op().forward(p, w, [x, x, x], FwdCtx(training=False))
    assert np.abs(np.asarray(y) - naive(p, w, x)).max() < 2e-5


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_a_window_under_sequence_parallelism_is_refused(impl, monkeypatch):
    monkeypatch.setenv("FF_ATTENTION_IMPL", impl)
    p = params(window=4)
    x = jnp.zeros((1, 8, E), jnp.float32)
    with pytest.raises(AttentionConfigError):
        op().forward(p, weights(p), [x, x, x], FwdCtx(training=False))
    with pytest.raises(ValueError):
        MultiHeadAttentionParams(embed_dim=E, num_heads=H, window=4)


# -- the band in the block walks -------------------------------------------------
def dense_band(q, k, v, window, q_offset=0, kv_offset=0):
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    sq, sk = q.shape[1], k.shape[1]
    qp = q_offset + np.arange(sq)[:, None]
    kp = kv_offset + np.arange(sk)[None, :]
    mask = (kp <= qp) & (kp >= 0)
    if window:
        mask &= kp > qp - window
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    sc = np.where(mask[None, None], sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", pr, v)


def test_tile_plan_skips_what_lies_wholly_outside_the_band():
    # 8 x 8 blocks of 128 under a window of 256: a query block sees its own
    # block (the diagonal crosses it), the one before (full: every pair
    # inside the band) and the one before that (the band's lower edge)
    assert K._tile_state(512, 640, 512, 640, True, 256) == "masked"
    assert K._tile_state(512, 640, 384, 512, True, 256) == "full"
    assert K._tile_state(512, 640, 256, 384, True, 256) == "masked"
    assert K._tile_state(512, 640, 128, 256, True, 256) == "skipped"
    assert K._tile_state(512, 640, 640, 768, True, 256) == "skipped"
    # a window wider than a block leaves a full tile between the edges
    assert K._tile_state(512, 640, 256, 384, True, 512) == "full"
    assert K._tile_state(512, 640, 0, 128, True, 512) == "masked"
    assert K._tile_state(640, 768, 0, 128, True, 512) == "skipped"
    # no window: as it was
    assert K._tile_state(512, 640, 0, 128, True) == "full"
    causal = K.flash_tile_counts(1024, 1024, 128, 128, True)
    banded = K.flash_tile_counts(1024, 1024, 128, 128, True, 256)
    assert causal == (36, 28) and banded == (1 + 2 + 6 * 3, 64 - 21)
    plan = K._walk(K._axis_blocks(1024, 128), K._axis_blocks(1024, 128),
                   lambda qb, kb: K._tile_state(*qb, *kb, True, 256))
    assert plan[0] == ((0, 128), (0, 128, 0, 128))
    # one dot over the three blocks, masked from edge to edge
    assert plan[5] == ((640, 768), (384, 768, 384, 768))


@pytest.mark.parametrize("window,sq", [(24, 64), (16, 48), (100, 64)])
def test_flash_kernel_under_a_window_forward_and_backward(window, sq):
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, sq, 2, 8), jnp.float32)
               for _ in range(3))
    want = dense_band(q, k, v, window)
    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    def plain(q, k, v):
        pos = jnp.arange(sq)
        mask = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - window)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(8)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def kernel(q, k, v):
        return K.flash_attention(q, k, v, True, block_q=16, block_k=16,
                                 interpret=True, window=window)

    assert np.abs(np.asarray(kernel(q, k, v)) - want).max() < 1e-5
    for a, b in zip(jax.grad(loss(kernel), (0, 1, 2))(q, k, v),
                    jax.grad(loss(plain), (0, 1, 2))(q, k, v)):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-4


@pytest.mark.parametrize("q_offset,kv_offset", [(0, 0), (32, 0), (7, -16)])
def test_chunk_scan_walks_the_band(q_offset, kv_offset):
    """Blocks of 8 queries against the 12 + 8 keys their band reaches, the
    offsets traced; keys at positions below 0 (a ring not yet full) are no
    keys."""
    rng = np.random.RandomState(4)
    sq, sk = 21, 64
    q = jnp.asarray(rng.randn(2, sq, 3, 8), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, sk, 3, 8), jnp.float32)
            for _ in range(2))
    fn = jax.jit(lambda qo, ko: K._chunk_scan(
        q, k, v, causal=True, chunk_size=8, q_offset=qo, kv_offset=ko,
        window=12)[0])
    got = np.asarray(fn(jnp.int32(q_offset), jnp.int32(kv_offset)))
    want = dense_band(q, k, v, 12, q_offset, kv_offset)
    assert np.abs(got - want).max() < 1e-5
    # no window: the scan as it was
    plain = K.chunked_attention(q, k[:, :sq], v[:, :sq], causal=True,
                                chunk_size=8)
    assert np.abs(np.asarray(plain)
                  - dense_band(q, k[:, :sq], v[:, :sq], 0)).max() < 1e-5


# -- the ring --------------------------------------------------------------------
def test_a_window_layers_cache_is_a_ring_of_whole_pages():
    assert window_ring(512, 8192) == 512 and window_ring(500, 8192) == 512
    assert window_ring(16, 256) == 16 and window_ring(20, 256) == 32
    assert window_ring(512, 64) == 64  # never longer than max_len
    k, v = op().init_decode_state(params(window=20), 3, 256, jnp.float32)
    assert k.shape == v.shape == (3, 32, KV * D)
    k, _ = op().init_decode_state(params(), 3, 256, jnp.float32)
    assert k.shape == (3, 256, KV * D)


@pytest.mark.parametrize("impl", ["dense", "paged"])
@pytest.mark.parametrize("window", [16, 12])
def test_decode_through_a_ring_is_decode_through_a_full_cache(
        impl, window, monkeypatch):
    """Token by token from an empty cache to three times past the window,
    rows at positions of their own (a per-row `t`: the second row starts 5
    tokens later). Window 16 is one page, so the ring is the window and the
    paged kernel reads it by length; window 12 leaves a ring of 16 longer
    than the window, whose stale entries the dense branch masks by
    position."""
    monkeypatch.setenv("FF_DECODE_IMPL", impl)
    p = params(window=window, rope=ROPES["default"], head_gate=True)
    w, ctx = weights(p), FwdCtx(training=False, counters={})
    steps, lag = 3 * window + 4, 5
    x = np.random.RandomState(5).randn(2, steps, E).astype(np.float32)
    want = naive(p, w, x)
    cache = op().init_decode_state(p, 2, 128, jnp.float32)
    assert cache[0].shape[1] == 16
    # row 1 runs `lag` tokens behind row 0: while it waits, it is fed its
    # first token at position 0 again (the slot is not yet occupied)
    got = np.zeros((2, steps, E))
    for i in range(steps + lag):
        t = np.array([min(i, steps - 1), max(i - lag, 0)])
        tok = np.stack([x[0, t[0]], x[1, t[1]]])[:, None]
        (y,), cache = op().forward_decode(
            p, w, [jnp.asarray(tok)] * 3, ctx, cache, jnp.asarray(t))
        got[0, t[0]], got[1, t[1]] = np.asarray(y)[:, 0]
    assert np.abs(got - want).max() < 2e-5
    # the last step read min(t + 1, window) positions a row
    assert int(ctx.counters["attn_window_positions_read"]) > 0


def test_positions_read_are_counted_by_layer_kind():
    for kw, name, want in ((dict(window=16), "attn_window_positions_read",
                            16 + 4),
                           (dict(rope=ROPES["default"]),
                            "attn_full_positions_read", 41 + 4)):
        p = params(**kw)
        assert op().counters_of(p) == (name,)
        ctx = FwdCtx(training=False, counters={})
        cache = op().init_decode_state(p, 2, 64, jnp.float32)
        op().forward_decode(p, weights(p), [jnp.zeros((2, 1, E))] * 3, ctx,
                            cache, jnp.asarray([40, 3]))
        assert int(ctx.counters[name]) == want
    assert op().counters_of(params()) == ()


@pytest.mark.parametrize("plen,bucket", [(37, 64), (16, 16), (5, 8),
                                         (50, 64)])
def test_a_padded_prefill_leaves_the_last_real_keys_in_the_ring(plen, bucket):
    """A bucket longer than the ring with a prompt that is no multiple of
    it: after the block the ring holds exactly the last min(plen, 16) real
    keys, each at its position's slot, and nothing of the padded tail;
    decoding on from there is the full forward."""
    p = params(window=16, rope=ROPES["partial"])
    w, ctx = weights(p), FwdCtx(training=False)
    rng = np.random.RandomState(6)
    x = rng.randn(1, plen + 6, E).astype(np.float32)
    block = np.concatenate([x[:, :plen], 9.0 * np.ones(
        (1, bucket - plen, E), np.float32)], 1)  # a tail that would show
    want = naive(p, w, x)
    cache = op().init_decode_state(p, 1, 128, jnp.float32)
    (y,), cache = op().forward_decode(
        p, w, [jnp.asarray(block)] * 3, ctx, cache, jnp.int32(0),
        valid=jnp.asarray([plen]))
    assert np.abs(np.asarray(y)[:, :plen] - want[:, :plen]).max() < 2e-5
    # the keys the ring must hold: the rotated keys of the real positions
    # alone, through a block with no padding and a cache that keeps all
    full = op().init_decode_state(params(rope=ROPES["partial"]), 1, 128,
                                  jnp.float32)
    _, full = op().forward_decode(
        params(rope=ROPES["partial"]), w, [jnp.asarray(x[:, :plen])] * 3,
        ctx, full, jnp.int32(0))
    ring_k = np.asarray(cache[0])[0]
    for pos in range(plen):
        held = np.allclose(ring_k[pos % 16], np.asarray(full[0])[0, pos])
        assert held == (pos >= plen - 16), pos
    if plen < 16:  # slots no real position reached stay empty
        assert np.all(ring_k[plen:] == 0)
    for t in range(plen, plen + 6):
        (y,), cache = op().forward_decode(
            p, w, [jnp.asarray(x[:, t:t + 1])] * 3, ctx, cache,
            jnp.asarray([t]))
        assert np.abs(np.asarray(y)[:, 0] - want[:, t]).max() < 2e-5


def test_a_later_block_reads_the_ring_before_itself(monkeypatch):
    """A second block at t > 0 (a prompt continued) sees the ring's keys in
    the order of their positions and then its own; long enough for the
    banded scan to take it."""
    import flexflow_tpu.ops.attention as A

    p = params(window=16)
    w, ctx = weights(p), FwdCtx(training=False)
    x = np.random.RandomState(7).randn(2, 70, E).astype(np.float32)
    want = naive(p, w, x)
    for budget in (A._DENSE_SCORE_BYTES, 1):  # dense, then the scan
        monkeypatch.setattr(A, "_DENSE_SCORE_BYTES", budget)
        cache = op().init_decode_state(p, 2, 128, jnp.float32)
        got = []
        for lo, hi in ((0, 23), (23, 30), (30, 70)):
            (y,), cache = op().forward_decode(
                p, w, [jnp.asarray(x[:, lo:hi])] * 3, ctx, cache,
                jnp.int32(lo))
            got.append(np.asarray(y))
        assert np.abs(np.concatenate(got, 1) - want).max() < 2e-5


def test_insert_row_carries_a_ring_strip():
    """The batch-1 strip of a window layer is a ring leaf like the running
    batch's: insert_row puts it at its slot as it puts any leaf."""
    pw, pf = params(window=16), params()
    def tree(batch, fill):
        out = {sec: {} for sec in decode.SHARED_SECTIONS
               + decode.SLOT_SECTIONS}
        out["mha"]["w"] = tuple(
            fill + c for c in op().init_decode_state(pw, batch, 64, jnp.float32))
        out["mha"]["f"] = tuple(
            fill + c for c in op().init_decode_state(pf, batch, 64, jnp.float32))
        return out
    merged = decode.insert_row(tree(3, 0.0), tree(1, 7.0), 1)
    k = merged["mha"]["w"][0]
    assert k.shape == (3, 16, KV * D)
    assert np.all(np.asarray(k[1]) == 7) and np.all(np.asarray(k[0]) == 0)
    assert merged["mha"]["f"][0].shape == (3, 64, KV * D)
    held = decode.state_bytes(merged)["kv"]
    assert held == 3 * (16 + 64) * 2 * KV * D * 4
    assert decode.kv_bytes_by_kind(merged, 64) == {
        "window": 3 * 16 * 2 * KV * D * 4, "full": 3 * 64 * 2 * KV * D * 4}


# -- an op with none of the three is the op as it was ----------------------------
def test_old_params_lower_unchanged():
    """Weights, cache, counters and the lowered text of forward and decode
    of an op with no rope, window or gate are what the defaults give, and
    name no scope of the new ones."""
    old = MultiHeadAttentionParams(embed_dim=E, num_heads=H, kdim=D, vdim=D,
                                   bias=False, causal=True, num_kv_heads=KV)
    assert old == params() and not old.marked and old.rope is None
    assert [s.name for s in op().weights(
        old, [(1, 1, E)] * 3, [DataType.DT_FLOAT] * 3)] == \
        ["wq", "wk", "wv", "wo"]
    w, ctx = weights(old), FwdCtx(training=False, counters={})
    x = jnp.zeros((2, 1, E), jnp.float32)
    cache = op().init_decode_state(old, 2, 32, jnp.float32)

    def step(p):
        return jax.jit(lambda c, t: op().forward_decode(
            p, weights(p), [x] * 3, ctx, c, t)).lower(
                cache, jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)

    text = step(old)
    assert "ff.attn" not in text and ctx.counters == {}
    marked = step(params(rope=ROPES["default"]))
    assert "ff.attn.rope" in marked and "ff.attn.full" not in text
    assert w.keys() == {"wq", "wk", "wv", "wo"}
