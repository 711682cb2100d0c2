"""Grouped-query heads in the attention op (ops/attention.py), its decode
cache and the paged flash-decode kernel (kernels/decode.py): key-value heads
fewer than query heads, query head i reading key-value head i // group. Each
path against a naive grouped-query reference written here, and
`num_kv_heads == num_heads` against the op as it was."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.ff_types import DataType, OperatorType
from flexflow_tpu.ops.attention import MultiHeadAttentionParams
from flexflow_tpu.ops.registry import FwdCtx, get_op_def

E, H, KV, D = 24, 4, 2, 8


def op():
    return get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION)


def params(kv=KV, **kw):
    return MultiHeadAttentionParams(embed_dim=E, num_heads=H, kdim=D, vdim=D,
                                    bias=False, causal=True, num_kv_heads=kv,
                                    **kw)


def weights(p, seed=0):
    rng = np.random.RandomState(seed)
    spec = op().weights(p, [(1, 1, E)] * 3, [DataType.DT_FLOAT] * 3)
    return {s.name: jnp.asarray(0.4 * rng.randn(*s.shape), jnp.float32)
            for s in spec}


def naive(p, w, x):
    """Causal grouped-query attention head by head, in numpy."""
    x, w = np.asarray(x, np.float64), {k: np.asarray(v, np.float64)
                                       for k, v in w.items()}
    b, s, _ = x.shape
    out = np.zeros((b, s, E))
    for h in range(p.num_heads):
        g = h // (p.num_heads // p.kv_heads)
        q, k, v = x @ w["wq"][:, h], x @ w["wk"][:, g], x @ w["wv"][:, g]
        sc = q @ k.transpose(0, 2, 1) / np.sqrt(D)
        sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        out += (pr @ v) @ w["wo"][h]
    return out


def test_the_weights_and_the_cache_hold_the_key_value_heads_alone():
    p = params()
    shapes = {s.name: s.shape for s in op().weights(
        p, [(1, 1, E)] * 3, [DataType.DT_FLOAT] * 3)}
    assert shapes == {"wq": (E, H, D), "wk": (E, KV, D), "wv": (E, KV, D),
                      "wo": (H, D, E)}
    k, v = op().init_decode_state(p, 3, 16, jnp.float32)
    assert k.shape == v.shape == (3, 16, KV * D)
    assert p.group == 2 and params(kv=0).group == 1
    with pytest.raises(ValueError):
        params(kv=3)


def test_forward_is_the_naive_grouped_reference():
    p = params()
    w = weights(p)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 10, E), jnp.float32)
    (y,) = op().forward(p, w, [x, x, x], FwdCtx(training=False))
    assert np.abs(np.asarray(y) - naive(p, w, x)).max() < 1e-5


@pytest.mark.parametrize("impl", ["dense", "paged"])
def test_prefill_then_decode_is_the_full_forward(impl, monkeypatch):
    """A block of 6 through the cache, then 5 single tokens with every row
    at its own position: the dense one-token branch (group rows sharing
    lanes) and the paged kernel in interpret mode, against the reference's
    full forward."""
    monkeypatch.setenv("FF_DECODE_IMPL", impl)
    p, ctx = params(), FwdCtx(training=False)
    w = weights(p)
    x = jnp.asarray(np.random.RandomState(2).randn(3, 11, E), jnp.float32)
    want = naive(p, w, x)
    cache = op().init_decode_state(p, 3, 16, jnp.float32)
    (y,), cache = op().forward_decode(p, w, [x[:, :6]] * 3, ctx, cache,
                                      jnp.int32(0))
    got = [np.asarray(y)]
    for t in range(6, 11):
        (y,), cache = op().forward_decode(
            p, w, [x[:, t:t + 1]] * 3, ctx, cache, jnp.full((3,), t))
        got.append(np.asarray(y))
    assert np.abs(np.concatenate(got, 1) - want).max() < 1e-5


@pytest.mark.parametrize("b,h,kv,d,page,pp,lengths,dtype", [
    (3, 4, 2, 8, 4, 4, [10, 1, 7], np.float32),
    (4, 6, 3, 8, 4, 40, [160, 129, 0, 37], np.float32),
    # the cell's shape class at fewer slots: 32 heads over 2 key-value heads
    # of 128, page 16, bf16: a row of 256 lanes, which Mosaic tiles
    (3, 32, 2, 128, 16, 16, [250, 37, 129], jnp.bfloat16),
], ids=["three-slots", "ragged-scattered", "cell-shape-bf16"])
def test_paged_kernel_reads_a_groups_lanes(b, h, kv, d, page, pp, lengths,
                                           dtype):
    from flexflow_tpu.kernels.decode import (decode_block_pages,
                                             paged_decode_reference,
                                             paged_flash_decode)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, d), dtype)
    pool_k = jnp.asarray(rng.randn(b * pp, page, kv, d), dtype)
    pool_v = jnp.asarray(rng.randn(b * pp, page, kv, d), dtype)
    table = rng.permutation(b * pp).reshape(b, pp).astype(np.int32)
    lengths = np.array(lengths, np.int32)
    if dtype == jnp.bfloat16:
        assert decode_block_pages(kv * d, kv * d, page, dtype) == 8
    out = paged_flash_decode(q, pool_k, pool_v, table, lengths,
                             interpret=True)
    ref = paged_decode_reference(q, pool_k, pool_v, table, lengths)
    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    live = lengths > 0
    assert out.shape == (b, h, d) and np.all(out[~live] == 0)
    np.testing.assert_allclose(
        out[live], ref[live], atol=1e-5 if dtype == np.float32 else 2e-2)


def test_as_many_key_value_heads_as_heads_is_the_op_as_it_was():
    """`num_kv_heads == num_heads` through the builder is the same params
    (so the same cached programs), and forward, prefill and decode give the
    ungrouped op's outputs bit for bit."""
    from flexflow_tpu import FFConfig, FFModel

    m = FFModel(FFConfig())
    x = m.create_tensor((2, 8, E), DataType.DT_FLOAT)
    m.multihead_attention(x, x, x, E, H, causal=True, num_kv_heads=H,
                          name="a")
    m.multihead_attention(x, x, x, E, H, causal=True, name="b")
    m.multihead_attention(x, x, x, E, H, causal=True, num_kv_heads=2,
                          name="c")
    a, b, c = (layer.params for layer in m.layers[-3:])
    assert a == b and hash(a) == hash(b) and c.kv_heads == 2
    old, new = params(kv=0), params(kv=H)
    w = weights(old)
    ctx = FwdCtx(training=False)
    x = jnp.asarray(np.random.RandomState(4).randn(2, 9, E), jnp.float32)
    assert np.array_equal(*(np.asarray(op().forward(p, w, [x] * 3, ctx)[0])
                            for p in (old, new)))
    outs = []
    for p in (old, new):
        cache = op().init_decode_state(p, 2, 16, jnp.float32)
        (y0,), cache = op().forward_decode(p, w, [x[:, :8]] * 3, ctx, cache,
                                           jnp.int32(0))
        (y1,), cache = op().forward_decode(p, w, [x[:, 8:]] * 3, ctx, cache,
                                           jnp.full((2,), 8))
        outs.append(np.concatenate([np.asarray(y0), np.asarray(y1)], 1))
    assert np.array_equal(*outs)
