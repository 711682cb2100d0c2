"""Kernel correctness tests: chunked attention, Pallas flash attention
(interpret mode on CPU), ring attention on the 8-device mesh — all checked
against naive attention."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.attention import (
    chunked_attention,
    flash_attention,
    flash_attention_folded,
    flash_tile_counts,
    ring_attention,
)

RNG = np.random.RandomState(0)


def naive_attention(q, k, v, causal=False):
    b, sq, h, d = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(b=2, s=64, h=4, d=16):
    return (
        jnp.asarray(RNG.randn(b, s, h, d).astype(np.float32)),
        jnp.asarray(RNG.randn(b, s, h, d).astype(np.float32)),
        jnp.asarray(RNG.randn(b, s, h, d).astype(np.float32)),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_matches_naive(causal):
    q, k, v = qkv()
    ours = chunked_attention(q, k, v, causal=causal, chunk_size=16)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-5)


def test_chunked_nondivisible_seq():
    q, k, v = qkv(s=50)
    ours = chunked_attention(q, k, v, chunk_size=16)
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-5)


def test_chunked_grad_matches_naive():
    q, k, v = qkv(s=32)
    g1 = jax.grad(lambda q_: jnp.sum(chunked_attention(q_, k, v, chunk_size=8)))(q)
    g2 = jax.grad(lambda q_: jnp.sum(naive_attention(q_, k, v)))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_interpret_matches_naive(causal):
    q, k, v = qkv(s=64)
    ours = flash_attention(q, k, v, causal, 32, 32, True)  # interpret mode
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-5)


def test_flash_custom_vjp():
    q, k, v = qkv(s=32)
    g = jax.grad(
        lambda q_: jnp.sum(flash_attention(q_, k, v, False, 16, 16, True))
    )(q)
    ref = jax.grad(lambda q_: jnp.sum(naive_attention(q_, k, v)))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref), atol=1e-4)


def test_flash_vdim_differs_from_kdim():
    """v_head_dim != qk_head_dim (FFModel.multihead_attention exposes
    separate kdim/vdim like the reference's cuDNN MHA) must work through
    the fused kernels, fwd and bwd."""
    q, k, _ = qkv(s=32)
    rng = np.random.RandomState(3)
    v = jnp.asarray(rng.randn(q.shape[0], 32, q.shape[2], 24)
                    .astype(np.float32))
    out = flash_attention(q, k, v, False, 16, 16, True)
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    for i in range(3):
        go = jax.grad(lambda *a: jnp.sum(
            flash_attention(a[0], a[1], a[2], False, 16, 16, True)),
            argnums=i)(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(naive_attention(*a)), argnums=i)(
            q, k, v)
        np.testing.assert_allclose(np.asarray(go), np.asarray(gr),
                                   atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_bwd_all_grads(causal):
    """The Pallas backward kernels (dq + dkv, lse-recompute scheme) must
    match dense-softmax autodiff for every input, with uneven block
    tiling (s=48 vs blocks 16/32)."""
    q, k, v = qkv(s=48)
    rng = np.random.RandomState(7)
    g_out = jnp.asarray(rng.randn(*q.shape).astype(np.float32))

    def ours(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal, 16, 32, True) * g_out)

    def ref(q_, k_, v_):
        return jnp.sum(naive_attention(q_, k_, v_, causal=causal) * g_out)

    for i in range(3):
        go = jax.grad(ours, argnums=i)(q, k, v)
        gr = jax.grad(ref, argnums=i)(q, k, v)
        np.testing.assert_allclose(np.asarray(go), np.asarray(gr),
                                   atol=2e-4, rtol=1e-3)


def _qkv_shapes(sq, sk, d=16, dv=None, b=2, h=2, seed=11):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, w).astype(np.float32))
                 for s, w in ((sq, d), (sk, d), (sk, dv or d), (sq, dv or d)))


# (seq_q, seq_k, v's width, block_q, block_k): several blocks along both
# axes, so a causal call has tiles of all three kinds (skipped, masked,
# full) and every gradient is gathered over more than one tile
BLOCKED_SHAPES = [
    (64, 64, 16, 16, 16),     # the square: 6 of 16 tiles above the diagonal
    (64, 64, 16, 32, 16),     # blocks of two sizes
    (16, 32, 16, 8, 8),       # more keys than queries: keys 16.. are never seen
    (32, 16, 16, 8, 8),       # more queries than keys: rows 16.. see every key
    (32, 32, 24, 8, 16),      # v wider than k
    (48, 48, 16, 32, 32),     # a short last block
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,dv,bq,bk", BLOCKED_SHAPES)
def test_flash_blocked_forward_and_grads_match_naive(sq, sk, dv, bq, bk,
                                                     causal):
    """The blocked walk (block_q / block_k forced, interpret mode) gives
    the output and all three gradients of the naive reference, whichever
    tiles it skips."""
    q, k, v, g_out = _qkv_shapes(sq, sk, dv=dv)

    def ours(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal, bq, bk, True)

    def ref(q_, k_, v_):
        return naive_attention(q_, k_, v_, causal=causal)

    np.testing.assert_allclose(np.asarray(ours(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=1e-5)
    go = jax.grad(lambda *a: jnp.sum(ours(*a) * g_out), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * g_out), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", go, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def test_flash_blocks_do_not_change_the_answer():
    """One algorithm, whatever the blocks: the default walk, one block a
    row and blocks of 16 agree to float32 rounding (they differ only in
    the order of the sums)."""
    q, k, v, g_out = _qkv_shapes(64, 64)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(4, 64, 16)
    qf, kf, vf, gf = fold(q), fold(k), fold(v), fold(g_out)

    def grads(**blocks):
        return jax.grad(lambda *a: jnp.sum(flash_attention_folded(
            *a, True, True, **blocks) * gf), (0, 1, 2))(qf, kf, vf)

    base = grads()
    for blocks in ({"block_q": 64, "block_k": 64},
                   {"block_q": 16, "block_k": 16}):
        for a, b in zip(grads(**blocks), base):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


@pytest.mark.parametrize("shape,want", [
    ((1024, 1024, 256, 256, True), (10, 6)),
    ((1024, 1024, 128, 128, True), (36, 28)),
    ((1024, 1024, 512, 512, True), (3, 1)),
    ((1024, 1024, 1024, 1024, False), (1, 0)),   # a non-causal row
    ((1024, 1024, 256, 256, False), (16, 0)),    # nothing to skip
    ((16, 32, 8, 8, True), (3, 5)),
    ((32, 16, 8, 8, True), (7, 1)),
])
def test_flash_tile_counts(shape, want):
    assert flash_tile_counts(*shape) == want


@pytest.mark.parametrize("sq,sk,bq,bk", [(16, 32, 8, 8), (32, 16, 8, 8),
                                          (48, 40, 16, 32), (24, 24, 8, 16)])
def test_flash_skips_no_tile_that_holds_an_unmasked_pair(sq, sk, bq, bk):
    """Top-left aligned (kv_pos <= q_pos) whatever the two lengths: a
    tile is skipped exactly when every pair in it is masked, and goes
    unmasked exactly when none is."""
    from flexflow_tpu.kernels.attention import _axis_blocks, _tile_state

    seen = np.tril(np.ones((sq, sk), bool))
    for q0, q1 in _axis_blocks(sq, bq):
        for k0, k1 in _axis_blocks(sk, bk):
            tile = seen[q0:q1, k0:k1]
            state = _tile_state(q0, q1, k0, k1, True)
            assert (state == "skipped") == (not tile.any()), (q0, k0)
            assert (state == "full") == bool(tile.all()), (q0, k0)
            assert _tile_state(q0, q1, k0, k1, False) == "full"


def test_flash_tiles_counter_reads_the_walk(tmp_path):
    """ff_flash_tiles_total{pass, state} after one traced forward +
    backward is flash_tile_counts of the blocks, once a kernel built."""
    from flexflow_tpu import obs
    from flexflow_tpu.obs import TelemetryConfig

    q, k, v, _ = _qkv_shapes(64, 64)
    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))):
        jax.grad(lambda q_: jnp.sum(
            flash_attention(q_, k, v, True, 16, 16, True)))(q)
        found = obs.active().metrics.find
        read = {(p, s): found("ff_flash_tiles_total", **{"pass": p,
                                                         "state": s}).value
                for p in ("fwd", "bwd") for s in ("computed", "skipped")}
    computed, skipped = flash_tile_counts(64, 64, 16, 16, True)
    assert (computed, skipped) == (10, 6)
    assert read == {("fwd", "computed"): 10.0, ("fwd", "skipped"): 6.0,
                    ("bwd", "computed"): 10.0, ("bwd", "skipped"): 6.0}


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_naive(causal):
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("sp",))
    q, k, v = qkv(b=2, s=64, h=4, d=16)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal,
                          chunk_size=16),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    ours = ring(q, k, v)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_naive(causal):
    """Ulysses all_to_all sequence parallelism (head scatter) must be
    exact, like ring — it's plain attention over re-sharded data."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from flexflow_tpu.kernels.attention import ulysses_attention

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("sp",))
    q, k, v = qkv(b=2, s=64, h=4, d=16)

    uly = shard_map(
        functools.partial(ulysses_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    ours = uly(q, k, v)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=1e-4)
    g = jax.grad(lambda q_: jnp.sum(uly(q_, k, v)))(q)
    gr = jax.grad(lambda q_: jnp.sum(naive_attention(q_, k, v,
                                                     causal=causal)))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)


def test_ring_attention_grad():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("sp",))
    q, k, v = qkv(b=1, s=32, h=2, d=8)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", chunk_size=8),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    g = jax.grad(lambda q_: jnp.sum(ring(q_, k, v)))(q)
    ref = jax.grad(lambda q_: jnp.sum(naive_attention(q_, k, v)))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# FF_ATTENTION_IMPL dispatch (ops/attention.py)
# ---------------------------------------------------------------------------

def _mha_forward(monkeypatch, impl, *, dropout=0.0, training=False):
    """Run the MHA op forward under a forced impl, recording which kernel
    path executed."""
    import flexflow_tpu.ops.attention as mha
    from flexflow_tpu.ops.registry import FwdCtx, get_op_def
    from flexflow_tpu.ff_types import OperatorType

    if impl is not None:
        monkeypatch.setenv("FF_ATTENTION_IMPL", impl)
    else:
        monkeypatch.delenv("FF_ATTENTION_IMPL", raising=False)

    called = {}
    import flexflow_tpu.kernels.attention as kern

    real_chunked = kern.chunked_attention

    def spy_chunked(*a, **k):
        called.setdefault("path", "chunked")
        return real_chunked(*a, **k)

    def spy_flash(q, k_, v, causal=False, **kw):
        called.setdefault("path", "flash")
        return real_chunked(q, k_, v, causal=causal)

    monkeypatch.setattr(kern, "chunked_attention", spy_chunked)
    monkeypatch.setattr(kern, "flash_attention", spy_flash)

    params = mha.MultiHeadAttentionParams(embed_dim=16, num_heads=2)
    opdef = get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION)
    x = jnp.asarray(RNG.randn(2, 8, 16).astype(np.float32))
    shapes, dtypes = [(2, 8, 16)] * 3, None
    from flexflow_tpu.ff_types import DataType
    ws = opdef.weights(params, shapes, [DataType.DT_FLOAT] * 3)
    key = jax.random.PRNGKey(0)
    weights = {}
    for w in ws:
        key, sub = jax.random.split(key)
        weights[w.name] = jax.random.normal(sub, w.shape, jnp.float32) * 0.1
    if dropout:
        params = mha.MultiHeadAttentionParams(
            embed_dim=16, num_heads=2, dropout=dropout
        )
    ctx = FwdCtx(training=training, rng=key if training else None,
                 seq_length=-1, compute_dtype=None, aux_losses=None,
                 n_devices=1, mesh=None)
    out, = opdef.forward(params, weights, [x, x, x], ctx)
    return called.get("path", "dense"), out


@pytest.mark.parametrize("impl,expected", [
    (None, "dense"),        # auto at tiny size -> dense
    ("dense", "dense"),
    ("chunked", "chunked"),
    ("flash", "chunked"),   # flash on CPU backend falls back to chunked
])
def test_attention_impl_dispatch(monkeypatch, impl, expected):
    path, out = _mha_forward(monkeypatch, impl)
    assert path == expected
    assert out.shape == (2, 8, 16)


def test_attention_impl_invalid(monkeypatch):
    with pytest.raises(ValueError, match="FF_ATTENTION_IMPL"):
        _mha_forward(monkeypatch, "falsh")


def test_attention_impl_dropout_warns_and_runs_dense(monkeypatch):
    # on the CPU backend the fused dropout kernel is unavailable, so
    # forced-flash-with-dropout still lands on dense — with ONE warning
    # per (impl, layer, reason), not one per trace
    import flexflow_tpu.ops.attention as mha

    mha.reset_attention_fallback_warnings()
    with pytest.warns(UserWarning, match="dense path"):
        path, _ = _mha_forward(monkeypatch, "flash", dropout=0.5, training=True)
    assert path == "dense"
    # second identical call: deduped (no warning)
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        path, _ = _mha_forward(monkeypatch, "flash", dropout=0.5, training=True)
    assert path == "dense"
