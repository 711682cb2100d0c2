"""The causal block walk a full layer's long prefill block takes
(kernels/attention.py `_causal_scan`): its real rows are `_chunk_scan`'s
whole walk, a query block wholly in the padded tail computes nothing, the
two tile counters are the arithmetic, the op takes the walk above the byte
threshold and the dense branch below it, and the Laguna and hybrid cells
served through it at their rehearsal sizes still agree with their
references."""
import functools
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.kernels import attention as K
from flexflow_tpu.ops import attention as A
from flexflow_tpu.ops.attention import (MultiHeadAttentionParams,
                                        RotaryParams)
from flexflow_tpu.ops.registry import FwdCtx, get_op_def
from flexflow_tpu.ff_types import DataType, OperatorType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a block of 100 queries (no whole number of 32-row blocks) against a cache
# of 448 positions in chunks of 16; 6 query heads of 16, values of 8
S0, BLOCK, CHUNK, SK, H, D, DV = 100, 32, 16, 448, 6, 16, 8


def tiles_by_hand(t, valid, s0=S0, sk=SK, block=BLOCK, chunk=CHUNK):
    """(computed, skipped): block i meets chunks 0 .. the one holding its
    last real query, none where its first row is past the real ones."""
    n_blocks, n_chunks = math.ceil(s0 / block), math.ceil(sk / chunk)
    computed = sum(
        0 if i * block >= valid
        else min(n_chunks, math.ceil((t + min((i + 1) * block, valid)) / chunk))
        for i in range(n_blocks))
    return computed, n_blocks * n_chunks - computed


@functools.lru_cache(maxsize=None)
def operands(group, dtype, sk=SK):
    rng = np.random.RandomState(group)
    n = H // group
    q = jnp.asarray(rng.randn(1, S0, H, D), dtype)
    k = jnp.asarray(rng.randn(1, sk, n * D), dtype)
    v = jnp.asarray(rng.randn(1, sk, n * DV), dtype)
    return q, k, v


@functools.lru_cache(maxsize=None)
def walks(group, dtype, sk=SK):
    """(the walk, the parent's whole walk over the cache repeated to every
    query head), each jitted once for every offset and `valid`."""
    n = H // group

    def by_query_head(c, width):
        return jnp.repeat(c.reshape(1, sk, n, width), group, axis=2)

    walk = jax.jit(lambda q, k, v, t, valid: K._causal_scan(
        q, k, v, block=BLOCK, chunk=CHUNK, q_offset=t, valid=valid))
    whole = jax.jit(lambda q, k, v, t: K._chunk_scan(
        q, by_query_head(k, D), by_query_head(v, DV), causal=True,
        chunk_size=CHUNK, q_offset=t)[0])
    return walk, whole


@pytest.mark.parametrize("group", [1, 6])
@pytest.mark.parametrize("valid", [1, BLOCK - 1, BLOCK, BLOCK + 1, S0])
@pytest.mark.parametrize("t", [0, 37, 300])
def test_real_rows_are_the_whole_walks(t, valid, group):
    """Chunk boundaries that coincide: with bfloat16 operands (what the chip
    serves) the real rows are the parent's to the last bit; with float32
    ones within round-off (XLA's CPU backend orders a product of another
    shape otherwise). The counters are the arithmetic."""
    for dtype, tol in ((jnp.bfloat16, 0.0), (jnp.float32, 2e-6)):
        q, k, v = operands(group, dtype)
        walk, whole = walks(group, dtype)
        out, computed, skipped = walk(q, k, v, jnp.int32(t), jnp.int32(valid))
        want = np.asarray(whole(q, k, v, jnp.int32(t)), np.float32)[:, :valid]
        got = np.asarray(out, np.float32)
        assert out.shape == (1, S0, H, DV) and out.dtype == dtype
        assert np.abs(got[:, :valid] - want).max() <= tol
        assert np.isfinite(got).all()
        assert (int(computed), int(skipped)) == tiles_by_hand(t, valid)


def test_a_wholly_padded_block_computes_nothing():
    """One real query: blocks 1..3 lie wholly in the padded tail, compute
    no tile and come out as finite zeros; block 0 meets one chunk."""
    q, k, v = operands(1, jnp.float32)
    walk, _ = walks(1, jnp.float32)
    out, computed, skipped = walk(q, k, v, jnp.int32(0), jnp.int32(1))
    out = np.asarray(out)
    assert (int(computed), int(skipped)) == (1, 4 * 28 - 1)
    assert np.all(out[:, BLOCK:] == 0)
    assert np.isfinite(out).all() and np.any(out[:, 0] != 0)


def test_tiles_of_a_whole_prompt_by_hand():
    """100 real queries from position 0: the four blocks reach 32, 64, 96
    and 100 keys, 2 + 4 + 6 + 7 chunks of 16, of 4 x 28."""
    assert tiles_by_hand(0, S0) == (19, 93)
    got = K.causal_tiles(S0, SK, block=BLOCK, chunk=CHUNK, q_offset=0,
                         valid=S0)
    assert np.asarray(got).tolist() == [2, 4, 6, 7]


def test_a_cache_that_is_no_whole_number_of_chunks():
    """440 positions in chunks of 16: the last chunk is padded and masked,
    as _chunk_scan pads it."""
    sk = 440
    q, k, v = operands(6, jnp.float32, sk)
    walk, whole = walks(6, jnp.float32, sk)
    t = jnp.int32(340)
    out, computed, _ = walk(q, k, v, t, jnp.int32(S0))
    want = np.asarray(whole(q, k, v, t))
    assert np.abs(np.asarray(out) - want).max() < 2e-6
    assert int(computed) == tiles_by_hand(340, S0, sk=sk)[0]


# -- the op's dispatch ---------------------------------------------------------
E, OH, KV, OD = 24, 6, 2, 8


def op_params():
    # rotary embeddings mark the op: it declares the prefill counters
    return MultiHeadAttentionParams(
        embed_dim=E, num_heads=OH, kdim=OD, vdim=OD, bias=False, causal=True,
        num_kv_heads=KV, rope=RotaryParams())


@pytest.mark.parametrize("budget", ["walk", "dense"])
def test_the_op_takes_the_walk_above_the_byte_threshold(budget, monkeypatch):
    """A prefill block of 40 (30 real) from position 5 against a cache of 64:
    past a threshold of 1 byte the walk runs and counts its tiles; under the
    default one the dense branch runs and counts none. The real rows agree."""
    opdef = get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION)
    p = op_params()
    assert opdef.counters_of(p, "prefill") == A.PREFILL_TILE_COUNTERS
    assert opdef.counters_of(
        MultiHeadAttentionParams(embed_dim=E, num_heads=OH, kdim=OD, vdim=OD,
                                 bias=False, causal=True, num_kv_heads=KV,
                                 window=16), "prefill") == ()
    rng = np.random.RandomState(0)
    w = {s.name: jnp.asarray(0.4 * rng.randn(*s.shape), jnp.float32)
         for s in opdef.weights(p, [(1, 1, E)] * 3, [DataType.DT_FLOAT] * 3)}
    x = jnp.asarray(rng.randn(1, 40, E), jnp.float32)
    calls = []
    real = K._causal_scan
    monkeypatch.setattr(K, "_causal_scan",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    monkeypatch.setattr(A, "_PREFILL_BLOCK", 16)
    monkeypatch.setattr(A, "_KEY_CHUNK", 16)

    def prefill():
        ctx = FwdCtx(training=False, counters={})
        cache = opdef.init_decode_state(p, 1, 64, jnp.float32)
        (y,), _ = opdef.forward_decode(p, w, [x] * 3, ctx, cache,
                                       jnp.int32(5), valid=jnp.asarray([30]))
        return np.asarray(y)[:, :30], ctx.counters

    dense, none = prefill()
    assert not calls and "attn_prefill_tiles_computed" not in none
    if budget == "walk":
        monkeypatch.setattr(A, "_DENSE_SCORE_BYTES", 1)
        got, counted = prefill()
        assert [kw["block"] for kw in calls] == [16]
        # blocks of 16 from position 5 end at 21, 35 (the 30th real row);
        # the third is padded: chunks 2 + 3 + 0 of 3 x 4
        assert int(counted["attn_prefill_tiles_computed"]) == 5
        assert int(counted["attn_prefill_tiles_skipped"]) == 7
        assert np.abs(got - dense).max() < 2e-5


# -- the cells at their rehearsal sizes, served through the walk -----------------
def served_through_the_walk(cell_name, lengths, outs):
    """Build `cell_name` at its rehearsal sizes with every prefill block past
    the byte threshold and walks of 32 queries and 64 keys, serve prompts of
    `lengths` (drawn from seed 7), and return (rows for serve.logit_gaps,
    what they added to the batcher's stats, the cell, the reference module,
    the batcher's bucket of a length)."""
    sys.path.insert(0, REPO)
    from perfbench.harness import runctx, serve, spec

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(A, "_DENSE_SCORE_BYTES", 0)
        mp.setattr(A, "_PREFILL_BLOCK", 32)
        mp.setattr(A, "_KEY_CHUNK", 64)
        cell = spec.cell(cell_name, rehearsal=True)
        builder, ref = spec.family(cell.config)
        sc = serve.ServeCell(cell, builder, ref, runctx.Spans())
        sc.build()
        sc.load_seed(11)
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, cell.config["vocab_size"], n)
                   for n in lengths]
        try:
            sc.start()  # serves two warm-up requests of its own
            warm = dict(sc.batcher.stats)
            reqs = [sc._offer(np.asarray(p, np.int32), o)
                    for p, o in zip(prompts, outs)]
            assert sc.drain(reqs, 600.0)
            rows = [{"prompt": np.asarray(p, np.int32),
                     "tokens": np.asarray(r.result(timeout=1.0))}
                    for p, r in zip(prompts, reqs)]
            added = {k: v - warm.get(k, 0)
                     for k, v in sc.batcher.stats.items()
                     if isinstance(v, (int, float))}
            bucket = sc.batcher._bucket
            sc.batcher.stop(timeout=60.0)
        finally:
            sc.free()
    return rows, added, cell, ref, bucket


def test_laguna_served_through_the_walk_is_the_reference():
    """Two full layers walk blocks of 32 over chunks of 64 of a 256-position
    cache (prompts in longer buckets, so wholly padded blocks too); the
    tokens' logit gap against the reference is float32 round-off (2e-5, as
    the cell's own rehearsal test), and the counters are the arithmetic."""
    from perfbench.harness import serve

    lengths = [150, 97, 130, 5, 33, 70]
    rows, added, cell, ref, bucket = served_through_the_walk(
        "serve-lagunas-code-saturated", lengths, [20, 16, 9, 30, 14, 8])
    n_full = cell.config["layer_types"].count("full_attention")
    want = [tiles_by_hand(0, n, s0=bucket(n), sk=256, block=32, chunk=64)
            for n in lengths]
    assert added["attn_prefill_tiles_computed"] == \
        n_full * sum(c for c, _ in want)
    assert added["attn_prefill_tiles_skipped"] == \
        n_full * sum(s for _, s in want)
    gaps = serve.logit_gaps(ref, cell.config, 11, rows)
    assert max(float(g.max()) for g in gaps) < 2e-5


def test_hybrid_served_through_the_walk_is_the_reference():
    """The hybrid's full layers take the walk beside chunked delta-rule
    layers that are told `valid`: against the reference at 2e-5, as the
    cell's own rehearsal test. Its attention is unmarked: nothing counted."""
    from perfbench.harness import serve

    rows, added, cell, ref, _ = served_through_the_walk(
        "serve-olmohybrid-docs-saturated", [150, 97, 5, 33, 70],
        [12, 20, 30, 14, 8])
    assert "attn_prefill_tiles_computed" not in added
    gaps = serve.logit_gaps(ref, cell.config, 11, rows)
    assert max(float(g.max()) for g in gaps) < 2e-5
