"""Attention kernels: chunked online-softmax attention, Pallas flash
attention, and ring attention for sequence/context parallelism.

These replace the reference's cuDNN `cudnnMultiHeadAttnForward` path
(src/ops/attention.cc + attention.cu) with TPU-native kernels, and add the
long-context capability the reference lacks entirely (SURVEY §5: no ring
attention / sequence parallelism there).

Three tiers:
  * chunked_attention — lax.scan over KV chunks with running (max, sum,
    acc): O(seq) memory, jax-differentiable, what XLA fuses well. Default
    for long sequences on any backend.
  * flash_attention  — Pallas TPU kernel for the forward (blocked QK^T on
    the MXU, VMEM-resident accumulators), custom_vjp whose backward reuses
    chunked_attention's VJP (same math, exact gradients).
  * ring_attention   — shard_map over a seq-sharded mesh axis: each step
    computes a partial-attention block against the resident KV shard, then
    ppermutes KV around the ring (compute/ICI overlap is XLA's job);
    online-softmax merge keeps exactness. Differentiable through scan +
    ppermute.

Layout: (batch, seq, heads, head_dim) — "bshd".
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def pallas_compiled() -> bool:
    """THE decision between the compiled (Mosaic) Pallas kernels and every
    other attention path: compiled exactly when JAX's default backend is
    the TPU. No other code asks the backend that question. Under the
    default (auto) dispatch a backend that is not "tpu" takes the jnp
    paths, counted in ff_attention_fallback_total where a kernel was
    asked for — never the Pallas interpreter, which runs only where a
    test passes interpret=True or FF_DECODE_IMPL=paged is set by hand
    off the TPU."""
    return jax.default_backend() == "tpu"


def out_struct(shape, dtype, like):
    """pallas_call out_shape that varies over the same manual mesh axes
    as `like`: inside shard_map (check_vma) a kernel output without a vma
    is rejected, and outside it the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _chunk_scan(q, k, v, *, causal: bool, chunk_size: int, q_offset=0,
                kv_offset=0):
    """Online-softmax accumulation over KV chunks. q: (b, sq, h, d)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]                  # v_head_dim may differ from qk's d
    n_chunks = max(1, (sk + chunk_size - 1) // chunk_size)
    pad = n_chunks * chunk_size - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    kc = k.reshape(b, n_chunks, chunk_size, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk_size, h, dv).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, inputs):
        m_prev, l_prev, acc_prev = carry
        ci, k_blk, v_blk = inputs
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        kv_pos = kv_offset + ci * chunk_size + jnp.arange(chunk_size)
        mask = kv_pos[None, :] <= (sk + kv_offset - 1)  # padding mask
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None, :, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)  # (b,h,q)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * jnp.exp(m_prev - m_new) + jnp.sum(p, axis=-1)
        acc_new = acc_prev * jnp.exp(m_prev - m_new)[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    # Derive carries from q so they inherit q's varying manual axes when
    # running inside shard_map (fresh zeros would be unvarying and scan
    # would reject the carry type mismatch).
    zq = 0.0 * q.astype(jnp.float32).transpose(0, 2, 1, 3)  # (b,h,sq,d)
    m0 = zq[..., 0] + NEG_INF
    l0 = zq[..., 0]
    a0 = jnp.broadcast_to(zq[..., :1], zq.shape[:-1] + (dv,))  # (b,h,sq,dv)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype), m, l


def chunked_attention(q, k, v, *, causal: bool = False, chunk_size: int = 256):
    """Memory-efficient exact attention. (b, s, h, d) -> (b, s, h, d)."""
    out, _, _ = _chunk_scan(q, k, v, causal=causal,
                            chunk_size=min(chunk_size, k.shape[1]))
    return out


# ---------------------------------------------------------------------------
# Counter-based dropout bits (shared by the Pallas kernels and the dense
# reference path)
# ---------------------------------------------------------------------------
# The mask is a pure function of (seeds, element index): each score element
# (row, q, k) hashes its flat index with two 32-bit seeds drawn from the op's
# PRNG key, and keeps the probability iff the hash clears the drop threshold.
# Because the bits are counter-based, the flash kernels regenerate the exact
# same mask per block (forward AND backward) from the block offsets alone —
# no O(s^2) mask tensor ever touches HBM — and the dense path can materialize
# the identical mask for parity tests. Index arithmetic is uint32 with
# wraparound on both sides, so the two paths can never disagree.

def _mix32(h):
    """murmur3-style 32-bit finalizer (jnp uint32, wraps)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def _keep_bits(idx, s0, s1):
    """uint32 hash of a flat element index under two uint32 seeds."""
    h = (idx * jnp.uint32(0x9E3779B1)) ^ s0
    h = _mix32(h)
    h = h ^ s1
    return _mix32(h)


def _drop_threshold(rate: float) -> int:
    """Keep an element iff hash >= threshold: P(drop) == rate."""
    return min(0xFFFFFFFF, int(round(float(rate) * 4294967296.0)))


def dropout_seeds(rng):
    """Two uint32 seeds for the counter-based mask, drawn from a jax
    PRNG key (deterministic per key; works for both old uint32[2] keys
    and new-style typed keys)."""
    return jax.random.bits(rng, (2,), jnp.uint32)


def attention_dropout_mask(seeds, rate: float, bh: int, sq: int, sk: int):
    """The FULL (bh, sq, sk) keep-mask the flash kernels apply blockwise.

    `bh` rows follow the folded (batch*heads, b-major) layout; the dense
    path reshapes its (b, h, sq, sk) probs tensor to match. This is the
    parity oracle: flash-with-dropout under `seeds` equals dense attention
    masked with exactly this array."""
    if rate <= 0.0:
        return jnp.ones((bh, sq, sk), bool)
    s0 = seeds[0].astype(jnp.uint32)
    s1 = seeds[1].astype(jnp.uint32)
    row = lax.broadcasted_iota(jnp.uint32, (bh, sq, sk), 0)
    qp = lax.broadcasted_iota(jnp.uint32, (bh, sq, sk), 1)
    kp = lax.broadcasted_iota(jnp.uint32, (bh, sq, sk), 2)
    idx = (row * jnp.uint32(sq) + qp) * jnp.uint32(sk) + kp
    return _keep_bits(idx, s0, s1) >= jnp.uint32(_drop_threshold(rate))


def _keep_tile(seed_ref, row_u, sq: int, sk: int, kv_off, tile_q: int,
               tile_k: int, rate: float):
    """In-kernel keep-mask for one (tile_q, tile_k) score tile of row
    `row_u` (uint32 scalar), with the kv axis offset by `kv_off` — the
    blockwise view of attention_dropout_mask."""
    s0 = seed_ref[0]
    s1 = seed_ref[1]
    qp = lax.broadcasted_iota(jnp.uint32, (tile_q, tile_k), 0)
    kp = jnp.uint32(kv_off) + lax.broadcasted_iota(
        jnp.uint32, (tile_q, tile_k), 1
    )
    idx = (row_u * jnp.uint32(sq) + qp) * jnp.uint32(sk) + kp
    return _keep_bits(idx, s0, s1) >= jnp.uint32(_drop_threshold(rate))


# ---------------------------------------------------------------------------
# Pallas flash-attention forward
# ---------------------------------------------------------------------------

def _causal_mask(s, *, q_axis: int, kv_axis: int, kv_offset=0):
    """Apply the causal mask to a score tile; used (axis-swapped) by the
    forward, dq, and dkv kernels so they can never disagree."""
    q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kv_pos = kv_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, kv_axis)
    return jnp.where(kv_pos <= q_pos, s, NEG_INF)


def _flash_fwd_kernel(*refs, causal: bool, scale: float, g: int,
                      dropout: float = 0.0):
    """One program = g (batch*head) rows (g unrolled — measured 206→131 us
    at the bench shape by amortizing per-program overhead). Q/K/V for the
    whole row are VMEM resident (the fused path is capped to shapes where
    that holds), so each score tile is ONE MXU dot followed by a row
    softmax — no online accumulation. Dots take the inputs' dtype (bf16
    on the mixed-precision path = native MXU rate) and accumulate f32;
    scores/probs never touch HBM, which is what makes this beat the XLA
    dense path (134 MB of f32 scores per layer at the bench shape).

    dropout > 0 threads the counter-based keep-mask (_keep_tile) into the
    prob tile after the softmax statistics: l and the saved lse stay
    UNdropped (the standard flash-dropout scheme), only the p @ v
    contraction sees the masked/rescaled probs — so the mask never exists
    outside VMEM and the backward regenerates it bit-identically."""
    if dropout > 0.0:
        q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        seed_ref = None
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0
    for i in range(g):
        q = q_ref[i]                      # (seq_q, d), input dtype
        k = k_ref[i]                      # (seq_k, d)
        sq, sk = q.shape[0], k.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                         # (seq_q, seq_k) f32
        if causal:
            s = _causal_mask(s, q_axis=0, kv_axis=1)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            row_u = (pl.program_id(0) * g + i).astype(jnp.uint32)
            keep = _keep_tile(seed_ref, row_u, sq, sk, 0, sq, sk, dropout)
            p = jnp.where(keep, p * inv_keep, 0.0)
        o = jnp.dot(p.astype(q.dtype), v_ref[i],
                    preferred_element_type=jnp.float32)
        o_ref[i] = (o / jnp.maximum(l, 1e-30).astype(jnp.float32)).astype(
            o_ref.dtype
        )
        # log-sum-exp per query row, the backward's softmax residual;
        # stored (1, seq_q) — lanes-major, so the block shape (g, 1,
        # seq_q) satisfies the Mosaic (sublane, lane) tiling rule
        lse_ref[i] = (m + jnp.log(jnp.maximum(l, 1e-30))).T


def _flash_bwd_kernel(*refs, causal: bool, scale: float,
                      g: int, bk: int, dropout: float = 0.0):
    """Fused dq/dk/dv for g (batch*head) rows in ONE program: the prob
    tile is recomputed from q/k and the saved lse exactly once (the old
    split dq/dkv kernels each recomputed it), delta = rowsum(do*o) is
    computed in VMEM, and the transposed contractions for dk/dv avoid
    materializing pᵀ. Measured 541→306 us fwd+bwd at the bench shape.

    The kv axis is tiled at `bk` (unrolled — shapes are static): only a
    (seq_q, bk) slab of the score/prob/ds tiles is live at a time, which
    is what lets g=4 fit VMEM (full seq_k tiles capped g at 2; round-2
    measured the full-tile g=4 variant REGRESSING on VMEM pressure).

    dropout > 0 regenerates the forward's counter-based keep-mask per
    (row, kv-block) — same seeds, same indices, so bit-identical — and
    applies it where the chain rule puts it: dP = D ∘ (dO Vᵀ) before the
    softmax backward, and dV = (P ∘ D)ᵀ dO. delta = rowsum(dO ∘ O)
    already equals rowsum(P ∘ dP) under dropout, so the ds formula is
    unchanged."""
    if dropout > 0.0:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, seed_ref,
         dq_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dk_ref, dv_ref) = refs
        seed_ref = None
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0
    n_blocks = (k_ref.shape[1] + bk - 1) // bk
    sk_total = k_ref.shape[1]
    for i in range(g):
        q = q_ref[i]
        do = do_ref[i]
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[i].astype(jnp.float32),
            axis=-1, keepdims=True,
        )                                 # (seq_q, 1)
        lse_col = lse_ref[i].T            # lse (1, seq_q) -> column
        dq_acc = None
        for j in range(n_blocks):
            if causal and j * bk > q_ref.shape[1] - 1:
                # block entirely above the diagonal: p == 0 exactly —
                # skip its four dots, just zero the dk/dv slabs
                dk_ref[i, j * bk:(j + 1) * bk] = jnp.zeros_like(
                    dk_ref[i, j * bk:(j + 1) * bk])
                dv_ref[i, j * bk:(j + 1) * bk] = jnp.zeros_like(
                    dv_ref[i, j * bk:(j + 1) * bk])
                continue
            k = k_ref[i, j * bk:(j + 1) * bk]
            v = v_ref[i, j * bk:(j + 1) * bk]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                     # (seq_q, bk)
            if causal:
                s = _causal_mask(s, q_axis=0, kv_axis=1, kv_offset=j * bk)
            p = jnp.exp(s - lse_col)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout > 0.0:
                row_u = (pl.program_id(0) * g + i).astype(jnp.uint32)
                keep = _keep_tile(seed_ref, row_u, q.shape[0], sk_total,
                                  j * bk, q.shape[0], k.shape[0], dropout)
                dp = jnp.where(keep, dp * inv_keep, 0.0)
                pb = jnp.where(keep, p * inv_keep, 0.0).astype(q.dtype)
            else:
                pb = p.astype(q.dtype)
            ds = p * (dp - delta)
            dsb = ds.astype(q.dtype)
            dq = jnp.dot(dsb, k, preferred_element_type=jnp.float32)
            dq_acc = dq if dq_acc is None else dq_acc + dq
            dk = jax.lax.dot_general(
                dsb, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_ref[i, j * bk:(j + 1) * bk] = (dk * scale).astype(dk_ref.dtype)
            dv = jax.lax.dot_general(
                pb, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dv_ref[i, j * bk:(j + 1) * bk] = dv.astype(dv_ref.dtype)
        dq_ref[i] = (dq_acc * scale).astype(dq_ref.dtype)


def _bhsd_to_fold(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _fold_to_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# The fused path keeps the full (seq_q, seq_k) f32 score tile plus Q/K/V
# in VMEM per program; past this limit fall back to chunked_attention
# (long-context single-chip) or ring attention (sequence-parallel).
FLASH_FUSED_MAX_TILE = 1024 * 1024


def flash_supported(seq_q: int, seq_k: int) -> bool:
    return seq_q * seq_k <= FLASH_FUSED_MAX_TILE


def _pick_g(bh: int, sq: int, sk: int, budget: int, cap: int) -> int:
    """Rows per program: batch (b*h) rows until the f32 score tiles hit
    the VMEM budget (floats) or the measured sweet spot `cap`. Measured on
    v5e at 512x512/d64: fwd best at g=4, fused bwd (4 extra tiles live)
    at g=2; g=8 regresses — VMEM pressure beats overhead amortization."""
    g = 1
    for cand in (2, 4, 8):
        if cand > cap or bh % cand or cand * sq * sk > budget:
            break
        g = cand
    return g


def _flash_fwd_folded(qf, kf, vf, *, causal: bool, interpret: bool,
                      dropout: float = 0.0, seeds=None):
    """Core forward on (b*h, s, d) folded operands."""
    bh, sq, d = qf.shape
    sk = kf.shape[1]
    dv = vf.shape[-1]                 # v_head_dim may differ from qk's d
    g = _pick_g(bh, sq, sk, budget=2 * 1024 * 1024, cap=4)
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal, scale=scale,
                               g=g, dropout=dropout)
    in_specs = [
        pl.BlockSpec((g, sq, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((g, sk, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((g, sk, dv), lambda i: (i, 0, 0)),
    ]
    args = (qf, kf, vf)
    if dropout > 0.0:
        # two uint32 seeds ride in SMEM; the mask itself is regenerated
        # per score tile from counters (never materialized in HBM)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args = args + (jnp.asarray(seeds, jnp.uint32),)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh // g,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((g, sq, dv), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, 1, sq), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            out_struct((bh, sq, dv), qf.dtype, qf),
            out_struct((bh, 1, sq), jnp.float32, qf),
        ],
        interpret=interpret,
        name="ff_flash_fwd",
    )(*args)
    return out, lse


def _flash_bwd_folded(qf, kf, vf, of, lse, dof, *, causal: bool,
                      interpret: bool, dropout: float = 0.0, seeds=None):
    """Core backward on (b*h, s, d) folded operands."""
    bh, sq, d = qf.shape
    sk = kf.shape[1]
    dv_d = vf.shape[-1]               # v_head_dim may differ from qk's d
    # Default: FULL kv tile at g=2. The kv-blocked variant (bk < sk, which
    # halves live VMEM and admits g=4) was the round-2 verdict's suggested
    # retry; a round-3 sweep on a v5e found the full-tile g=2 schedule
    # the fastest, so blocking ships as an env-tunable (FF_FLASH_BWD_BK /
    # FF_FLASH_BWD_G, 0 = auto) rather than the default (ROADMAP C4: no
    # cell has measured it since).
    bk = int(os.environ.get("FF_FLASH_BWD_BK", "0")) or sk
    if bk <= 0 or bk > sk:
        bk = sk
    gg = int(os.environ.get("FF_FLASH_BWD_G", "0"))
    if gg <= 0 or bh % gg:
        # invalid override (non-divisor g would truncate the grid and leave
        # gradient rows unwritten) -> auto
        gg = _pick_g(bh, sq, bk, budget=1024 * 1024, cap=2)
    scale = 1.0 / math.sqrt(d)
    in_specs = [
        pl.BlockSpec((gg, sq, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((gg, sk, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((gg, sk, dv_d), lambda i: (i, 0, 0)),
        pl.BlockSpec((gg, sq, dv_d), lambda i: (i, 0, 0)),
        pl.BlockSpec((gg, sq, dv_d), lambda i: (i, 0, 0)),
        pl.BlockSpec((gg, 1, sq), lambda i: (i, 0, 0)),
    ]
    args = (qf, kf, vf, dof, of, lse)
    if dropout > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args = args + (jnp.asarray(seeds, jnp.uint32),)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, causal=causal, scale=scale,
                          g=gg, bk=bk, dropout=dropout),
        grid=(bh // gg,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((gg, sq, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gg, sk, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gg, sk, dv_d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            out_struct((bh, sq, d), qf.dtype, qf),
            out_struct((bh, sk, d), kf.dtype, qf),
            out_struct((bh, sk, dv_d), vf.dtype, qf),
        ],
        interpret=interpret,
        name="ff_flash_bwd",
    )(*args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_folded_core(qf, kf, vf, seeds, causal, interpret, dropout):
    out, _ = _flash_fwd_folded(qf, kf, vf, causal=causal,
                               interpret=interpret, dropout=dropout,
                               seeds=seeds)
    return out


def _flash_folded_vjp_fwd(qf, kf, vf, seeds, causal, interpret, dropout):
    out, lse = _flash_fwd_folded(qf, kf, vf, causal=causal,
                                 interpret=interpret, dropout=dropout,
                                 seeds=seeds)
    return out, (qf, kf, vf, out, lse, seeds)


def _flash_folded_vjp_bwd(causal, interpret, dropout, res, g):
    qf, kf, vf, out, lse, seeds = res
    dq, dk, dv = _flash_bwd_folded(qf, kf, vf, out, lse, g, causal=causal,
                                   interpret=interpret, dropout=dropout,
                                   seeds=seeds)
    return dq, dk, dv, None  # seeds are integral: no cotangent


_flash_folded_core.defvjp(_flash_folded_vjp_fwd, _flash_folded_vjp_bwd)


def flash_attention_folded(qf, kf, vf, causal: bool = False,
                           interpret: bool = False, *,
                           dropout: float = 0.0, seeds=None):
    """flash_attention on PRE-FOLDED (batch*heads, seq, head_dim)
    operands. The MHA op's fast path projects q/k/v straight into this
    layout (einsum "bse,ehd->bhsd" + free reshape), so the per-layer
    fold/unfold transposes of the bshd wrapper never materialize.

    dropout/seeds thread attention dropout INTO the kernels: the
    counter-based keep-mask (attention_dropout_mask with these `seeds`,
    two uint32s from dropout_seeds(rng)) is regenerated per VMEM tile in
    the forward and the backward, so dropout no longer forces the
    dense-materialized path."""
    assert flash_supported(qf.shape[1], kf.shape[1]), (
        "sequence too long for the fused VMEM tile — use chunked_attention "
        "or ring_attention"
    )
    dropout = float(dropout)
    if dropout > 0.0 and seeds is None:
        raise ValueError("flash dropout needs seeds (dropout_seeds(rng))")
    if seeds is None:
        seeds = jnp.zeros((2,), jnp.uint32)
    return _flash_folded_core(qf, kf, vf, seeds, causal, interpret, dropout)


def flash_attention(q, k, v, causal: bool = False, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False, *,
                    dropout: float = 0.0, seeds=None):
    """Fused Pallas attention: forward AND backward keep scores/probs in
    VMEM (the backward recomputes the prob tile from the saved per-row
    log-sum-exp — the standard flash-attention scheme) and batch several
    (batch*head) rows per program (_pick_g). Requires
    flash_supported(seq_q, seq_k); block_q/block_k are accepted for
    signature stability but rows are processed as whole tiles. Routes
    through the folded core, so gradients and RNG-threaded dropout
    (dropout/seeds) behave identically to flash_attention_folded."""
    b, _, h, _ = q.shape
    out = flash_attention_folded(
        _bhsd_to_fold(q), _bhsd_to_fold(k), _bhsd_to_fold(v),
        causal=causal, interpret=interpret, dropout=dropout, seeds=seeds,
    )
    return _fold_to_bhsd(out, b, h)


def local_attention(q, k, v, *, causal: bool = False):
    """The single device-local streaming dispatch: fused Pallas kernel on
    TPU while its VMEM tile fits, chunked scan otherwise. Both the MHA
    op's streaming branch (ops/attention.py) and ulysses_attention route
    through here so the selection policy cannot drift between them."""
    if pallas_compiled() and flash_supported(q.shape[1], k.shape[1]):
        return flash_attention(q, k, v, causal)
    return chunked_attention(q, k, v, causal=causal)


# ---------------------------------------------------------------------------
# Ring attention (sequence/context parallelism over a mesh axis)
# ---------------------------------------------------------------------------

def ulysses_attention(q, k, v, axis_name: str, *, causal: bool = False):
    """DeepSpeed-Ulysses-style sequence parallelism: q/k/v arrive sharded
    along the sequence dim over `axis_name` (LOCAL shards, inside
    shard_map). One all_to_all re-shards sequence->heads so each device
    holds the FULL sequence for num_heads/n heads, local fused attention
    runs, and a second all_to_all restores the seq sharding. Two
    all_to_alls over ICI instead of ring's n-1 ppermutes — wins when
    heads divide the axis and the full-seq score tile still fits.

    No reference equivalent (SURVEY §5: sequence parallelism absent
    there); the head-scatter recipe follows the public Ulysses pattern
    (PAPERS.md)."""
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    assert h % n == 0, f"heads {h} must divide the {axis_name} axis {n}"
    # (b, s/n, h, d) -> (b, s, h/n, d)
    def scatter_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = local_attention(qh, kh, vh, causal=causal)
    return gather_heads(out)


def ring_attention(q, k, v, axis_name: str, *, causal: bool = False,
                   chunk_size: int = 256):
    """Exact attention when q/k/v are sharded along the sequence dim over
    `axis_name`. Must be called inside shard_map (q/k/v are the LOCAL
    shards). Each of the `n` steps attends against the resident KV shard,
    then rotates KV one hop around the ring (lax.ppermute over ICI),
    merging partial results with online softmax.

    No reference equivalent — this is the TPU build's first-class CP
    (SURVEY §5 gap); the blockwise formulation follows the public
    ring-attention recipe (PAPERS.md)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, sq_local, h, d = q.shape

    def step(carry, i):
        m, l, acc, k_cur, v_cur = carry
        # whose shard is resident this step
        src = (idx - i) % n
        kv_off = src * sq_local
        out_blk, m_blk, l_blk = _chunk_scan(
            q, k_cur, v_cur, causal=causal,
            chunk_size=min(chunk_size, sq_local),
            q_offset=idx * sq_local, kv_offset=kv_off,
        )
        acc_blk = out_blk.transpose(0, 2, 1, 3).astype(jnp.float32) * \
            jnp.maximum(l_blk[..., None], 1e-30)
        m_new = jnp.maximum(m, m_blk)
        alpha_old = jnp.exp(m - m_new)
        alpha_blk = jnp.exp(m_blk - m_new)
        l_new = l * alpha_old + l_blk * alpha_blk
        acc_new = acc * alpha_old[..., None] + acc_blk * alpha_blk[..., None]
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    zq = 0.0 * q.astype(jnp.float32).transpose(0, 2, 1, 3)  # (b,h,sq,d)
    m0 = zq[..., 0] + NEG_INF
    l0 = zq[..., 0]
    a0 = jnp.broadcast_to(zq[..., :1], zq.shape[:-1] + (v.shape[-1],))
    (m, l, acc, _, _), _ = lax.scan(step, (m0, l0, a0, k, v), jnp.arange(n))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
