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
  * flash_attention  — two Pallas TPU kernels under one custom_vjp:
    `ff_flash_fwd` (scores, row softmax and p @ v in VMEM, the row's
    log-sum-exp saved) and `ff_flash_bwd` (_flash_bwd_kernel: dq, dk and
    dv in one program, the probabilities recomputed from the saved
    log-sum-exp). Both walk the (seq_q, seq_k) score matrix in blocks; a
    causal call never computes a block that lies wholly above the
    diagonal, and masks only the blocks the diagonal crosses.
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


def _band_scan(q, k, v, *, window: int, block: int, q_offset, kv_offset):
    """_chunk_scan under a window (query at position p sees keys at
    p - window < j <= p, and none below position 0): the walk is over
    blocks of `block` queries, and each meets only the window + block keys
    its band reaches, one softmax a block, so what lies wholly outside the
    band is never computed. Offsets may be traced."""
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    n_blocks = -(-sq // block)
    pad = n_blocks * block - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    span = min(sk, window + block)
    scale = 1.0 / math.sqrt(d)
    qb = q.reshape(b, n_blocks, block, h, d).transpose(1, 0, 2, 3, 4)

    def body(inputs):
        i, q_blk = inputs
        q_pos = q_offset + i * block + jnp.arange(block)
        # the first key the block's first query sees, held inside the keys
        # there are: the mask below goes by true positions
        start = jnp.clip(q_offset - kv_offset + i * block - window + 1,
                         0, sk - span)
        k_blk = lax.dynamic_slice_in_dim(k, start, span, axis=1)
        v_blk = lax.dynamic_slice_in_dim(v, start, span, axis=1)
        kv_pos = kv_offset + start + jnp.arange(span)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        mask = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :] >= 0) \
            & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v_blk,
                         preferred_element_type=jnp.float32)
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype), m, l

    out, m, l = lax.map(body, (jnp.arange(n_blocks), qb))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_blocks * block, h, dv)
    m, l = (a.transpose(1, 2, 0, 3).reshape(b, h, -1)[..., :sq]
            for a in (m, l))
    return out[:, :sq], m, l


def _online_update(carry, s, mask, pv):
    """One key tile into a running (m, l, acc) softmax: `s` the tile's
    float32 scores (..., q, k), `mask` which of them count, `pv(p)` the
    tile's product of probabilities and values. A tile whose every score
    is masked leaves a row that has seen a key as it was (p = 0, and the
    rescale is exp(0))."""
    m_prev, l_prev, acc_prev = carry
    s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_prev * jnp.exp(m_prev - m_new) + jnp.sum(p, axis=-1)
    acc_new = acc_prev * jnp.exp(m_prev - m_new)[..., None] + pv(p)
    return m_new, l_new, acc_new


def causal_tiles(sq: int, sk: int, *, block: int, chunk: int, q_offset,
                 valid):
    """How many key chunks each query block of a causal block walk meets
    (int32 (ceil(sq / block),), traced where q_offset or `valid` is):
    chunks 0 .. the one holding the block's last real query, where the
    block's real queries are those before q_offset + valid; none for a
    block wholly past them."""
    starts = jnp.arange(-(-sq // block), dtype=jnp.int32) * block
    ends = q_offset + jnp.minimum(starts + block, valid)
    reach = jnp.minimum(-(-ends // chunk), -(-sk // chunk))
    return jnp.where(starts < valid, reach, 0)


def _causal_scan(q, k, v, *, block: int, chunk: int, q_offset, valid=None):
    """_chunk_scan's causal walk for a prefill block against a cache,
    forward only: q (b, sq, h, d) at positions q_offset.., k and v the
    cache as stored, (b, sk, kv_heads * d) and (b, sk, kv_heads * dv) at
    positions 0... Query blocks of `block` rows go under lax.map;
    block i walks key chunks 0 .. n_i (causal_tiles, traced) and no
    further, so no chunk above its last real query, nor at or past the
    prompt's end (q_offset + valid), is computed. Each chunk's (m, l, acc)
    update is _chunk_scan's, in its order, so a real row comes out as
    there. A block wholly in the padded tail computes nothing: its rows
    are zeros. Grouped-query heads are one product per key-value head,
    the cache never repeated. Returns (out (b, sq, h, dv), tiles computed,
    tiles skipped); a traced trip count has no reverse-mode derivative."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    n = k.shape[-1] // d                      # key-value heads
    g, dv = h // n, v.shape[-1] // n
    valid = sq if valid is None else valid
    n_blocks, n_chunks = -(-sq // block), -(-sk // chunk)
    if n_blocks * block != sq:
        q = jnp.pad(q, ((0, 0), (0, n_blocks * block - sq), (0, 0), (0, 0)))
    if n_chunks * chunk != sk:  # the padding mask below keeps it out
        k, v = (jnp.pad(a, ((0, 0), (0, n_chunks * chunk - sk), (0, 0)))
                for a in (k, v))
    scale = 1.0 / math.sqrt(d)
    reach = causal_tiles(sq, sk, block=block, chunk=chunk, q_offset=q_offset,
                         valid=valid)
    qb = q.reshape(b, n_blocks, block, n, g, d).transpose(1, 0, 2, 3, 4, 5)

    def one_block(inputs):
        i, q_blk, n_i = inputs
        q_pos = q_offset + i * block + jnp.arange(block)

        def chunk_step(ci, carry):
            k_blk, v_blk = (
                lax.dynamic_slice_in_dim(a, ci * chunk, chunk, axis=1)
                .reshape(b, chunk, n, -1) for a in (k, v))
            s = jnp.einsum("bqngd,bknd->bngqk", q_blk, k_blk.astype(q.dtype),
                           preferred_element_type=jnp.float32) * scale
            kv_pos = ci * chunk + jnp.arange(chunk)
            mask = (kv_pos[None, :] <= sk - 1) \
                & (kv_pos[None, :] <= q_pos[:, None])
            return _online_update(carry, s, mask, lambda p: jnp.einsum(
                "bngqk,bknd->bngqd", p, v_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32))

        m0 = jnp.full((b, n, g, block), NEG_INF, jnp.float32)
        m, l, acc = lax.fori_loop(0, n_i, chunk_step, (
            m0, jnp.zeros_like(m0), jnp.zeros(m0.shape + (dv,), jnp.float32)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, block, h, dv)

    out = lax.map(one_block, (jnp.arange(n_blocks), qb, reach))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_blocks * block, h, dv)
    computed = jnp.sum(reach)
    return out[:, :sq].astype(q.dtype), computed, \
        n_blocks * n_chunks - computed


def _chunk_scan(q, k, v, *, causal: bool, chunk_size: int, q_offset=0,
                kv_offset=0, window: int = 0):
    """Online-softmax accumulation over KV chunks. q: (b, sq, h, d). With
    a `window` (causal only) the walk is _band_scan's."""
    if window:
        assert causal, "a window is a band under the causal mask"
        return _band_scan(q, k, v, window=window, block=chunk_size,
                          q_offset=q_offset, kv_offset=kv_offset)
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
        ci, k_blk, v_blk = inputs
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        kv_pos = kv_offset + ci * chunk_size + jnp.arange(chunk_size)
        mask = kv_pos[None, :] <= (sk + kv_offset - 1)  # padding mask
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        return _online_update(carry, s, mask, lambda p: jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )), None

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


def chunked_attention(q, k, v, *, causal: bool = False, chunk_size: int = 256,
                      window: int = 0):
    """Memory-efficient exact attention. (b, s, h, d) -> (b, s, h, d)."""
    out, _, _ = _chunk_scan(q, k, v, causal=causal,
                            chunk_size=min(chunk_size, k.shape[1]),
                            window=window)
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


def _keep_tile(seed_ref, row_u, sq: int, sk: int, q_off: int, kv_off: int,
               tile_q: int, tile_k: int, rate: float):
    """In-kernel keep-mask for one (tile_q, tile_k) score tile of row
    `row_u` (uint32 scalar) whose first element is query `q_off`, key
    `kv_off` — the blockwise view of attention_dropout_mask."""
    s0 = seed_ref[0]
    s1 = seed_ref[1]
    qp = jnp.uint32(q_off) + lax.broadcasted_iota(
        jnp.uint32, (tile_q, tile_k), 0
    )
    kp = jnp.uint32(kv_off) + lax.broadcasted_iota(
        jnp.uint32, (tile_q, tile_k), 1
    )
    idx = (row_u * jnp.uint32(sq) + qp) * jnp.uint32(sk) + kp
    return _keep_bits(idx, s0, s1) >= jnp.uint32(_drop_threshold(rate))


# ---------------------------------------------------------------------------
# The block walk both flash kernels share
# ---------------------------------------------------------------------------
# The (seq_q, seq_k) score matrix of one (batch*head) row is cut into
# (block_q, block_k) tiles. Under the causal mask, which is top-left
# aligned (kv_pos <= q_pos) whatever the two lengths are, a tile is
#   "skipped"  every pair masked: its probabilities are exp(-1e30 - m) == 0
#              exactly, so it is never computed: no dot, no exp, no mask;
#   "masked"   the diagonal crosses it: computed, and masked;
#   "full"     every pair unmasked: computed, and not masked.
# Under a window (kv_pos > q_pos - window besides) the unmasked pairs are
# a band, and a tile wholly below it is skipped like one above the diagonal.
# Shapes are static, so the walk is a Python loop unrolled at trace time.

def _axis_blocks(n: int, block: int):
    """[(start, stop)] of an axis of n positions cut every `block`; the
    last may be short."""
    return [(s, min(s + block, n)) for s in range(0, n, block)]


def _tile_state(q0: int, q1: int, k0: int, k1: int, causal: bool,
                window: int = 0) -> str:
    """How the mask meets the tile of queries [q0, q1) and keys [k0, k1)."""
    if not causal:
        return "full"
    if k0 > q1 - 1 or (window and k1 - 1 <= q0 - window):
        return "skipped"  # above the diagonal, or wholly below the band
    if k1 - 1 <= q0 and (not window or k0 > q1 - 1 - window):
        return "full"
    return "masked"


def flash_tile_counts(seq_q: int, seq_k: int, block_q: int, block_k: int,
                      causal: bool, window: int = 0):
    """(computed, skipped) tiles of one row: what ff_flash_tiles_total
    counts for each kernel built."""
    states = [_tile_state(*qb, *kb, causal, window)
              for qb in _axis_blocks(seq_q, block_q)
              for kb in _axis_blocks(seq_k, block_k)]
    skipped = states.count("skipped")
    return len(states) - skipped, skipped


def _walk(outer, inner, state_of):
    """[(outer block, reach)]: what each block of the outer axis computes
    along the inner one. Along either axis the states run full.. masked..
    skipped (or the reverse; under a window skipped.. masked.. full..
    masked.. skipped), so the computed blocks are one stretch:
    reach = (lo, hi, mask_lo, mask_hi), one dot over inner positions
    [lo, hi) of which [mask_lo, mask_hi) needs the mask (under a window it
    spans both edges of the band, and the full blocks between them are
    masked to no effect), or None where the outer block computes nothing."""
    plan = []
    for ob in outer:
        states = [(ib, state_of(ob, ib)) for ib in inner]
        seen = [ib for ib, st in states if st != "skipped"]
        masked = [ib for ib, st in states if st == "masked"]
        if not seen:
            plan.append((ob, None))
            continue
        lo, hi = seen[0][0], seen[-1][1]
        mask = (masked[0][0], masked[-1][1]) if masked else (hi, hi)
        plan.append((ob, (lo, hi) + mask))
    return plan


def _default_blocks(seq_q: int, seq_k: int, causal: bool):
    """(block_q, block_k) from the shape. A non-causal call has nothing
    to skip and keeps one block a row."""
    if not causal:
        return seq_q, seq_k
    block = 128
    return (block if seq_q % block == 0 else seq_q,
            block if seq_k % block == 0 else seq_k)


def _resolve_blocks(which: str, seq_q, seq_k, causal, block_q, block_k,
                    window: int = 0):
    """(block_q, block_k): the caller's, else from the shape; counted in
    ff_flash_tiles_total{pass=which}, a Python side effect where a kernel
    goes into a program: once a trace and never an execution (as
    executor._count_trace)."""
    from .. import obs

    from_shape = _default_blocks(seq_q, seq_k, causal)
    block_q, block_k = block_q or from_shape[0], block_k or from_shape[1]
    counts = flash_tile_counts(seq_q, seq_k, block_q, block_k, causal,
                               window)
    for state, n in zip(("computed", "skipped"), counts):
        obs.count("ff_flash_tiles_total", n,
                  help="(query block, key block) tiles of one row program "
                       "of a flash kernel, as it was built",
                  **{"pass": which, "state": state})
    return block_q, block_k


# ---------------------------------------------------------------------------
# Pallas flash-attention forward
# ---------------------------------------------------------------------------

def _causal_mask(s, *, q_offset: int, kv_offset: int, window: int = 0):
    """Apply the causal mask (under a window, the band) to the score tile
    whose first element is (q_offset, kv_offset); forward and backward
    share it so they can never disagree."""
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kv_pos = kv_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = kv_pos <= q_pos
    if window:
        seen = seen & (kv_pos > q_pos - window)
    return jnp.where(seen, s, NEG_INF)


def _mask_stretch(s, axis: int, reach, other_offset: int, window: int = 0):
    """Mask the part [mask_lo, mask_hi) of the score tile `s`, which
    spans [lo, hi) along `axis` (0: queries, 1: keys) and starts at
    `other_offset` along the other: only the blocks the diagonal crosses
    pay the iota / compare / select."""
    lo, hi, mask_lo, mask_hi = reach
    parts = []
    for a, b, masked in ((lo, mask_lo, False), (mask_lo, mask_hi, True),
                         (mask_hi, hi, False)):
        if a == b:
            continue
        part = lax.slice_in_dim(s, a - lo, b - lo, axis=axis)
        if masked:
            q_offset, kv_offset = ((a, other_offset) if axis == 0
                                   else (other_offset, a))
            part = _causal_mask(part, q_offset=q_offset,
                                kv_offset=kv_offset, window=window)
        parts.append(part)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


def _staged(items, first, second):
    """second(item, first(item)) for every item, the next item's first
    stage issued before this item's second: program order is what the
    scheduler starts from, and so one block's dots run on the MXU under
    the previous block's softmax (measured on the compiled schedule:
    15-20% fewer bundles than block after block)."""
    held = None
    for it in items:
        got = first(it)
        if held is not None:
            second(*held)
        held = (it, got)
    if held is not None:
        second(*held)


def _for_each_row(g: int, body):
    """body(i) for the g (batch*head) rows of one program, as a loop and
    not unrolled: several rows a program amortize the per-program
    overhead, and one row's code keeps the Mosaic compile at one row's
    (unrolled, 4 rows took 6 times as long to compile, once a layer)."""
    if g == 1:
        body(0)
    else:
        lax.fori_loop(0, g, lambda i, carry: (body(i), carry)[1], 0)


def _flash_fwd_kernel(*refs, scale: float, g: int, plan,
                      dropout: float = 0.0, window: int = 0):
    """One program = g (batch*head) rows (_for_each_row: they amortize
    the per-program overhead). Q/K/V of the whole row are VMEM resident (the
    fused path is capped to shapes where that holds). `plan` gives each
    query block the keys it attends, 0 .. the diagonal: ONE MXU dot whose
    width grows with the block, the mask on the blocks the diagonal
    crosses, then a row softmax — no online accumulation, and nothing at
    all for the keys above the diagonal. Dots take the inputs' dtype
    (bf16 on the mixed-precision path = native MXU rate) and accumulate
    f32; scores/probs never touch HBM.

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
    sq, sk = q_ref.shape[1], k_ref.shape[1]
    first_row = pl.program_id(0) * g      # read outside the row loop

    def scores(i, step):
        (q0, q1), reach = step
        k0, k1 = reach[:2]
        s = jax.lax.dot_general(
            q_ref[i, q0:q1], k_ref[i, k0:k1], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                         # (block_q, k1 - k0) f32
        return _mask_stretch(s, 1, reach, q0, window)

    def finish(i, step, s):
        (q0, q1), reach = step
        k0, k1 = reach[:2]
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        if dropout > 0.0:
            row_u = (first_row + i).astype(jnp.uint32)
            keep = _keep_tile(seed_ref, row_u, sq, sk, q0, k0, q1 - q0,
                              k1 - k0, dropout)
            p = jnp.where(keep, p * inv_keep, 0.0)
        o = jnp.dot(p.astype(q_ref.dtype), v_ref[i, k0:k1],
                    preferred_element_type=jnp.float32)
        o_ref[i, q0:q1] = (o / l).astype(o_ref.dtype)
        # log-sum-exp per query row, the backward's softmax residual;
        # stored (1, seq_q) — lanes-major, so the block shape (g, 1,
        # seq_q) satisfies the Mosaic (sublane, lane) tiling rule
        lse_ref[i, :, q0:q1] = (m + jnp.log(l)).T

    # every query row sees its own key, so no query block's reach is None
    _for_each_row(g, lambda i: _staged(plan, functools.partial(scores, i),
                                       functools.partial(finish, i)))


def _flash_bwd_kernel(*refs, scale: float, g: int, plan,
                      dropout: float = 0.0, window: int = 0):
    """Fused dq/dk/dv for g (batch*head) rows in ONE program: the prob
    tile is recomputed from q/k and the saved lse exactly once, delta =
    rowsum(do*o) is computed in VMEM, and the transposed contractions for
    dk/dv avoid materializing pᵀ.

    `plan` gives each key block the query rows that see it, the diagonal
    .. seq_q: one set of five dots over that stretch, the mask on the
    blocks the diagonal crosses. dk and dv of a key block are complete
    after it and written once; dq gathers a term from every key block, in
    the float32 scratch `dq_acc`. A key block no query sees (seq_k >
    seq_q) gets zero gradients and no dot.

    dropout > 0 regenerates the forward's counter-based keep-mask per
    tile — same seeds, same absolute (row, q, k), so bit-identical — and
    applies it where the chain rule puts it: dP = D ∘ (dO Vᵀ) before the
    softmax backward, and dV = (P ∘ D)ᵀ dO. delta = rowsum(dO ∘ O)
    already equals rowsum(P ∘ dP) under dropout, so the ds formula is
    unchanged."""
    if dropout > 0.0:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, seed_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
        seed_ref = None
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0
    sq, sk = q_ref.shape[1], k_ref.shape[1]
    first_row = pl.program_id(0) * g      # read outside the row loop
    def scores(i, step):
        (k0, k1), reach = step
        if reach is None:
            return None
        q0, q1 = reach[:2]
        s = jax.lax.dot_general(
            q_ref[i, q0:q1], k_ref[i, k0:k1], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                         # (q1 - q0, block_k)
        s = _mask_stretch(s, 0, reach, k0, window)
        dp = jax.lax.dot_general(
            do_ref[i, q0:q1], v_ref[i, k0:k1], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return s, dp

    def finish(i, delta, lse_col, step, got):
        (k0, k1), reach = step
        if reach is None:
            dk_ref[i, k0:k1] = jnp.zeros_like(dk_ref[i, k0:k1])
            dv_ref[i, k0:k1] = jnp.zeros_like(dv_ref[i, k0:k1])
            return
        q0, q1 = reach[:2]
        s, dp = got
        q = q_ref[i, q0:q1]
        p = jnp.exp(s - lse_col[q0:q1])
        if dropout > 0.0:
            row_u = (first_row + i).astype(jnp.uint32)
            keep = _keep_tile(seed_ref, row_u, sq, sk, q0, k0, q1 - q0,
                              k1 - k0, dropout)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
            pb = jnp.where(keep, p * inv_keep, 0.0).astype(q.dtype)
        else:
            pb = p.astype(q.dtype)
        dsb = (p * (dp - delta[q0:q1])).astype(q.dtype)
        dq = jnp.dot(dsb, k_ref[i, k0:k1],
                     preferred_element_type=jnp.float32)
        if k0 == 0 and not window:
            # every query sees key 0: the first key block's stretch is
            # the whole axis, and sets what the later ones add to
            dq_acc[...] = dq
        else:
            dq_acc[q0:q1] += dq
        dk = jax.lax.dot_general(
            dsb, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_ref[i, k0:k1] = (dk * scale).astype(dk_ref.dtype)
        dv = jax.lax.dot_general(
            pb, do_ref[i, q0:q1], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dv_ref[i, k0:k1] = dv.astype(dv_ref.dtype)

    def row(i):
        delta = jnp.sum(
            do_ref[i].astype(jnp.float32) * o_ref[i].astype(jnp.float32),
            axis=-1, keepdims=True,
        )                                 # (seq_q, 1)
        lse_col = lse_ref[i].T            # stored (1, seq_q), lanes-major
        if window:  # no key block is seen by every query: start from zero
            dq_acc[...] = jnp.zeros_like(dq_acc)
        _staged(plan, functools.partial(scores, i),
                functools.partial(finish, i, delta, lse_col))
        dq_ref[i] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    _for_each_row(g, row)


def _bhsd_to_fold(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _fold_to_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# The fused path keeps Q/K/V of a whole row in VMEM per program, and a
# non-causal call its full (seq_q, seq_k) f32 score tile; past this limit
# fall back to chunked_attention (long-context single-chip) or ring
# attention (sequence-parallel).
FLASH_FUSED_MAX_TILE = 1024 * 1024


def flash_supported(seq_q: int, seq_k: int) -> bool:
    return seq_q * seq_k <= FLASH_FUSED_MAX_TILE


def _pick_g(bh: int, tile: int, budget: int) -> int:
    """Rows per program: batch (b*h) rows until the f32 score tiles live
    at once (`tile` floats: the widest block of the plan) hit the VMEM
    budget (floats), 4 at the most. At 1,024 positions 1, 2, 4 and 8
    rows a program measured the same on a v5e; shorter rows have more
    per-program overhead to share."""
    g = 1
    for cand in (2, 4):
        if bh % cand or cand * tile > budget:
            break
        g = cand
    return g


# The two calls are jitted so that the layers of a model, which call them
# on one shape, share ONE traced kernel: walking the blocks in Python for
# each of 24 layers, forward and backward, in each build of the train step
# cost 10 s of set-up that no compile cache gives back. Inlined, so the
# program XLA gets is the one it would get without the jit.
_STATIC = ("causal", "interpret", "dropout", "block_q", "block_k", "window")


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _flash_fwd_call(qf, kf, vf, seeds, *, causal: bool, interpret: bool,
                    dropout: float, block_q: int, block_k: int,
                    window: int = 0):
    bh, sq, d = qf.shape
    sk = kf.shape[1]
    dv = vf.shape[-1]                 # v_head_dim may differ from qk's d
    plan = _walk(_axis_blocks(sq, block_q), _axis_blocks(sk, block_k),
                 lambda qb, kb: _tile_state(*qb, *kb, causal, window))
    # live at once: a query block's scores against every key it attends
    tile = max((q1 - q0) * (reach[1] - reach[0])
               for (q0, q1), reach in plan)
    g = _pick_g(bh, tile, budget=2 * 1024 * 1024)
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, g=g,
                               plan=plan, dropout=dropout, window=window)
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


def _flash_fwd_folded(qf, kf, vf, *, causal: bool, interpret: bool,
                      dropout: float = 0.0, seeds=None, block_q=None,
                      block_k=None, window: int = 0):
    """Core forward on (b*h, s, d) folded operands."""
    block_q, block_k = _resolve_blocks("fwd", qf.shape[1], kf.shape[1],
                                       causal, block_q, block_k, window)
    return _flash_fwd_call(qf, kf, vf, seeds, causal=causal,
                           interpret=interpret, dropout=dropout,
                           block_q=block_q, block_k=block_k, window=window)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _flash_bwd_call(qf, kf, vf, of, lse, dof, seeds, *, causal: bool,
                    interpret: bool, dropout: float, block_q: int,
                    block_k: int, window: int = 0):
    bh, sq, d = qf.shape
    sk = kf.shape[1]
    dv_d = vf.shape[-1]               # v_head_dim may differ from qk's d
    plan = _walk(_axis_blocks(sk, block_k), _axis_blocks(sq, block_q),
                 lambda kb, qb: _tile_state(*qb, *kb, causal, window))
    # live at once: the s, p, dp and ds tiles of one key block
    tile = 4 * max((k1 - k0) * (reach[1] - reach[0])
                   for (k0, k1), reach in plan if reach)
    gg = _pick_g(bh, tile, budget=4 * 1024 * 1024)
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
        functools.partial(_flash_bwd_kernel, scale=scale, g=gg, plan=plan,
                          dropout=dropout, window=window),
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
        scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32)],
        interpret=interpret,
        name="ff_flash_bwd",
    )(*args)
    return dq, dk, dv


def _flash_bwd_folded(qf, kf, vf, of, lse, dof, *, causal: bool,
                      interpret: bool, dropout: float = 0.0, seeds=None,
                      block_q=None, block_k=None, window: int = 0):
    """Core backward on (b*h, s, d) folded operands."""
    block_q, block_k = _resolve_blocks("bwd", qf.shape[1], kf.shape[1],
                                       causal, block_q, block_k, window)
    return _flash_bwd_call(qf, kf, vf, of, lse, dof, seeds, causal=causal,
                           interpret=interpret, dropout=dropout,
                           block_q=block_q, block_k=block_k, window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_folded_core(qf, kf, vf, seeds, causal, interpret, dropout,
                       block_q, block_k, window):
    out, _ = _flash_fwd_folded(qf, kf, vf, causal=causal,
                               interpret=interpret, dropout=dropout,
                               seeds=seeds, block_q=block_q,
                               block_k=block_k, window=window)
    return out


def _flash_folded_vjp_fwd(qf, kf, vf, seeds, causal, interpret, dropout,
                          block_q, block_k, window):
    out, lse = _flash_fwd_folded(qf, kf, vf, causal=causal,
                                 interpret=interpret, dropout=dropout,
                                 seeds=seeds, block_q=block_q,
                                 block_k=block_k, window=window)
    return out, (qf, kf, vf, out, lse, seeds)


def _flash_folded_vjp_bwd(causal, interpret, dropout, block_q, block_k,
                          window, res, g):
    qf, kf, vf, out, lse, seeds = res
    dq, dk, dv = _flash_bwd_folded(qf, kf, vf, out, lse, g, causal=causal,
                                   interpret=interpret, dropout=dropout,
                                   seeds=seeds, block_q=block_q,
                                   block_k=block_k, window=window)
    return dq, dk, dv, None  # seeds are integral: no cotangent


_flash_folded_core.defvjp(_flash_folded_vjp_fwd, _flash_folded_vjp_bwd)


def flash_attention_folded(qf, kf, vf, causal: bool = False,
                           interpret: bool = False, *,
                           dropout: float = 0.0, seeds=None,
                           block_q: int | None = None,
                           block_k: int | None = None, window: int = 0):
    """flash_attention on PRE-FOLDED (batch*heads, seq, head_dim)
    operands. The MHA op's fast path projects q/k/v straight into this
    layout (einsum "bse,ehd->bhsd" + free reshape), so the per-layer
    fold/unfold transposes of the bshd wrapper never materialize.

    block_q / block_k cut the score matrix into the tiles both kernels
    walk (None: chosen from the shape, _default_blocks); a causal call
    computes no tile above the diagonal, nor, under a `window` (query i
    sees keys i - window < j <= i), one wholly below the band.

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
    if window and not causal:
        raise ValueError("a window is a band under the causal mask")
    return _flash_folded_core(qf, kf, vf, seeds, causal, interpret, dropout,
                              block_q, block_k, window)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False, *,
                    dropout: float = 0.0, seeds=None, window: int = 0):
    """Fused Pallas attention: forward AND backward keep scores/probs in
    VMEM (the backward recomputes the prob tile from the saved per-row
    log-sum-exp — the standard flash-attention scheme) and batch several
    (batch*head) rows per program (_pick_g). Requires
    flash_supported(seq_q, seq_k). Routes through the folded core, so
    block sizes, gradients and RNG-threaded dropout (dropout/seeds)
    behave identically to flash_attention_folded."""
    b, _, h, _ = q.shape
    out = flash_attention_folded(
        _bhsd_to_fold(q), _bhsd_to_fold(k), _bhsd_to_fold(v),
        causal=causal, interpret=interpret, dropout=dropout, seeds=seeds,
        block_q=block_q, block_k=block_k, window=window,
    )
    return _fold_to_bhsd(out, b, h)


def local_attention(q, k, v, *, causal: bool = False, window: int = 0):
    """The single device-local streaming dispatch: fused Pallas kernel on
    TPU while its VMEM tile fits, chunked scan otherwise. Both the MHA
    op's streaming branch (ops/attention.py) and ulysses_attention route
    through here so the selection policy cannot drift between them."""
    if pallas_compiled() and flash_supported(q.shape[1], k.shape[1]):
        return flash_attention(q, k, v, causal, window=window)
    return chunked_attention(q, k, v, causal=causal, window=window)


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
