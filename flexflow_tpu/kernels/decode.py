"""Paged flash-decode attention kernel (the serving hot path).

One query token per slot attends over a PAGED KV pool: K/V live in
fixed-size physical pages, each slot's logical sequence is a list of
page indices (the vLLM PagedAttention layout), and the kernel walks a
slot's LIVE pages with an online softmax: no (seq x seq) score tensor,
no dense gather of the pool, and a page past the slot's length is never
fetched, so a freshly admitted request costs one page of work while a
long-running neighbor streams its whole cache.

Layouts (position-major, the way the cache lies in memory):
  q          (slots, heads, head_dim)            one token per slot
  k/v pages  (num_pages, page_size, heads, d)    in the kernel
             (num_pages, page_size, heads*d): one page is ONE contiguous,
             lane-dense run of page_size x heads*d values (64 KB for 32
             heads of 64 in bf16 at page 16)
  page_table (slots, pages_per_slot) int32       physical page ids; only
             a slot's live entries are ever read
  lengths    (slots,) int32                      tokens live per slot
             (position t attends to pos <= t, i.e. length = t + 1); a
             slot of length 0 reads nothing and returns zeros

Grid: ``(slots,)``. One grid step is one slot with ALL its heads. Inside
it a loop walks the slot's live positions in blocks of ``block_pages``
pages (``decode_block_pages``: from heads*d, the page size, the dtype
and the VMEM budget stated there). The pool stays in HBM
(``memory_space=ANY``); each live page of a block is one DMA into one of
two VMEM buffers, and the next block (the next slot's first block at a
slot's end) is in flight while this one is computed. The page table and
the lengths ride as SCALAR-PREFETCH operands and only steer those DMAs,
so physical pages may be scattered anywhere in the pool. A dead page
costs nothing: the loop ends at the slot's last live block, and inside
that block a page past the length is neither fetched nor waited for (the
buffers are zeroed once, so what a skipped page leaves behind is finite
and its probability is exactly 0).

Arithmetic: all heads at once on the MXU. The query row (1, heads*d) is
spread to a block-diagonal (heads, heads*d) matrix whose row h holds head
h's d values on head h's lanes; ``scores = Q_bd K_blk^T`` is then every
head's scores over the block in one product with K straight from the
cache in its own type, and ``P V_blk`` accumulates (heads, heads*dv) of
which head h's lanes of row h are the answer (the other blocks are the
price of never re-laying the cache out by head: 1/heads of the MXU work
is kept, and the kernel is bound by the cache's bytes all the same).
With grouped-query heads (fewer key-value heads than query heads) a page
row is kv_heads*d wide and row h of the spread query lies on the lanes of
ITS GROUP's key-value head, h // (heads / kv_heads): the rows of a group
share lanes, so the caller spreads the query and picks each row's lanes
out of the (heads, kv_heads*dv) result, and the kernel does neither.
Scores, the running max / sum and the accumulator are f32; ``p`` is cast
to the cache's type before ``p v``, as the dense branch does.

``paged_view_of_cache`` views the batcher's dense per-slot caches
(slots, max_len, heads*d) as such a pool: a pure reshape, because every
slot's pages are contiguous in its own strip; a real PagePool-backed
pool (runtime/kvcache.py page tables) drops in with the same signature.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, out_struct

# VMEM the four K/V block buffers (K and V, double-buffered) may take
# together; the accumulator, the spread query and the products' temporaries
# (a few heads x heads*d f32 arrays) fit beside them under Mosaic's 16 MB
# scoped limit.
KV_VMEM_BUDGET = 8 << 20


def decode_page_size(max_len: int, preferred: int = 16) -> int:
    """Largest page size <= preferred dividing max_len (>= 1 always)."""
    p = max(1, min(int(preferred), int(max_len)))
    while max_len % p:
        p -= 1
    return p


def _pages_to_128_positions(page_size: int) -> int:
    return 128 // math.gcd(128, page_size)


def _sublane_tile(dtype) -> int:
    """Rows of one register tile of 128 lanes: 8 of 32 bits, 16 of bf16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def decode_block_pages(hd_k: int, hd_v: int, page_size: int,
                       dtype) -> Optional[int]:
    """Pages one block of the kernel's inner loop holds, from the shapes;
    None where Mosaic cannot tile the kernel (the caller takes the dense
    branch). The rules: a page row (heads*d values) fills whole 128-lane
    registers; a page is whole sublane tiles of the dtype, so it lands in
    its buffer at a tile boundary; a block is the fewest pages that make
    a multiple of 128 positions (the scores' lane axis and the second
    product's contraction); two K and two V blocks fit KV_VMEM_BUDGET.
    max_len does not enter: on the v5e blocks of 128, 256 and 512
    positions stream a full cache equally fast (730 GB/s), and the
    shortest wastes least on a slot's last, partly dead block and on a
    slot that holds one token (PERF.md, PR 26)."""
    if hd_k % 128 or hd_v % 128 or page_size % _sublane_tile(dtype):
        return None
    itemsize = jnp.dtype(dtype).itemsize
    pages = _pages_to_128_positions(page_size)
    if 2 * pages * page_size * (hd_k + hd_v) * itemsize > KV_VMEM_BUDGET:
        return None
    return pages


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, sems, buf_ref, m_ref, l_ref, acc_ref,
                         *, page_size: int, block_pages: int,
                         pages_per_slot: int, d: int, dv: int, scale: float,
                         grouped: bool):
    """One program = one slot, all heads. (m, l, acc) live in VMEM scratch
    across the slot's blocks; ``buf_ref`` (SMEM) says which of the two
    buffers holds the slot's first block, which the previous program
    started on its way out. ``grouped``: the query block arrives spread,
    (heads, kv_heads*d), and the whole (heads, kv_heads*dv) result goes
    out (paged_flash_decode spreads and picks)."""
    slot = pl.program_id(0)
    n_slots = pl.num_programs(0)
    block = block_pages * page_size
    length = len_ref[slot]
    n_blocks = jnp.maximum(pl.cdiv(length, block), 1)

    def each_live_page(s, blk, buf, act):
        """`act` on the K and the V copy of every live page of block `blk`
        of slot `s`. A loop, not `block_pages` unrolled branches: the
        step's program holds this kernel once a layer, and its tracing is
        paid in every process, cached executable or not."""
        first = blk * block_pages
        live = jnp.clip(pl.cdiv(len_ref[s], page_size) - first,
                        0, block_pages)

        def one(j, carry):
            page = pt_ref[s * pages_per_slot + first + j]
            rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            act(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, rows],
                                      sems.at[buf, 0]))
            act(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, rows],
                                      sems.at[buf, 1]))
            return carry

        jax.lax.fori_loop(0, live, one, 0)

    def start(s, blk, buf):
        each_live_page(s, blk, buf, lambda c: c.start())

    @pl.when(slot == 0)
    def _first():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        buf_ref[0] = 0
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    rows_q = acc_ref.shape[0]                     # heads, to a sublane tile

    def own_lanes(width, per_head):
        """(rows_q, width) mask: row h owns lanes [h*per_head, (h+1)*per_head)."""
        row = jax.lax.broadcasted_iota(jnp.int32, (rows_q, width), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows_q, width), 1)
        return (lane >= row * per_head) & (lane < (row + 1) * per_head)

    # the select runs on 32-bit registers (the mask's layout), the
    # product on the cache's own type
    if grouped:
        q_bd = q_ref[0].astype(kbuf.dtype)        # (rows_q, kv_heads*d)
    else:
        q = q_ref[0].astype(jnp.float32)          # (1, heads*d)
        q_bd = jnp.where(own_lanes(q.shape[-1], d), q, 0.0) \
            .astype(kbuf.dtype)

    def body(blk, buf):
        nxt = 1 - buf

        # what comes next: this slot's next block, or the next slot's first
        last = blk + 1 == n_blocks
        next_slot = jnp.where(last, slot + 1, slot)

        @pl.when(next_slot < n_slots)
        def _prefetch():
            start(next_slot, jnp.where(last, 0, blk + 1), nxt)

        each_live_page(slot, blk, buf, lambda c: c.wait())
        k = kbuf[buf]                             # (block, heads*d)
        v = vbuf[buf]                             # (block, heads*dv)
        s = jax.lax.dot_general(
            q_bd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                 # (rows_q, block)
        pos = blk * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = pos < length
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[...]                       # (rows_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32,
        )
        return nxt

    buf_ref[0] = jax.lax.fori_loop(0, n_blocks, body, buf_ref[0])

    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    if grouped:
        o_ref[0] = out.astype(o_ref.dtype)
    else:
        out = jnp.where(own_lanes(out.shape[-1], dv), out, 0.0)
        o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, page_table, lengths, *,
                       interpret: bool = False):
    """Single-token attention over the paged KV pool.

    q (slots, heads, d); k_pages/v_pages (num_pages, page_size, kv_heads,
    d/dv), or already (num_pages, page_size, kv_heads*d/dv), kv_heads =
    heads or a divisor of it (grouped-query heads: query head i reads
    key-value head i // (heads / kv_heads)); page_table (slots,
    pages_per_slot) int32; lengths (slots,) int32. Returns (slots, heads,
    dv). interpret=True runs the same kernel on CPU, at
    any shape; compiled, a shape `decode_block_pages` cannot tile is a
    ValueError (ops/attention.py asks first and takes the dense branch)."""
    b, h, d = q.shape
    n_phys, page_size = k_pages.shape[:2]
    k_pages = k_pages.reshape(n_phys, page_size, -1)
    v_pages = v_pages.reshape(n_phys, page_size, -1)
    hd_k, hd_v = k_pages.shape[-1], v_pages.shape[-1]
    kv_heads = hd_k // d
    group = h // kv_heads
    dv = hd_v // kv_heads
    pages_per_slot = page_table.shape[1]
    block_pages = decode_block_pages(hd_k, hd_v, page_size, k_pages.dtype)
    if block_pages is None:
        if not interpret:
            raise ValueError(
                f"paged_flash_decode cannot tile heads*d={hd_k}/{hd_v}, "
                f"page_size={page_size}, {k_pages.dtype}: see "
                "decode_block_pages")
        block_pages = _pages_to_128_positions(page_size)
    block = block_pages * page_size
    # heads to a whole sublane tile of the operand the MXU takes them in
    tile = _sublane_tile(k_pages.dtype)
    rows_q = -(-h // tile) * tile
    grouped = group > 1
    if grouped:
        # row h on its group's lanes, zeros elsewhere; rows past the last
        # head are zeros (their softmax is uniform, their result unread)
        own = (jnp.arange(hd_k)[None, :] // d
               == jnp.arange(h)[:, None] // group)             # (h, kv*d)
        q_in = jnp.where(own, jnp.tile(q, (1, 1, kv_heads)), 0)
        q_in = jnp.pad(q_in, ((0, 0), (0, rows_q - h), (0, 0)))
        q_rows = out_rows = rows_q
    else:
        q_in, q_rows, out_rows = q.reshape(b, 1, hd_k), 1, 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        # Mosaic tiles the LAST TWO block dims (multiples of (8, 128), or
        # the array's full extent). q and the output carry one row per
        # slot, so they ride as (slots, 1, heads*d): the block's last two
        # dims are then the array's own.
        in_specs=[
            pl.BlockSpec((1, q_rows, hd_k), lambda s, pt, ln: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, out_rows, hd_v),
                               lambda s, pt, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block, hd_k), k_pages.dtype),
            pltpu.VMEM((2, block, hd_v), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, hd_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, page_size=page_size,
            block_pages=block_pages, pages_per_slot=pages_per_slot,
            d=d, dv=dv, scale=1.0 / math.sqrt(d), grouped=grouped),
        grid_spec=grid_spec,
        out_shape=out_struct((b, out_rows, hd_v), q.dtype, q),
        # the slots run in order: each starts the next one's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ff_paged_decode",
    )(jnp.asarray(page_table, jnp.int32).reshape(-1),
      jnp.asarray(lengths, jnp.int32),
      q_in.astype(k_pages.dtype), k_pages, v_pages)
    if not grouped:
        return out.reshape(b, h, dv)
    # row h's answer lies on its group's lanes
    out = out[:, :h].reshape(b, kv_heads, group, kv_heads, dv)
    return jnp.einsum("bkgkd->bkgd", out).reshape(b, h, dv)


def paged_decode_reference(q, k_pages, v_pages, page_table, lengths):
    """Dense parity oracle: gather every slot's pages, mask positions
    past its length, one softmax. O(slots * pages * page_size) memory:
    test-sized only. Pools are (num_pages, page_size, kv_heads, d)."""
    b, h, d = q.shape
    n_pages = page_table.shape[1]
    page_size = k_pages.shape[1]
    kv_heads = k_pages.shape[2]
    # (slots, n_pages, page_size, kv_heads, d) -> (slots, positions, heads,
    # d), a key-value head repeated for its group's query heads
    k = jnp.repeat(jnp.take(k_pages, page_table, axis=0)
                   .reshape(b, n_pages * page_size, kv_heads, d),
                   h // kv_heads, axis=2)
    v = jnp.repeat(jnp.take(v_pages, page_table, axis=0)
                   .reshape(b, n_pages * page_size, kv_heads, -1),
                   h // kv_heads, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    pos = jnp.arange(n_pages * page_size)[None, None, :]
    s = jnp.where(pos < lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_view_of_cache(k_cache, v_cache, page_size: int, step=None):
    """View the batcher's dense per-slot caches (slots, max_len, heads*d)
    as a paged pool (slots*pages_per_slot, page_size, heads*d): slot b's
    logical page i is physical page ``b * pages_per_slot + i``. A pure
    reshape of the strips as they lie; nothing moves. Requires
    page_size | max_len.

    The caches of an op inside a loop region hold every step's strip,
    (slots, steps, max_len, heads*d): the pool is then all of them, and the
    table of `step` (a traced int) points each slot at its step's pages,
    ``(b * steps + step) * pages_per_slot + i``, so one step is read in
    place and no slice of the pool is made."""
    b, max_len = k_cache.shape[0], k_cache.shape[-2]
    if page_size <= 0 or max_len % page_size:
        raise ValueError(
            f"page_size {page_size} must divide the cache length {max_len}")
    pp = max_len // page_size
    strips = b if step is None else b * k_cache.shape[1]
    first = jnp.arange(b) if step is None \
        else jnp.arange(b) * k_cache.shape[1] + step
    table = (first[:, None] * pp + jnp.arange(pp)[None, :]).astype(jnp.int32)
    return (k_cache.reshape(strips * pp, page_size, -1),
            v_cache.reshape(strips * pp, page_size, -1), table)
