"""Paged flash-decode attention kernel (the serving hot path).

One query token per slot attends over a PAGED KV pool: K/V live in
fixed-size physical pages, each slot's logical sequence is a list of
page indices (the vLLM PagedAttention layout), and the kernel walks a
slot's pages with an online softmax — no (seq x seq) score tensor, no
dense gather of the pool, and dead pages past the slot's length are
skipped, so a freshly admitted request costs one page of work while a
long-running neighbor streams its whole cache.

Grid: ``(slots, heads, pages_per_slot)`` with the page axis innermost.
The page table and per-slot lengths ride as SCALAR-PREFETCH operands
(pltpu.PrefetchScalarGridSpec): the K/V BlockSpec index_map reads
``page_table[slot, page]`` to DMA exactly the physical page the slot
needs next — the gather happens in the block pipeline, not as a
materialized jnp.take. Running (max, sum, acc) live in VMEM scratch
across the page axis; the output row is written once, on the last page.

Layouts:
  q          (slots, heads, head_dim)           — one token per slot
  k/v pages  (heads, num_pages, page_size, d)   — head-major pool
  page_table (slots, pages_per_slot) int32      — physical page ids;
             entries past a slot's live pages MUST still be in range
             (0 is fine) — the kernel masks them, the DMA does not.
  lengths    (slots,) int32                     — tokens live per slot
             (positions t attend to pos <= t, i.e. length = t + 1)

``paged_view_of_cache`` adapts the batcher's dense per-slot caches
(slots, max_len, heads, d) into this layout as a pure reshape/transpose
(every slot's pages are contiguous in its own cache strip), so the
serving path gets the kernel without a separate pool allocator; a real
PagePool-backed pool (runtime/kvcache.py page tables) drops in with the
same signature.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, out_struct


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, page_size: int,
                         scale: float):
    """One program = one (slot, head, page) cell. Scratch (m, l, acc)
    persists across the innermost page axis; pl.when gates init on the
    first page, the online-softmax update on live pages only, and the
    normalized write-out on the last page."""
    s_id = pl.program_id(0)
    page = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s_id]
    start = page * page_size

    @pl.when(start < length)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)       # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (page_size, d)
        v = v_ref[0, 0].astype(jnp.float32)       # (page_size, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                 # (1, page_size)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]                       # (1, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32,
        )

    @pl.when(page == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, page_table, lengths, *,
                       interpret: bool = False):
    """Single-token attention over the paged KV pool.

    q (slots, heads, d); k_pages/v_pages (heads, num_pages, page_size,
    d/dv); page_table (slots, pages_per_slot) int32; lengths (slots,)
    int32. Returns (slots, heads, dv). interpret=True runs the same
    kernel on CPU."""
    b, h, d = q.shape
    page_size = k_pages.shape[2]
    dv = v_pages.shape[-1]
    n_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, n_pages),
        # Mosaic tiles the LAST TWO block dims (multiples of (8, 128), or
        # the array's full extent). q and the output carry one row per
        # (slot, head), so they ride as (slots, heads, 1, d): the block's
        # last two dims (1, d) are then the array's own, like the K/V
        # page blocks' (page_size, d).
        in_specs=[
            pl.BlockSpec((1, 1, 1, d),
                         lambda s, hh, i, pt, ln: (s, hh, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda s, hh, i, pt, ln: (hh, pt[s, i], 0, 0)),
            pl.BlockSpec((1, 1, page_size, dv),
                         lambda s, hh, i, pt, ln: (hh, pt[s, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dv),
                               lambda s, hh, i, pt, ln: (s, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=page_size,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=out_struct((b, h, 1, dv), q.dtype, q),
        interpret=interpret,
        name="ff_paged_decode",
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q[:, :, None, :], k_pages, v_pages)
    return out[:, :, 0, :]


def paged_decode_reference(q, k_pages, v_pages, page_table, lengths):
    """Dense parity oracle: gather every slot's pages, mask positions
    past its length, one softmax. O(slots * pages * page_size) memory —
    test-sized only."""
    b, h, d = q.shape
    page_size = k_pages.shape[2]
    n_pages = page_table.shape[1]
    # (slots, heads, n_pages*page_size, d)
    k = jnp.take(k_pages, page_table, axis=1).transpose(1, 0, 2, 3, 4)
    v = jnp.take(v_pages, page_table, axis=1).transpose(1, 0, 2, 3, 4)
    k = k.reshape(b, h, n_pages * page_size, d)
    v = v.reshape(b, h, n_pages * page_size, v_pages.shape[-1])
    s = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    pos = jnp.arange(n_pages * page_size)[None, None, :]
    s = jnp.where(pos < lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bhtd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_view_of_cache(k_cache, v_cache, page_size: int):
    """View the batcher's dense per-slot caches (slots, max_len, heads,
    d) as a paged pool: slot b's logical page i is physical page
    ``b * pages_per_slot + i`` — a reshape/transpose, no copy semantics
    beyond XLA's layout change. Requires page_size | max_len."""
    b, max_len, h, d = k_cache.shape
    if page_size <= 0 or max_len % page_size:
        raise ValueError(
            f"page_size {page_size} must divide the cache length {max_len}")
    pp = max_len // page_size

    def to_pool(c):
        # (b, max_len, h, d) -> (h, b*pp, page_size, d)
        return c.reshape(b, pp, page_size, h, c.shape[-1]) \
                .transpose(3, 0, 1, 2, 4) \
                .reshape(c.shape[2], b * pp, page_size, c.shape[-1])

    table = (jnp.arange(b)[:, None] * pp + jnp.arange(pp)[None, :]) \
        .astype(jnp.int32)
    return to_pool(k_cache), to_pool(v_cache), table


def decode_page_size(max_len: int, preferred: int = 16) -> int:
    """Largest page size <= preferred dividing max_len (>= 1 always)."""
    p = max(1, min(int(preferred), int(max_len)))
    while max_len % p:
        p -= 1
    return p
