"""Every Pallas kernel must LOWER for the TPU platform, checked without a
chip: `jax.export` with platforms=["tpu"] runs the Pallas->Mosaic lowering
(block-shape tiling rules, shard_map's vma typing of kernel outputs) that
tier-1's CPU runs never reach, because every kernel path is gated on the
TPU backend. Shapes are the full-width model's (chip_smoke.py): hidden
1024 = 16 heads x 64, seq 512, batch 8; 8 decode slots over max_len 1024.

Lowering is necessary, not sufficient. The `slow` test below goes one step
further where the installed libtpu can describe a v5e without one being
attached: it runs the real TPU compiler, Mosaic and its scoped-VMEM limit
included, ahead of time (seconds per kernel, no chip). Whether the compiled
kernels then RUN and are right, only `python chip_smoke.py` on the chip says.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from flexflow_tpu.kernels.attention import (
    flash_attention,
    flash_attention_folded,
)
from flexflow_tpu.kernels.decode import paged_flash_decode


def _mosaic_calls(fn, *args) -> int:
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    return exported.mlir_module().count("tpu_custom_call")


def _compile_for_v5e(fn, *args):
    """Compile `fn` ahead of time for one device of a described (not
    attached) v5e 2x2 host; skips where libtpu cannot describe one."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compile-only support here
        pytest.skip(f"no TPU topology description available: {e!r}")
    on_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip)
            for a in args]
    return jax.jit(fn).lower(*args).compile()


def _fwd_bwd(attn):
    """d(sum attn)/d(q, k, v): one forward and one backward kernel."""
    return jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )


@pytest.mark.parametrize("bh,seq,dtype,dropout", [
    (128, 512, jnp.bfloat16, 0.0),   # the train step's shape
    (128, 512, jnp.bfloat16, 0.1),   # in-kernel dropout: SMEM seeds, u32 hash
    (16, 1024, jnp.float32, 0.0),    # the largest tile flash_supported admits
    (16, 1024, jnp.bfloat16, 0.0),
])
def test_flash_forward_backward_lowers_for_tpu(bh, seq, dtype, dropout):
    x = jax.ShapeDtypeStruct((bh, seq, 64), dtype)
    seeds = jnp.array([1, 2], jnp.uint32) if dropout else None
    attn = functools.partial(flash_attention_folded, causal=True,
                             dropout=dropout, seeds=seeds)
    assert _mosaic_calls(_fwd_bwd(attn), x, x, x) == 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode_lowers_for_tpu(dtype):
    slots, heads, d, max_len, page = 8, 16, 64, 1024, 16
    pages = max_len // page
    q = jax.ShapeDtypeStruct((slots, heads, d), dtype)
    pool = jax.ShapeDtypeStruct((heads, slots * pages, page, d), dtype)
    table = jax.ShapeDtypeStruct((slots, pages), jnp.int32)
    lengths = jax.ShapeDtypeStruct((slots,), jnp.int32)
    assert _mosaic_calls(paged_flash_decode, q, pool, pool, table,
                         lengths) == 1


@pytest.mark.slow
def test_kernels_compile_for_v5e_without_a_chip():
    x = jax.ShapeDtypeStruct((16, 1024, 64), jnp.float32)  # largest tile
    attn = functools.partial(flash_attention_folded, causal=True)
    _compile_for_v5e(_fwd_bwd(attn), x, x, x)
    x = jax.ShapeDtypeStruct((128, 512, 64), jnp.bfloat16)
    attn = functools.partial(flash_attention_folded, causal=True,
                             dropout=0.1, seeds=jnp.array([1, 2], jnp.uint32))
    _compile_for_v5e(_fwd_bwd(attn), x, x, x)
    slots, heads, d, pages, page = 8, 16, 64, 64, 16
    for dtype in (jnp.float32, jnp.bfloat16):
        _compile_for_v5e(
            paged_flash_decode,
            jax.ShapeDtypeStruct((slots, heads, d), dtype),
            jax.ShapeDtypeStruct((heads, slots * pages, page, d), dtype),
            jax.ShapeDtypeStruct((heads, slots * pages, page, d), dtype),
            jax.ShapeDtypeStruct((slots, pages), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32))


def test_flash_lowers_for_tpu_under_shard_map():
    """The multi-chip train step runs the kernel on per-chip shards under
    shard_map (ops/attention.py): with check_vma on, every kernel output
    must carry the manual axes it varies over."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    spec = P("data", None, "model", None)
    attn = jax.shard_map(
        functools.partial(flash_attention, causal=False),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    x = jax.ShapeDtypeStruct((8, 512, 16, 64), jnp.bfloat16)
    assert _mosaic_calls(_fwd_bwd(attn), x, x, x) == 2
