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


def _mosaic_operands(fn, *args):
    """The operand and result types of each Mosaic call in the module
    exported for the TPU, one string a call."""
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module()
    return [line.split(" : ", 1)[-1] for line in text.splitlines()
            if "@tpu_custom_call" in line]


def _mosaic_calls(fn, *args) -> int:
    return len(_mosaic_operands(fn, *args))


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


@pytest.mark.parametrize("bh,seq,dtype,dropout,causal", [
    (128, 512, jnp.bfloat16, 0.0, True),   # chip_smoke.py's train step
    (128, 512, jnp.bfloat16, 0.1, True),   # in-kernel dropout: SMEM seeds, u32 hash
    (16, 1024, jnp.float32, 0.0, True),    # the largest tile flash_supported admits
    (16, 1024, jnp.bfloat16, 0.0, True),
    (64, 1024, jnp.bfloat16, 0.0, True),   # the cell train-gpt2m-1chip
    (64, 1024, jnp.bfloat16, 0.0, False),  # one block a row: nothing to skip
])
def test_flash_forward_backward_lowers_for_tpu(bh, seq, dtype, dropout,
                                               causal):
    x = jax.ShapeDtypeStruct((bh, seq, 64), dtype)
    seeds = jnp.array([1, 2], jnp.uint32) if dropout else None
    attn = functools.partial(flash_attention_folded, causal=causal,
                             dropout=dropout, seeds=seeds)
    calls = _mosaic_operands(_fwd_bwd(attn), x, x, x)
    assert len(calls) == 2
    # perfbench's flash_attn_roofline knows the kernels by the folded
    # operand (harness/trace.py Summary.kernel): both calls work on it
    folded = "tensor<%dx%dx64x%s>" % (bh, seq, jnp.dtype(dtype).name
                                      .replace("bfloat", "bf")
                                      .replace("float", "f"))
    assert all(folded in c for c in calls), calls


@pytest.mark.parametrize("causal", [True, False])
def test_flash_compiled_calls_keep_the_folded_operand(causal):
    """The same two kernels through the real TPU compiler, for a
    described v5e, at the training cell's shape: two Mosaic calls, each
    with `bf16[64,1024,64]` among its operands in the compiled HLO."""
    x = jax.ShapeDtypeStruct((64, 1024, 64), jnp.bfloat16)
    attn = functools.partial(flash_attention_folded, causal=causal)
    text = _compile_for_v5e(_fwd_bwd(attn), x, x, x).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2, calls
    assert all("bf16[64,1024,64]" in c for c in calls), calls


def _paged_decode_args(slots, heads, d, max_len, page, dtype):
    """paged_flash_decode's operands for a pool of slots * max_len
    positions in the cache's own layout, (pages, page, heads * d)."""
    pages = max_len // page
    pool = jax.ShapeDtypeStruct((slots * pages, page, heads * d), dtype)
    return (jax.ShapeDtypeStruct((slots, heads, d), dtype), pool, pool,
            jax.ShapeDtypeStruct((slots, pages), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32))


# chip_smoke.py's server (8 slots, 16 heads) and the serving cell
# `serve-opt1.3b-saturated` (16 slots, 32 heads), both 64 wide, max_len
# 1,024 in pages of 16
PAGED_DECODE_SHAPES = [(8, 16, 64, 1024, 16), (16, 32, 64, 1024, 16)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", PAGED_DECODE_SHAPES,
                         ids=["chip-smoke", "serving-cell"])
def test_paged_flash_decode_lowers_for_tpu(shape, dtype):
    assert _mosaic_calls(paged_flash_decode,
                         *_paged_decode_args(*shape, dtype)) == 1


def _causal_lm(hidden, heads, layers, slots, max_len):
    from flexflow_tpu import (ActiMode, AggrMode, DataType, FFConfig,
                              FFModel, LossType, MetricsType, SGDOptimizer)

    cfg = FFConfig()
    cfg.batch_size = slots
    cfg.search_budget = 1
    cfg.workersPerNode = 1    # one chip: a Mosaic call is not partitioned
    m = FFModel(cfg)
    ids = m.create_tensor((slots, max_len), DataType.DT_INT32)
    t = m.embedding(ids, 29, hidden, AggrMode.AGGR_MODE_NONE)
    for _ in range(layers):
        t = m.multihead_attention(t, t, t, hidden, heads, causal=True)
        t = m.dense(t, hidden, ActiMode.AC_MODE_RELU)
    m.softmax(m.dense(t, 29))
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


def _decode_step_module(monkeypatch, hidden, heads, layers=2, slots=4,
                        max_len=32):
    """The batched decode step of a small causal LM, exported for the TPU
    from here."""
    return _export_decode_step(
        monkeypatch, _causal_lm(hidden, heads, layers, slots, max_len),
        slots, max_len)


def _export_decode_step(monkeypatch, m, slots, max_len, module=True):
    """(module text, cache shapes) of `m`'s batched decode step, exported
    for the TPU from the CPU: `pallas_compiled` says yes, as it would on
    the chip, so `auto` takes the kernel wherever the shape allows. With
    module=False, (the Exported, the step's argument shapes)."""
    from flexflow_tpu.kernels import attention as kattn

    monkeypatch.delenv("FF_DECODE_IMPL", raising=False)
    monkeypatch.setattr(kattn, "pallas_compiled", lambda: True)
    init, step = m.executor.build_decode(slots, max_len)
    params = m.state.params
    shapes = (jax.eval_shape(lambda p: p, params),
              jax.eval_shape(init, params, ()),
              jax.ShapeDtypeStruct((slots,), jnp.int32),
              [jax.ShapeDtypeStruct((slots, 1), jnp.int32)])
    exported = jax.export.export(step, platforms=["tpu"])(*shapes)
    if module:
        return exported.mlir_module(), shapes[1]
    return exported, shapes


def test_decode_step_holds_one_kernel_a_layer_and_no_cache_transpose(
        monkeypatch):
    """The kernel reads the cache where it lies: in the decode step's
    module there is one Mosaic call a layer and no `transpose` whose
    operand has as many elements as a cache (the pool is a reshape of the
    strips; the per-head re-layout of PR 25's kernel is gone)."""
    import re

    layers, slots, max_len, hidden = 2, 4, 32, 128
    text, caches = _decode_step_module(monkeypatch, hidden, heads=2,
                                       layers=layers, slots=slots,
                                       max_len=max_len)
    assert text.count("tpu_custom_call") == layers
    leaves = jax.tree_util.tree_leaves(caches["mha"])
    assert {leaf.shape for leaf in leaves} == {(slots, max_len, hidden)}
    cache_elems = slots * max_len * hidden
    for line in text.splitlines():
        if "stablehlo.transpose" not in line:
            continue
        dims = re.search(r"tensor<([0-9x]+)x[a-z]", line).group(1)
        assert np.prod([int(n) for n in dims.split("x")]) < cache_elems, line


@pytest.mark.parametrize("donates", [True, False],
                         ids=["as_on_the_chip", "as_on_the_cpu"])
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_decode_step_donates_every_cache_leaf_it_rewrites(
        kind, donates, monkeypatch):
    """The step built as on the chip hands XLA every key, value and
    recurrent-state leaf to write in place: in the module exported for the
    TPU each such argument is aliased to an output (or left to XLA as a
    donor), and nothing else is given away, the weights least of all. The
    build the CPU gets, the suite's own, donates nothing."""
    import re
    from collections import Counter

    from flexflow_tpu.parallel.executor import PCGExecutor

    if kind == "attention":
        build = functools.partial(_decode_step_module, monkeypatch,
                                  hidden=128, heads=2)
    else:
        import sys

        from tests.test_linear_attention import hybrid

        monkeypatch.setattr(sys, "argv", sys.argv[:1])
        build = functools.partial(_export_decode_step, monkeypatch,
                                  hybrid(batch=4, seq=32), 4, 32)
    assert not PCGExecutor.donates_buffers(None)  # JAX_PLATFORMS=cpu
    if donates:
        monkeypatch.setattr(PCGExecutor, "donates_buffers", lambda self: True)
    text, caches = build()
    main = next(line for line in text.splitlines()
                if "func.func public @main" in line)
    args = main[:main.index(") -> (")]
    given = Counter(
        shape for shape, attrs in re.findall(
            r"%arg\d+: tensor<([^>]+)>(?: (\{[^}]*\}))?", args)
        if "tf.aliasing_output" in attrs or "jax.buffer_donor" in attrs)
    mlir = {"float32": "f32", "bfloat16": "bf16"}
    rewritten = Counter(
        "x".join(map(str, leaf.shape)) + "x" + mlir[leaf.dtype.name]
        for sec in ("mha", "prefix", "recurrent")
        for leaf in jax.tree_util.tree_leaves(caches[sec]))
    assert len(caches["recurrent"]) == (kind == "hybrid")
    assert sum(rewritten.values()) >= 4
    assert given == (rewritten if donates else Counter())


@pytest.mark.slow
@pytest.mark.parametrize("donates", [True, False],
                         ids=["as_on_the_chip", "as_on_the_cpu"])
def test_donated_decode_step_compiles_for_v5e_without_a_cache_copy(
        donates, monkeypatch):
    """One step further than the lowering, as for the kernels: the TPU's
    compiler, given the donated step, aliases every cache leaf to its
    output and leaves no `copy` of a cache's shape; given the undonated
    one it aliases nothing (there every leaf is copied before the append)."""
    import re

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from flexflow_tpu.parallel.executor import PCGExecutor

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compile-only support here
        pytest.skip(f"no TPU topology description available: {e!r}")
    monkeypatch.setattr(PCGExecutor, "donates_buffers",
                        lambda self: donates)
    slots, max_len, hidden = 8, 256, 256
    exported, shapes = _export_decode_step(
        monkeypatch, _causal_lm(hidden, 2, 2, slots, max_len), slots,
        max_len, module=False)
    on_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(
        exported.call, donate_argnums=(1,) if donates else ()).lower(
        *jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=on_chip), shapes)
    ).compile()
    leaves = jax.tree_util.tree_leaves(shapes[1]["mha"])
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert aliased == (cache_bytes if donates else 0)
    dims = ",".join(map(str, leaves[0].shape))
    copies = re.findall(r"= \w+\[" + dims + r"\]\S* copy\(",
                        compiled.as_text())
    assert not (donates and copies), copies


def test_untileable_decode_shape_falls_back_dense_and_counts_once(
        monkeypatch, tmp_path):
    """heads * head_dim = 16 fills no 128-lane register: under `auto` on
    the TPU the one layer's step takes the dense branch, counts the new
    reason once and warns once."""
    import warnings

    import flexflow_tpu.obs as obs
    from flexflow_tpu.obs import TelemetryConfig
    from flexflow_tpu.ops import attention as mha

    mha.reset_attention_fallback_warnings()
    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            text, _ = _decode_step_module(monkeypatch, hidden=16, heads=2,
                                          layers=1)
        count = obs.active().metrics.find("ff_attention_fallback_total",
                                          reason="paged_untileable")
        assert count is not None and count.value == 1.0
    assert text.count("tpu_custom_call") == 0
    told = [w for w in caught if "cannot tile" in str(w.message)]
    assert len(told) == 1, [str(w.message) for w in caught]


@pytest.mark.slow
def test_kernels_compile_for_v5e_without_a_chip():
    x = jax.ShapeDtypeStruct((16, 1024, 64), jnp.float32)  # largest tile
    attn = functools.partial(flash_attention_folded, causal=True)
    _compile_for_v5e(_fwd_bwd(attn), x, x, x)
    x = jax.ShapeDtypeStruct((128, 512, 64), jnp.bfloat16)
    attn = functools.partial(flash_attention_folded, causal=True,
                             dropout=0.1, seeds=jnp.array([1, 2], jnp.uint32))
    _compile_for_v5e(_fwd_bwd(attn), x, x, x)
    # Mosaic's scoped-VMEM limit, met here before the chip is
    for shape in PAGED_DECODE_SHAPES:
        for dtype in (jnp.float32, jnp.bfloat16):
            _compile_for_v5e(paged_flash_decode,
                             *_paged_decode_args(*shape, dtype))


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
