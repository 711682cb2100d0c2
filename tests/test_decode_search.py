"""Decode-objective strategy search, the paged flash-decode kernel, and
disaggregated prefill/decode serving (ISSUE: Splitwise/DistServe through
the repo's own PCG search).

The contract: single-token decode is HBM-bandwidth-bound where training
is MXU-bound, so (1) the decode cost oracle must price a token's BYTES,
not the padded sequence's FLOPs; (2) compile_decode() must be able to
pick a DIFFERENT strategy than training and the decode objective must
rank it faster; (3) the paged kernel is bit-for-bit checked against the
dense masked reference across ragged per-slot positions (including a
freshly admitted 1-token slot mid-stream); (4) the ContinuousBatcher
stays EXACT vs incremental_generate with the decode-searched strategy
active; (5) the second strategy round-trips through strategy_io; (6) a
first-publication decode series is warn-only in the bench gate."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    AggrMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.ff_types import OperatorType
from flexflow_tpu.pcg.lowering import layers_to_pcg
from flexflow_tpu.pcg.machine_view import MachineView
from flexflow_tpu.search import CostModel, MachineModel, simulate_runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ, HIDDEN, HEADS = 29, 16, 16, 2


def build_lm(batch=2, seq=SEQ, layers=1, workers=None):
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.search_budget = 1
    if workers:
        cfg.workersPerNode = workers
    m = FFModel(cfg)
    ids = m.create_tensor((batch, seq), DataType.DT_INT32)
    t = m.embedding(ids, VOCAB, HIDDEN, AggrMode.AGGR_MODE_NONE)
    for _ in range(layers):
        t = m.multihead_attention(t, t, t, HIDDEN, HEADS, causal=True)
        t = m.dense(t, HIDDEN, ActiMode.AC_MODE_RELU)
    t = m.softmax(m.dense(t, VOCAB))
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


def transformer_graph(seq=64, batch=8, hidden=128, heads=8):
    model = FFModel(FFConfig())
    x = model.create_tensor((batch, seq, hidden), DataType.DT_FLOAT)
    t = model.multihead_attention(x, x, x, hidden, heads)
    t = model.dense(t, hidden, ActiMode.AC_MODE_RELU)
    t = model.dense(t, hidden)
    graph, _ = layers_to_pcg(model.layers)
    return graph


# ---------------------------------------------------------------------------
# decode cost objective (search/cost_model.py)
# ---------------------------------------------------------------------------

def test_decode_objective_prices_one_token_not_the_sequence():
    """Decode cost of an op must not grow with sequence length (one
    token streams the same weights regardless), while the training
    objective prices the whole padded sequence. And a decode step has no
    backward and no weight-grad sync."""
    machine = MachineModel(num_nodes=1, workers_per_node=4)
    cm_dec = CostModel(machine, objective="decode")
    cm_train = CostModel(machine)
    v = MachineView(start_device_id=0, dim=(1,), stride=(1,))

    def dense_op(g):
        return [o for o in g.ops if o.op_type == OperatorType.OP_LINEAR][0]

    g64, g256 = transformer_graph(seq=64), transformer_graph(seq=256)
    d64 = cm_dec.measure_operator_cost(dense_op(g64), v)
    d256 = cm_dec.measure_operator_cost(dense_op(g256), v)
    assert d64.forward_time == pytest.approx(d256.forward_time, rel=1e-9)
    assert d64.backward_time == 0.0 and d64.sync_time == 0.0
    t64 = cm_train.measure_operator_cost(dense_op(g64), v)
    t256 = cm_train.measure_operator_cost(dense_op(g256), v)
    assert t256.forward_time > t64.forward_time * 2
    # per-token decode is far cheaper than a full training forward
    assert d64.forward_time < t64.forward_time


def test_decode_objective_ranks_memory_bound_ops_by_bytes():
    """A weight-heavy, FLOPs-light op (embedding lookup) must dominate a
    FLOPs-heavy op under the decode objective: the token streams the
    whole table shard but multiplies almost nothing."""
    machine = MachineModel(num_nodes=1, workers_per_node=4)
    cm = CostModel(machine, objective="decode")
    from flexflow_tpu.search.cost_model import op_decode_bytes

    m = FFModel(FFConfig())
    ids = m.create_tensor((2, 16), DataType.DT_INT32)
    t = m.embedding(ids, 50000, 64, AggrMode.AGGR_MODE_NONE)
    t = m.dense(t, 64, ActiMode.AC_MODE_RELU)
    g, _ = layers_to_pcg(m.layers)
    emb = [o for o in g.ops if o.op_type == OperatorType.OP_EMBEDDING][0]
    den = [o for o in g.ops if o.op_type == OperatorType.OP_LINEAR][0]
    assert op_decode_bytes(emb) > op_decode_bytes(den)
    v = MachineView(start_device_id=0, dim=(1,), stride=(1,))
    assert cm.measure_operator_cost(emb, v).forward_time > \
        cm.measure_operator_cost(den, v).forward_time


def test_cost_objective_validated():
    machine = MachineModel(num_nodes=1, workers_per_node=4)
    with pytest.raises(ValueError):
        CostModel(machine, objective="tokens")


# ---------------------------------------------------------------------------
# compile_decode: the second searched strategy
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 CPU devices")
def test_compile_decode_selects_a_different_faster_strategy():
    """The acceptance gate: on an 8-device mesh the decode-objective
    search picks a strategy that DIFFERS from the training one, and the
    decode cost model ranks it strictly faster than the training
    strategy (both priced by the same simulator under the decode
    objective)."""
    m = build_lm(workers=8)
    m.compile_decode()
    assert m.decode_executor is not None
    train_degs = sorted(
        tuple(v.dim) for v in (m.searched_views or {}).values())
    dec_degs = sorted(
        tuple(v.dim) for v in (m.decode_searched_views or {}).values())
    assert train_degs != dec_degs, (
        f"decode search should pick a different strategy: {dec_degs}")
    cm = m._build_cost_model(objective="decode")
    t_train = simulate_runtime(m.graph, m.searched_views, cm)
    t_dec = simulate_runtime(m.decode_graph, m.decode_searched_views, cm)
    assert t_dec < t_train, (
        f"decode objective must rank its own strategy faster: "
        f"{t_dec} vs {t_train}")
    # the search recorded its own trajectory, separate from training's
    assert m.decode_trajectory is not None
    phases = {e.get("name") for e in m.decode_trajectory.of_kind("phase")}
    assert "decode_strategy_search" in phases


def test_compile_decode_strategy_roundtrips_through_strategy_io(tmp_path):
    path = str(tmp_path / "decode_strategy.json")
    m = build_lm()
    m.compile_decode(export_path=path)
    exported = {tuple(v.dim) for v in m.decode_searched_views.values()}

    m2 = build_lm()
    m2.compile_decode(strategy_path=path)
    imported = {tuple(v.dim) for v in m2.decode_searched_views.values()}
    assert imported == exported
    assert m2.decode_executor is not None


# ---------------------------------------------------------------------------
# paged flash-decode kernel (kernels/decode.py) — interpret-mode parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,d,page,pp,lengths,dtype", [
    # a long-running slot, a freshly admitted 1-token slot (mid-stream
    # admission), and a mid-stream one; all inside one block of 128
    (3, 2, 8, 4, 4, [10, 1, 7], np.float32),
    # ragged 1 .. max_len over a scattered table: lengths that are no
    # multiple of the page (37, 129), a slot that ends exactly with its
    # first block, a full one (two blocks, the second 8 live pages of 32)
    # and an empty one (reads nothing, gives zeros)
    (6, 3, 8, 4, 40, [1, 160, 129, 37, 0, 128], np.float32),
    # the serving cell's shape class at fewer slots: 32 heads of 64, page
    # 16, bf16, what decode_block_pages tiles for the chip
    (3, 32, 64, 16, 32, [512, 37, 300], jnp.bfloat16),
], ids=["three-slots", "ragged-scattered", "cell-shape-bf16"])
def test_paged_flash_decode_matches_dense_reference(b, h, d, page, pp,
                                                    lengths, dtype):
    from flexflow_tpu.kernels.decode import (
        decode_block_pages,
        paged_decode_reference,
        paged_flash_decode,
    )

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, d), dtype)
    # position-major pool, the way the cache lies: (pages, page, heads, d)
    pool_k = jnp.asarray(rng.randn(b * pp, page, h, d), dtype)
    pool_v = jnp.asarray(rng.randn(b * pp, page, h, d), dtype)
    # scattered, non-contiguous page assignment per slot
    table = rng.permutation(b * pp).reshape(b, pp).astype(np.int32)
    lengths = np.array(lengths, np.int32)
    if dtype == jnp.bfloat16:
        assert decode_block_pages(h * d, h * d, page, dtype) == 8
    out = paged_flash_decode(q, pool_k, pool_v, table, lengths,
                             interpret=True)
    ref = paged_decode_reference(q, pool_k, pool_v, table, lengths)
    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    live = lengths > 0
    assert np.all(out[~live] == 0)
    np.testing.assert_allclose(
        out[live], ref[live], atol=1e-5 if dtype == np.float32 else 2e-2)


def test_decode_block_pages_follows_the_shapes():
    """Block sizes come from heads*d, the page, the dtype's tile and the
    stated VMEM budget; a shape Mosaic cannot tile gives None."""
    from flexflow_tpu.kernels.decode import (
        KV_VMEM_BUDGET,
        decode_block_pages,
    )

    bf16, f32 = jnp.bfloat16, jnp.float32
    assert decode_block_pages(2048, 2048, 16, bf16) == 8     # the cell
    assert decode_block_pages(1024, 1024, 16, f32) == 8      # chip_smoke
    assert decode_block_pages(2048, 2048, 8, f32) == 16      # page 8
    assert decode_block_pages(1024, 1024, 48, f32) == 8      # 384 = 3 x 128
    # a wide model: two K and two V blocks must fit the budget
    assert 4 * 128 * 8192 * 2 <= KV_VMEM_BUDGET
    assert decode_block_pages(8192, 8192, 16, bf16) == 8
    assert decode_block_pages(16384, 16384, 16, bf16) is None
    # lanes not filled; a bf16 page of 8 rows is half a sublane tile
    assert decode_block_pages(16, 16, 16, f32) is None
    assert decode_block_pages(2048, 2048, 8, bf16) is None


@pytest.mark.parametrize("preferred", [4, 6], ids=["page-4", "page-6"])
def test_paged_view_of_cache_matches_dense_attention(preferred):
    """The serving adapter: dense per-slot caches viewed as a paged pool
    must reproduce plain masked attention over the dense caches, and the
    view is a reshape of the strips as they lie: slot b's page i is rows
    [i*page, (i+1)*page) of strip b."""
    from flexflow_tpu.kernels.decode import (
        decode_page_size,
        paged_flash_decode,
        paged_view_of_cache,
    )

    b, max_len, h, d = 2, 12, 2, 8
    rng = np.random.RandomState(1)
    kc = rng.randn(b, max_len, h, d).astype(np.float32)
    vc = rng.randn(b, max_len, h, d).astype(np.float32)
    q = rng.randn(b, h, d).astype(np.float32)
    lengths = np.array([5, 9], np.int32)
    ps = decode_page_size(max_len, preferred=preferred)
    assert ps == preferred
    folded = (b, max_len, h * d)       # the cache as init_decode_cache lays it
    kp, vp, table = paged_view_of_cache(
        jnp.asarray(kc.reshape(folded)), jnp.asarray(vc.reshape(folded)), ps)
    assert kp.shape == (b * max_len // ps, ps, h * d)
    np.testing.assert_array_equal(
        np.asarray(kp)[np.asarray(table)].reshape(kc.shape), kc)
    out = np.asarray(paged_flash_decode(q, kp, vp, table, lengths,
                                        interpret=True))
    # dense oracle straight off the original caches
    s = np.einsum("bhd,bthd->bht", q, kc) / np.sqrt(d)
    mask = np.arange(max_len)[None, None, :] < lengths[:, None, None]
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bht,bthd->bhd", p, vc)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    with pytest.raises(ValueError):
        paged_view_of_cache(jnp.asarray(kc.reshape(folded)),
                            jnp.asarray(vc.reshape(folded)), 5)


def test_decode_attn_bench_rehearses_and_both_branches_agree(tmp_path):
    """scripts/decode_attn_bench.py (PERF.md's kernel-against-dense table)
    runs end to end off the chip at a toy shape, and the whole attention
    op gives the same output under FF_DECODE_IMPL=paged (the kernel,
    interpreted) and =dense (the block-diagonal product over the folded
    rows) at every profile of slot lengths."""
    out = tmp_path / "attn.json"
    script = os.path.join(REPO, "scripts", "decode_attn_bench.py")
    r = subprocess.run(
        [sys.executable, script, "--cpu-rehearsal", "--slots", "4",
         "--heads", "4", "--head-dim", "32", "--max-len", "64",
         "--layers", "2", "--reps", "1", "--out", str(out)],
        capture_output=True, text=True, env=os.environ.copy(), timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(out.read_text())
    assert not got["on_chip"] and set(got["profiles"]) == {
        "3-live", "third", "half", "full"}
    for row in got["profiles"].values():
        assert row["kernel_rel_err"] < 2e-2          # bf16
        assert row["op_paged_out"] == pytest.approx(row["op_dense_out"],
                                                    rel=1e-2)
    # without a chip and without the flag it refuses to print a rate
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=os.environ.copy(), timeout=300)
    assert r.returncode == 2 and "no rate" in r.stderr


def test_decode_impl_env_gates_paged_path(monkeypatch):
    """FF_DECODE_IMPL=paged runs generation through the paged kernel
    (interpret mode on CPU) and must stay EXACT vs the dense masked
    path; unknown values raise. Each impl gets a FRESH model — the env
    knob is read at trace time and the jitted decode step is cached per
    executor, so flipping it under a cached build would be a no-op."""
    from flexflow_tpu.runtime.serving import incremental_generate

    prompt = np.array([[3, 1, 4]], np.int32)
    monkeypatch.setenv("FF_DECODE_IMPL", "dense")
    ref = incremental_generate(build_lm(), prompt, max_new_tokens=5)
    monkeypatch.setenv("FF_DECODE_IMPL", "paged")
    out = incremental_generate(build_lm(), prompt, max_new_tokens=5)
    np.testing.assert_array_equal(out, ref)
    monkeypatch.setenv("FF_DECODE_IMPL", "wat")
    with pytest.raises(ValueError):
        incremental_generate(build_lm(), prompt, max_new_tokens=1)


# ---------------------------------------------------------------------------
# disaggregated serving (runtime/serving.py)
# ---------------------------------------------------------------------------

def test_batcher_exact_with_decode_strategy_active():
    from flexflow_tpu.runtime.serving import (
        AdmissionQueue,
        ContinuousBatcher,
        GenerationRequest,
        ServingConfig,
        incremental_generate,
    )

    m = build_lm()
    m.compile_decode()
    q = AdmissionQueue(max_depth=16)
    b = ContinuousBatcher(
        m, ServingConfig(max_len=SEQ, slots=3, page_size=4,
                         precompile=False, default_deadline_s=120.0), q,
    ).start()
    assert b.decode_strategy_active, (
        "batched decode should lower from the decode-searched strategy")
    rng = np.random.RandomState(0)
    cases = []
    try:
        for _ in range(5):
            plen = int(rng.randint(1, 6))
            new = int(rng.randint(1, 6))
            prompt = rng.randint(0, VOCAB, plen).astype(np.int32)
            req = GenerationRequest(prompt, new, deadline_s=120.0)
            q.offer(req)
            cases.append((prompt, new, req))
        for prompt, new, req in cases:
            out = req.result(timeout=300.0)
            ref = incremental_generate(m, prompt[None], max_new_tokens=new)
            np.testing.assert_array_equal(out, ref[0])
    finally:
        b.stop()


def test_decode_strategy_path_via_serving_config(tmp_path):
    """ServingConfig.decode_strategy_path imports the second strategy at
    batcher construction when the model was only compile()d."""
    from flexflow_tpu.runtime.serving import (
        AdmissionQueue,
        ContinuousBatcher,
        ServingConfig,
    )

    path = str(tmp_path / "dec.json")
    build_lm().compile_decode(export_path=path)

    m = build_lm()
    assert m.decode_executor is None
    b = ContinuousBatcher(
        m, ServingConfig(max_len=SEQ, slots=2, page_size=4,
                         precompile=False, decode_strategy_path=path),
        AdmissionQueue(max_depth=4),
    )
    assert m.decode_executor is not None
    assert b.decode_strategy_active


def test_incompatible_decode_executor_falls_back_counted():
    """A decode executor whose graph cannot consume the training param
    store must NOT be swapped in: the batcher falls back to the training
    lowering, counts ff_decode_fallback_total and stays functional."""
    from flexflow_tpu import obs
    from flexflow_tpu.obs.telemetry import TelemetryConfig
    from flexflow_tpu.parallel.decode import reset_decode_fallback_warnings
    from flexflow_tpu.runtime.serving import (
        AdmissionQueue,
        ContinuousBatcher,
        ServingConfig,
    )
    import tempfile

    m = build_lm()
    m.compile_decode()
    # sabotage: rename a weight-bearing decode-graph op so its weights
    # can't be found in the training param store
    for op in m.decode_executor.topo:
        if op.weights and not op.is_parallel_op:
            op.name = op.name + "_rewritten"
            break
    reset_decode_fallback_warnings()
    with tempfile.TemporaryDirectory() as td, \
            obs.session(TelemetryConfig(dir=td)):
        with pytest.warns(UserWarning, match="decode_strategy_incompatible"):
            b = ContinuousBatcher(
                m, ServingConfig(max_len=SEQ, slots=2, page_size=4,
                                 precompile=False),
                AdmissionQueue(max_depth=4),
            )
        assert not b.decode_strategy_active
        c = obs.active().metrics.find(
            "ff_decode_fallback_total",
            reason="decode_strategy_incompatible",
        )
        assert c is not None and c.value >= 1.0
