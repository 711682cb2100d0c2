"""Loop regions (FFModel.loop): layers that run several steps over ONE copy
of their weights, each step on what the last one gave.

Held here, on seeded random weights at a small size on the CPU: the region's
forward is the same layers written out once a step over shared weights; its
gradients are the sum over the steps of that unrolled graph's; decode through
the stacked caches (one copy a step, axis 1) follows the full forward token
by token at per-row positions, from prefilled strips inserted into a batch;
the lowered decode step holds the body once; the sizing and the cost model
count the steps; and the graph machinery keeps a region whole.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (ActiMode, AggrMode, DataType, FFConfig, FFModel,
                          LossType, SGDOptimizer)
from flexflow_tpu.parallel import decode

V, H, HEADS, L, S, B, STEPS = 61, 16, 2, 2, 8, 2, 3


def block(m, x, tag):
    """The sandwich block of a looped model (Ouro's): four RMS norms, rotary
    attention, a gated MLP."""
    def norm(t, name):
        return m.rms_norm(t, eps=1e-6, name=f"{tag}.{name}")

    a = norm(x, "n1")
    a = m.multihead_attention(a, a, a, H, HEADS, kdim=H // HEADS,
                              vdim=H // HEADS, causal=True, bias=False,
                              rope={"theta": 1e4}, name=f"{tag}.attn")
    x = m.add(x, norm(a, "n2"), name=f"{tag}.r1")
    f = norm(x, "n3")
    f = m.multiply(m.dense(f, 24, ActiMode.AC_MODE_SILU, use_bias=False,
                           name=f"{tag}.gate"),
                   m.dense(f, 24, use_bias=False, name=f"{tag}.up"),
                   name=f"{tag}.glu")
    f = m.dense(f, H, use_bias=False, name=f"{tag}.down")
    return m.add(x, norm(f, "n4"), name=f"{tag}.r2")


def build(looped=True, steps=STEPS, batch=B):
    """A looped model, or the same layers written out `steps` times (op
    names `s{u}.h{i}...`), the final norm in every step."""
    argv, sys.argv = sys.argv, sys.argv[:1]
    try:
        cfg = FFConfig()
    finally:
        sys.argv = argv
    cfg.batch_size = batch
    m = FFModel(cfg)
    ids = m.create_tensor((batch, S), DataType.DT_INT32, name="ids")
    x = m.embedding(ids, V, H, AggrMode.AGGR_MODE_NONE, name="wte")
    if looped:
        with m.loop(steps, name="ut") as ut:
            h = ut.enter(x)
            for i in range(L):
                h = block(m, h, f"h{i}")
            x = ut.exit(m.rms_norm(h, eps=1e-6, name="norm_f"))
    else:
        for u in range(steps):
            for i in range(L):
                x = block(m, x, f"s{u}.h{i}")
            x = m.rms_norm(x, eps=1e-6, name=f"s{u}.norm_f")
    m.softmax(m.dense(x, V, use_bias=False, name="head"), name="probs")
    m.compile(optimizer=SGDOptimizer(lr=0.1),
              loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[])
    return m


def noisy(params, seed):
    """Every weight drawn anew: norm scales 1 + N(0, 0.2), the rest N(0,
    0.3), so that no layer is an identity."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([
        (1.0 if leaf.ndim == 1 else 0.0)
        + (0.2 if leaf.ndim == 1 else 0.3)
        * jax.random.normal(k, leaf.shape, leaf.dtype)
        for k, leaf in zip(keys, leaves)])


def unrolled_params(looped, steps=STEPS):
    """The looped model's weights under the unrolled model's names: step u's
    copy of layer i is layer i."""
    out = {}
    for name, w in looped.items():
        if name.startswith(("h", "norm_f")) and name != "head":
            for u in range(steps):
                out[f"s{u}.{name}"] = w
        else:
            out[name] = w
    return out


@pytest.fixture(scope="module")
def models():
    m, u = build(True), build(False)
    params = noisy(m.state.params, 3)
    m.state = m.state.__class__(params=params, opt_state=m.state.opt_state,
                                net_state=m.state.net_state)
    return m, u, params


def ids_of(seed, batch=B):
    return np.random.RandomState(seed).randint(0, V, (batch, S)) \
        .astype(np.int32)


# -- the graph ------------------------------------------------------------------
def test_the_graph_records_one_region_and_holds_its_weights_once(models):
    m, u, params = models
    (reg,) = m.graph.loops()
    assert (reg.name, reg.steps) == ("ut", STEPS)
    assert reg.entry.name == "ut.entry" and reg.source.guid not in {
        t.guid for op in reg.ops for t in op.outputs}
    assert {op.name for op in reg.body} == {
        op.name for op in m.graph.ops if op.loop is not None} - {"ut.entry"}
    assert m.graph.loop_problems() == [] and m.graph.check_correctness()
    # every weight once: the unrolled model holds STEPS copies of the body's
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    body = count({k: v for k, v in params.items()
                  if k.startswith("h") and k != "head"})
    assert count(u.state.params) - count(params) == (STEPS - 1) * (
        body + H)   # and the final norm's scale


def test_a_weights_get_and_set_see_one_copy(models):
    m, _, _ = models
    layer = m.get_layer_by_name("h0.up")
    w = layer.get_weight_tensor(0)
    old = np.asarray(w.get_weights(m))
    assert old.shape == (H, 24)
    w.set_weights(m, old + 1.0)
    np.testing.assert_array_equal(np.asarray(w.get_weights(m)), old + 1.0)
    w.set_weights(m, old)


def test_the_builder_refuses_a_region_that_is_not_whole():
    argv, sys.argv = sys.argv, sys.argv[:1]
    try:
        m = FFModel(FFConfig())
    finally:
        sys.argv = argv
    x = m.embedding(m.create_tensor((1, 4), DataType.DT_INT32), V, H,
                    AggrMode.AGGR_MODE_NONE)
    with pytest.raises(ValueError, match="enter"):
        with m.loop(2, name="a") as a:
            m.relu(x)
    with pytest.raises(ValueError, match="exit"):
        with m.loop(2, name="b") as b:
            h = b.enter(x)
            b.exit(m.dense(h, H + 1))      # shaped otherwise than the source
    with pytest.raises(ValueError, match="nest"):
        with m.loop(2, name="c") as c:
            with m.loop(2, name="d"):
                pass
    with pytest.raises(ValueError, match="steps"):
        with m.loop(0, name="e"):
            pass


def test_rewrites_stay_out_of_a_region(models):
    """Fusion leaves the region's ops as they are, the substitutions find
    none of them, and a rewrite that reads a body tensor from outside the
    region fails the graph's check."""
    from flexflow_tpu.ff_types import OperatorType
    from flexflow_tpu.pcg.fusion import apply_fusion
    from flexflow_tpu.search.substitution import _find_ops, copy_graph

    m, _, _ = models
    fused = apply_fusion(m.graph)
    assert [op.name for op in fused.loops()[0].ops] == \
        [op.name for op in m.graph.loops()[0].ops]
    assert [op.name for op in _find_ops(m.graph, OperatorType.OP_LINEAR)] \
        == ["head"]
    g2, _ = copy_graph(m.graph)
    assert [op.name for op in g2.loops()[0].ops] == \
        [op.name for op in m.graph.loops()[0].ops]
    inner = next(op for op in g2.ops if op.name == "h0.r1")
    head = next(op for op in g2.ops if op.name == "head")
    head.inputs[0] = inner.outputs[0]
    g2._producer_cache = None
    assert not g2.check_correctness()
    assert any("outside loop" in msg for _, msg in g2.loop_problems())


# -- forward and training ---------------------------------------------------------
def test_looped_forward_equals_the_layers_written_out(models):
    m, u, params = models
    ids = jnp.asarray(ids_of(1))
    got = m.executor.build_forward()(params, [ids])
    want = u.executor.build_forward()(unrolled_params(params), [ids])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def loss_and_grads(model, params, ids, labels):
    ex = model.executor
    probs = ex.build_forward()(params, [jnp.asarray(ids)])
    grads, _ = ex.build_grad_step()(params, [jnp.asarray(ids)],
                                    jnp.asarray(labels))
    return float(ex.loss_fn(probs, jnp.asarray(labels))), grads


def test_each_shared_weights_gradient_is_the_sum_over_the_steps(models):
    m, u, params = models
    ids, labels = ids_of(2), ids_of(4)[..., None]
    loss, grads = loss_and_grads(m, params, ids, labels)
    uloss, ugrads = loss_and_grads(u, unrolled_params(params), ids, labels)
    assert loss == pytest.approx(uloss, rel=1e-5)
    for op, ws in grads.items():
        for name, g in ws.items():
            if op.startswith(("h", "norm_f")) and op != "head":
                want = sum(ugrads[f"s{s}.{op}"][name] for s in range(STEPS))
            else:
                want = ugrads[op][name]
            np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=f"{op}/{name}")


def test_fit_trains_through_the_region():
    """fit() over a looped graph: its first step moves each shared weight
    by the learning rate times its gradient summed over the steps, and the
    loss it starts from is the unrolled graph's."""
    m, u = build(True), build(False)
    params = noisy(m.state.params, 5)
    m.state = m.state.__class__(params=params,
                                opt_state=m.optimizer.init_state(params),
                                net_state=m.state.net_state)
    x, y = ids_of(6), ids_of(7)[..., None]
    loss, grads = loss_and_grads(m, params, x, y)
    uloss, _ = loss_and_grads(u, unrolled_params(params), x, y)
    assert loss == pytest.approx(uloss, rel=1e-5)
    m.fit(x, y, batch_size=B, epochs=1, verbose=0)
    for op, name in (("h1.down", "kernel"), ("h0.attn", "wq"),
                     ("norm_f", "scale")):
        moved = np.asarray(m.state.params[op][name]) \
            - np.asarray(params[op][name])
        np.testing.assert_allclose(moved, -0.1 * np.asarray(grads[op][name]),
                                   rtol=1e-3, atol=1e-7)


# -- decode ---------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["dense", "paged"])
def test_decode_through_stacked_caches_follows_the_full_forward(
        models, monkeypatch, impl):
    """Rows prefilled one at a time to lengths of their own (batch-1 strips
    inserted into a batch at their slots), then decoded token by token at
    per-row positions: every logit the full forward's, on the dense branch
    and through the paged kernel (its interpreter here), which reads each
    step's pages of the stacked pool through its table. The stacked leaves
    have one copy a step on axis 1, and a decode step runs the body STEPS
    times."""
    m, _, params = models
    ex = m.executor
    monkeypatch.setenv("FF_DECODE_IMPL", impl)
    monkeypatch.setattr(ex, "_decode_builds", {})  # steps traced anew
    ids = ids_of(8)
    full = np.asarray(ex.build_forward()(params, [jnp.asarray(ids)]))
    init1, step1 = ex.build_decode(1, S)
    initB, stepB = ex.build_decode(B, S)
    caches = initB(params)
    for leaf in jax.tree_util.tree_leaves(caches["mha"]):
        assert leaf.shape == (B, STEPS, S, H)
    lengths = [3, 5]
    for slot, n in enumerate(lengths):
        strip = init1(params)
        probs, strip = step1(params, strip, jnp.int32(0),
                             [jnp.asarray(ids[slot:slot + 1, :n])])
        np.testing.assert_allclose(np.asarray(probs)[0], full[slot, :n],
                                   rtol=1e-5, atol=1e-6)
        assert int(strip["prefill_counters"]["loop_prefill_passes"]) == STEPS
        caches = decode.insert_row(caches, strip, slot)
    pos = np.asarray(lengths)
    while pos.max() < S:
        at = np.minimum(pos, S - 1)
        probs, caches = stepB(params, caches, jnp.asarray(at, jnp.int32),
                              [jnp.asarray(ids[np.arange(B), at][:, None])])
        assert int(caches["counters"]["loop_passes"]) == STEPS
        for row in range(B):
            if pos[row] < S:
                np.testing.assert_allclose(
                    np.asarray(probs)[row, 0], full[row, pos[row]],
                    rtol=1e-5, atol=1e-6)
        pos += 1


def test_insert_row_writes_every_step_of_one_slot(models):
    m, _, params = models
    init1, step1 = m.executor.build_decode(1, S)
    initB, _ = m.executor.build_decode(B, S)
    strip = init1(params)
    _, strip = step1(params, strip, jnp.int32(0), [jnp.asarray(ids_of(9)[:1])])
    batch = decode.insert_row(initB(params), strip, 1)
    for got, row in zip(jax.tree_util.tree_leaves(batch["mha"]),
                        jax.tree_util.tree_leaves(strip["mha"])):
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(row[0]))
        assert not np.asarray(got[0]).any()
        # each step keeps keys of its own
        assert not np.array_equal(np.asarray(row[0, 0]), np.asarray(row[0, 1]))


def test_the_lowered_decode_step_holds_one_body():
    """The decode step of a looped graph lowers the layers once, in one
    loop: as many matrix products as the same graph run one step, and none
    more for the steps."""
    def products(steps):
        m = build(True, steps=steps)
        init, step = m.executor.build_decode(B, S)
        caches = jax.eval_shape(init, m.state.params)
        text = step.lower(m.state.params, caches,
                          jax.ShapeDtypeStruct((B,), jnp.int32),
                          [jax.ShapeDtypeStruct((B, 1), jnp.int32)]).as_text()
        return text.count("stablehlo.dot_general"), text.count(
            "stablehlo.while")

    one, four = products(1), products(4)
    assert four == one
    assert one[1] >= 1


# -- sizes and prices --------------------------------------------------------------
def test_reservation_and_cost_count_the_steps(models):
    from flexflow_tpu.runtime.kvcache import (KVCacheConfig, kv_page_bytes,
                                              slot_reservation_bytes)
    from flexflow_tpu.pcg.machine_view import MachineView
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.machine_model import MachineModel

    m, u, _ = models
    kv = KVCacheConfig(num_pages=64, page_size=4)
    # k and v, H float32 values a position, L layers, STEPS copies each
    assert slot_reservation_bytes(m, kv, 6) == STEPS * L * 8 * 2 * H * 4
    assert slot_reservation_bytes(u, kv, 6) == slot_reservation_bytes(m, kv, 6)
    assert kv_page_bytes(m, 4) == STEPS * L * 4 * 2 * H * 4
    cm = CostModel(MachineModel(num_nodes=1, workers_per_node=1))
    op = next(o for o in m.graph.ops if o.name == "h0.up")
    twin = next(o for o in u.graph.ops if o.name == "s0.h0.up")
    view = MachineView(start_device_id=0, dim=(1,), stride=(1,))
    looped, plain = cm.measure_operator_cost(op, view), \
        cm.measure_operator_cost(twin, view)
    assert looped.forward_time == pytest.approx(STEPS * plain.forward_time)
    assert looped.backward_time == pytest.approx(STEPS * plain.backward_time)
    assert looped.weights_memory == plain.weights_memory
    assert looped.sync_time == plain.sync_time


# -- graphs with no region lower as they did ---------------------------------------
# sha256 (first 16 hex digits) of the lowered text, locations stripped, of each
# benchmark cell's programs at its rehearsal sizes, read on the tree before
# loop regions came in: a graph with no region lowers to the same programs
LOWERED_BEFORE_LOOPS = {
    "serve-opt1.3b-saturated/decode": "56b844922761d3d6",
    "serve-opt1.3b-saturated/prefill": "79678012d04611cf",
    "serve-olmohybrid-docs-saturated/decode": "7c9affcf3227c9b7",
    "serve-olmohybrid-docs-saturated/prefill": "f51d9541cdcac52c",
    "serve-nemotron3nano-reason-saturated/decode": "11366f3b3e263345",
    "serve-nemotron3nano-reason-saturated/prefill": "2c38cfb47f1cf2cf",
    "serve-lagunas-code-saturated/decode": "754c65be427f46fd",
    "serve-lagunas-code-saturated/prefill": "7c820f94d22ec07b",
    "train-gpt2m-1chip/train": "877b6ef4e3599369",
}


def _digest(lowered):
    import hashlib
    import re

    text = re.sub(r"loc\([^)]*\)", "", lowered.as_text())
    text = re.sub(r"#loc\d*( = .*)?", "", text)
    # the names of results and arguments: a cache keyed by a tensor's guid
    # is named by it, and guids count the tensors the process made before
    text = re.sub(r'jax\.(result|arg)_info = "[^"]*"', "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def lowered_cells():
    """Each cell's batched decode step, its prefill block (the batch-1 step
    with a prompt's length and row) and the training cell's train step,
    lowered on shapes at the rehearsal sizes."""
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench.harness import runctx, serve, spec, train

    argv, sys.argv = sys.argv, sys.argv[:1]
    out = {}
    try:
        for name in LOWERED_BEFORE_LOOPS:
            cell_name, program = name.split("/")
            if program != "decode":
                continue
            cell = spec.cell(cell_name, rehearsal=True)
            sc = serve.ServeCell(cell, *spec.family(cell.config),
                                 runctx.Spans())
            sc.build()
            sv, m = cell.params["serving"], sc.model
            for which, ex, batch in (("decode", m.decode_executor,
                                      sv["slots"]), ("prefill", m.executor, 1)):
                init, step = ex.build_decode(batch, sv["max_len"])
                caches = jax.eval_shape(init, m.state.params, ())
                if batch == 1:
                    tok = jax.ShapeDtypeStruct((1, sv["max_len"]), jnp.int32)
                    low = step.lower(m.state.params, caches, jnp.int32(0),
                                     [tok], jnp.int32(3), jnp.int32(2))
                else:
                    low = step.lower(
                        m.state.params, caches,
                        jax.ShapeDtypeStruct((batch,), jnp.int32),
                        [jax.ShapeDtypeStruct((batch, 1), jnp.int32)])
                out[f"{cell_name}/{which}"] = _digest(low)
            sc.free()
        cell = spec.cell("train-gpt2m-1chip", rehearsal=True)
        tc = train.TrainCell(cell, *spec.family(cell.config), runctx.Spans())
        tc.build()
        m, mix = tc.model, cell.mix
        out["train-gpt2m-1chip/train"] = _digest(
            m.executor.build_train_step().lower(
                m.state,
                [jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)],
                jax.ShapeDtypeStruct((mix["batch"], mix["seq"], 1), jnp.int32),
                jax.random.PRNGKey(0)))
    finally:
        sys.argv = argv
    return out


@pytest.mark.parametrize("program", sorted(LOWERED_BEFORE_LOOPS))
def test_graphs_without_a_region_lower_as_they_did(lowered_cells, program):
    assert lowered_cells[program] == LOWERED_BEFORE_LOOPS[program]


# -- the batcher's memo of prefilled strips ------------------------------------
@pytest.mark.parametrize("free_strips, hits", [(None, 2), (3.0, 2), (1.5, 0)])
def test_the_prefill_memo_keeps_strips_only_while_they_fit(
        models, monkeypatch, free_strips, hits):
    """One prompt three times through one slot, the memo sized for two
    strips. It keeps them where two fit in what the device has free when
    the batch is made (beside the batch and one admission's batch-1 cache):
    with room for three strips both repeats replay it, with room for one and
    a half none does and the memo stays empty; where the backend reports no
    memory (the CPU: None) its entries alone bound it, as before. The tokens
    are the same every time."""
    from flexflow_tpu.runtime import serving
    from flexflow_tpu.runtime.serving import (AdmissionQueue,
                                              ContinuousBatcher,
                                              GenerationRequest,
                                              ServingConfig)

    m, _, _ = models
    strip = 2 * L * STEPS * S * H * 4    # k and v, every step, float32
    monkeypatch.setattr(serving, "_device_free_bytes", lambda: None
                        if free_strips is None else int(free_strips * strip))
    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(m, ServingConfig(max_len=S, slots=1, page_size=4,
                                           precompile=False,
                                           prefix_cache_entries=2), q).start()
    prompt = ids_of(12)[0, :5]
    try:
        outs = []
        for _ in range(3):  # one after another: each admission sees the memo
            r = GenerationRequest(prompt.copy(), 3, deadline_s=120.0)
            q.offer(r)
            outs.append(r.result(timeout=300.0))
    finally:
        b.stop()
    assert b.stats["prefill_skips"] == hits
    assert len(b._prefix_cache) == (0 if hits == 0 else 1)
    for out in outs[1:]:
        np.testing.assert_array_equal(out, outs[0])


# the looped cell on one v5e: free with the weights, the batch and one
# batch-1 cache live (read there on the chip), and one strip's bytes
OURO_FREE, OURO_STRIP = 3_435_901_440, 48 * 4 * 2 * 1024 * 2048 * 2


@pytest.mark.parametrize("free, strip, entries, kept", [
    (None, OURO_STRIP, 8, 8),              # no memory reported: entries
    (OURO_FREE, OURO_STRIP, 8, 0),         # the looped cell: the memo is off
    (OURO_FREE, OURO_STRIP, 2, 2),         # two of its strips do fit
    (8 * 10 ** 6, 10 ** 6, 8, 8),          # eight fit exactly
    (8 * 10 ** 6 - 1, 10 ** 6, 8, 0),      # a byte short: none, not seven
    (10 ** 12, 10 ** 6, 0, 0),             # switched off by its setting
], ids=["no-memory", "looped-cell", "looped-cell-2", "exact", "short",
        "off"])
def test_the_memo_keeps_all_its_strips_or_none(free, strip, entries, kept):
    """The memo keeps `prefix_cache_entries` strips where that many fit in
    the room, and none where they do not: the looped cell's 1.61 GB strips
    never sit beside its batch, so its peak is weights, caches and the one
    admission's batch-1 cache."""
    from flexflow_tpu.runtime import serving

    assert serving._memo_entries(free, strip, entries) == kept


def test_the_memos_room_is_read_as_the_device_reports_it(monkeypatch):
    """What the device has free, as a plain integer past 32 bits (a v5e
    reports some 16 GB), and None where its backend reports nothing."""
    from flexflow_tpu.runtime import serving

    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    limit, used = 15_750_000_000, 12_314_098_560
    monkeypatch.setattr(jax, "devices", lambda: [Device(
        {"bytes_limit": limit, "bytes_in_use": used})])
    free = serving._device_free_bytes()
    assert type(free) is int and free == limit - used > 2 ** 31
    monkeypatch.setattr(jax, "devices", lambda: [Device(None)])
    assert serving._device_free_bytes() is None
