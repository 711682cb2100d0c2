"""The decode step owns its caches (executor.build_decode).

On an accelerator the jitted step donates its `caches` argument, so XLA
appends to every cache leaf in place instead of copying the whole KV cache
first; on the CPU it donates nothing (executor.donates_buffers says why). The CPU
suite cannot see the saved copy. It holds what donation asks of the
callers: with donation forced on here, every caller that rebinds from the
step's return value serves the same tokens as the undonated build, and one
that read a consumed cache would raise "Array has been deleted".
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (AggrMode, DataType, FFConfig, FFModel, LossType,
                          MetricsType, SGDOptimizer)
from flexflow_tpu.parallel import decode
from flexflow_tpu.parallel.executor import PCGExecutor
from flexflow_tpu.runtime.serving import (AdmissionQueue, ContinuousBatcher,
                                          GenerationRequest,
                                          incremental_beam_generate,
                                          incremental_generate,
                                          incremental_seq2seq_generate)
from tests.test_serving import VOCAB, _serve_cfg, build_lm


@pytest.fixture(scope="module")
def lm():
    return build_lm()


@pytest.fixture(scope="module")
def hybrid():
    """One gated delta-rule layer and one attention layer: both kinds of
    per-slot state."""
    import sys

    from tests.test_linear_attention import hybrid as build

    argv, sys.argv = sys.argv, sys.argv[:1]
    try:
        return build()
    finally:
        sys.argv = argv


@pytest.fixture
def donating(monkeypatch):
    """The rule of the chip, on the CPU: every build_decode under this
    fixture donates. Without the persistent compile cache, whose CPU
    executables have lost their aliasing before (executor.donates_buffers)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(PCGExecutor, "donates_buffers", lambda self: True)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _buffers(tree):
    """Where every leaf of `tree` lies: one address a device's shard."""
    return [shard.data.unsafe_buffer_pointer()
            for leaf in jax.tree_util.tree_leaves(tree)
            for shard in leaf.addressable_shards]


def _leaves(caches, *sections):
    return [leaf for sec in sections
            for leaf in jax.tree_util.tree_leaves(caches[sec])]


# -- the step itself ------------------------------------------------------------
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_the_step_consumes_the_caches_it_is_handed(kind, request):
    m = request.getfixturevalue("lm" if kind == "attention" else "hybrid")
    params = m.state.params
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 29, (2, 4)),
                       jnp.int32)

    def two_steps():
        init, step = m.executor.build_decode(2, 16)
        old = init(params, ())
        _, new = step(params, old, jnp.int32(0), [toks[:, :3]])
        logits, _ = step(params, new, jnp.asarray([3, 3], jnp.int32),
                         [toks[:, 3:]])
        # the consumed tree, for the caller to look at what became of it
        return old, np.asarray(logits)  # fflint: disable=FFL102

    old, plain = two_steps()
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(old))
    request.getfixturevalue("donating")
    old, donated = two_steps()
    assert _leaves(old, "mha", "prefix", "recurrent")
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(old))
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(_leaves(old, "mha", "recurrent")[0])
    # what came back stepped again to the same numbers; the weights were
    # lent, not given
    np.testing.assert_array_equal(donated, plain)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(params))


def test_the_cpu_build_donates_nothing(lm):
    assert not lm.executor.donates_buffers()
    init, step = lm.executor.build_decode(2, 16)
    old = init(lm.state.params, ())
    step(lm.state.params, old, jnp.int32(0), [jnp.zeros((2, 3), jnp.int32)])
    leaves = jax.tree_util.tree_leaves(old)  # fflint: disable=FFL102
    assert not any(leaf.is_deleted() for leaf in leaves)  # undonated


def test_a_donated_and_an_undonated_build_are_two_builds(lm, monkeypatch):
    plain = lm.executor.build_decode(2, 16)
    monkeypatch.setattr(PCGExecutor, "donates_buffers", lambda self: True)
    donated = lm.executor.build_decode(2, 16)
    assert donated is not plain
    assert lm.executor.build_decode(2, 16) is donated


def test_init_caches_hands_out_nothing_of_the_callers(donating):
    """A static input that a decoder-side op reads as it came lies in
    caches["static"]: the step consumes the caches, not the caller's
    array, which serves the next init_caches too. So does the compiled
    init (decode.compiled_init, the batcher's): no leaf it hands out lies
    in a buffer of the weights' or the inputs', nor in another leaf's."""
    vocab, dec_len, hidden, bs = 24, 8, 16, 2
    cfg = FFConfig()
    cfg.batch_size = bs
    m = FFModel(cfg)
    dec_ids = m.create_tensor((bs, dec_len), DataType.DT_INT32)
    bias_in = m.create_tensor((bs, dec_len, hidden), DataType.DT_FLOAT)
    t = m.embedding(dec_ids, vocab, hidden, AggrMode.AGGR_MODE_NONE)
    t = m.add(t, bias_in)
    t = m.multihead_attention(t, t, t, hidden, 2, causal=True)
    t = m.dense(t, vocab)
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    rng = np.random.RandomState(2)
    xd = rng.randint(0, vocab, (bs, dec_len)).astype(np.int32)
    xb = jnp.asarray(rng.randn(bs, dec_len, hidden).astype(np.float32))
    full = np.asarray(m.executor.build_forward()(
        m.state.params, [jnp.asarray(xd), xb]))
    eager, step = m.executor.build_decode(bs, dec_len, decode_input=0)
    theirs = set(_buffers((m.state.params, xb)))
    # the second and third rounds read xb again
    for init in (eager, decode.compiled_init(eager), eager):
        caches = init(m.state.params, [xb])
        ours = _buffers(caches)
        assert len(set(ours)) == len(ours) and not theirs & set(ours)
        assert _leaves(caches, "static")
        for t_ in range(3):
            logits, caches = step(m.state.params, caches, jnp.int32(t_),
                                  [jnp.asarray(xd[:, t_:t_ + 1])])
            np.testing.assert_allclose(np.asarray(logits)[:, 0], full[:, t_],
                                       rtol=2e-4, atol=2e-4)
    assert not xb.is_deleted()


def test_a_leaf_that_changes_type_is_counted_not_donated(
        lm, donating, monkeypatch, tmp_path):
    """XLA aliases a donated leaf to an output of its own shape and type.
    An op whose cache comes back as another is copied every step after
    all: the step says so once, where it is traced."""
    import warnings

    import flexflow_tpu.obs as obs
    from flexflow_tpu.ff_types import OperatorType
    from flexflow_tpu.obs import TelemetryConfig
    from flexflow_tpu.ops.registry import get_op_def
    from flexflow_tpu.parallel import decode as dec

    mha = get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION)
    real = mha.forward_decode

    def halved(*a, **kw):
        outs, (k, v) = real(*a, **kw)
        return outs, (k.astype(jnp.bfloat16), v)

    monkeypatch.setattr(mha, "forward_decode", halved)
    dec.reset_decode_fallback_warnings()
    init, step = lm.executor.build_decode(3, 16)
    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            step(lm.state.params, init(lm.state.params, ()), jnp.int32(0),
                 [jnp.zeros((3, 2), jnp.int32)])
        count = obs.active().metrics.find("ff_decode_fallback_total",
                                          reason="cache_not_donated")
        assert count is not None and count.value == 1.0
    told = [w for w in caught if "cache_not_donated" in str(w.message)]
    assert len(told) == 1 and "bfloat16" in str(told[0].message)


# -- the callers ----------------------------------------------------------------
def _serve(m, prompts, news, vocab, **cfg):
    q = AdmissionQueue(max_depth=32)
    b = ContinuousBatcher(m, _serve_cfg(**cfg), q).start()
    try:
        reqs = [GenerationRequest(p.copy(), n, deadline_s=120.0)
                for p, n in zip(prompts, news)]
        for r in reqs:
            q.offer(r)
        outs = [r.result(timeout=300.0) for r in reqs]
    finally:
        b.stop()
    assert b.pool.pages_in_use == 0
    return outs, b


@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_the_batcher_serves_the_same_tokens_from_donated_caches(
        kind, request, tmp_path):
    """Seven requests through two slots (admissions into a running batch,
    retirements, slots used again), one prompt three times (a
    `_prefix_cache` hit inserts a memoized strip, which is a step's
    output): the tokens of the undonated build, and `stats` and the
    session's gauge say which build served."""
    import flexflow_tpu.obs as obs
    from flexflow_tpu.obs import TelemetryConfig

    m = request.getfixturevalue("lm" if kind == "attention" else "hybrid")
    vocab = VOCAB if kind == "attention" else 97
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, vocab, int(n)).astype(np.int32)
               for n in rng.randint(1, 7, 5)]
    prompts += [prompts[1].copy(), prompts[1].copy()]
    news = [int(n) for n in rng.randint(2, 8, 7)]
    plain, b0 = _serve(m, prompts, news, vocab)
    assert b0.stats["decode_caches_donated"] == 0
    donating = request.getfixturevalue("donating")  # noqa: F841
    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))):
        donated, b1 = _serve(m, prompts, news, vocab)
        gauge = obs.active().metrics.find(
            "ff_serving_decode_caches_donated", replica=b1.name)
        assert gauge is not None and gauge.value == 1.0
    assert b1.stats["decode_caches_donated"] == 1
    assert b1.stats["finished"] == 7 and b1.stats["prefill_skips"] >= 1
    assert b1.stats["iterations"] > 7
    for a, b in zip(plain, donated):
        np.testing.assert_array_equal(a, b)


def test_an_insert_never_holds_a_second_generation_of_the_caches(
        hybrid, donating):
    """`_insert_slot` writes a prefilled strip into every per-slot leaf with
    one program. Under donation (the chip's rule) it is handed the running
    batch: each old leaf is written in place and deleted, so what the
    insert holds beside the caches is nothing, not a copy of them all (on
    the chip that copy was the peak of the device's memory). The strip is
    only read: the prefix memo keeps it to replay."""
    b = ContinuousBatcher(hybrid, _serve_cfg(), AdmissionQueue(max_depth=4))
    params = hybrid.state.params
    _, strip = b._step1(params, b._init1(params, ()), jnp.int32(0),
                        [jnp.zeros((1, 4), jnp.int32)], jnp.int32(4),
                        jnp.int32(3))
    b._insert_slot(0, strip, request="r0")
    old = _leaves(b._caches, "prefix", "mha", "recurrent")
    assert len(old) == 4  # (k, v) and (S, conv_tail)
    shared = _leaves(b._caches, "counters", "prefill_counters")
    b._insert_slot(1, strip, request="r1")
    assert all(leaf.is_deleted() for leaf in old)
    new = _leaves(b._caches, "mha", "recurrent")
    assert len(new) == 4 and not any(leaf.is_deleted() for leaf in new)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(strip))
    # the shared sections pass around the program as they are
    assert _leaves(b._caches, "counters", "prefill_counters") == shared
    assert b.stats["insert_programs"] == 2


def test_a_memoised_strip_outlives_the_inserts_that_replay_it(lm, donating):
    """One prompt three times through one slot: the second and third are
    the memo's strip replayed into a batch that donates; the strip is never
    consumed, and every replay serves the tokens of the prefill."""
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, VOCAB, 5).astype(np.int32)
    outs, b = _serve(lm, [prompt] * 3, [6] * 3, VOCAB, slots=1)
    assert b.stats["prefill_skips"] == 2 and b.stats["insert_programs"] == 3
    strips = [strip for _, strip in b._prefix_cache.values()]
    assert len(strips) == 1
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(strips))
    want = incremental_generate(lm, prompt[None], max_new_tokens=6)[0]
    for out in outs:
        np.testing.assert_array_equal(out, want)


def _op_tree(kind, batch, seed):
    """A tree of caches as the ops declare them (init_decode_state), every
    leaf noise: fused attention with as many key-value heads as query
    heads, grouped-query, a window's ring beside a full layer, and the
    gated delta-rule's state and convolution tail beside attention."""
    from flexflow_tpu.ff_types import OperatorType
    from flexflow_tpu.ops.attention import MultiHeadAttentionParams
    from flexflow_tpu.ops.linear_attention import GatedDeltaNetParams
    from flexflow_tpu.ops.registry import get_op_def

    def mha(**kw):
        return get_op_def(OperatorType.OP_MULTIHEAD_ATTENTION) \
            .init_decode_state(MultiHeadAttentionParams(
                embed_dim=24, num_heads=6, kdim=8, vdim=8, bias=False,
                causal=True, **kw), batch, 64, jnp.bfloat16)

    tree = {sec: {} for sec in decode.SHARED_SECTIONS + decode.SLOT_SECTIONS}
    if kind == "attention":
        tree["mha"]["a"] = mha()
        tree["prefix"][7] = jnp.zeros((batch, 2, 64, 8), jnp.float32)
    elif kind == "grouped_query":
        tree["mha"]["a"] = mha(num_kv_heads=2)
    elif kind == "ring":
        tree["mha"]["w"] = mha(num_kv_heads=2, window=16)
        tree["mha"]["f"] = mha(num_kv_heads=2)
    else:
        tree["mha"]["a"] = mha()
        tree["recurrent"]["g"] = get_op_def(
            OperatorType.OP_GATED_DELTA_NET).init_decode_state(
                GatedDeltaNetParams(24, 2, 8, 16), batch, 64, jnp.bfloat16)
    tree["counters"]["n"] = jnp.zeros((), jnp.int32)
    rng = np.random.RandomState(seed)
    for sec in decode.SLOT_SECTIONS:
        tree[sec] = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.randn(*x.shape), x.dtype), tree[sec])
    return tree


@pytest.mark.parametrize("kind",
                         ["attention", "grouped_query", "ring", "hybrid"])
def test_the_compiled_insert_writes_what_the_eager_one_wrote(kind):
    """insert_row's one program against the parent's eager update of each
    per-slot leaf: the same tensors, bit for bit, in every slot, and the
    donated batch is consumed while the row is not."""
    batch, row = _op_tree(kind, 3, 0), _op_tree(kind, 1, 1)
    counters = batch["counters"]
    for slot in (2, 0):
        want = {sec: jax.tree_util.tree_map(
            lambda o, r: np.asarray(jax.lax.dynamic_update_slice_in_dim(
                o, r.astype(o.dtype), slot, axis=0)), batch[sec], row[sec])
            for sec in decode.SLOT_SECTIONS}
        old = _leaves(batch, *decode.SLOT_SECTIONS)
        batch = decode.insert_row(batch, row, slot, donate=True)
        assert all(leaf.is_deleted() for leaf in old)
        for sec in decode.SLOT_SECTIONS:
            got = jax.tree_util.tree_leaves(batch[sec])
            exp = jax.tree_util.tree_leaves(want[sec])
            assert len(got) == len(exp)
            for g, e in zip(got, exp):
                assert (g.dtype, g.shape) == (e.dtype, e.shape)
                assert np.asarray(g).tobytes() == e.tobytes()
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(row))
    assert batch["counters"] is counters  # passed around the program


def test_once_served_no_step_is_built_again(donating):
    """Warm-up and the first requests go through the callables the loop
    keeps using, `_init1`, `_step1`, the insert and `_stepB`, so the
    donated programs are the ones compiled before traffic counts: a second
    round of requests of the same buckets traces nothing, and nothing
    served after `_warmup_compiles` compiles a program, the insert into
    the first admission's fresh batch and into a stepped one included (the
    benchmark's `compiles_in_window` rests on this)."""
    import threading

    import jax.monitoring

    from flexflow_tpu.parallel import executor as ex

    m = build_lm()  # its own model: no step built by an earlier test
    for program in decode._INSERT.values():  # nor an insert
        program.clear_cache()
    rng = np.random.RandomState(5)
    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(m, _serve_cfg(precompile=True), q)
    traces, real = [], ex._count_trace
    compiled = []

    def on_event(name, *_args, **kw):
        if name.startswith(("/jax/core/compile", "/jax/compilation_cache")):
            compiled.append((name, kw.get("fun_name")))

    ex._count_trace = lambda p: (traces.append(p), real(p))[1]
    try:
        # on a thread of its own, as the serve thread warms up and serves:
        # JAX keys what it builds on thread-local state as well, which the
        # test process's main thread may carry from tests run before it
        warmup = threading.Thread(target=b._warmup_compiles)
        warmup.start()
        warmup.join()
        warm = list(traces)
        jax.monitoring.register_event_duration_secs_listener(on_event)
        b.start()  # warms again, from what is built
        built = []
        for _ in range(2):
            reqs = [GenerationRequest(rng.randint(0, VOCAB, n).astype(
                np.int32), 4, deadline_s=120.0) for n in (2, 5)]
            for r in reqs:
                q.offer(r)
            for r in reqs:
                r.result(timeout=300.0)
            built.append(list(traces))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        ex._count_trace = real
        b.stop()
    for program in ("decode_step", "prefill", "insert", "init_caches"):
        assert program in warm, program
    assert built[1] == built[0] == warm
    assert compiled == []
    assert b.stats["decode_caches_donated"] == 1 and b.stats["finished"] == 4


@pytest.mark.parametrize("api", ["incremental", "beam"])
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_generate_apis_return_the_same_tokens_from_donated_caches(
        api, kind, request):
    m = request.getfixturevalue("lm" if kind == "attention" else "hybrid")
    vocab = VOCAB if kind == "attention" else 97
    prompt = np.random.RandomState(4).randint(0, vocab, (2, 4)) \
        .astype(np.int32)

    def run():
        if api == "incremental":
            return incremental_generate(m, prompt, max_new_tokens=6)
        return incremental_beam_generate(m, prompt, num_beams=3,
                                         max_new_tokens=6)

    plain = run()
    request.getfixturevalue("donating")
    np.testing.assert_array_equal(run(), plain)


def test_seq2seq_generate_returns_the_same_tokens_from_donated_caches(
        request):
    """An encoder-decoder graph: the encoder's side lies in `static` and
    `mha_static`, which pass through the step and come back aliased."""
    from flexflow_tpu import ActiMode

    vocab, enc_len, dec_len, hidden, heads, bs = 40, 7, 10, 32, 4, 2
    cfg = FFConfig()
    cfg.batch_size = bs
    m = FFModel(cfg)
    enc_ids = m.create_tensor((bs, enc_len), DataType.DT_INT32)
    dec_ids = m.create_tensor((bs, dec_len), DataType.DT_INT32)
    enc = m.embedding(enc_ids, vocab, hidden, AggrMode.AGGR_MODE_NONE)
    enc = m.multihead_attention(enc, enc, enc, hidden, heads)
    enc = m.dense(enc, hidden, ActiMode.AC_MODE_RELU)
    t = m.embedding(dec_ids, vocab, hidden, AggrMode.AGGR_MODE_NONE)
    t = m.multihead_attention(t, t, t, hidden, heads, causal=True)
    t = m.multihead_attention(t, enc, enc, hidden, heads)
    t = m.dense(t, hidden, ActiMode.AC_MODE_RELU)
    t = m.dense(t, vocab)
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    xe = np.random.RandomState(1).randint(0, vocab, (bs, enc_len)) \
        .astype(np.int32)
    plain = incremental_seq2seq_generate(m, xe, max_new_tokens=6)
    beams = incremental_beam_generate(
        m, np.zeros((bs, 1), np.int32), num_beams=3, max_new_tokens=5,
        encoder_ids=xe)
    request.getfixturevalue("donating")
    np.testing.assert_array_equal(
        incremental_seq2seq_generate(m, xe, max_new_tokens=6), plain)
    np.testing.assert_array_equal(
        incremental_beam_generate(
            m, np.zeros((bs, 1), np.int32), num_beams=3, max_new_tokens=5,
            encoder_ids=xe), beams)
