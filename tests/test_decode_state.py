"""The decode caches' contract (parallel/decode.py): one module knows the
tree a step steps, and its callers do four things with it: make it empty
(init_caches), write one prefilled row into a slot of a running batch
(insert_row), take rows by index (take_rows), and ask what it holds by
kind (state_bytes; declared_state_bytes for what it would hold).

Held over the four shapes the tree takes: a fused-MHA decoder ("mha"), a
hybrid with a gated delta-rule layer ("recurrent" beside it), a
primitive-op attention graph whose products lie in "prefix" and whose
baked mask reaches the step through a computed "static" leaf of leading
axis 1, and an encoder-decoder with "static" and "mha_static".
"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (ActiMode, AggrMode, DataType, FFConfig, FFModel,
                          LossType, MetricsType, SGDOptimizer)
from flexflow_tpu.parallel import decode
from flexflow_tpu.runtime.kvcache import (KVCacheConfig, kv_page_bytes,
                                          recurrent_slot_bytes,
                                          slot_reservation_bytes)
from flexflow_tpu.runtime.verify import ServingConfigError
from tests.test_linear_attention import hybrid
from tests.test_serving import build_lm

SLOTS = 3


def primitive(batch=2, seq=8, vocab=16, hidden=8):
    """Attention from batch_matmul and softmax (as tests/test_serving_qa.py
    builds it), its causal mask computed from baked constants, so that it
    reaches the step as a static value with a leading axis of 1."""
    cfg = FFConfig()
    cfg.batch_size = batch
    m = FFModel(cfg)
    ids = m.create_tensor((batch, seq), DataType.DT_INT32)
    t = m.embedding(ids, vocab, hidden, AggrMode.AGGR_MODE_NONE)
    scores = m.batch_matmul(t, m.transpose(t, (0, 2, 1)))
    mask = np.where(np.tril(np.ones((seq, seq), bool)), 0.0, -1e9
                    ).astype(np.float32)[None]
    bias = m.add(m.create_constant_tensor(mask, DataType.DT_FLOAT),
                 m.create_constant_tensor(np.zeros_like(mask),
                                          DataType.DT_FLOAT))
    probs = m.softmax(m.add(scores, bias), axis=-1)
    m.dense(m.batch_matmul(probs, t), 4)
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
              [MetricsType.METRICS_MEAN_SQUARED_ERROR])
    return m


def encoder_decoder(batch=2, enc_len=7, dec_len=10, vocab=40, hidden=32,
                    heads=4):
    cfg = FFConfig()
    cfg.batch_size = batch
    m = FFModel(cfg)
    enc_ids = m.create_tensor((batch, enc_len), DataType.DT_INT32)
    dec_ids = m.create_tensor((batch, dec_len), DataType.DT_INT32)
    enc = m.embedding(enc_ids, vocab, hidden, AggrMode.AGGR_MODE_NONE)
    enc = m.multihead_attention(enc, enc, enc, hidden, heads)
    enc = m.dense(enc, hidden, ActiMode.AC_MODE_RELU)
    t = m.embedding(dec_ids, vocab, hidden, AggrMode.AGGR_MODE_NONE)
    t = m.multihead_attention(t, t, t, hidden, heads, causal=True)
    t = m.multihead_attention(t, enc, enc, hidden, heads)
    # the decoder reads the encoder's output as it is too: a "static" leaf
    t = m.add(t, m.dense(m.reduce_mean(enc, [1], keepdims=True), hidden))
    m.dense(m.dense(t, hidden, ActiMode.AC_MODE_RELU), vocab)
    m.compile(SGDOptimizer(lr=0.01),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


class Graph:
    """One compiled graph with what a test needs to step it: the cap, the
    vocabulary, the static inputs of a batch of `n` rows (the same for
    every row, as beam search and a shared encoder have them), and the
    sections its tree must fill."""

    def __init__(self, model, max_len, vocab, filled, enc_len=None):
        self.model, self.max_len, self.vocab = model, max_len, vocab
        self.filled, self.enc_len = filled, enc_len
        self.params = model.state.params

    def build(self, n):
        return self.model.executor.build_decode(n, self.max_len)

    def empty(self, init, n):
        if self.enc_len is None:
            return init(self.params, ())
        enc = np.arange(self.enc_len, dtype=np.int32) % self.vocab
        return init(self.params, [np.broadcast_to(enc, (n, self.enc_len))])


BUILDERS = {
    "mha": lambda: Graph(build_lm(), 16, 29, {"mha"}),
    "hybrid": lambda: Graph(hybrid(), 16, 97, {"mha", "recurrent"}),
    "primitive": lambda: Graph(primitive(), 8, 16, {"prefix", "static"}),
    "encoder_decoder": lambda: Graph(
        encoder_decoder(), 10, 40, {"mha", "mha_static", "static"},
        enc_len=7),
}


@functools.lru_cache(maxsize=None)
def built(kind):
    """Each graph compiled once for the file: no test changes a model."""
    argv, sys.argv = sys.argv, sys.argv[:1]  # compile() reads the flags
    try:
        return BUILDERS[kind]()
    finally:
        sys.argv = argv


@pytest.fixture(params=list(BUILDERS))
def graph(request):
    return built(request.param)


def _tokens(g, seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, g.vocab, shape).astype(np.int32))


def _rows(caches, keep):
    """Every per-slot leaf's rows `keep`, on the host."""
    return [np.asarray(leaf)[keep] for sec in decode.SLOT_SECTIONS
            for leaf in jax.tree_util.tree_leaves(caches[sec])]


def test_a_row_written_into_a_running_batch_steps_as_it_does_alone(graph):
    g, slot, others = graph, 1, [0, 2]
    initB, stepB = g.build(SLOTS)
    init1, step1 = g.build(1)
    _, batch = stepB(g.params, g.empty(initB, SLOTS), jnp.int32(0),
                     [_tokens(g, 0, SLOTS, 3)])
    _, row = step1(g.params, g.empty(init1, 1), jnp.int32(0),
                   [_tokens(g, 1, 1, 5)])
    assert {sec for sec in batch if batch[sec]} == g.filled
    before = _rows(batch, others)

    batch = decode.insert_row(batch, row, slot)
    for kept, was in zip(_rows(batch, others), before):
        np.testing.assert_array_equal(kept, was)
    for put, one in zip(_rows(batch, [slot]), _rows(row, [0])):
        np.testing.assert_array_equal(put, one)

    nxt = _tokens(g, 2, SLOTS, 1)
    t = jnp.asarray([3, 5, 3], jnp.int32)
    together, _ = stepB(g.params, batch, t, [nxt])
    alone, _ = step1(g.params, row, t[slot:slot + 1], [nxt[slot:slot + 1]])
    np.testing.assert_allclose(np.asarray(together)[slot],
                               np.asarray(alone)[0], rtol=1e-5, atol=1e-6)


def test_rows_taken_by_a_permutation_give_permuted_logits(graph):
    g, perm = graph, np.asarray([2, 0, 1], np.int32)
    init, step = g.build(SLOTS)
    _, caches = step(g.params, g.empty(init, SLOTS), jnp.int32(0),
                     [_tokens(g, 3, SLOTS, 4)])
    nxt = _tokens(g, 4, SLOTS, 1)
    straight, _ = step(g.params, caches, jnp.int32(4), [nxt])

    taken = decode.take_rows(caches, jnp.asarray(perm))
    for sec in decode.SHARED_SECTIONS:
        assert taken[sec] is caches[sec]
    if "static" in g.filled and g.enc_len is None:
        # the old comment's warning: a gather over this leaf's axis of 1
        # would have filled the rows beyond it with NaN
        assert [leaf.shape[0] for leaf in caches["static"].values()] == [1]
    for was, now in zip(_rows(caches, perm), _rows(taken, slice(None))):
        np.testing.assert_array_equal(now, was)
    permuted, _ = step(g.params, taken, jnp.int32(4), [nxt[perm]])
    np.testing.assert_allclose(np.asarray(permuted),
                               np.asarray(straight)[perm],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["mha", "hybrid", "primitive"])
def test_bytes_held_by_kind_are_what_the_sizing_says(kind):
    """What the tree holds is pages x kv_page_bytes + recurrent_slot_bytes
    a slot: the tree and the sizing read the same declarations."""
    g = built(kind)
    kv = KVCacheConfig(num_pages=64, page_size=4)
    init, _ = g.build(SLOTS)
    held = decode.state_bytes(g.empty(init, SLOTS))
    page = kv_page_bytes(g.model, kv.page_size)
    fixed = recurrent_slot_bytes(g.model)
    assert held["fixed"] == SLOTS * fixed
    assert (fixed > 0) == (kind == "hybrid")
    if kind == "primitive":
        # no op declares keys and values: they lie in the prefix section
        assert page is None and held["kv"] > 0
    else:
        assert held["kv"] == SLOTS * kv.pages_for(g.max_len) * page
        assert held["kv"] + held["fixed"] == SLOTS * slot_reservation_bytes(
            g.model, kv, g.max_len)


def test_a_prefix_leaf_without_a_slot_axis_is_refused():
    """A graph that folds batch with another axis has prefix leaves whose
    rows are no slots: it cannot be continuously batched, and says so."""
    g = built("primitive")
    initB, _ = g.build(SLOTS)
    init1, _ = g.build(1)
    batch, row = g.empty(initB, SLOTS), g.empty(init1, 1)
    guid = next(iter(row["prefix"]))
    folded = row["prefix"][guid]
    row["prefix"][guid] = jnp.concatenate([folded, folded])  # 2 "heads"
    with pytest.raises(ServingConfigError, match="no per-slot leading axis"):
        decode.insert_row(batch, row, 0)
