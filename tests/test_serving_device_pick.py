"""The batcher picks each slot's next token on the device (ISSUE 34).

A decode iteration dispatches the batched step and, right behind it, one
jitted argmax over the step's (slots, 1, vocabulary) output; the host
blocks on and fetches `slots` ids and the step's counters. The CPU suite
cannot see the time that saves. It holds that the served tokens are greedy
decoding's, that the device's pick is numpy's (the first index of a
maximum), what an iteration brings to the host, and that the pick, and
every other program the loop runs, is built in `_warmup_compiles`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.runtime import serving
from flexflow_tpu.runtime.serving import (AdmissionQueue, ContinuousBatcher,
                                          GenerationRequest,
                                          incremental_generate)
from tests.test_decode_donation import (donating, hybrid,  # noqa: F401
                                       lm)
from tests.test_serving import VOCAB, _serve_cfg

SLOTS = 3


def _model(kind, request):
    m = request.getfixturevalue("lm" if kind == "attention" else "hybrid")
    return m, (VOCAB if kind == "attention" else 97)


def _requests(vocab, n, seed, new=(2, 9)):
    rng = np.random.RandomState(seed)
    return [GenerationRequest(
        rng.randint(0, vocab, int(rng.randint(1, 7))).astype(np.int32),
        int(rng.randint(*new)), deadline_s=120.0) for _ in range(n)]


def _serve(batcher, reqs):
    batcher.start()
    try:
        for r in reqs:
            batcher.queue.offer(r)
        return [r.result(timeout=300.0) for r in reqs]
    finally:
        batcher.stop()


@pytest.fixture
def inside(monkeypatch):
    """A list that is non-empty while a `_decode_iteration` runs."""
    flag, iteration = [], ContinuousBatcher._decode_iteration

    def marked(self):
        flag.append(1)
        try:
            return iteration(self)
        finally:
            flag.pop()

    monkeypatch.setattr(ContinuousBatcher, "_decode_iteration", marked)
    return flag


# -- (a) the same tokens ----------------------------------------------------------
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_served_sequences_are_greedy_decodings(kind, request):
    """Nine requests through three slots (admissions into a running batch,
    slots used again, an empty slot's row in most steps): each sequence is
    what `incremental_generate` picks from the logits on the host."""
    m, vocab = _model(kind, request)
    b = ContinuousBatcher(m, _serve_cfg(slots=SLOTS),
                          AdmissionQueue(max_depth=16))
    reqs = _requests(vocab, 9, seed=5)
    outs = _serve(b, reqs)
    assert b.stats["finished"] == 9 and b.pool.pages_in_use == 0
    for r, out in zip(reqs, outs):
        ref = incremental_generate(m, r.prompt[None],
                                   max_new_tokens=r.max_new_tokens)
        np.testing.assert_array_equal(out, ref[0])


# -- (b) the pick itself ----------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [1, 5])
def test_the_devices_pick_is_numpys_first_maximum(dtype, rows):
    """Ties, repeated maxima, values that differ only below bfloat16's
    precision (so that they tie once rounded), a row of one value (what an
    empty slot may hold) and a row of -inf: the lowest index wins."""
    import ml_dtypes

    np_dt = np.float32 if dtype == "float32" else ml_dtypes.bfloat16
    vocab = 257
    rng = np.random.RandomState(rows)
    x = rng.randn(rows, 1, vocab).astype(np.float32)
    x[0, 0, [200, 17, 93]] = 7.0  # a repeated maximum, not in order
    if rows > 1:
        x[1, 0] = 0.0  # an empty slot's row: every id ties
        x[2, 0] = -np.inf
        x[3, 0, 31], x[3, 0, 100] = 5.0, 5.0 + 1e-3  # one value in bfloat16
        x[4, 0, vocab - 1] = 9.0  # the last id
    x = x.astype(np_dt)
    ids = serving._best_ids(jnp.asarray(x))
    assert ids.shape == (rows,) and ids.dtype == jnp.int32
    want = np.argmax(x[:, 0], axis=-1)
    np.testing.assert_array_equal(np.asarray(ids), want)
    assert want[0] == 17
    if rows > 1:
        assert tuple(want[1:3]) == (0, 0) and want[4] == vocab - 1
        assert want[3] == (100 if dtype == "float32" else 31)


# -- (c), (d) what an iteration brings to the host --------------------------------
def test_an_iteration_fetches_ids_not_logits(lm, inside, monkeypatch,
                                             tmp_path):
    """Some tens of iterations: `decode_fetch_bytes` over `iterations` is
    4 bytes a slot and the step's counters; no array of slots x vocabulary
    elements passes `jax.device_get` inside `_decode_iteration`, which
    fetches once; the session's gauge reads what `stats` does."""
    import flexflow_tpu.obs as obs
    from flexflow_tpu.obs import TelemetryConfig

    fetched, get = [], jax.device_get

    def device_get(tree):
        if inside:
            fetched.append([int(np.size(leaf))
                            for leaf in jax.tree_util.tree_leaves(tree)])
        return get(tree)

    monkeypatch.setattr(jax, "device_get", device_get)
    b = ContinuousBatcher(lm, _serve_cfg(slots=SLOTS),
                          AdmissionQueue(max_depth=16))
    with obs.session(TelemetryConfig(dir=str(tmp_path / "tel"))):
        _serve(b, _requests(VOCAB, 8, seed=7, new=(8, 11)))
        gauge = obs.active().metrics.find("ff_serving_decode_fetch_bytes",
                                          replica=b.name)
    n = b.stats["iterations"]
    assert n >= 20
    assert 4 * SLOTS <= b.stats["decode_fetch_bytes"] / n <= 8 * SLOTS + 64
    assert gauge is not None and gauge.value == b.stats["decode_fetch_bytes"]
    assert len(fetched) == n  # one fetch an iteration
    assert max(max(sizes) for sizes in fetched) == SLOTS < SLOTS * VOCAB
    assert all(sum(sizes) <= SLOTS + 16 for sizes in fetched)


# -- (e) built with the step ------------------------------------------------------
@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_after_warmup_an_iteration_compiles_nothing(kind, request, donating):
    """From empty in-memory caches and no persistent one, `_warmup_compiles`
    builds the batched step, the feed of its tokens (from the host's and
    from the ids of the step before), the pick, every prefill bucket, the
    batch-1 cache and the insert, on caches made as the served ones are:
    serving then traces, compiles or loads no program on any thread."""
    import threading

    import jax.monitoring

    m, vocab = _model(kind, request)
    events, after = [], threading.Event()

    def on_event(name, *_args, **kw):
        if after.is_set() and name.startswith(("/jax/core/compile",
                                               "/jax/compilation_cache")):
            events.append((name, kw.get("fun_name")))

    jax.clear_caches()
    b = ContinuousBatcher(m, _serve_cfg(slots=SLOTS),
                          AdmissionQueue(max_depth=16))
    b._warmup_compiles()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    after.set()
    try:
        _serve(b, _requests(vocab, 6, seed=11))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert b.stats["iterations"] >= 5 and b.stats["decode_steps_ahead"] > 0
    assert events == []
