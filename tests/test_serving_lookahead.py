"""The batcher queues decode step n + 1 before it books step n.

Each decode iteration dispatches the next batched step first, its tokens
this step's ids as they lie on the device (or the host's token for a slot
admitted since), then waits for, fetches and books this step while the
device runs the next. The CPU suite cannot see the device time that saves.
It holds that the served tokens are those of one step at a time; that a
row computed for a slot gone by the time its ids arrive (EOS, a deadline
shed, an abort) is discarded and counted; that `decode_steps_ahead` counts
every step queued ahead; and that stopping, a failover and a teardown
sweep wait for the step in flight and read no donated buffer. That warm-up
builds every program the loop runs is `tests/test_serving_device_pick.py`'s.
"""
import time

import jax
import numpy as np
import pytest

from flexflow_tpu.runtime.resilience import FaultInjector
from flexflow_tpu.runtime.serving import (AdmissionQueue, ContinuousBatcher,
                                          DeadlineExceededError,
                                          GenerationRequest,
                                          ReplicaDeathError, ReplicaSet,
                                          RequestShedError,
                                          incremental_generate)
from tests.test_decode_donation import (_buffers, donating,  # noqa: F401
                                       hybrid, lm)
from tests.test_serving import VOCAB, _serve_cfg, build_lm

SLOTS = 3


@pytest.fixture(scope="module")
def moe():
    """Grouped-query attention and two expert banks: a step that counts."""
    import sys

    from tests.test_expert_bank import build_lm as build

    argv, sys.argv = sys.argv, sys.argv[:1]
    try:
        return build()
    finally:
        sys.argv = argv


@pytest.fixture
def iterations(monkeypatch):
    """Per `_decode_iteration` call: (whether a step was queued at its
    start, whether the loop was stopping)."""
    seen, iteration = [], ContinuousBatcher._decode_iteration

    def recorded(self):
        seen.append((self._queued is not None, self._stop.is_set()))
        return iteration(self)

    monkeypatch.setattr(ContinuousBatcher, "_decode_iteration", recorded)
    return seen


def _one_step_at_a_time(monkeypatch):
    """The synchronous order, for the comparison: nothing is queued ahead,
    so every iteration dispatches its step from the host's tokens."""
    rows = ContinuousBatcher._rows
    monkeypatch.setattr(ContinuousBatcher, "_rows",
                        lambda self, after: {} if after is not None
                        else rows(self, after))


def _serve(b, reqs, *, stop=True):
    b.start()
    try:
        for r in reqs:
            b.queue.offer(r)
        for r in reqs:
            r.wait(timeout=300.0)
        assert all(r.done() for r in reqs)
    finally:
        if stop:
            b.stop(timeout=60.0)
    return [(r.tokens.tolist(), None) if r.error is None
            else (None, type(r.error)) for r in reqs]


# -- (a) the tokens of one step at a time, discarded rows counted --------------
NEWS = [9, 3, 6, 2, 8, 5, 7, 4, 6, 8]


def _requests(vocab, eos_prompt):
    rng = np.random.RandomState(9)
    reqs = [GenerationRequest(
        rng.randint(0, vocab, int(rng.randint(1, 6))).astype(np.int32),
        n, deadline_s=120.0) for n in NEWS]
    reqs[0] = GenerationRequest(eos_prompt, NEWS[0], deadline_s=120.0)
    return reqs


def _eos_mid_decode(model, vocab):
    """A prompt and a token that its greedy answer first gives as its 4th
    to 6th output token: with it as EOS the request ends mid-decode."""
    rng = np.random.RandomState(2)
    for _ in range(50):
        prompt = rng.randint(0, vocab, 3).astype(np.int32)
        out = incremental_generate(model, prompt[None], max_new_tokens=9)
        gen = out[0, 3:].tolist()
        for k in range(3, 6):
            if gen[k] not in gen[:k]:
                return prompt, gen[k], k + 1
    raise AssertionError("no greedy answer ends on a token of its own")


def _fated(model, reqs, eos):
    """Two requests that reach a fourth token without EOS: the first to be
    shed, the second aborted, at the booking of their third."""
    out = []
    for i, r in enumerate(reqs[1:], 1):
        ref = incremental_generate(model, r.prompt[None],
                                   max_new_tokens=r.max_new_tokens)
        if r.max_new_tokens >= 5 and eos not in \
                ref[0, len(r.prompt):len(r.prompt) + 4].tolist():
            out.append(i)
    assert len(out) >= 2, "no two requests outlive their third token"
    return out[:2]


def _fates(monkeypatch, reqs, shed, abort):
    """Shed request `shed` (its deadline gone) and abort request `abort` at
    the booking that gives each its third token; count every retirement
    but by `max_new_tokens` of a slot the queued step holds a row for."""
    fate = {reqs[shed].id: "shed", reqs[abort].id: "abort"}
    retire, gone = ContinuousBatcher._maybe_retire, []

    def retired(self, i):
        slot = self.slots[i]
        if slot is None:
            return retire(self, i)
        generated = len(slot.tokens) - slot.prompt_len
        what = fate.get(slot.req.id) if generated == 3 else None
        if what == "shed":
            slot.req.deadline = time.monotonic() - 1.0
        elif what == "abort":
            slot.req._finish(error=RequestShedError(
                f"request {slot.req.id} aborted", reason="aborted"))
        queued = self._queued is not None and self._queued.rows.get(i) is slot
        retire(self, i)
        if queued and self.slots[i] is None \
                and generated < slot.req.max_new_tokens:
            gone.append(what or "eos")

    monkeypatch.setattr(ContinuousBatcher, "_maybe_retire", retired)
    return gone


@pytest.mark.parametrize("kind", ["attention", "hybrid"])
def test_the_loop_serves_the_tokens_of_one_step_at_a_time(
        kind, request, monkeypatch, iterations):
    """Ten requests through three slots (admissions into a running batch,
    asked for 2 to 9 tokens), one ending on EOS, one shed for its
    deadline and one aborted mid-decode: each gets what the synchronous
    order gives it, and the queued steps' rows of the three are discarded
    and counted."""
    m = request.getfixturevalue("lm" if kind == "attention" else "hybrid")
    vocab = VOCAB if kind == "attention" else 97
    prompt, eos, at = _eos_mid_decode(m, vocab)
    shed, abort = _fated(m, _requests(vocab, prompt), eos)
    runs = {}
    for order in ("ahead", "one at a time"):
        with monkeypatch.context() as mp:
            if order != "ahead":
                _one_step_at_a_time(mp)
            reqs = _requests(vocab, prompt)
            gone = _fates(mp, reqs, shed, abort)
            del iterations[:]
            b = ContinuousBatcher(m, _serve_cfg(slots=SLOTS,
                                                eos_token_id=eos),
                                  AdmissionQueue(max_depth=16))
            runs[order] = (_serve(b, reqs), dict(b.stats), list(gone),
                           list(iterations))
    (served, stats, gone, its), (plain, sync, sync_gone, sync_its) = \
        runs["ahead"], runs["one at a time"]
    assert served == plain
    assert served[0][0] is not None and served[0][0][-1] == eos
    assert len(served[0][0]) == len(prompt) + at
    assert served[shed] == (None, DeadlineExceededError)
    assert served[abort] == (None, RequestShedError)
    assert stats["shed_decode"] == sync["shed_decode"] == 1
    assert stats["retired_eos"] == sync["retired_eos"] >= 1
    # the three retirements came with a step queued that holds their rows
    assert {"eos", "shed", "abort"} <= set(gone)
    assert stats["decode_rows_discarded"] == len(gone)
    assert sync["decode_rows_discarded"] == 0 and sync_gone == []
    # every step but those dispatched from an empty queue went ahead
    assert len(its) == stats["iterations"]
    started_empty = sum(1 for queued, _ in its if not queued)
    assert stats["decode_steps_ahead"] == stats["iterations"] - started_empty
    assert stats["decode_steps_ahead"] >= stats["iterations"] // 2
    assert sync["decode_steps_ahead"] == 0
    assert len(sync_its) == sync["iterations"]
    assert all(not queued for queued, _ in sync_its)


def test_a_slot_that_ends_by_its_length_has_no_row_queued(lm, iterations):
    """A slot whose next id is its last (`max_new_tokens`) is left out of
    the step queued behind it: with no EOS, no shed and no abort, no row is
    discarded and nothing is queued once the batch is empty."""
    reqs = [GenerationRequest(np.arange(1, 1 + n, dtype=np.int32), new,
                              deadline_s=120.0)
            for n, new in ((3, 7), (2, 2), (5, 4), (1, 9), (4, 3))]
    b = ContinuousBatcher(lm, _serve_cfg(slots=SLOTS),
                          AdmissionQueue(max_depth=16))
    served = _serve(b, reqs)
    for r, (tokens, _) in zip(reqs, served):
        ref = incremental_generate(lm, r.prompt[None],
                                   max_new_tokens=r.max_new_tokens)
        assert tokens == ref[0].tolist()
    assert b.stats["decode_rows_discarded"] == 0
    assert b._queued is None and b.active_slots == 0
    assert b.stats["decode_steps_ahead"] > 0
    assert b.stats["decode_steps_ahead"] == b.stats["iterations"] - sum(
        1 for queued, _ in iterations if not queued)


# -- (b) draining ----------------------------------------------------------------
@pytest.fixture
def settled(monkeypatch):
    """Per `_settle` call: whether a step was queued, and whether one was
    after it."""
    seen, settle = [], ContinuousBatcher._settle

    def recorded(self):
        queued = self._queued is not None
        settle(self)
        seen.append((queued, self._queued is not None))

    monkeypatch.setattr(ContinuousBatcher, "_settle", recorded)
    return seen


def _after_a_step_queued_ahead(monkeypatch, at, then):
    """Call `then(batcher)` right after the first step queued ahead once
    `at` iterations are booked: the loop next finds a step in flight."""
    dispatch = ContinuousBatcher._dispatch

    def dispatched(self, rows, after):
        queued = dispatch(self, rows, after)
        if after is not None and self.stats["iterations"] >= at:
            then(self)
        return queued

    monkeypatch.setattr(ContinuousBatcher, "_dispatch", dispatched)


def test_stop_books_the_step_in_flight_and_its_counters(
        moe, donating, iterations, monkeypatch):
    """Stopped with a step queued, the batcher books that step as its last
    iteration (which queues nothing), keeps caches no program consumed,
    and its `stats` hold every dispatched step's counters once: each step
    routes all 4 rows in 2 layers."""
    from tests.test_expert_bank import K

    _after_a_step_queued_ahead(monkeypatch, 6, lambda b: b._stop.set())
    b = ContinuousBatcher(moe, _serve_cfg(slots=4, max_len=32),
                          AdmissionQueue(max_depth=8)).start()
    reqs = [GenerationRequest(np.arange(1, 1 + n, dtype=np.int32), 24,
                              deadline_s=120.0) for n in (5, 3, 7)]
    try:
        for r in reqs:
            b.queue.offer(r)
        b._thread.join(timeout=120.0)
    finally:
        b.stop(timeout=60.0)
    assert not b.thread_alive() and not b.dead and b.death_cause is None
    assert b._queued is None
    # the last iteration started stopping, with the queued step to book
    assert iterations[-1] == (True, True)
    leaves = jax.tree_util.tree_leaves(b._caches)
    assert leaves and not any(leaf.is_deleted() for leaf in leaves)
    jax.block_until_ready(b._caches)
    s = b.stats
    assert s["moe_assignments_held"] + s["moe_assignments_elsewhere"] \
        == s["iterations"] * 4 * K * 2
    assert s["decode_steps_ahead"] == s["iterations"] - sum(
        1 for queued, _ in iterations if not queued)
    # what each slot was served up to the stop begins its greedy answer
    live = b.in_flight()
    assert len(live) == 3
    for slot in live:
        ref = incremental_generate(moe, slot.req.prompt[None],
                                   max_new_tokens=24)
        assert slot.tokens == ref[0, :len(slot.tokens)].tolist()


def test_a_queued_steps_counters_are_a_copy_of_the_caches_leaves(
        moe, donating, monkeypatch):
    """The caches that hold a step's counters go, donated, into the step
    queued behind it (on the chip XLA may alias those scalars to the next
    step's): what the host fetches is the pick's copy, no buffer of the
    caches."""
    dispatch, seen = ContinuousBatcher._dispatch, []

    def dispatched(self, rows, after):
        queued = dispatch(self, rows, after)
        mine = _buffers(queued.counters)
        seen.append(bool(mine) and not set(mine) & set(_buffers(self._caches)))
        return queued

    monkeypatch.setattr(ContinuousBatcher, "_dispatch", dispatched)
    b = ContinuousBatcher(moe, _serve_cfg(slots=4, max_len=32),
                          AdmissionQueue(max_depth=8))
    served = _serve(b, [GenerationRequest(np.arange(1, 4, dtype=np.int32), 6,
                                          deadline_s=120.0)])
    assert served[0][1] is None
    assert len(seen) >= 5 and all(seen)


def test_a_replica_marked_dead_waits_for_its_step_and_requeues(
        lm, donating, settled, monkeypatch):
    """A lone batcher marked dead with a step queued (a watchdog's
    failover): the serve thread waits for that step, drops it unbooked,
    and hands its slots back to the queue; nothing reads a donated buffer
    and nothing stays queued."""
    _after_a_step_queued_ahead(monkeypatch, 3,
                               lambda b: setattr(b, "dead", True))
    q = AdmissionQueue(max_depth=8)
    b = ContinuousBatcher(lm, _serve_cfg(slots=2), q)
    reqs = [GenerationRequest(np.arange(1, 4, dtype=np.int32), 12,
                              deadline_s=120.0) for _ in range(2)]
    b.start()
    try:
        for r in reqs:
            q.offer(r)
        b._thread.join(timeout=120.0)
    finally:
        b.stop(timeout=10.0)
    assert not b._thread.is_alive() and b.death_cause is None
    assert settled == [(True, False)]
    assert b._queued is None and b.active_slots == 0
    assert b.stats["stranded_requeued"] == 2 and len(q) == 2
    assert not any(r.done() for r in reqs)
    jax.block_until_ready(b._caches)


def test_failover_with_a_step_in_flight_loses_no_request(tmp_path, settled):
    """A replica killed with a step queued ahead (the `replica_death` fault
    fires after the admissions, with a step in flight): it waits for that
    step, its slots are requeued, and the replicas serve every request
    greedy decoding's tokens."""
    fi = FaultInjector()
    fi.inject("replica_death", at_step=3, replica="replica0",
              exc=ReplicaDeathError("injected"))
    rs = ReplicaSet(build_lm, _serve_cfg(), replicas=2, ckpt_dir=str(tmp_path),
                    fault_injector=fi, health_timeout_s=60.0,
                    restart_backoff_s=0.05).start()
    rng = np.random.RandomState(6)
    try:
        reqs = [rs.submit(rng.randint(0, VOCAB, 3).astype(np.int32),
                          max_new_tokens=8, deadline_s=120.0)
                for _ in range(6)]
        outs = [r.result(timeout=180.0) for r in reqs]
        assert fi.fired["replica_death"] == 1
    finally:
        rs.stop()
    # the dying replica waited for the step it had queued
    assert (True, False) in settled
    ref_model = build_lm()
    for r, out in zip(reqs, outs):
        ref = incremental_generate(ref_model, r.prompt[None],
                                   max_new_tokens=8)
        np.testing.assert_array_equal(out, ref[0])
