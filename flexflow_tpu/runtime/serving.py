"""Overload-robust inference serving over compiled models.

TPU-native counterpart to the reference's Triton prototype (triton/src/,
~8k LoC "incomplete prototype" serving ONNX models on Legion — SURVEY
§2.6), grown into a production front end whose adversary is the offered
load, not the strategy (the Orca OSDI'22 lesson: schedule at iteration
granularity, shed at admission, never hang):

  * **generation APIs** — greedy/beam/KV-cache decode over the compiled
    graph (`greedy_generate`, `incremental_generate`, ...);
  * **continuous batching** — `ContinuousBatcher` keeps a running decode
    batch whose slots each advance through their OWN sequence
    (per-slot positions, executor.build_decode), admitting new requests
    and retiring finished ones every iteration, with KV memory governed
    by the paged allocator (runtime/kvcache.py);
  * **admission control** — bounded queue with end-to-end deadlines
    (checked at enqueue, dequeue and every decode iteration), a token
    bucket whose refill adapts to the p95 of `ff_serving_latency_seconds`,
    and KV-page backpressure; every rejection is a typed
    `RequestShedError` subclass counted in `ff_serving_shed_total` —
    zero silent drops;
  * **replica failover** — `ReplicaSet` runs N batcher replicas off one
    shared queue, health-checked by the elastic runtime's
    `HealthMonitor` (runtime/elastic.py); a dead/hung replica's
    in-flight requests are requeued onto its siblings while it restarts
    (via `restore_elastic` resharding when a checkpoint dir is given),
    and replica count scales with queue depth;
  * **dynamic batching** — the original `BatchScheduler` (pads/packs
    single-shot forward requests to the compiled batch) stays for
    non-generative and encoder-decoder models.

  * **prefix sharing** — admission consults the page pool's
    content-addressed index (`reserve(..., tokens=prompt)`): published
    prompt pages are attached refcounted and discounted from the KV
    charge, prompts seen verbatim before skip their prefill compute
    entirely (a bounded host-side strip cache — exact because identical
    prompt + identical params reproduce the identical cache strip), and
    failover stranding/requeue transfers page ownership exactly once
    (typed `KVCacheAccountingError` on double release, never silent).

Chaos-testable on CPU: FaultInjector sites ``replica_death``,
``slow_worker``, ``kv_exhaustion``, ``serving_worker``,
``shared_page_corruption``, ``release_race`` and ``cow_fault``
(tests/test_serving.py, tests/test_kvshare.py, scripts/load_check.py).
"""
from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
import uuid
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.request_trace import (
    NULL_REQUEST_TRACE,
    SLOMonitor,
    mint_request_trace,
    record_request_stages,
)
from ..parallel import decode
from .kvcache import (KVCacheConfig, KVCacheExhaustedError, PagePool,
                      slot_reservation_bytes)
from .resilience import ResilienceError
from .verify import NotCompiledError, ServingConfigError

logger = logging.getLogger("flexflow_tpu.runtime.serving")


def greedy_generate(
    model,
    encoder_ids: np.ndarray,
    *,
    max_new_tokens: Optional[int] = None,
    start_token_id: int = 0,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
) -> np.ndarray:
    """Greedy autoregressive seq2seq decode over a compiled encoder-decoder
    FFModel (e.g. an imported MT5ForConditionalGeneration) whose two graph
    inputs are (encoder_ids, decoder_ids) and whose output is per-position
    vocab logits.

    The compiled graph is static-shape, so each step re-runs the SAME
    jitted forward with the decoder prefix grown by one token — the causal
    mask guarantees position t sees only tokens <= t, so the padded tail
    cannot leak. No KV cache: one full forward per token (O(L) calls of
    one cached executable). The reference has no generation API at all —
    its serving story is the Triton prototype's single forward — so this
    is a capability upgrade on the serving side.
    """
    if model.executor is None:
        raise NotCompiledError("compile() the model first")
    fwd = model.executor.build_forward()
    enc_t, dec_t = model._fit_input_tensors[:2]
    bs, dec_len = dec_t.dims[0], dec_t.dims[1]
    if tuple(encoder_ids.shape) != tuple(enc_t.dims):
        raise ServingConfigError(
            f"encoder_ids shape {tuple(encoder_ids.shape)} != compiled input "
            f"shape {tuple(enc_t.dims)}"
        )
    want = dec_len - 1 if max_new_tokens is None else max_new_tokens
    steps = min(want, dec_len - 1)
    enc = np.asarray(encoder_ids, enc_t.data_type.np_dtype)

    def next_logits(t, dec):
        return np.asarray(fwd(model.state.params, [enc, dec],
                              model.state.net_state))[:, t]

    return _greedy_decode_loop(
        bs, dec_len, steps, next_logits, dec_t.data_type.np_dtype,
        start_token_id=start_token_id, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id,
    )


def _greedy_decode_loop(bs, dec_len, steps, next_logits, dec_dt, *,
                        start_token_id, eos_token_id, pad_token_id):
    """The shared greedy seq2seq loop: greedy_generate (full forward per
    token) and incremental_seq2seq_generate (KV-cache step per token)
    differ ONLY in how position t's logits are produced — sharing the
    scaffold keeps their documented token-exact equivalence structural.
    next_logits(t, dec) -> (bs, vocab) values for position t given the
    decoder buffer so far."""
    dec = np.full((bs, dec_len), pad_token_id, dec_dt)
    dec[:, 0] = start_token_id
    if steps <= 0:
        return dec[:, :1]
    finished = np.zeros(bs, bool)
    for t in range(steps):
        nxt = next_logits(t, dec).argmax(-1)
        if eos_token_id is not None:
            nxt = np.where(finished, pad_token_id, nxt)
            finished |= nxt == eos_token_id
        dec[:, t + 1] = nxt
        if eos_token_id is not None and finished.all():
            break
    return dec[:, : t + 2]


def incremental_seq2seq_generate(
    model,
    encoder_ids: np.ndarray,
    *,
    max_new_tokens: Optional[int] = None,
    start_token_id: int = 0,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    assume_causal: bool = False,
) -> np.ndarray:
    """KV-cache greedy decode for a compiled encoder-decoder FFModel —
    same signature and token-exact output as greedy_generate, but
    O(1)/token: the encoder runs ONCE (executor.build_decode computes the
    static subgraph and the cross-attention K/V at init), each step feeds
    one decoder position through the liveness-analyzed decoder subgraph
    (parallel/decode.py). Works on imported HF graphs (mt5) where
    attention is primitive batch_matmul/softmax ops."""
    if model.executor is None:
        raise NotCompiledError("compile() the model first")
    if len(model._fit_input_tensors) < 2:
        raise ServingConfigError(
            "incremental_seq2seq_generate needs an encoder-decoder model "
            "(two graph inputs); use incremental_generate for decoder-only"
        )
    ex = model.executor
    enc_t, dec_t = model._fit_input_tensors[:2]
    bs, dec_len = dec_t.dims[0], dec_t.dims[1]
    if tuple(encoder_ids.shape) != tuple(enc_t.dims):
        raise ServingConfigError(
            f"encoder_ids shape {tuple(encoder_ids.shape)} != compiled input "
            f"shape {tuple(enc_t.dims)}"
        )
    want = dec_len - 1 if max_new_tokens is None else max_new_tokens
    steps = min(want, dec_len - 1)
    if steps <= 0:
        out = np.full((bs, 1), start_token_id, dec_t.data_type.np_dtype)
        return out
    init_caches, step = ex.build_decode(bs, dec_len,
                                        assume_causal=assume_causal)
    caches = init_caches(
        model.state.params,
        [np.asarray(encoder_ids, enc_t.data_type.np_dtype)],
    )

    def next_logits(t, dec):
        nonlocal caches
        logits, caches = step(
            model.state.params, caches, jnp.int32(t),
            [jnp.asarray(dec[:, t : t + 1])],
        )
        return np.asarray(logits)[:, -1]

    return _greedy_decode_loop(
        bs, dec_len, steps, next_logits, dec_t.data_type.np_dtype,
        start_token_id=start_token_id, eos_token_id=eos_token_id,
        pad_token_id=pad_token_id,
    )


def incremental_generate(
    model,
    prompt_ids: np.ndarray,
    *,
    max_new_tokens: int,
    max_len: Optional[int] = None,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    static_inputs=(),
    decode_input: Optional[int] = None,
    assume_causal: bool = False,
) -> np.ndarray:
    """KV-cache autoregressive decoding for a causal decoder-only FFModel
    (token ids in, per-position vocab logits out): each step feeds ONE
    position through executor.build_decode, appending that position's K/V
    to per-layer caches — one O(max_len)-wide attention row per token
    instead of greedy_generate's full O(L²) forward per token. Capability the reference
    lacks entirely (its Triton prototype serves single forwards).

    prompt_ids: (batch, prompt_len) int array. Returns (batch, total_len)
    including the prompt.

    static_inputs: arrays for any non-decode graph inputs (e.g. an
    explicit attention-mask input), passed through to init_caches;
    decode_input selects which graph input the prompt drives (default:
    build_decode's convention, the last); assume_causal vouches for
    primitive-op attention whose causality can't be proven from baked
    constants (parallel/decode.py)."""
    if model.executor is None:
        raise NotCompiledError("compile() the model first")
    prompt_ids = np.asarray(prompt_ids)
    bs, plen = prompt_ids.shape
    if max_new_tokens <= 0:
        return prompt_ids.copy()
    total = plen + max_new_tokens
    cap = max_len or total
    if cap < total:
        raise ServingConfigError(f"max_len {cap} < prompt+new {total}")
    init_caches, step = model.executor.build_decode(
        bs, cap, decode_input=decode_input, assume_causal=assume_causal
    )
    caches = init_caches(model.state.params, list(static_inputs))
    dec_idx = (decode_input if decode_input is not None
               else len(model._fit_input_tensors) - 1)
    in_t = model._fit_input_tensors[dec_idx]
    id_dt = in_t.data_type.np_dtype

    out = np.full((bs, total), pad_token_id, id_dt)
    out[:, :plen] = prompt_ids
    finished = np.zeros(bs, bool)
    # one-shot prefill: the whole prompt goes through a single step (the
    # decode kernels handle any block width with intra-block causal
    # masking), populating every prompt position's K/V at once
    logits, caches = step(
        model.state.params, caches, jnp.int32(0),
        [jnp.asarray(prompt_ids.astype(id_dt))],
    )
    nxt = np.asarray(logits)[:, -1].argmax(-1)
    if eos_token_id is not None:
        finished |= nxt == eos_token_id
    out[:, plen] = nxt
    for t in range(plen, total - 1):
        if eos_token_id is not None and finished.all():
            break  # out is already pad-filled to the documented full width
        tok = out[:, t : t + 1].astype(id_dt)
        logits, caches = step(
            model.state.params, caches, jnp.int32(t), [jnp.asarray(tok)]
        )
        nxt = np.asarray(logits)[:, 0].argmax(-1)
        if eos_token_id is not None:
            nxt = np.where(finished, pad_token_id, nxt)
            finished |= nxt == eos_token_id
        out[:, t + 1] = nxt
    return out


def incremental_beam_generate(
    model,
    prompt_ids: np.ndarray,
    *,
    num_beams: int = 4,
    max_new_tokens: int,
    max_len: Optional[int] = None,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    encoder_ids: Optional[np.ndarray] = None,
    static_inputs=(),
    assume_causal: bool = False,
) -> np.ndarray:
    """Beam search over the KV-cache decoder: the decode step is built at
    batch=num_beams (build_decode jits for any batch, so no
    compiled-batch packing), each step feeds ONE position per beam, and on
    a beam reorder the per-layer caches are gathered along the batch axis
    on-device. Scores are sums of log-probs (probability and logit output
    heads both handled — _as_log_probs), no length penalty; samples decode
    sequentially.

    prompt_ids: (n, prompt_len). Returns (n, prompt_len + max_new_tokens)
    top beams. For encoder-decoder models pass encoder_ids (n, enc_len)
    and a prompt of start tokens — each sample's encoder statics and
    cross-attention K/V are computed once at its init."""
    import jax

    if model.executor is None:
        raise NotCompiledError("compile() the model first")
    prompt_ids = np.asarray(prompt_ids)
    plen = prompt_ids.shape[1]
    if max_new_tokens <= 0:
        return prompt_ids.copy()
    in_t = model._fit_input_tensors[-1]
    total = plen + max_new_tokens
    cap = max_len or total
    if cap < total:
        raise ServingConfigError(f"max_len {cap} < prompt+new {total}")
    init_caches, step = model.executor.build_decode(
        num_beams, cap, assume_causal=assume_causal
    )
    id_dt = in_t.data_type.np_dtype
    prob_hint = model.output_probability_like()
    if encoder_ids is not None:
        enc_t = model._fit_input_tensors[0]
        enc_rows = np.asarray(encoder_ids, enc_t.data_type.np_dtype)
        if enc_rows.shape[0] != prompt_ids.shape[0]:
            raise ServingConfigError(
                f"encoder_ids rows {enc_rows.shape[0]} != prompt rows "
                f"{prompt_ids.shape[0]}"
            )

    outs = []
    for i, row in enumerate(prompt_ids.astype(id_dt)):
        if encoder_ids is None:
            # static_inputs (if any) must be shaped for batch=num_beams
            caches = init_caches(model.state.params, list(static_inputs))
        else:
            enc_block = np.broadcast_to(
                enc_rows[i], (num_beams,) + enc_rows[i].shape
            ).copy()
            # static_inputs are the non-decode inputs AFTER the encoder
            # ids (input order), shaped for batch=num_beams
            caches = init_caches(model.state.params,
                                 [enc_block] + list(static_inputs))
        beams = np.full((num_beams, total), pad_token_id, id_dt)
        beams[:, :plen] = row
        scores = np.full(num_beams, -np.inf)
        scores[0] = 0.0  # beams identical until the first branch
        done = np.zeros(num_beams, bool)
        # prefill: same prompt in every beam slot, one block step
        block = np.broadcast_to(row, (num_beams, plen)).copy()
        logits, caches = step(model.state.params, caches, jnp.int32(0),
                              [jnp.asarray(block)])
        logp = _as_log_probs(np.asarray(logits)[:, -1], prob_hint)
        for t in range(plen, total):
            src_beams, toks, scores = _beam_topk(
                scores, logp, done, pad_token_id, num_beams
            )
            beams = beams[src_beams]
            beams[:, t] = np.where(done[src_beams], pad_token_id, toks)
            if eos_token_id is not None:
                done = done[src_beams] | (beams[:, t] == eos_token_id)
            # per-beam caches follow their beams (identity gathers are
            # common early on; the shuffle stays on the device)
            caches = decode.take_rows(
                caches, jnp.asarray(src_beams.astype(np.int32)))
            if (eos_token_id is not None and done.all()) or t == total - 1:
                break
            logits, caches = step(
                model.state.params, caches, jnp.int32(t),
                [jnp.asarray(beams[:, t : t + 1])],
            )
            logp = _as_log_probs(np.asarray(logits)[:, 0], prob_hint)
        outs.append(beams[0])
    return np.stack(outs)


def _log_softmax(x: np.ndarray) -> np.ndarray:
    m = x.max(axis=-1, keepdims=True)
    e = np.exp(x - m)
    return (x - m) - np.log(e.sum(axis=-1, keepdims=True))


def _as_log_probs(x: np.ndarray,
                  probability: Optional[bool] = None) -> np.ndarray:
    """Model outputs may be PROBABILITIES (the framework convention: CE
    models end in softmax/sigmoid) or raw logits (imported heads).
    log-softmax of probabilities is NOT log(p) — it flattens every gap to
    <1 nat and corrupts beam accumulation. The caller passes the answer
    from the graph's tail op (model.output_probability_like()); the
    numeric sniff (non-negative rows summing to ~1) is only the fallback
    for the undetermined case — bf16 softmax heads over large vocabs can
    drift past its tolerance, so the structural answer wins."""
    if probability is None:
        probability = bool(
            (x >= 0).all() and np.allclose(x.sum(axis=-1), 1.0, atol=1e-3)
        )
    if probability:
        return np.log(np.clip(x, 1e-30, None))
    return _log_softmax(x)


def _beam_topk(scores, logp, done, pad_token_id, num_beams):
    """One beam-search selection step, shared by beam_generate and
    incremental_beam_generate: finished beams propagate unchanged via a
    single pad candidate; top-k via argpartition (O(n), no full sort)."""
    vocab = logp.shape[-1]
    cand = scores[:, None] + np.where(done[:, None], -np.inf, logp)
    for b in np.nonzero(done)[0]:
        cand[b, pad_token_id] = scores[b]
    flat = np.argpartition(cand.ravel(), -num_beams)[-num_beams:]
    flat = flat[np.argsort(cand.ravel()[flat])[::-1]]
    return flat // vocab, flat % vocab, cand.ravel()[flat]


def beam_generate(
    model,
    encoder_ids: np.ndarray,
    *,
    num_beams: int = 4,
    max_new_tokens: Optional[int] = None,
    start_token_id: int = 0,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
) -> np.ndarray:
    """Beam-search decode over the same compiled forward as greedy_generate
    (scores are sum of per-token log-probs; no length penalty). Each step
    runs the beams of ONE sample as a batch-shaped forward, so the
    compiled batch size must be >= num_beams; samples decode sequentially.
    num_beams=1 degenerates to greedy."""
    if model.executor is None:
        raise NotCompiledError("compile() the model first")
    fwd = model.executor.build_forward()
    enc_t, dec_t = model._fit_input_tensors[:2]
    bs, dec_len = dec_t.dims[0], dec_t.dims[1]
    if num_beams > bs:
        raise ServingConfigError(
            f"num_beams {num_beams} > compiled batch {bs}; recompile with a "
            "larger batch"
        )
    if tuple(encoder_ids.shape[1:]) != tuple(enc_t.dims[1:]):
        raise ServingConfigError(
            f"encoder_ids row shape {tuple(encoder_ids.shape[1:])} != "
            f"compiled {tuple(enc_t.dims[1:])}"
        )
    want = dec_len - 1 if max_new_tokens is None else max_new_tokens
    steps = min(want, dec_len - 1)
    n_rows = encoder_ids.shape[0]
    if steps <= 0:
        return np.full((n_rows, 1), start_token_id, dec_t.data_type.np_dtype)
    prob_hint = model.output_probability_like()

    outs = []
    for row in np.asarray(encoder_ids, enc_t.data_type.np_dtype):
        # beams packed into the compiled batch; unused slots repeat beam 0
        enc = np.broadcast_to(row, (bs,) + row.shape).copy()
        beams = np.full((num_beams, dec_len), pad_token_id,
                        dec_t.data_type.np_dtype)
        beams[:, 0] = start_token_id
        scores = np.full(num_beams, -np.inf)
        scores[0] = 0.0  # all beams identical at t=0: keep one alive
        done = np.zeros(num_beams, bool)
        for t in range(steps):
            dec = np.full((bs, dec_len), pad_token_id, beams.dtype)
            dec[:num_beams] = beams
            logp = _as_log_probs(
                np.asarray(fwd(model.state.params, [enc, dec],
                               model.state.net_state))[:num_beams, t],
                prob_hint,
            )
            src, tok, scores = _beam_topk(scores, logp, done, pad_token_id,
                                          num_beams)
            beams = beams[src]
            beams[:, t + 1] = tok
            done = done[src]
            if eos_token_id is not None:
                done = done | (tok == eos_token_id)
                if done.all():
                    break
        # fixed width for every sample (early-stopped rows carry pad after
        # EOS) so the batch stacks even when samples finish at different t
        outs.append(beams[int(np.argmax(scores)), : steps + 1])
    return np.stack(outs, axis=0)


# ----------------------------------------------------------------------
# typed admission failures — every non-admitted request gets one of these
# (and a ff_serving_shed_total increment); silence is a bug
# ----------------------------------------------------------------------
class RequestShedError(ResilienceError):
    """The serving runtime refused (or abandoned) a request on purpose —
    load shedding, not a fault. NOT a TimeoutError subclass: the default
    RetryPolicy must not hammer an overloaded service with retries."""

    reason = "shed"

    def __init__(self, msg: str, *, reason: Optional[str] = None):
        super().__init__(msg)
        if reason is not None:
            self.reason = reason


class DeadlineExceededError(RequestShedError):
    """The request's deadline passed (or provably cannot be met) before
    a result was produced — whether it was still queued, being admitted,
    or mid-decode. `stage` says where along the pipeline it died."""

    reason = "deadline"

    def __init__(self, msg: str, *, stage: str = "queue"):
        super().__init__(msg)
        self.stage = stage


class QueueFullError(RequestShedError):
    """The bounded admission queue is at capacity — the canonical
    overload signal. Clients should back off; the server stays live."""

    reason = "queue_full"


class RateLimitedError(RequestShedError):
    """The token-bucket rate limiter is empty: offered load exceeds the
    (possibly p95-adapted) sustainable rate."""

    reason = "rate_limited"


class ReplicaDeathError(ResilienceError):
    """A serving replica crashed (or the ``replica_death`` fault site
    simulated it). Raised inside the replica's serve loop; the
    ReplicaSet requeues its in-flight work and restarts it."""


def _shed(reason: str, n: float = 1.0) -> None:
    from .. import obs

    obs.count("ff_serving_shed_total", n,
              help="requests shed by admission control/deadlines",
              reason=reason)


# ----------------------------------------------------------------------
# serving configuration
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ServingConfig:
    """Knobs for the continuous-batching runtime (docs/serving.md).

    `max_len` caps prompt+generated tokens per sequence (the decode
    cache width); `slots` is the in-flight sequence count per replica
    (the decode batch). KV paging defaults to exactly covering
    `slots` full-length sequences — set `num_pages` smaller to exercise
    admission backpressure, larger for headroom. `rate_limit` (req/s)
    enables the token bucket; with `adaptive_rate` its refill follows
    the p95 of `ff_serving_latency_seconds` via AIMD toward
    `target_p95_s`."""

    max_len: int
    slots: int = 4
    page_size: int = 16
    num_pages: Optional[int] = None
    watermark: float = 0.0
    # content-addressed prefix sharing (docs/serving.md "Prefix
    # sharing"): admission attaches already-published prompt pages
    # refcounted (discounting them from the KV charge) and publishes
    # this prompt's full blocks for later arrivals. Exactness is
    # unconditional — shared pages are immutable by construction
    # (copy-on-write in the pool is the enforced safety valve).
    share_prefixes: bool = True
    # prompts memoized for exact prefill-FLOP skipping (LRU entries of
    # (bucket, prompt) -> prefilled cache strip); 0 disables the skip
    # while keeping page-level dedup, and so does a device that cannot
    # hold this many strips beside the batch (_memo_entries)
    prefix_cache_entries: int = 8
    max_queue_depth: int = 64
    default_deadline_s: float = 30.0
    default_max_new_tokens: int = 16
    rate_limit: Optional[float] = None
    rate_burst: int = 8
    adaptive_rate: bool = False
    target_p95_s: float = 1.0
    # SLO targets (obs/request_trace.SLOMonitor): completed requests are
    # judged against these; violations count in ff_slo_violations_total
    # and a sustained violation fraction scales the ReplicaSet up. None
    # disables the corresponding check.
    slo_ttft_s: Optional[float] = None
    slo_p99_s: Optional[float] = None
    eos_token_id: Optional[int] = None
    assume_causal: bool = False
    # disaggregated prefill/decode: when set (and the model has no
    # decode-searched strategy yet), compile_decode() imports this
    # strategy file so the batched decode step lowers from the
    # decode-objective strategy while prefill keeps the train-searched
    # (compute-bound) one. Ignored when model.decode_executor exists.
    decode_strategy_path: Optional[str] = None
    # online decode re-search (the StrategyTuner's serving leg,
    # docs/adaptation.md): when the admitted prompt-length distribution
    # drifts more than decode_retune_threshold (relative to the
    # distribution observed around the last decode build) across at
    # least decode_retune_min_admissions requests, the batcher re-runs
    # compile_decode() between batches (active_slots == 0 only — the
    # running batch's caches belong to the old lowering) and hot-swaps
    # the batched decode step. The existing _decode_executor_mismatch
    # probe vets the candidate; any incompatibility falls back to the
    # current decode step (the rollback path), and either way the
    # attempt lands in ff_strategy_swaps_total{leg="serving"}.
    decode_retune: bool = False
    decode_retune_threshold: float = 0.5
    decode_retune_min_admissions: int = 8
    decode_retune_cooldown_iters: int = 50
    idle_wait_s: float = 0.005
    # compile every decode executable (all prefill buckets + the batched
    # step) when the replica boots, BEFORE it takes traffic: a mid-run
    # jit compile stalls the whole running batch (and on a shared-core
    # CPU harness can starve sibling replicas into watchdog failovers)
    precompile: bool = True

    def __post_init__(self):
        if self.max_len <= 1:
            raise ServingConfigError(f"max_len must be > 1: {self.max_len}")
        if self.slots <= 0:
            raise ServingConfigError(f"slots must be positive: {self.slots}")
        if self.max_queue_depth <= 0:
            raise ServingConfigError(
                f"max_queue_depth must be positive: {self.max_queue_depth}"
            )

    def kv_config(self) -> KVCacheConfig:
        cfg = KVCacheConfig(num_pages=1, page_size=self.page_size)
        pages = self.num_pages
        if pages is None:
            pages = self.slots * cfg.pages_for(self.max_len)
        return KVCacheConfig(num_pages=pages, page_size=self.page_size,
                             watermark=self.watermark)


class GenerationRequest:
    """One decode request: prompt ids in, prompt+generated ids out.

    Completion is exactly-once and owner-checked: a failover requeue
    bumps `generation`, so a stalled replica that later wakes up cannot
    publish a result for work that was handed to a sibling. Callers
    block on `result()`, which raises the request's TYPED error (shed /
    deadline / abort) instead of returning garbage or hanging."""

    def __init__(self, prompt: np.ndarray, max_new_tokens: int, *,
                 deadline_s: float = 30.0):
        self.id = uuid.uuid4().hex[:12]
        self.prompt = np.asarray(prompt)
        if self.prompt.ndim != 1:
            raise ServingConfigError(
                f"prompt must be a 1-D token array, got shape "
                f"{self.prompt.shape}"
            )
        self.max_new_tokens = int(max_new_tokens)
        self.submitted_t = time.monotonic()
        self.deadline = self.submitted_t + float(deadline_s)
        self.admitted_t: Optional[float] = None  # last slot admission
        self.first_token_t: Optional[float] = None
        self.finished_t: Optional[float] = None
        # one time.monotonic() per generated token, stamped where the
        # token is appended to its slot (the first IS first_token_t): the
        # gaps are what a streaming user sees, an admission's stall of
        # every slot included. Restarts with a failover re-admission.
        self.token_t: List[float] = []
        self.generation = 0  # bumped on failover requeue
        # flight recorder (obs/request_trace.py): ReplicaSet.submit /
        # AdmissionQueue.offer mint a sampled context; the shared null
        # object keeps the unsampled path allocation-free
        self.trace = NULL_REQUEST_TRACE
        self._event = threading.Event()
        self._lock = threading.Lock()
        self.tokens: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None

    # -- completion (exactly once, owner-checked) ------------------------
    def _finish(self, *, tokens: Optional[np.ndarray] = None,
                error: Optional[BaseException] = None,
                generation: Optional[int] = None) -> bool:
        with self._lock:
            if self._event.is_set():
                return False
            if generation is not None and generation != self.generation:
                return False  # requeued to another replica meanwhile
            self.tokens = tokens
            self.error = error
            self.finished_t = time.monotonic()
            self._event.set()
            return True

    def _requeue_bump(self) -> Optional[int]:
        """Take ownership away from a dead replica; returns the new
        generation, or None when the request already finished."""
        with self._lock:
            if self._event.is_set():
                return None
            self.generation += 1
            return self.generation

    # -- client API ------------------------------------------------------
    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        from .resilience import InferenceTimeout

        if not self._event.wait(timeout):
            raise InferenceTimeout(
                f"request {self.id} unanswered after {timeout}s"
            )
        if self.error is not None:
            raise self.error
        return self.tokens


class TokenBucket:
    """Classic token bucket with an AIMD-adaptable refill rate: the
    additive-increase/multiplicative-decrease loop (`adapt`) follows the
    serving p95 toward a latency target, so sustained overload tightens
    admission instead of growing the queue without bound."""

    def __init__(self, rate: float, burst: int, *,
                 clock: Callable[[], float] = time.monotonic):
        self.rate = float(rate)
        self.burst = max(1, int(burst))
        self._clock = clock
        self._tokens = float(self.burst)
        self._last = clock()
        self._lock = threading.Lock()
        self.min_rate = max(0.1, self.rate / 64.0)
        self.max_rate = self.rate * 16.0

    def try_acquire(self, n: float = 1.0) -> bool:
        with self._lock:
            now = self._clock()
            self._tokens = min(self.burst,
                               self._tokens + (now - self._last) * self.rate)
            self._last = now
            if self._tokens >= n:
                self._tokens -= n
                return True
            return False

    def adapt(self, p95_s: float, target_p95_s: float) -> float:
        """One AIMD step: p95 over target multiplicatively cuts the
        refill; under target additively grows it back. Returns the new
        rate (also exported as ff_serving_admission_rate)."""
        from .. import obs

        with self._lock:
            if p95_s == p95_s:  # NaN = no samples yet: leave the rate be
                if p95_s > target_p95_s:
                    self.rate = max(self.min_rate, self.rate * 0.7)
                else:
                    self.rate = min(self.max_rate, self.rate + 1.0)
            rate = self.rate
        obs.gauge_set("ff_serving_admission_rate", rate,
                      help="token-bucket refill rate (requests/s)")
        return rate


class AdmissionQueue:
    """Bounded FIFO shared by every replica's batcher. `offer` sheds at
    enqueue (queue full / dead-on-arrival deadline); `poll` sheds
    expired requests at dequeue so a blown deadline is never executed
    on-device; `requeue` (failover) pushes to the FRONT and is exempt
    from the bound — admitted work is never dropped by its own rescue."""

    def __init__(self, max_depth: int):
        self.max_depth = max_depth
        self._q: deque = deque()
        self._lock = threading.Lock()
        self._nonempty = threading.Condition(self._lock)
        self._closed: Optional[Callable] = None  # error factory, see close

    def __len__(self) -> int:
        with self._lock:
            return len(self._q)

    def _export_depth(self) -> None:
        from .. import obs

        obs.gauge_set("ff_serving_queue_depth", len(self),
                      help="requests waiting for a decode slot")

    def offer(self, req: GenerationRequest) -> None:
        if req.trace is NULL_REQUEST_TRACE:
            # direct-queue callers (no ReplicaSet) still get a flight
            # recorder; the mint is deterministic per id, so a request
            # already judged unsampled stays unsampled
            req.trace = mint_request_trace(req.id)
        now = time.monotonic()
        if now >= req.deadline:
            err = DeadlineExceededError(
                f"request {req.id} dead on arrival "
                f"({now - req.deadline:.3f}s past deadline)", stage="enqueue",
            )
            _shed("deadline")
            req.trace.shed("deadline", stage="enqueue")
            req._finish(error=err)
            raise err
        with self._lock:
            if self._closed is not None:
                err = self._closed(req)
                _shed("aborted")
                req.trace.shed("aborted", stage="enqueue")
                req._finish(error=err)
                raise err
            if len(self._q) >= self.max_depth:
                full = QueueFullError(
                    f"admission queue at capacity ({self.max_depth})"
                )
                _shed("queue_full")
                req.trace.shed("queue_full", stage="enqueue")
                req._finish(error=full)
                raise full
            req.trace.queue_begin(depth=len(self._q))
            self._q.append(req)
            self._nonempty.notify()
        self._export_depth()

    def requeue(self, req: GenerationRequest) -> None:
        with self._lock:
            self._q.appendleft(req)
            self._nonempty.notify()
        self._export_depth()

    def poll(self, timeout: float = 0.0) -> Optional[GenerationRequest]:
        """Next live request, shedding expired ones at dequeue (typed
        error + counter — the satellite-fix semantics: a request that
        blew its deadline while queued must not reach the device)."""
        deadline = time.monotonic() + timeout
        with self._lock:
            while True:
                while self._q:
                    req = self._q.popleft()
                    if req.done():
                        continue  # aborted/shed elsewhere
                    now = time.monotonic()
                    if now >= req.deadline:
                        _shed("deadline")
                        req.trace.shed("deadline", stage="dequeue")
                        req._finish(error=DeadlineExceededError(
                            f"request {req.id} expired in queue "
                            f"({now - req.deadline:.3f}s past deadline)",
                            stage="dequeue",
                        ))
                        continue
                    self._export_depth_locked()
                    return req
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._nonempty.wait(remaining)

    def _export_depth_locked(self) -> None:
        from .. import obs

        obs.gauge_set("ff_serving_queue_depth", len(self._q),
                      help="requests waiting for a decode slot")

    def drain(self, error_factory) -> int:
        """Fail every queued request with a typed error (shutdown path —
        zero silent drops). Returns the number drained."""
        with self._lock:
            pending = list(self._q)
            self._q.clear()
        n = 0
        for req in pending:
            if req._finish(error=error_factory(req)):
                _shed("aborted")
                n += 1
        self._export_depth()
        return n

    def close(self, error_factory) -> int:
        """The last consumer is gone (a lone batcher's serve thread
        died): fail what is queued AND whatever is offered from now on
        with `error_factory(req)`, so a caller blocked in `result()`
        gets the cause at once instead of waiting out its deadline.
        Returns the number drained."""
        with self._lock:
            self._closed = error_factory
        return self.drain(error_factory)


# ----------------------------------------------------------------------
# continuous (in-flight) batching
# ----------------------------------------------------------------------
def _device_free_bytes() -> Optional[int]:
    """Bytes the first device has free now, or None where its backend
    reports no memory (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats or "bytes_in_use" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats["bytes_in_use"])


def _memo_entries(free: Optional[int], strip: int, entries: int) -> int:
    """How many prefilled strips of `strip` bytes the batcher's memo
    keeps where the device has `free` bytes beside its weights, the batch's
    caches and one admission's batch-1 cache: all `entries`, or none where
    that many do not fit. A memo too large for the device is off, not cut
    down: its strips would take the room a prefill or a decode step needs,
    and `prefix_cache_entries` = 0 means the same. No bound but `entries`
    where the backend reports no memory (`free` None)."""
    if free is None or entries * strip <= free:
        return entries
    return 0


@jax.jit
def _best_ids(logits):
    """The best id of every row of a step's one-position output
    (rows, 1, vocab), picked on the device: the host fetches `rows` ids,
    not `rows x vocab` logits. Greedy, and numpy's answer: the lowest
    index wins a tie."""
    return jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)


@jax.jit
def _pick(logits, counters):
    """A batched decode step's pick (`_best_ids`) and a copy of what the
    step counted: the caches that hold the step's own counters go, donated,
    into the step queued behind it, so the host fetches them from here."""
    return _best_ids(logits), jax.tree_util.tree_map(jnp.copy, counters)


@jax.jit
def _feed(ids, host):
    """A batched decode step's tokens, (rows, 1): each row's `host` token,
    or where that is -1 the row's id in `ids`, the pick of the step before,
    which never leaves the device."""
    return jnp.where(host >= 0, host, ids.astype(host.dtype))[:, None]


@dataclasses.dataclass
class _Slot:
    req: GenerationRequest
    generation: int
    seq_key: str
    tokens: List[int]
    prompt_len: int
    pos: int  # cache positions written == len(tokens) - 1


@dataclasses.dataclass
class _Queued:
    """A batched decode step on the device whose ids the host has not
    booked yet."""
    rows: Dict[int, _Slot]  # row -> the occupant it was computed for
    ids: jax.Array  # (slots,) int32: the pick
    counters: dict  # what the step counted, copied by the pick


class ContinuousBatcher:
    """Iteration-level decode scheduler for ONE replica (Orca-style): a
    running batch of `config.slots` sequences, each at its own position
    (the per-slot `t` vector of executor.build_decode). Every iteration:

      1. retire finished slots (EOS / max_new_tokens / blown deadline)
         and release their KV pages;
      2. admit queued requests into free slots — deadline re-checked at
         dequeue, KV pages reserved worst-case (backpressure when the
         pool can't cover it; typed shed when it never could), prompt
         prefilled through a batch-1 decode step bucketed to powers of
         two (bounds recompilation), and the prefilled cache strip
         inserted into the running batch;
      3. queue the NEXT batched decode step for every slot that goes on,
         fed this step's ids on the device, then book this step: its one
         sync fetches `slots` ids (and the step's counters), never the
         slots x vocabulary logits, while the next step runs. Each step's
         best ids are picked behind it on the device.

    Decoder-only models only (one graph input): encoder-decoder graphs
    compute per-request encoder statics that a shared running batch
    cannot represent — those go through BatchScheduler.

    Faults: ``replica_death`` raises out of the loop (the ReplicaSet
    requeues + restarts), ``slow_worker`` stalls an iteration inside the
    health-monitored step window so the watchdog sees a hung step,
    ``kv_exhaustion`` fires in the page pool."""

    def __init__(self, model, config: ServingConfig,
                 queue_: AdmissionQueue, *,
                 name: str = "replica0",
                 pool: Optional[PagePool] = None,
                 fault_injector=None,
                 monitor=None,
                 on_dead: Optional[Callable] = None,
                 device_lock: Optional[threading.RLock] = None,
                 slo: Optional[SLOMonitor] = None):
        if model.executor is None:
            raise NotCompiledError("compile() the model first")
        if len(model._fit_input_tensors) != 1:
            raise ServingConfigError(
                "continuous batching serves decoder-only models (one graph "
                "input); use BatchScheduler/incremental_seq2seq_generate "
                "for encoder-decoder graphs"
            )
        self.model = model
        self.config = config
        self.queue = queue_
        self.name = name
        self.fault_injector = fault_injector
        self.monitor = monitor
        self.on_dead = on_dead
        self.slo = slo  # shared SLOMonitor (ReplicaSet-owned), or None
        self.pool = pool or PagePool(config.kv_config(),
                                     fault_injector=fault_injector)
        # ALL in-process replicas must funnel device work through one
        # lock: concurrent jitted executions + compiles from sibling
        # threads can wedge the single-process CPU backend (and on a
        # shared core buy nothing anyway) — production replicas live in
        # separate processes and never contend here
        self._device_lock = device_lock or threading.RLock()
        ex = model.executor
        # prefill ALWAYS lowers from the train-searched (compute-bound)
        # strategy: a prompt is a full-sequence forward, exactly the
        # shape the training objective priced
        init1, self._step1 = ex.build_decode(
            1, config.max_len, assume_causal=config.assume_causal
        )
        # the batch-1 cache each prefill fills, made by one program
        self._init1 = decode.compiled_init(init1)
        # the steps and the insert consume the caches where this holds
        self._donates = ex.donates_buffers()
        # batched decode prefers the decode-searched strategy (HBM
        # roofline objective) when one exists / is configured AND its
        # cache pytree is splice-compatible with the prefill lowering —
        # _insert_slot_locked writes prefill caches into the running
        # batch leaf for leaf, so the two lowerings must agree on cache
        # structure. Anything else falls back to the training executor
        # (counted, warned once).
        self.decode_strategy_active = False
        dex = getattr(model, "decode_executor", None)
        if dex is None and config.decode_strategy_path:
            model.compile_decode(strategy_path=config.decode_strategy_path)
            dex = model.decode_executor
        initB, stepB = ex.build_decode(
            config.slots, config.max_len, assume_causal=config.assume_causal
        )
        if dex is not None:
            from ..parallel.decode import (DecodeExactnessError,
                                           decode_fallback)
            try:
                initB_d, stepB_d = dex.build_decode(
                    config.slots, config.max_len,
                    assume_causal=config.assume_causal,
                )
                problem = self._decode_executor_mismatch(dex, initB_d)
                if problem is not None:
                    decode_fallback(self.name, "decode_strategy_incompatible",
                                    problem)
                else:
                    initB, stepB = initB_d, stepB_d
                    self.decode_strategy_active = True
            except DecodeExactnessError as e:
                decode_fallback(self.name, "decode_strategy_unbuildable",
                                str(e))
        self._initB, self._stepB = initB, stepB
        in_t = model._fit_input_tensors[-1]
        self._id_dt = in_t.data_type.np_dtype
        self._caches = None
        # the step queued ahead of the host's bookkeeping, or None: nothing
        # is queued when no slot goes on, or the loop is stopping
        self._queued: Optional[_Queued] = None
        self.slots: List[Optional[_Slot]] = [None] * config.slots
        self._stop = threading.Event()
        self.dead = False
        self.death_cause: Optional[BaseException] = None
        self.draining = False
        self._thread: Optional[threading.Thread] = None
        self._iteration = 0
        self._admit_seq = 0  # per-admission nonce: pool keys stay unique
        # even if a request is ever double-admitted across a failover race
        # slot teardown mutex: _release and _strand_slots TAKE the slot
        # under this lock before touching the pool, so a wedged serve
        # thread waking up mid-steal and the watchdog can never both
        # release the same seq_key (pool double-release is typed now)
        self._teardown_lock = threading.Lock()
        # exact prefill-skip memo: (bucket, prompt bytes) -> (first
        # token, batch-1 cache strip). Identical prompt + identical
        # params reproduce the identical strip, so replaying it is
        # bit-exact; bounded LRU, invalidated on decode retune.
        self._prefix_cache: "OrderedDict" = OrderedDict()
        # strips the memo keeps: prefix_cache_entries, or 0 where the
        # device cannot hold them (_size_memo, when the batch is made)
        self._memo_entries = config.prefix_cache_entries
        # per-token service-time EWMA drives the "cannot meet deadline"
        # early shed; warms up after the first measured iterations
        self._token_ewma_s: Optional[float] = None
        # decode-retune drift watch: admitted prompt-length EWMA vs the
        # distribution frozen at the last decode build (tuner serving leg)
        self._plen_ewma: Optional[float] = None
        self._plen_at_build: Optional[float] = None
        self._plen_admissions = 0
        self._retune_cooldown_until = 0
        self.stats = {"admitted": 0, "finished": 0, "iterations": 0,
                      "prefills": 0, "retired_eos": 0, "shed_decode": 0,
                      "stranded_requeued": 0, "decode_retunes": 0,
                      "prefix_hits": 0, "prefill_skips": 0,
                      # always-on phase counters: seconds inside each
                      # ff.serve.* span (obs.mark), and the prompt tokens
                      # prefilled against the bucket they were padded to
                      "admit_s": 0.0, "admit_reserve_s": 0.0,
                      "prefill_s": 0.0, "prefill_init_s": 0.0,
                      "prefill_dispatch_s": 0.0, "prefill_wait_s": 0.0,
                      "prefill_fetch_s": 0.0, "insert_s": 0.0,
                      # programs the inserts and the prefills' batch-1
                      # caches dispatched, summed: one an admission, one
                      # a computed prefill
                      "insert_programs": 0, "prefill_init_programs": 0,
                      "decode_s": 0.0, "decode_prepare_s": 0.0,
                      "decode_dispatch_s": 0.0, "decode_wait_s": 0.0,
                      "decode_fetch_s": 0.0, "decode_sample_s": 0.0,
                      # bytes the decode iterations fetched, summed: over
                      # "iterations" it is 4 x slots and a few scalars
                      "decode_fetch_bytes": 0,
                      # steps dispatched while the step before them was
                      # not yet booked, and rows such a step computed for
                      # a slot gone (EOS, a shed, an abort) when booked
                      "decode_steps_ahead": 0, "decode_rows_discarded": 0,
                      "idle_s": 0.0, "prefill_tokens": 0,
                      "prefill_bucket_tokens": 0,
                      # bucket - prompt, summed: positions a prefill ran
                      # and every op with a recurrent state masked
                      "prefill_masked_tokens": 0,
                      # gauges: bytes the slots hold of each kind of
                      # per-slot state, set when the caches are made
                      "kv_cache_bytes": 0, "recurrent_state_bytes": 0,
                      # 1 where the decode steps consume their caches and
                      # append in place (executor.build_decode donates them
                      # on an accelerator: one rule for every executor of
                      # this process), set when the steps are built
                      "decode_caches_donated": int(self._donates)}

    def _decode_executor_mismatch(self, dex, initB_d) -> Optional[str]:
        """None if the decode-searched lowering can serve the batched
        step, else a human-readable reason. Two lowerings are
        splice-compatible when (a) every weight-bearing op in the decode
        graph finds its weights in the (training) param store by op
        name, and (b) the decode-build's cache pytree matches the
        prefill build's section-by-section: guid-keyed 'static'/'prefix'
        sections must agree (guids differ across lowerings, so in
        practice both must be empty — true for decoder-only fused-MHA
        graphs), 'mha' sections must cover the same op names with the
        same per-slot leaf shapes, and so must 'recurrent' (the state of
        fixed size some ops keep beside keys and values). Probed with
        jax.eval_shape — no cache
        allocation happens here."""
        params = (self.model.state.params
                  if getattr(self.model, "state", None) is not None else None)
        if params is not None:
            missing = [op.name for op in dex.topo
                       if op.weights and not op.is_parallel_op
                       and op.name not in params]
            if missing:
                return (f"decode graph ops {missing} have no weights in the "
                        f"model's param store")
        try:
            dec = jax.eval_shape(initB_d, params, ())
            pre = jax.eval_shape(self._init1, params, ())
        except Exception as e:
            return f"cache shape probe failed: {e}"
        for section in ("static", "prefix", "mha_static"):
            d_keys = set(dec.get(section, {}))
            p_keys = set(pre.get(section, {}))
            if d_keys != p_keys:
                return (f"{section!r} cache keys differ between the decode- "
                        f"and train-searched lowerings "
                        f"({len(d_keys)} vs {len(p_keys)} entries)")
        for section, what in (("mha", "attention cache"),
                              ("recurrent", "recurrent state")):
            if set(dec[section]) != set(pre[section]):
                return (f"{what} op names differ between the decode- "
                        "and train-searched lowerings")
            for name, dleaves in dec[section].items():
                dflat, dtree = jax.tree_util.tree_flatten(dleaves)
                pflat, ptree = jax.tree_util.tree_flatten(pre[section][name])
                if dtree != ptree:
                    return f"{what} structure differs for {name!r}"
                for a, b in zip(dflat, pflat):
                    if a.shape[1:] != b.shape[1:] or a.dtype != b.dtype:
                        return (f"{what} leaf mismatch for {name!r}: "
                                f"{a.shape}/{a.dtype} vs {b.shape}/{b.dtype}")
        return None

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._serve_loop, daemon=True,
                name=f"ff-serve-{self.name}",
            )
            self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    def thread_alive(self) -> bool:
        return (self._thread is not None and self._thread.is_alive()
                and not self.dead)

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def in_flight(self) -> List[_Slot]:
        return [s for s in self.slots if s is not None]

    # -- admission -------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        b = 1
        while b < plen:
            b *= 2
        return min(b, self.config.max_len)

    def _reserve_tokens(self, plen: int, max_new: int) -> int:
        # prefill touches the whole padded bucket; decode grows to
        # plen + max_new - 1 written positions (the last sampled token's
        # K/V is never appended). Reserve the max so growth can't stall.
        return min(self.config.max_len,
                   max(self._bucket(plen), plen + max_new))

    def _try_admit_one(self) -> bool:
        req = self.queue.poll(timeout=0.0)
        if req is None:
            return False
        from .. import obs

        # from a successful poll to the slot filled or the request shed
        with obs.mark("ff.serve.admit", cat="serving",
                      into=(self.stats, "admit_s"), request=req.id,
                      prompt_len=len(req.prompt)) as span:
            return self._admit(req, span)

    def _admit(self, req: GenerationRequest, span) -> bool:
        from .. import obs

        now = time.monotonic()
        plen = len(req.prompt)
        total = plen + req.max_new_tokens
        if plen < 1 or total > self.config.max_len:
            err = RequestShedError(
                f"request {req.id}: prompt {plen} + max_new "
                f"{req.max_new_tokens} exceeds max_len "
                f"{self.config.max_len}", reason="too_long",
            )
            _shed("too_long")
            req.trace.shed("too_long", stage="admit", replica=self.name)
            req._finish(error=err)
            return True
        # early shed: with a warmed service-time estimate, a request
        # whose decode provably outlives its deadline never runs
        if self._token_ewma_s is not None:
            eta = now + req.max_new_tokens * self._token_ewma_s
            if eta > req.deadline:
                _shed("deadline")
                req.trace.shed("deadline", stage="admit",
                               replica=self.name)
                req._finish(error=DeadlineExceededError(
                    f"request {req.id} cannot meet its deadline: needs "
                    f"~{req.max_new_tokens * self._token_ewma_s:.3f}s, has "
                    f"{max(0.0, req.deadline - now):.3f}s", stage="admit",
                ))
                return True
        generation = req.generation
        self._admit_seq += 1
        seq_key = f"{req.id}:{generation}:{self.name}:{self._admit_seq}"
        share = self.config.share_prefixes
        prompt_tokens = req.prompt.tolist() if share else None
        # the host's bookkeeping before any device work: the page reserve,
        # the slot, and the lookup of a prefilled strip to replay
        with obs.mark("ff.serve.admit.reserve", cat="serving",
                      into=(self.stats, "admit_reserve_s")):
            try:
                # with the prompt given, reserve() attaches published
                # prefix pages refcounted and only charges the unshared
                # remainder — the dedup that lets N same-prefix sessions
                # share one pool
                reserved = self._reserve_tokens(plen, req.max_new_tokens)
                rr = self.pool.reserve(seq_key, reserved,
                                       tokens=prompt_tokens)
            except KVCacheExhaustedError as e:
                return self._kv_exhausted(req, e)
            slot_idx = self.slots.index(None)
            bucket = self._bucket(plen)
            req.admitted_t = time.monotonic()
            req.trace.admitted(self.name, generation=generation,
                               slot=slot_idx, prompt_len=plen)
            if rr.shared_pages:
                self.stats["prefix_hits"] += 1
            if req.trace.sampled:
                req.trace.event("kv_reserve", replica=self.name,
                                pages=rr.pages, shared=rr.shared_pages,
                                bytes=slot_reservation_bytes(
                                    self.model, self.pool.config, reserved),
                                **self.pool.snapshot())
            cache_key = ((bucket, req.prompt.astype(self._id_dt).tobytes())
                         if share and self._memo_entries > 0 else None)
            cached = (self._prefix_cache.get(cache_key)
                      if cache_key is not None else None)
        span.set(slot=slot_idx, bucket=bucket, skipped=cached is not None)
        prefill_span = req.trace.span("prefill", replica=self.name,
                                      bucket=bucket, prompt_len=plen,
                                      skipped=cached is not None)
        try:
            if cached is not None:
                # exact FLOP skip: this verbatim prompt was prefilled
                # before under the same params, so its strip (and first
                # token) are bit-identical — replay instead of compute
                first, caches1 = cached
                self._prefix_cache.move_to_end(cache_key)
                self.stats["prefill_skips"] += 1
            else:
                first, caches1 = self._prefill(req, plen)
                self.stats["prefill_tokens"] += plen
                self.stats["prefill_bucket_tokens"] += bucket
                self.stats["prefill_masked_tokens"] += bucket - plen
        except BaseException:
            self.pool.release(seq_key)
            raise
        self._insert_slot(slot_idx, caches1, request=req.id)
        if cache_key is not None and cached is None:
            self._memo_put(cache_key, first, caches1)
        prefill_span.done()
        req.first_token_t = time.monotonic()
        req.token_t = [req.first_token_t]
        obs.observe("ff_serving_ttft_seconds",
                    req.first_token_t - req.submitted_t,
                    help="time from submit to first generated token")
        slot = _Slot(req=req, generation=generation, seq_key=seq_key,
                     tokens=list(req.prompt.tolist()) + [first],
                     prompt_len=plen, pos=plen)
        self.pool.touch(seq_key, bucket)
        if share:
            # make this prompt's full pages content-addressable so
            # later same-prefix admissions attach instead of allocating
            self.pool.publish(seq_key, prompt_tokens)
        self.slots[slot_idx] = slot
        self.stats["admitted"] += 1
        self.stats["prefills"] += 1
        self._note_admitted_plen(plen)
        self._maybe_retire(slot_idx)
        return True

    def _memo_put(self, key, first: int, caches1) -> None:
        """Keep a prefilled strip for an exact replay, the least recently
        used going first once the memo holds `_memo_entries`."""
        memo = self._prefix_cache
        memo[key] = (first, caches1)
        while len(memo) > self._memo_entries:
            memo.popitem(last=False)

    def _size_memo(self, caches1) -> None:
        """Size the memo when the batch's caches are made: the device's
        free memory is read then, with the weights, the batch and this
        admission's batch-1 cache `caches1` live, so that the strips the
        memo keeps always leave room for the next admission's."""
        jax.block_until_ready(self._caches)
        strip = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            {sec: caches1[sec] for sec in decode.SLOT_SECTIONS}))
        self._memo_entries = _memo_entries(
            _device_free_bytes(), strip, self.config.prefix_cache_entries)
        while len(self._prefix_cache) > self._memo_entries:
            self._prefix_cache.popitem(last=False)

    def _kv_exhausted(self, req: GenerationRequest,
                      e: KVCacheExhaustedError) -> bool:
        """An admission the page pool cannot cover now: shed the request
        typed when it never could (True), else put it back to wait for
        retirements (False)."""
        from .. import obs

        if e.never_fits:
            _shed("kv_exhausted")
            req.trace.shed("kv_exhausted", stage="admit", replica=self.name,
                           pages_needed=e.pages_needed)
            # a can-NEVER-fit request is a sizing bug, not backpressure —
            # worth a forensics bundle (deduped per exception; backpressure
            # requeues below stay silent)
            obs.record_failure(e, replica=self.name, request=req.id,
                               kv_snapshot=self.pool.snapshot())
            req._finish(error=RequestShedError(
                f"request {req.id} can never fit the KV page pool: {e}",
                reason="kv_exhausted",
            ))
            return True
        # backpressure: put it back and wait for retirements
        self.queue.requeue(req)
        req.trace.event("kv_backpressure", replica=self.name,
                        pages_needed=e.pages_needed, pages_free=e.pages_free)
        obs.event("serving_kv_backpressure", cat="serving",
                  replica=self.name, request=req.id,
                  pages_needed=e.pages_needed, pages_free=e.pages_free)
        return False

    def _prefill(self, req: GenerationRequest, plen: int):
        """Run the prompt through the batch-1 decode step, padded to a
        power-of-two bucket (bounds distinct jit shapes to log2(max_len)).
        The padded tail's garbage K/V sits at positions >= plen, which
        decode overwrites position-by-position before the causal mask
        ever exposes them; a recurrent state is never overwritten, so the
        step is told the prompt's length and the tail leaves it alone. It
        is told the one row wanted too, the last real token's: what
        follows the last attention runs on that row alone."""
        from .. import obs

        bucket = self._bucket(plen)
        padded = np.zeros((1, bucket), self._id_dt)
        padded[0, :plen] = req.prompt.astype(self._id_dt)
        stats, params = self.stats, self.model.state.params
        with self._device_lock, obs.mark(
                "ff.serve.prefill", cat="serving",
                into=(stats, "prefill_s"), request=req.id, bucket=bucket):
            # the batch-1 cache the step fills, made anew each prefill
            with obs.mark("ff.serve.prefill.init", cat="serving",
                          into=(stats, "prefill_init_s")):
                caches1 = self._init1(params, ())
                stats["prefill_init_programs"] += 1
            with obs.mark("ff.serve.prefill.dispatch", cat="serving",
                          into=(stats, "prefill_dispatch_s")):
                logits, caches1 = self._step1(
                    params, caches1, jnp.int32(0), [jnp.asarray(padded)],
                    jnp.int32(plen), jnp.int32(plen - 1),
                )
                # the step put out the one row needed, the last real
                # token's, not bucket x vocabulary of them; its best id is
                # one number, picked behind it on the device
                ids = _best_ids(logits)
            # ONE sync, split in two as a decode iteration's: the wait for
            # the device, then the copy of the id and of what the prefill
            # counted (summed into `stats`)
            with obs.mark("ff.serve.prefill.wait", cat="serving",
                          into=(stats, "prefill_wait_s")):
                jax.block_until_ready(ids)
            with obs.mark("ff.serve.prefill.fetch", cat="serving",
                          into=(stats, "prefill_fetch_s")):
                ids, counted = jax.device_get(
                    (ids, caches1["prefill_counters"]))
            first = int(ids[0])
        for name, value in counted.items():
            stats[name] = stats.get(name, 0) + int(value)
        return first, caches1

    def _insert_slot(self, slot_idx: int, caches1, *, request) -> None:
        """Swap a prefilled batch-1 cache strip into the running batch:
        every per-slot cache leaf is written wholesale at `slot_idx`, so
        whatever a previous occupant left there is fully replaced. The
        writes are dispatched, not waited for."""
        from .. import obs

        with self._device_lock, obs.mark(
                "ff.serve.insert", cat="serving",
                into=(self.stats, "insert_s"), request=request,
                slot=slot_idx):
            self._insert_slot_locked(slot_idx, caches1)

    def _insert_slot_locked(self, slot_idx: int, caches1) -> None:
        if self._caches is None:
            self._caches = self._initB(self.model.state.params, ())
            self._size_memo(caches1)
            self._note_state_bytes()
            for name in self._caches["counters"]:
                self.stats.setdefault(name, 0)
        # the insert consumes the caches as a step does: nothing here
        # holds the old tree while its successor is made
        caches, self._caches = self._caches, None
        self._caches = decode.insert_row(caches, caches1, slot_idx,
                                         donate=self._donates)
        self.stats["insert_programs"] += 1

    def _note_state_bytes(self) -> None:
        """What the slots hold of each kind of per-slot state, as gauges
        (`stats` and the telemetry session): keys and values, which grow
        with a sequence's length up to what is held here, and recurrent
        state, which does not."""
        from .. import obs

        held = decode.state_bytes(self._caches)
        self.stats["kv_cache_bytes"] = held["kv"]
        self.stats["recurrent_state_bytes"] = held["fixed"]
        for kind in ("kv_cache_bytes", "recurrent_state_bytes"):
            obs.gauge_set("ff_serving_" + kind, self.stats[kind],
                          help="bytes the decode slots hold of this kind "
                               "of per-slot state", replica=self.name)
        # the keys and values again, by what their leaves are: rings of a
        # window's positions, and leaves of max_len; and those of the ops
        # inside a loop region, every step's
        for kind, held in decode.kv_bytes_by_kind(
                self._caches, self.config.max_len,
                looped=decode.looped_ops(self.model.executor.topo)).items():
            self.stats[f"kv_cache_bytes_{kind}"] = held
            obs.gauge_set("ff_serving_kv_cache_bytes", held,
                          help="bytes the decode slots hold of this kind "
                               "of per-slot state", replica=self.name,
                          kind=kind)
        obs.gauge_set("ff_serving_decode_caches_donated",
                      self.stats["decode_caches_donated"],
                      help="1 where the decode step owns these caches and "
                           "appends in place, 0 where it copies them first",
                      replica=self.name)

    def _step_counts(self, fetched) -> dict:
        """What one decode iteration adds to `stats`, as the values to set:
        the bytes it brought to the host (`slots` ids and the step's
        counters, whatever the vocabulary) and the step's counters folded
        (parallel/decode.py, the caches' "counters" section: whatever the
        graph's ops report, by name): a name that ends in "_max" keeps the
        largest a step has seen, any other sums."""
        stats = self.stats
        new = {"decode_fetch_bytes": stats["decode_fetch_bytes"] + sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(fetched))}
        for name, value in fetched[1].items():
            value = int(value)
            new[name] = max(stats[name], value) if name.endswith("_max") \
                else stats[name] + value
        return new

    # -- retirement ------------------------------------------------------
    def _release(self, slot_idx: int) -> None:
        # take-then-release: whoever swaps the slot out owns the ONE
        # pool release for its seq_key (double release is typed now)
        with self._teardown_lock:
            slot = self.slots[slot_idx]
            self.slots[slot_idx] = None
        if slot is not None:
            freed = self.pool.release(slot.seq_key)
            if slot.req.trace.sampled:
                slot.req.trace.event("kv_release", replica=self.name,
                                     pages=freed, **self.pool.snapshot())

    def _finish_slot(self, slot_idx: int) -> None:
        from .. import obs

        slot = self.slots[slot_idx]
        generated = len(slot.tokens) - slot.prompt_len
        ok = slot.req._finish(tokens=np.asarray(slot.tokens, self._id_dt),
                              generation=slot.generation)
        if ok:
            latency = time.monotonic() - slot.req.submitted_t
            obs.observe("ff_serving_latency_seconds", latency,
                        help="end-to-end serving request latency")
            obs.count("ff_serving_requests_total",
                      help="serving requests answered")
            obs.count("ff_serving_tokens_total", generated,
                      help="tokens generated by the serving runtime")
            self.stats["finished"] += 1
            stages = record_request_stages(slot.req, generated=generated,
                                           slo=self.slo, replica=self.name)
            slot.req.trace.completed(
                self.name, generation=slot.generation, tokens=generated,
                **{f"{k}_s": round(v, 6) for k, v in stages.items()},
            )
        self._release(slot_idx)

    def _maybe_retire(self, slot_idx: int) -> None:
        slot = self.slots[slot_idx]
        if slot is None:
            return
        if slot.req.done():  # aborted / requeued elsewhere
            self._release(slot_idx)
            return
        now = time.monotonic()
        if now > slot.req.deadline:
            _shed("deadline")
            self.stats["shed_decode"] += 1
            slot.req.trace.shed("deadline", stage="decode",
                                replica=self.name,
                                tokens=len(slot.tokens) - slot.prompt_len)
            slot.req._finish(error=DeadlineExceededError(
                f"request {slot.req.id} blew its deadline mid-decode "
                f"after {len(slot.tokens) - slot.prompt_len} token(s)",
                stage="decode",
            ), generation=slot.generation)
            self._release(slot_idx)
            return
        generated = len(slot.tokens) - slot.prompt_len
        eos = self.config.eos_token_id
        if generated >= slot.req.max_new_tokens or (
            eos is not None and slot.tokens[-1] == eos
        ):
            if eos is not None and slot.tokens[-1] == eos:
                self.stats["retired_eos"] += 1
            self._finish_slot(slot_idx)

    # -- the iteration loop ---------------------------------------------
    def _rows(self, after: Optional[_Queued]) -> Dict[int, tuple]:
        """The rows of the next batched step: row -> (slot, position, host
        token), the token -1 where it is the row's id in `after`, the step
        still on the device. A slot `after` computed goes on from that id,
        unless the id is its last (`max_new_tokens`: it retires once `after`
        is booked); any other slot (nothing queued, or admitted since) from
        the token the host holds. An empty row is position 0, token 0."""
        rows = {}
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            if after is None or after.rows.get(i) is not slot:
                rows[i] = (slot, slot.pos, slot.tokens[slot.pos])
            elif (len(slot.tokens) - slot.prompt_len + 1
                  < slot.req.max_new_tokens):
                # a slot that ends by EOS, a shed or an abort once `after`
                # is booked has this row discarded then; it writes inside
                # the slot's own strip, which the next insert replaces
                rows[i] = (slot, min(slot.pos + 1, self.config.max_len - 1),
                           -1)
        return rows

    def _enqueue(self, caches, t_vec, host, prev):
        """Dispatch one batched step over `caches` and its pick behind it:
        the rows' tokens are `host`'s, or `prev`'s ids (the step before,
        on the device) where `host` holds -1. Waits for nothing; returns
        (ids, counters, caches), the caches consumed as the step's are."""
        toks = _feed(host if prev is None else prev, host)
        logits, caches = self._stepB(self.model.state.params, caches,
                                     jnp.asarray(t_vec), [toks])
        ids, counters = _pick(logits, caches["counters"])
        return ids, counters, caches

    def _dispatch(self, rows: Dict[int, tuple],
                  after: Optional[_Queued]) -> _Queued:
        """Queue the batched step over `rows` (`_rows(after)`)."""
        from .. import obs

        stats = self.stats
        with obs.mark("ff.serve.decode.prepare", cat="serving",
                      into=(stats, "decode_prepare_s")):
            t_vec = np.zeros(self.config.slots, np.int32)
            host = np.zeros(self.config.slots, self._id_dt)
            for i, (slot, t, tok) in rows.items():
                t_vec[i], host[i] = t, tok
                if self.config.share_prefixes:
                    # protocol guard: this step writes K/V at t. Only
                    # full PROMPT blocks are ever published, and decode
                    # positions sit strictly past them, so this is a
                    # no-op in steady state — but if a shared page were
                    # ever in the write path, the pool copies it private
                    # here (COW) instead of letting the write leak into
                    # siblings
                    self.pool.note_write(slot.seq_key, t)
        with self._device_lock, obs.mark(
                "ff.serve.decode.dispatch", cat="serving",
                into=(stats, "decode_dispatch_s")):
            ids, counters, self._caches = self._enqueue(
                self._caches, t_vec, host,
                None if after is None else after.ids)
        return _Queued({i: row[0] for i, row in rows.items()}, ids, counters)

    def _decode_iteration(self) -> dict:
        """Book one batched step, with the next one queued behind it on the
        device first; returns what it adds to `stats` (`_step_counts`),
        which the loop folds in with its count of the iteration."""
        from .. import obs

        stats = self.stats
        with obs.mark("ff.serve.decode", cat="serving",
                      into=(stats, "decode_s"), iteration=self._iteration,
                      occupancy=self.active_slots) as span:
            if self._queued is None:
                # nothing on the device (the first step after an empty
                # batch): this iteration's step goes from the host's tokens
                self._queued = self._dispatch(self._rows(None), None)
            step, self._queued = self._queued, None
            # the next step before the wait, its tokens this step's ids
            # where they lie: the device runs it while the host waits,
            # fetches and books. Not when the loop is stopping
            stopping = self._stop.is_set() or self.dead
            rows = {} if stopping else self._rows(step)
            with self._device_lock:
                if rows:
                    self._queued = self._dispatch(rows, step)
                # ONE sync, split in two: the wait for the device, then
                # the copy of `slots` ids to the host. The logits stay
                # where they were made
                with obs.mark("ff.serve.decode.wait", cat="serving",
                              into=(stats, "decode_wait_s")):
                    jax.block_until_ready(step.ids)
                with obs.mark("ff.serve.decode.fetch", cat="serving",
                              into=(stats, "decode_fetch_s")) as fetch:
                    # what the step's ops counted (a few scalars, or
                    # nothing) rides the fetch of its ids
                    fetched = jax.device_get((step.ids, step.counters))
            ids = fetched[0].tolist()
            # a sampled request's share of the iteration: from the
            # ff.serve.decode span's start to the ids on the host
            span_dur = fetch.t0 + fetch.dur - span.t0
            occupancy = len(step.rows)
            discarded = 0
            with obs.mark("ff.serve.decode.sample", cat="serving",
                          into=(stats, "decode_sample_s")):
                for i, was in step.rows.items():
                    slot = self.slots[i]
                    if slot is not was:
                        # retired, shed or taken by a teardown sweep since
                        # the step was queued: the row's id is no one's
                        discarded += 1
                        continue
                    slot.tokens.append(ids[i])
                    slot.req.token_t.append(time.monotonic())
                    slot.pos += 1
                    new_pages = self.pool.touch(
                        slot.seq_key,
                        max(self._bucket(slot.prompt_len), slot.pos))
                    if slot.req.trace.sampled:
                        # one completed span per sampled slot per
                        # iteration: slot occupancy + position make decode
                        # stalls and batch-sharing visible per request in
                        # the Perfetto lane
                        slot.req.trace.iteration(
                            self.name, t0=span.t0, dur_s=span_dur,
                            iteration=self._iteration, slot=i, pos=slot.pos,
                            occupancy=occupancy,
                        )
                        if new_pages:
                            slot.req.trace.event("kv_touch",
                                                 replica=self.name,
                                                 pages=len(new_pages),
                                                 pos=slot.pos)
                    self._maybe_retire(i)
        counts = self._step_counts(fetched)
        counts["decode_steps_ahead"] = (stats["decode_steps_ahead"]
                                        + (self._queued is not None))
        counts["decode_rows_discarded"] = (stats["decode_rows_discarded"]
                                           + discarded)
        return counts

    def _settle(self) -> None:
        """Wait for the step queued ahead, if any, and drop it unbooked: a
        dying replica's, whose slots go back to the queue. Nothing is
        queued after this, and no later program reads what it consumed."""
        step, self._queued = self._queued, None
        if step is not None:
            try:
                jax.block_until_ready(step.ids)
            except Exception:  # fflint: disable=FFL002 — the replica is already dead
                pass

    def _warmup_compiles(self) -> None:
        """Compile the batched decode step, the feed of its tokens, the pick
        of its rows' best ids, every prefill bucket, the batch-1 cache's
        program and the insert on throwaway caches before taking traffic.
        Runs on the serve thread under the HealthMonitor's compile grace
        window; the running batch then never waits on XLA mid-request."""
        params, donate = self.model.state.params, self._donates
        with self._device_lock:
            b = 1
            while True:
                caches1 = self._init1(params, ())
                logits, caches1 = self._step1(
                    params, caches1, jnp.int32(0),
                    [jnp.zeros((1, b), self._id_dt)], jnp.int32(b),
                    jnp.int32(b - 1))
                _best_ids(logits)
                if b >= self.config.max_len:
                    break
                b = min(2 * b, self.config.max_len)
            # the batched step, its feed and its pick as the loop queues
            # them, on caches made as the served ones are (a prefilled
            # strip inserted into a fresh batch): from the host's tokens,
            # then fed the ids of the step before. The served iterations
            # then find every program built. The prefills above were only
            # enqueued: the batch is made once they are done, as an
            # admission makes it, so that the device never holds their
            # temporaries beside both generations of a leaf
            jax.block_until_ready(caches1)
            caches = decode.insert_row(self._initB(params, ()), caches1, 0,
                                       donate=donate)
            t_vec = np.zeros(self.config.slots, np.int32)
            host = np.zeros(self.config.slots, self._id_dt)
            ids = None
            for _ in range(2):
                ids, _, caches = self._enqueue(caches, t_vec, host, ids)
            # the insert again, into a stepped batch: a fresh batch (the
            # first admission's) is not yet placed as the step's output is,
            # and the program is built for each placement
            decode.insert_row(caches, caches1, 0, donate=donate)

    def _strand_slots(self) -> int:
        """Hand every occupied slot back to the shared queue (or shed it
        typed when its deadline is gone) — the dying replica's half of
        failover. The serve thread calls this on ANY dead-exit, so a
        request admitted in the very race window where the ReplicaSet
        declared the replica dead still gets rescued; pool keys carry a
        per-admission nonce, so even a double-handled request can never
        collide in a page pool. Safe to call from the ReplicaSet too
        (stuck-thread steal): slots are taken under the teardown mutex
        so page refs transfer exactly once, and completion stays
        exactly-once via the generation check."""
        from .. import obs

        requeued = 0
        for i in range(len(self.slots)):
            # take-then-release under the teardown mutex: the dying
            # serve thread and a watchdog steal can both sweep, but only
            # the taker decrefs — page ownership transfers exactly once
            with self._teardown_lock:
                slot = self.slots[i]
                self.slots[i] = None
            if slot is None:
                continue
            self.pool.release(slot.seq_key)
            gen = slot.req._requeue_bump()
            if gen is None:
                continue  # finished meanwhile
            if time.monotonic() >= slot.req.deadline:
                _shed("deadline")
                slot.req.trace.shed("deadline", stage="failover",
                                    replica=self.name)
                slot.req._finish(error=DeadlineExceededError(
                    f"request {slot.req.id} expired during replica "
                    "failover", stage="failover",
                ))
                continue
            slot.req.trace.requeued(self.name, generation=gen,
                                    tokens_done=len(slot.tokens)
                                    - slot.prompt_len)
            self.queue.requeue(slot.req)
            requeued += 1
        if requeued:
            self.stats["stranded_requeued"] += requeued
            obs.count("ff_serving_requeues_total", requeued,
                      help="in-flight requests requeued by failover")
        return requeued

    # -- online decode re-search (the StrategyTuner's serving leg) -------
    def _note_admitted_plen(self, plen: int) -> None:
        """Feed one admission's prompt length into the drift watch. The
        first decode_retune_min_admissions requests freeze the baseline
        the later distribution is compared against."""
        if not self.config.decode_retune:
            return
        self._plen_admissions += 1
        self._plen_ewma = (float(plen) if self._plen_ewma is None
                           else 0.8 * self._plen_ewma + 0.2 * float(plen))
        if (self._plen_at_build is None and self._plen_admissions
                >= self.config.decode_retune_min_admissions):
            self._plen_at_build = self._plen_ewma

    def _retune_wanted(self) -> bool:
        cfg = self.config
        if (not cfg.decode_retune
                or self._iteration < self._retune_cooldown_until
                or self._plen_at_build is None
                or self._plen_ewma is None
                or self._plen_admissions < cfg.decode_retune_min_admissions):
            return False
        base = max(1.0, self._plen_at_build)
        return abs(self._plen_ewma - base) / base > cfg.decode_retune_threshold

    def _retune_decode(self) -> None:
        """Re-run the decode-objective strategy search and hot-swap the
        batched decode step. Only called with an empty batch: the live
        caches belong to the outgoing lowering, so they are dropped and
        rebuilt by the next admission's _initB. Any failure keeps the
        current decode step serving (the rollback path is the same
        decode_fallback the boot-time selection uses); every attempt
        lands in ff_strategy_swaps_total{leg="serving"}."""
        from .. import obs
        from ..parallel.decode import DecodeExactnessError, decode_fallback
        from .tuner import SWAP_METRIC, SWAP_METRIC_HELP

        cfg = self.config
        self._retune_cooldown_until = (self._iteration
                                       + cfg.decode_retune_cooldown_iters)
        obs.event("decode_retune_started", cat="serving", replica=self.name,
                  plen_ewma=round(self._plen_ewma or 0.0, 2),
                  plen_at_build=round(self._plen_at_build or 0.0, 2))
        outcome = "rolled_back"
        detail = None
        try:
            with self._device_lock:
                dex = self.model.compile_decode()
                initB_d, stepB_d = dex.build_decode(
                    cfg.slots, cfg.max_len, assume_causal=cfg.assume_causal,
                )
                problem = self._decode_executor_mismatch(dex, initB_d)
                if problem is not None:
                    detail = problem
                    decode_fallback(self.name, "decode_retune_incompatible",
                                    problem)
                else:
                    self._initB, self._stepB = initB_d, stepB_d
                    self._caches = None  # rebuilt by the next admission
                    # memoized strips came from the old serving epoch;
                    # drop them rather than reason about compatibility
                    self._prefix_cache.clear()
                    self.decode_strategy_active = True
                    outcome = "committed"
        except DecodeExactnessError as e:
            detail = str(e)
            decode_fallback(self.name, "decode_retune_unbuildable", str(e))
        except Exception as e:  # fflint: disable=FFL002 — a failed retune must not kill the replica
            detail = str(e)
            logger.warning("decode retune failed on %s; keeping the "
                           "current decode strategy: %s", self.name, e)
        # either way the drift baseline resets to the distribution the
        # retune decision saw — no immediate re-trigger
        self._plen_at_build = self._plen_ewma
        self.stats["decode_retunes"] += 1
        obs.count(SWAP_METRIC, help=SWAP_METRIC_HELP, outcome=outcome,
                  leg="serving")
        obs.event("decode_retune_finished", cat="serving",
                  replica=self.name, outcome=outcome,
                  **({"detail": detail[:200]} if detail else {}))

    def _iterate(self) -> None:
        """One decode iteration inside the health monitor's step window,
        and its counts folded into `stats`."""
        from .. import obs

        it = self._iteration
        if self.monitor is not None:
            self.monitor.step_started(it)
        t0 = time.monotonic()
        if self.fault_injector is not None and not self._stop.is_set():
            plan = self.fault_injector.fire("slow_worker", it,
                                            replica=self.name)
            if plan is not None:
                # a wedged device/interconnect: the iteration stalls INSIDE
                # the monitored step window so the HealthMonitor watchdog
                # sees a hung step
                time.sleep(float(plan.get("delay_s", 1.0)))
        counts = self._decode_iteration()
        dt = time.monotonic() - t0
        if self.monitor is not None:
            self.monitor.step_finished(it)
        # each active sequence gains one token per iteration, so the
        # iteration wall time IS the per-token service time. A sample
        # counts for at most twice the estimate: one stalled iteration (a
        # profiler starting, a host hiccup) would otherwise shed the long
        # requests admitted after it, while a lasting slowdown still lifts
        # it by a fifth an iteration
        e = self._token_ewma_s
        self._token_ewma_s = (
            dt if e is None else 0.8 * e + 0.2 * min(dt, 2.0 * e))
        self._iteration += 1
        # ONE update: whoever a finished request wakes reads `stats` of
        # whole iterations, never a step's counters without its count
        self.stats.update(counts, iterations=self.stats["iterations"] + 1)
        for name, value in counts.items():
            obs.gauge_set("ff_serving_" + name, value,
                          help="what the decode iterations counted under "
                               "this name", replica=self.name)
        obs.gauge_set("ff_serving_batch_occupancy", self.active_slots,
                      help="occupied decode slots", replica=self.name)

    def _serve_loop(self) -> None:
        from .. import obs

        try:
            if self.config.precompile:
                with obs.span("serving_warmup", cat="serving",
                              replica=self.name):
                    self._warmup_compiles()
            while not self._stop.is_set() and not self.dead:
                while (not self.draining and None in self.slots
                       and self._try_admit_one()):
                    pass
                if self.fault_injector is not None:
                    if self.fault_injector.fire(
                        "replica_death", self._iteration, replica=self.name
                    ) is not None:
                        raise ReplicaDeathError(
                            f"replica {self.name} death injected at "
                            f"iteration {self._iteration}"
                        )
                if self.active_slots == 0 and self._queued is None:
                    if self.draining:
                        return
                    if self._retune_wanted():
                        self._retune_decode()
                        continue
                    with obs.mark("ff.serve.idle", cat="serving",
                                  into=(self.stats, "idle_s"),
                                  session=False):
                        time.sleep(self.config.idle_wait_s)
                    continue
                self._iterate()
            # stopping: the step queued ahead is booked by the loop's last
            # iteration, which queues nothing behind it; a replica marked
            # dead only waits for it (its slots go back to the queue)
            if self._queued is not None:
                if self.dead:
                    self._settle()
                else:
                    self._iterate()
        except BaseException as e:  # replica died: hand off and stop
            self.dead = True
            self.death_cause = e
            logger.exception("serving replica %s died", self.name)
            obs.event("replica_died", cat="serving", replica=self.name,
                      error=type(e).__name__, detail=str(e)[:300])
            self._settle()
            self._strand_slots()
            if self.on_dead is not None:
                self.on_dead(self, e)
            else:
                # a lone batcher (no ReplicaSet): nobody is left to take
                # the stranded or queued work, so it fails with the cause
                # (bound to a local: `e` is unbound once this block ends,
                # and the queue calls the factory for later offers too)
                cause = e

                def orphaned(req: GenerationRequest) -> ReplicaDeathError:
                    err = ReplicaDeathError(
                        f"request {req.id}: serving replica {self.name} "
                        f"died and none is left to serve it: {cause!r}")
                    err.__cause__ = cause
                    return err

                self.queue.close(orphaned)
        else:
            # marked dead externally (watchdog/heartbeat failover) while
            # we were mid-iteration: whatever we still hold goes back to
            # the queue — the ReplicaSet's snapshot may have raced an
            # admission and seen these slots empty
            if self.dead and not self._stop.is_set():
                self._strand_slots()


# ----------------------------------------------------------------------
# multi-replica failover + autoscaling
# ----------------------------------------------------------------------
@dataclasses.dataclass
class _Replica:
    name: str
    model: object
    batcher: ContinuousBatcher
    monitor: object  # runtime.elastic.HealthMonitor


class ReplicaSet:
    """N continuous-batching replicas off ONE shared admission queue.

    * **admission** happens once, at `submit`: rate limiting (token
      bucket, optionally p95-adaptive), then the bounded queue — every
      rejection is typed and counted.
    * **health**: each replica gets a HealthMonitor (runtime/elastic.py)
      watching per-iteration step progress plus a heartbeat probing the
      serve thread; a hung or dead replica triggers failover.
    * **failover**: the dead replica's in-flight requests are requeued
      at the queue FRONT (generation-bumped so the corpse can't publish
      stale results; blown deadlines are shed typed), siblings keep
      draining the queue meanwhile, and a restart thread brings a
      replacement up — from the **warm-spare pool** when one is
      available (`warm_spares`: models built AND decode-precompiled at
      startup, so activation is just a checkpoint restore — an
      in-process rebuild's strategy search would steal the CPU from
      live replicas mid-overload), else a full rebuild through
      ``restore_elastic`` resharding when `ckpt_dir` is given — with
      exponential backoff and a bounded budget.
    * **autoscaling** (optional): queue depth above
      `scale_up_queue_depth` adds replicas up to `max_replicas`; a
      sustained-idle queue retires them down to `min_replicas`."""

    def __init__(self, model_fn: Callable[[], object],
                 config: ServingConfig, *,
                 replicas: int = 1,
                 max_replicas: Optional[int] = None,
                 ckpt_dir: Optional[str] = None,
                 fault_injector=None,
                 health_timeout_s: float = 30.0,
                 compile_grace_s: Optional[float] = None,
                 max_replica_restarts: int = 3,
                 restart_backoff_s: float = 0.2,
                 warm_spares: int = 0,
                 scale_up_queue_depth: Optional[int] = None,
                 scale_down_idle_s: float = 10.0,
                 autoscale_interval_s: float = 0.25,
                 artifact_store=None,
                 fleet_spool_dir: Optional[str] = None):
        self.model_fn = model_fn
        self.config = config
        # strategy/artifact store (runtime/artifact_store.py): every
        # replica/spare build runs under store.ambient(), so the opaque
        # model_fn's compile() reuses searched strategies — warm spares
        # and autoscaler scale-ups boot from the store instead of
        # re-searching
        self.artifact_store = artifact_store
        self.min_replicas = max(1, replicas)
        self.max_replicas = max(self.min_replicas, max_replicas or replicas)
        self.ckpt_dir = ckpt_dir
        self.fault_injector = fault_injector
        self.health_timeout_s = health_timeout_s
        self.compile_grace_s = compile_grace_s
        self.max_replica_restarts = max(0, max_replica_restarts)
        self.restart_backoff_s = restart_backoff_s
        self.warm_spares = max(0, warm_spares)
        self._spares: List[ContinuousBatcher] = []
        # one device lock across every replica (and restart/restore work)
        # in this process — see ContinuousBatcher.__init__
        self._device_lock = threading.RLock()
        self.scale_up_queue_depth = (scale_up_queue_depth
                                     or 2 * config.slots)
        self.scale_down_idle_s = scale_down_idle_s
        self.autoscale_interval_s = autoscale_interval_s
        self.queue = AdmissionQueue(config.max_queue_depth)
        self.bucket: Optional[TokenBucket] = None
        if config.rate_limit is not None:
            self.bucket = TokenBucket(config.rate_limit, config.rate_burst)
        # one SLO monitor across every replica: completion verdicts come
        # from the batchers' _finish_slot, the autoscaler and adaptive
        # admission read it back (obs/request_trace.SLOMonitor)
        self.slo = SLOMonitor(ttft_target_s=config.slo_ttft_s,
                              latency_p99_target_s=config.slo_p99_s)
        self._lock = threading.Lock()
        self._replicas: Dict[str, _Replica] = {}
        self._counter = 0
        self._restarts = 0
        self._pending_restarts = 0
        self._closed = False
        self._started = False
        self._scaler: Optional[threading.Thread] = None
        self._scaler_stop = threading.Event()
        self._idle_since: Optional[float] = None
        self._rate_check = 0
        # local latency reservoir: the adaptive bucket and the load
        # harness read p95 without needing a telemetry session
        from ..obs.metrics import Histogram

        self.latency = Histogram(threading.Lock())
        self.stats = {"submitted": 0, "requeued": 0, "restarts": 0,
                      "spares_used": 0, "scale_ups": 0, "scale_downs": 0,
                      "cold_start_s": []}
        # fleet observatory (obs/fleet.py, obs/anomaly.py): the sentinel
        # watches latency/ttft p95, queue depth, shed rate, KV occupancy
        # and per-replica heartbeat gaps each autoscale tick; scale-ups
        # name the anomaly that preceded them. With fleet_spool_dir set,
        # every replica's counters are spooled per tick — and once more
        # with a terminal status at death/drain — so the cross-process
        # rollup conserves request counts through kills and scale-downs.
        from ..obs.anomaly import AnomalySentinel

        self.sentinel = AnomalySentinel()
        self.fleet_spool_dir = fleet_spool_dir
        self._spools: Dict[str, object] = {}
        # replica name -> (iterations seen, monotonic time it changed)
        self._progress: Dict[str, Tuple[int, float]] = {}
        self._shed_seen = 0.0

    # -- fleet observatory ----------------------------------------------
    @staticmethod
    def _series_rec(name: str, kind: str, value) -> dict:
        if kind == "histogram":
            return {"name": name, "kind": kind, "labels": {},
                    "state": value}
        return {"name": name, "kind": kind, "labels": {},
                "value": float(value)}

    def _replica_series(self, batcher: ContinuousBatcher) -> List[dict]:
        st = batcher.stats
        snap = batcher.pool.snapshot()
        c, g = self._series_rec, self._series_rec
        return [
            c("ff_serving_requests_total", "counter", st["finished"]),
            c("ff_serving_admitted_total", "counter", st["admitted"]),
            c("ff_serving_prefills_total", "counter", st["prefills"]),
            c("ff_serving_shed_decode_total", "counter",
              st["shed_decode"]),
            c("ff_serving_stranded_requeued_total", "counter",
              st["stranded_requeued"]),
            g("ff_serving_active_slots", "gauge", batcher.active_slots),
            g("ff_kv_pages_in_use", "gauge", snap["pages_in_use"]),
            g("ff_kv_pages_shared", "gauge", snap["pages_shared"]),
        ]

    def _write_replica_spool(self, batcher: ContinuousBatcher,
                             status: str = "live") -> None:
        if self.fleet_spool_dir is None:
            return
        from ..obs.fleet import MetricSpool

        sp = self._spools.get(batcher.name)
        if sp is None:
            sp = MetricSpool(self.fleet_spool_dir, batcher.name,
                             replica=batcher.name)
            self._spools[batcher.name] = sp
        try:
            sp.write(series=self._replica_series(batcher), status=status)
        except OSError as e:
            logger.warning("fleet spool write for %s failed (%s)",
                           batcher.name, e)

    def _write_set_spool(self, status: str = "live") -> None:
        if self.fleet_spool_dir is None:
            return
        from ..obs.fleet import MetricSpool

        sp = self._spools.get("replicaset")
        if sp is None:
            sp = MetricSpool(self.fleet_spool_dir, "replicaset")
            self._spools["replicaset"] = sp
        st = self.stats
        rec = self._series_rec
        series = [
            rec("ff_serving_submitted_total", "counter", st["submitted"]),
            rec("ff_serving_requeued_total", "counter", st["requeued"]),
            rec("ff_replica_restarts_total", "counter", st["restarts"]),
            rec("ff_replica_scale_ups_total", "counter", st["scale_ups"]),
            rec("ff_serving_queue_depth", "gauge", len(self.queue)),
            rec("ff_serving_replicas", "gauge", self.replica_count()),
            rec("ff_serving_latency_seconds", "histogram",
                self.latency.state()),
        ]
        try:
            sp.write(series=series, status=status)
        except OSError as e:
            logger.warning("fleet replicaset spool write failed (%s)", e)

    def _observe_fleet(self, depth: int) -> None:
        """One autoscale tick of sentinel feeding + spool refresh. Knob
        choices: hysteresis 1 (the tick itself already integrates over
        the interval, and the scale-up decision wants the anomaly tag
        available the same tick the pressure appears); min_delta floors
        absolute — a queue depth of 1 against an all-zero warm baseline
        is not an incident, a slots-sized jump is; direction "high"
        because a draining queue or falling latency is recovery, and a
        recovery-tagged detector in cooldown would mask the NEXT real
        spike from the scale-up blame window."""
        now = time.monotonic()
        s = self.sentinel
        s.observe("queue_depth", float(depth),
                  min_delta=float(self.config.slots), hysteresis=1,
                  direction="high")
        if self.latency.count >= 8:
            s.observe("serving_latency_p95", self.latency.quantile(0.95),
                      min_delta=0.1, hysteresis=1, direction="high")
        if self.slo.ttft.count >= 8:
            s.observe("ttft_p95", self.slo.ttft.quantile(0.95),
                      min_delta=0.05, hysteresis=1, direction="high")
        with self._lock:
            reps = list(self._replicas.values())
        shed = 0.0
        occupancy = 0.0
        for r in reps:
            b = r.batcher
            shed += b.stats["shed_decode"]
            snap = b.pool.snapshot()
            occupancy = max(occupancy, snap["pages_in_use"]
                            / max(1, b.pool.config.num_pages))
            it = b.stats["iterations"]
            last = self._progress.get(b.name)
            if last is None or last[0] != it:
                self._progress[b.name] = (it, now)
            elif b.thread_alive():
                s.observe_gap(f"replica_heartbeat:{b.name}",
                              now - last[1],
                              limit_s=self.health_timeout_s)
            self._write_replica_spool(b)
        if reps:
            s.observe("kv_occupancy", occupancy, min_delta=0.2,
                      hysteresis=1, direction="high")
        delta = max(0.0, shed - self._shed_seen)
        self._shed_seen = shed
        s.observe("shed_rate",
                  delta / max(self.autoscale_interval_s, 1e-6),
                  min_delta=1.0, hysteresis=1, direction="high")
        self._write_set_spool()

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "ReplicaSet":
        if self._started:
            return self
        self._started = True
        # spares FIRST: built and decode-precompiled while nothing is
        # serving, so a failover — even one in the very first serving
        # iteration — finds them ready and activation costs only a
        # checkpoint restore
        for i in range(self.warm_spares):
            t0 = time.perf_counter()
            with self._store_scope():
                model = self.model_fn()
            self.stats["cold_start_s"].append(time.perf_counter() - t0)
            batcher = self._new_batcher(model, name=f"spare{i}")
            batcher._warmup_compiles()
            with self._lock:
                self._spares.append(batcher)
        for _ in range(self.min_replicas):
            self._add_replica()
        if self.ckpt_dir is not None:
            self._ensure_checkpoint()
        self._scaler = threading.Thread(target=self._autoscale_loop,
                                        daemon=True,
                                        name="ff-serve-autoscaler")
        self._scaler.start()
        return self

    def stop(self, timeout: float = 15.0, abort_pending: bool = True) -> None:
        self._closed = True
        self._scaler_stop.set()
        if self._scaler is not None:
            self._scaler.join(timeout=2.0)
        deadline = time.monotonic() + timeout
        with self._lock:
            reps = list(self._replicas.values())
        for rep in reps:
            rep.batcher.draining = True
        while time.monotonic() < deadline:
            if len(self.queue) == 0 and all(
                r.batcher.active_slots == 0 for r in reps
            ):
                break
            time.sleep(0.02)
        if abort_pending:
            self.queue.drain(lambda req: RequestShedError(
                f"request {req.id} aborted: serving shut down",
                reason="aborted",
            ))
        for rep in reps:
            rep.batcher.stop(timeout=5.0)
            for slot_idx, slot in enumerate(rep.batcher.slots):
                if slot is not None and abort_pending:
                    if slot.req._finish(error=RequestShedError(
                        f"request {slot.req.id} aborted: serving shut "
                        "down", reason="aborted",
                    ), generation=slot.generation):
                        _shed("aborted")
                    rep.batcher._release(slot_idx)
            rep.monitor.stop()
            # final spool AFTER the serve thread stopped: the tallies
            # are final, so the fleet rollup conserves counters exactly
            self._write_replica_spool(rep.batcher, status="exited")
        self._write_set_spool(status="exited")

    # -- replica management ---------------------------------------------
    def _store_scope(self):
        """The ambient-store context every replica/spare build runs
        under — a no-op without a store."""
        if self.artifact_store is not None:
            return self.artifact_store.ambient()
        import contextlib

        return contextlib.nullcontext()

    def _build_model(self, *, elastic: bool):
        t0 = time.perf_counter()
        try:
            with self._device_lock, self._store_scope():
                if elastic and self.ckpt_dir is not None:
                    from .elastic import ElasticRestoreError, restore_elastic

                    try:
                        model, _info = restore_elastic(self.model_fn,
                                                       self.ckpt_dir,
                                                       verbose=False)
                        return model
                    except ElasticRestoreError:
                        pass  # no restorable checkpoint: fresh build below
                return self.model_fn()
        finally:
            # replica cold-start latency (build + compile + restore):
            # scripts/load_check.py reads the p95 to show the artifact
            # store shortening kill-mid-ramp recovery
            self.stats["cold_start_s"].append(time.perf_counter() - t0)

    def _new_batcher(self, model,
                     name: Optional[str] = None) -> ContinuousBatcher:
        if name is None:
            with self._lock:
                name = f"replica{self._counter}"
                self._counter += 1
        return ContinuousBatcher(
            model, self.config, self.queue, name=name,
            fault_injector=self.fault_injector,
            on_dead=self._on_batcher_dead,
            device_lock=self._device_lock,
            slo=self.slo,
        )

    def _activate(self, batcher: ContinuousBatcher) -> _Replica:
        from . import elastic as el
        from .. import obs

        monitor = el.HealthMonitor(
            timeout_s=self.health_timeout_s,
            compile_grace_s=self.compile_grace_s,
            heartbeat_fn=self._thread_heartbeat(batcher),
            heartbeat_interval_s=max(0.05, self.health_timeout_s / 4.0),
            on_hang=lambda info, b=batcher: self._on_hang(b, info),
        )
        batcher.monitor = monitor
        rep = _Replica(name=batcher.name, model=batcher.model,
                       batcher=batcher, monitor=monitor)
        with self._lock:
            self._replicas[rep.name] = rep
        monitor.start()
        batcher.start()
        obs.event("replica_started", cat="serving", replica=rep.name)
        obs.gauge_set("ff_serving_replicas", self.replica_count(),
                      help="live serving replicas")
        return rep

    def _take_spare(self) -> Optional[ContinuousBatcher]:
        """A warm spare whose mesh still matches the live topology —
        activation only needs the latest checkpoint restored onto it.
        A stale spare (topology changed underneath it) is discarded."""
        while True:
            with self._lock:
                if not self._spares:
                    return None
                batcher = self._spares.pop()
            if batcher.model.executor.mesh_is_live():
                if self.ckpt_dir is not None:
                    from .resilience import CheckpointManager

                    with self._device_lock:
                        CheckpointManager(self.ckpt_dir).restore_latest(
                            batcher.model, elastic=True
                        )
                return batcher

    def _add_replica(self, *, elastic: bool = False,
                     allow_spare: bool = False) -> _Replica:
        if allow_spare:
            spare = self._take_spare()
            if spare is not None:
                self.stats["spares_used"] += 1
                return self._activate(spare)
        return self._activate(self._new_batcher(
            self._build_model(elastic=elastic)))

    def _thread_heartbeat(self, batcher: ContinuousBatcher):
        """PR-2 heartbeat transport probing the serve thread: a beat
        that finds the thread dead (crashed outside the step window)
        names it as a straggler, which escalates through on_hang."""

        def beat() -> Optional[list]:
            if batcher.dead or (
                batcher._thread is not None
                and not batcher._thread.is_alive()
                and not batcher._stop.is_set()
            ):
                return [batcher.name]
            return None

        return beat

    def _ensure_checkpoint(self) -> None:
        from .resilience import CheckpointManager

        mgr = CheckpointManager(self.ckpt_dir)
        if mgr.latest_step() is None:
            with self._lock:
                rep = next(iter(self._replicas.values()), None)
            if rep is not None:
                mgr.save(rep.model, step=0,
                         extra_meta={"serving": {"replica": rep.name}})

    def replica_count(self) -> int:
        with self._lock:
            return sum(1 for r in self._replicas.values()
                       if r.batcher.thread_alive())

    def replica_names(self) -> List[str]:
        with self._lock:
            return sorted(self._replicas)

    # -- failover --------------------------------------------------------
    def _on_hang(self, batcher: ContinuousBatcher, info: dict) -> None:
        from .. import obs

        obs.event("replica_hang", cat="serving", replica=batcher.name,
                  **{k: v for k, v in info.items() if k != "step"})
        self._fail_replica(batcher, ReplicaDeathError(
            f"replica {batcher.name} hung: {info.get('kind', 'unknown')}"
        ))

    def _on_batcher_dead(self, batcher: ContinuousBatcher,
                         exc: BaseException) -> None:
        self._fail_replica(batcher, exc)

    def _fail_replica(self, batcher: ContinuousBatcher,
                      exc: BaseException) -> None:
        """Take a replica out of rotation and restart it in the
        background. Idempotent — the watchdog and the serve loop may
        both report the same death.

        Slot rescue is the SERVE THREAD's job (_strand_slots on its
        dead-exit): snapshotting its slots from here would race its
        admission loop — the snapshot can miss a request admitted in
        that instant, which would then hang forever. Only when the
        thread is genuinely wedged (a real hung collective — it will
        never reach its exit path) does this thread steal the slots
        after a grace join."""
        from .. import obs

        with self._lock:
            rep = self._replicas.pop(batcher.name, None)
        if rep is None:
            return  # already handled
        batcher.dead = True
        rep.monitor.stop()
        if batcher._thread is not None and (
            batcher._thread is not threading.current_thread()
        ):
            batcher._thread.join(timeout=5.0)
            if batcher._thread.is_alive():
                # truly wedged: it cannot run its own exit stranding
                logger.warning("replica %s thread is wedged; stealing its "
                               "in-flight slots", batcher.name)
                batcher._strand_slots()
        requeued = batcher.stats["stranded_requeued"]
        self.stats["requeued"] += requeued
        logger.warning("replica %s failed (%s: %s); requeued %d in-flight "
                       "request(s)", batcher.name, type(exc).__name__, exc,
                       requeued)
        obs.event("replica_failover", cat="serving", replica=batcher.name,
                  requeued=requeued, error=type(exc).__name__,
                  detail=str(exc)[:300])
        obs.gauge_set("ff_serving_replicas", self.replica_count(),
                      help="live serving replicas")
        # forensics: the dying replica's KV pool audit + final counters,
        # while its state still exists (obs/flight_recorder.py)
        try:
            kv_pool: dict = {"snapshot": batcher.pool.snapshot()}
            kv_pool["audit"] = batcher.pool.audit().to_dict()
        except Exception as e:  # fflint: disable=FFL002 — forensics only
            kv_pool = {"error": f"{type(e).__name__}: {e}"}
        obs.forensics_dump("replica_death", error=exc,
                           replica=batcher.name, requeued=requeued,
                           stats=dict(batcher.stats), kv_pool=kv_pool)
        # terminal spool: the fleet rollup keeps this replica's final
        # tallies (counter conservation through the kill) and reads the
        # explicit "dead" status without waiting out the age window
        self._write_replica_spool(batcher, status="dead")
        self._spools.pop(batcher.name, None)
        if self._closed:
            return
        with self._lock:
            if self._restarts >= self.max_replica_restarts:
                obs.event("replica_restart_budget_exhausted", cat="serving",
                          replica=batcher.name,
                          restarts=self._restarts)
                return
            self._restarts += 1
            self._pending_restarts += 1
            restarts = self._restarts
        threading.Thread(
            target=self._restart_replica, args=(batcher.name, restarts),
            daemon=True, name=f"ff-serve-restart-{batcher.name}",
        ).start()

    @staticmethod
    def pool_release_quiet(batcher: ContinuousBatcher, slot: _Slot) -> None:
        # sweeps that legitimately race the serve loop's own release
        # (retirement / dead-exit stranding may have freed the slot
        # already) pass missing_ok so the typed double-release guard
        # stays armed for real failover bugs
        try:
            batcher.pool.release(slot.seq_key, missing_ok=True)
        except Exception:  # fflint: disable=FFL002 — best-effort cleanup
            pass

    def _restart_replica(self, dead_name: str, attempt: int) -> None:
        from .. import obs

        time.sleep(self.restart_backoff_s * (2.0 ** (attempt - 1)))
        try:
            rep = self._add_replica(elastic=True, allow_spare=True)
        except BaseException as e:
            logger.exception("restart of dead replica %s failed", dead_name)
            obs.event("replica_restart_failed", cat="serving",
                      replica=dead_name, error=type(e).__name__,
                      detail=str(e)[:300])
            return
        finally:
            with self._lock:
                self._pending_restarts -= 1
        self.stats["restarts"] += 1
        obs.count("ff_replica_restarts_total",
                  help="serving replicas restarted after death/hang")
        obs.event("replica_restarted", cat="serving", dead=dead_name,
                  replacement=rep.name, attempt=attempt,
                  elastic=self.ckpt_dir is not None)

    # -- autoscaling -----------------------------------------------------
    def _autoscale_loop(self) -> None:
        from .. import obs

        while not self._scaler_stop.wait(self.autoscale_interval_s):
            depth = len(self.queue)
            self._observe_fleet(depth)
            with self._lock:
                pending = self._pending_restarts
            # replicas mid-restart count toward capacity: scaling up to
            # "replace" one that failover is already replacing would
            # over-provision, and the later idle scale-down would drain
            # a replica that real traffic still needs
            n = self.replica_count() + pending
            slo_pressure = self.slo.should_scale_up()
            if ((depth >= self.scale_up_queue_depth or slo_pressure)
                    and n < self.max_replicas):
                try:
                    rep = self._add_replica(allow_spare=True)
                except BaseException as e:
                    obs.event("replica_scale_up_failed", cat="serving",
                              error=type(e).__name__, detail=str(e)[:300])
                    continue
                self.stats["scale_ups"] += 1
                # the sentinel saw this tick's observations already
                # (_observe_fleet runs first), so the pressure that
                # motivated this scale-up is in its blame window
                blame = self.sentinel.blame(
                    max_age_s=max(5.0, 20 * self.autoscale_interval_s))
                obs.event("replica_scale_up", cat="serving",
                          replica=rep.name, queue_depth=depth,
                          cause=("slo" if slo_pressure
                                 and depth < self.scale_up_queue_depth
                                 else "queue_depth"),
                          anomaly=blame or "",
                          slo_violation_rate=round(
                              self.slo.violation_rate(), 4))
                self._idle_since = None
                continue
            busy = depth > 0 or any(
                r.batcher.active_slots for r in self._replicas.values()
            )
            if busy:
                self._idle_since = None
                continue
            if n <= self.min_replicas:
                continue
            now = time.monotonic()
            if self._idle_since is None:
                self._idle_since = now
                continue
            if now - self._idle_since >= self.scale_down_idle_s:
                self._scale_down_one()
                self._idle_since = None

    def _scale_down_one(self) -> None:
        from .. import obs

        with self._lock:
            victims = [r for r in self._replicas.values()
                       if r.batcher.thread_alive()]
            if len(victims) <= self.min_replicas:
                return
            rep = victims[-1]
            del self._replicas[rep.name]
        # drain, don't kill: draining stops admissions and the loop exits
        # on its own once the last slot retires; a hard stop here would
        # orphan in-flight requests (a silent drop). Stragglers past the
        # grace window are requeued exactly like failover.
        rep.batcher.draining = True
        grace = time.monotonic() + 30.0
        while rep.batcher.active_slots and time.monotonic() < grace:
            time.sleep(0.02)
        for i in range(len(rep.batcher.slots)):
            # take the straggler slot under the batcher's teardown mutex
            # so this sweep and the (still-running) serve loop can't
            # both decref its pages
            with rep.batcher._teardown_lock:
                slot = rep.batcher.slots[i]
                rep.batcher.slots[i] = None
            if slot is None:
                continue
            gen = slot.req._requeue_bump()
            self.pool_release_quiet(rep.batcher, slot)
            if gen is not None:
                slot.req.trace.requeued(rep.name, generation=gen,
                                        scale_down=True)
                self.queue.requeue(slot.req)
                self.stats["requeued"] += 1
        rep.batcher.stop(timeout=5.0)
        rep.monitor.stop()
        self.stats["scale_downs"] += 1
        self._write_replica_spool(rep.batcher, status="exited")
        self._spools.pop(rep.name, None)
        obs.event("replica_scale_down", cat="serving", replica=rep.name)
        obs.gauge_set("ff_serving_replicas", self.replica_count(),
                      help="live serving replicas")

    # -- client API ------------------------------------------------------
    def _latency_p95(self) -> float:
        from .. import obs

        # the SLO monitor's window is fed by EVERY completed request
        # (record_request_stages), not just blocking generate() callers,
        # so it is the preferred signal when populated
        if self.slo.sample_count > 0:
            q = self.slo.latency_quantile(0.95)
            if q == q:  # not NaN
                return q
        tel = obs.active()
        if tel is not None:
            h = tel.metrics.find("ff_serving_latency_seconds")
            if h is not None and getattr(h, "count", 0):
                return h.quantile(0.95)
        return self.latency.quantile(0.95)

    def submit(self, prompt: np.ndarray, *,
               max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None) -> GenerationRequest:
        """Admission-controlled enqueue. Raises (typed, counted):
        RateLimitedError / QueueFullError / DeadlineExceededError. A
        returned request is ADMITTED: it will end in a result or a typed
        error — never silence."""
        if self._closed or not self._started:
            raise ServingConfigError(
                "ReplicaSet is not accepting requests (call start(); "
                "not after stop())"
            )
        req = GenerationRequest(
            prompt,
            max_new_tokens if max_new_tokens is not None
            else self.config.default_max_new_tokens,
            deadline_s=(deadline_s if deadline_s is not None
                        else self.config.default_deadline_s),
        )
        req.trace = mint_request_trace(req.id)
        if self.bucket is not None:
            if self.config.adaptive_rate:
                self._rate_check += 1
                if self._rate_check % 16 == 0:
                    self.bucket.adapt(self._latency_p95(),
                                      self.config.target_p95_s)
            if not self.bucket.try_acquire():
                err = RateLimitedError(
                    f"request {req.id} rate-limited "
                    f"({self.bucket.rate:.1f} req/s)"
                )
                _shed("rate_limited")
                req.trace.shed("rate_limited", stage="submit")
                req._finish(error=err)
                raise err
        self.queue.offer(req)  # sheds typed on full/dead-on-arrival
        self.stats["submitted"] += 1
        return req

    def generate(self, prompt: np.ndarray, *,
                 max_new_tokens: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 timeout: Optional[float] = None) -> np.ndarray:
        """Blocking submit+result; observes the local latency reservoir
        the adaptive rate limiter reads."""
        req = self.submit(prompt, max_new_tokens=max_new_tokens,
                          deadline_s=deadline_s)
        out = req.result(timeout)
        self.latency.observe(time.monotonic() - req.submitted_t)
        return out

    def queue_depth(self) -> int:
        return len(self.queue)

    def aggregate_stats(self) -> dict:
        with self._lock:
            reps = list(self._replicas.values())
        agg = dict(self.stats)
        agg["replicas"] = {r.name: dict(r.batcher.stats) for r in reps}
        agg["queue_depth"] = len(self.queue)
        return agg


class InferenceRequest:
    def __init__(self, inputs: List[np.ndarray],
                 deadline: Optional[float] = None):
        self.id = uuid.uuid4().hex
        self.inputs = inputs
        self.deadline = deadline  # absolute monotonic; None = no deadline
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None


class BatchScheduler:
    """Dynamic batcher (reference: triton/src/instance.cc lifecycle +
    per-request execution, re-thought as a batch queue).

    `max_delay_s`: how long to wait to fill a batch before running partial.

    Fault tolerance (runtime/resilience.py): `infer` raises a typed
    InferenceTimeout (retried under `retry_policy`) instead of asserting,
    and when the worker thread has died — crashed on a batch, or never
    started — falls back to DEGRADED mode, running the request unbatched
    on the caller's thread so the service keeps answering (slower, but
    up). A crashed worker is auto-restarted up to `max_worker_restarts`
    times with exponential backoff (`restart_backoff_s` base); once the
    budget is spent the scheduler stays degraded until the operator
    intervenes. Restart counts surface in `stats["worker_restarts"]`.
    `fault_injector` site ``serving_worker`` kills the worker
    deterministically in tests.

    Deadlines propagate INTO the queue: `infer(timeout=...)` stamps the
    request, and the worker sheds expired requests at dequeue with a
    typed DeadlineExceededError (counted in ff_serving_shed_total)
    instead of burning device time on an answer nobody is waiting for.
    `max_queue_depth` bounds the queue; beyond it `submit` sheds with
    QueueFullError."""

    def __init__(self, model, *, max_delay_s: float = 0.005,
                 retry_policy=None, fault_injector=None,
                 max_worker_restarts: int = 3,
                 restart_backoff_s: float = 0.25,
                 max_queue_depth: Optional[int] = None):
        if model.executor is None:
            raise NotCompiledError("compile() the model first")
        from .resilience import RetryPolicy

        self.model = model
        self.batch_size = model.executor.input_pts[0].material_shape()[0]
        self.max_delay_s = max_delay_s
        self.retry_policy = retry_policy or RetryPolicy(
            max_attempts=2, base_delay_s=0.01, max_delay_s=0.5
        )
        self.fault_injector = fault_injector
        self.max_worker_restarts = max(0, max_worker_restarts)
        self.restart_backoff_s = restart_backoff_s
        self.max_queue_depth = max_queue_depth
        self._q: "queue.Queue[InferenceRequest]" = queue.Queue()
        self._fwd = model.executor.build_forward()
        self._stop = threading.Event()
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._started = False
        self._worker_error: Optional[BaseException] = None
        # guards ALL restart/backoff state: _worker_error, _next_restart_t
        # and the worker_restarts stat — the worker thread and any number
        # of infer() callers race on these
        self._restart_lock = threading.Lock()
        self._next_restart_t = 0.0
        self.stats = {"requests": 0, "batches": 0, "padded_slots": 0,
                      "degraded": 0, "timeouts": 0, "worker_restarts": 0,
                      "shed": 0, "degraded_retries": 0}

    # -- client API ------------------------------------------------------
    def start(self):
        if not self._started:
            self._worker.start()
            self._started = True
        return self

    def stop(self):
        self._stop.set()
        if self._started:
            self._worker.join(timeout=5)

    def worker_alive(self) -> bool:
        return (self._started and self._worker.is_alive()
                and self._worker_error is None)

    def _maybe_restart_worker(self) -> bool:
        """Bounded auto-restart after a worker crash: spawn a fresh worker
        thread once the backoff window has elapsed, at most
        `max_worker_restarts` times. Returns True when a live worker is
        available (already alive, or just restarted); False keeps the
        caller on the degraded path."""
        if self.worker_alive():
            return True
        if not self._started or self._stop.is_set():
            return False
        with self._restart_lock:
            if self.worker_alive():  # another caller beat us to it
                return True
            if self.stats["worker_restarts"] >= self.max_worker_restarts:
                return False  # budget spent: stay degraded
            if time.monotonic() < self._next_restart_t:
                return False  # still backing off: degraded for now
            self.stats["worker_restarts"] += 1
            from .. import obs

            obs.count("ff_serving_worker_restarts_total",
                      help="serving worker threads restarted after crash")
            obs.event("serving_worker_restart", cat="serving",
                      restarts=self.stats["worker_restarts"])
            self._worker_error = None
            self._worker = threading.Thread(target=self._loop, daemon=True)
            self._worker.start()
            return True

    def submit(self, inputs: List[np.ndarray],
               deadline: Optional[float] = None) -> InferenceRequest:
        """Each request carries ONE sample per model input (no batch dim).
        `deadline` is absolute time.monotonic(); the worker sheds the
        request (typed) if it is still queued past it."""
        if (self.max_queue_depth is not None
                and self._q.qsize() >= self.max_queue_depth):
            self.stats["shed"] += 1
            _shed("queue_full")
            raise QueueFullError(
                f"BatchScheduler queue at capacity ({self.max_queue_depth})"
            )
        req = InferenceRequest([np.asarray(a) for a in inputs],
                               deadline=deadline)
        self._q.put(req)
        return req

    def infer(self, inputs: List[np.ndarray], timeout: float = 30.0) -> np.ndarray:
        """Blocking single-sample inference. Timeouts raise
        InferenceTimeout and are retried per `self.retry_policy`; a dead
        worker degrades to direct unbatched execution instead of hanging
        every caller until restart. A request whose deadline passes
        while still queued is shed with DeadlineExceededError (not
        retried, not executed)."""
        from .. import obs
        from .resilience import InferenceTimeout, retry

        t_start = time.perf_counter()
        deadline = time.monotonic() + timeout

        def attempt():
            if not self._maybe_restart_worker():
                return self._infer_direct(inputs)
            req = self.submit(inputs, deadline=deadline)
            if not req.event.wait(timeout):
                self.stats["timeouts"] += 1
                if not self.worker_alive():
                    # died while we waited — the request will never be
                    # answered from the queue
                    return self._degraded_retry(req, inputs)
                raise InferenceTimeout(
                    f"request {req.id} unanswered after {timeout}s "
                    f"(queue depth {self._q.qsize()})"
                )
            if req.error is not None:
                if isinstance(req.error, RequestShedError):
                    raise req.error  # shed on purpose: never re-executed
                # the worker failed ON this batch; answer from the
                # degraded path rather than bubbling its crash to callers
                return self._degraded_retry(req, inputs)
            return req.result

        try:
            out = retry(attempt, self.retry_policy)
        except BaseException:
            obs.count("ff_serving_errors_total",
                      help="serving requests that failed after retries")
            raise
        # latency percentiles ride the histogram's reservoir
        # (metrics.prom buckets + p50/p95/p99 in metrics.jsonl)
        obs.observe("ff_serving_latency_seconds",
                    time.perf_counter() - t_start,
                    help="end-to-end serving request latency")
        obs.count("ff_serving_requests_total",
                  help="serving requests answered")
        return out

    def _degraded_retry(self, req: InferenceRequest,
                        inputs: List[np.ndarray]) -> np.ndarray:
        """An in-flight request was orphaned by a worker death and is
        being re-run on the degraded path — surfaced as a structured
        event (the satellite fix: this used to happen silently)."""
        from .. import obs

        self.stats["degraded_retries"] += 1
        obs.count("ff_serving_degraded_retries_total",
                  help="in-flight requests re-run unbatched after a "
                       "worker death")
        obs.event("serving_degraded_retry", cat="serving",
                  request=req.id,
                  error=type(req.error).__name__ if req.error else "orphaned")
        return self._infer_direct(inputs)

    def _infer_direct(self, inputs: List[np.ndarray]) -> np.ndarray:
        """DEGRADED mode: run one request on the caller's thread, padded
        to the compiled batch (same jitted executable, no queue)."""
        self.stats["degraded"] += 1
        arrays = [
            jnp.asarray(np.broadcast_to(
                np.asarray(a)[None], (self.batch_size,) + np.asarray(a).shape
            ))
            for a in inputs
        ]
        out = np.asarray(self._fwd(self.model.state.params, arrays,
                                   self.model.state.net_state))
        return out[0]

    # -- batching loop ---------------------------------------------------
    def _shed_if_expired(self, req: InferenceRequest) -> bool:
        """Dequeue-time deadline check (satellite fix): a request whose
        caller already gave up must not reach the device — shed it with
        a typed error the caller sees instead of a silent late answer."""
        if req.deadline is None or time.monotonic() < req.deadline:
            return False
        self.stats["shed"] += 1
        _shed("deadline")
        req.error = DeadlineExceededError(
            f"request {req.id} expired while queued", stage="dequeue",
        )
        req.event.set()
        return True

    def _loop(self):
        import jax.numpy as jnp

        n_inputs = len(self.model.executor.input_pts)
        while not self._stop.is_set():
            batch: List[InferenceRequest] = []
            try:
                got = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            if not self._shed_if_expired(got):
                batch.append(got)
            fill_by = time.monotonic() + self.max_delay_s
            while len(batch) < self.batch_size:
                remaining = fill_by - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    got = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if not self._shed_if_expired(got):
                    batch.append(got)
            if not batch:
                continue
            try:
                if self.fault_injector is not None:
                    self.fault_injector.fire("serving_worker",
                                             self.stats["batches"])
                pad = self.batch_size - len(batch)
                arrays = []
                for i in range(n_inputs):
                    rows = [r.inputs[i] for r in batch]
                    stacked = np.stack(rows + [rows[-1]] * pad, axis=0)
                    arrays.append(jnp.asarray(stacked))
                out = np.asarray(self._fwd(self.model.state.params, arrays,
                                           self.model.state.net_state))
            except BaseException as e:
                # worker is no longer trustworthy: fail the in-flight
                # requests (their callers re-run degraded) and exit so
                # worker_alive() routes future traffic around the queue
                # until _maybe_restart_worker's backoff window opens.
                # Backoff state is written under the restart lock
                # (satellite fix): infer() callers racing through
                # _maybe_restart_worker read these fields, and an
                # unlocked write could let a restart slip in before the
                # backoff window was published.
                with self._restart_lock:
                    self._worker_error = e
                    self._next_restart_t = time.monotonic() + (
                        self.restart_backoff_s
                        * (2.0 ** self.stats["worker_restarts"])
                    )
                for r in batch:
                    r.error = e
                    r.event.set()
                return
            for j, r in enumerate(batch):
                r.result = out[j]
                r.event.set()
            self.stats["requests"] += len(batch)
            self.stats["batches"] += 1
            self.stats["padded_slots"] += pad
