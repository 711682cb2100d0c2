"""chip_smoke.py — does the system still start on the chip?

One process drives the two main paths once, through the entry points a user
calls, at the full width of the model the repo's chip history belongs to
(hidden 1024, 16 heads, 12 layers), with seeded random weights:

  kernels   every Pallas kernel compiled (interpret=False) at the shapes the
            full-width model produces, against its jnp reference; the gated
            delta rule's chunked form against its recurrence at the hybrid
            serving cell's head sizes, buckets of 256..4,096 with a masked tail
  trainer   FFModel -> build_transformer -> compile() -> fit(), seq 512,
            batch 8, mixed precision; one dispatch per step and several steps
            per dispatch; the loss must be finite and falling
  server    the causal LM of examples/python/decoder_lm.py (vocab 32k,
            max_len 1024, 8 slots) -> compile() -> compile_decode() ->
            AdmissionQueue + ContinuousBatcher; requests of different prompt
            lengths answered and checked against the full forward
  four chips (only where four are visible) the same trainer under pure data
            parallelism and under the Unity search, against the one-chip loss

It checks that nothing hid the device on the way: the lowered train step and
batched decode step hold the Mosaic custom calls, the fallback counters stayed
at zero, the decode-searched strategy is the one serving.

It proves the path runs and is right. It states no rate: the wall times it
prints are information for whoever reads the log, not results.

Exit code 0 and a last line {"ok": true, "device": {"platform", "kind",
"count"}} -- those keys and no others -- only on a TPU with every phase passed;
the line before it, `summary {...}`, carries the detail and ends with
"claim": null. Without a TPU it exits 2 and prints no result.
`--cpu-rehearsal` walks the same code at toy widths on the CPU (Pallas
interpreter) to debug the script itself; it proves nothing about the chip and
says so.
"""
import contextlib
import io
import json
import logging
import math
import os
import sys
import time
import traceback

REHEARSAL = "--cpu-rehearsal" in sys.argv[1:]
# FFConfig() parses sys.argv the way the reference's FFConfig does
sys.argv = sys.argv[:1]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------
if REHEARSAL:
    HIDDEN, HEADS, LAYERS, SEQ, BATCH = 64, 4, 2, 32, 8
    VOCAB, MAX_LEN, SLOTS = 128, 64, 4
    PROMPT_LENS = [2, 5, 9, 17, 40]
    FLASH_SHAPES = [(8, 32, jnp.float32, 0.0), (8, 32, jnp.float32, 0.1)]
    DECODE_LENGTHS = [1, 16, 17, 64]
    # (heads, key size, value size), then (bucket, real tokens) per row
    DELTA_HEADS, DELTA_BLOCKS = (2, 16, 8), [(64, 50), (128, 128), (128, 0)]
    # grouped-query paged decode: (query heads, key-value heads, head size)
    GQA_HEADS = (4, 2, 64)
    # Mamba-2: (heads, head size, groups, state size, chunk), (bucket, real)
    SSM_SIZES, SSM_BLOCKS = (4, 8, 2, 16, 16), [(32, 20), (8, 8), (48, 33)]
    # expert bank: (hidden, experts, held, top k, width, shared width), tokens
    BANK_SIZES, BANK_TOKENS = (32, 8, 4, 2, 24, 48), [4, 50]
    # window attention: (query heads, key-value heads, head size, window,
    # slots), the prefill buckets, the position whose YaRN angles are checked
    WINDOW_SIZES, WINDOW_BUCKETS, YARN_POSITION = (6, 2, 64, 16, 4), [64], 63
    YARN = dict(theta=500000.0, dim=32, scaling="yarn", factor=128.0,
                original_max_position_embeddings=16)
else:
    HIDDEN, HEADS, LAYERS, SEQ, BATCH = 1024, 16, 12, 512, 8
    VOCAB, MAX_LEN, SLOTS = 32000, 1024, 8
    PROMPT_LENS = [3, 17, 64, 130, 500, 900]
    # (batch*heads, seq, dtype, dropout): the train step's own shape, then
    # the largest tile flash_supported admits
    FLASH_SHAPES = [
        (BATCH * HEADS, SEQ, jnp.bfloat16, 0.0),
        (BATCH * HEADS, SEQ, jnp.bfloat16, 0.1),
        (HEADS, 1024, jnp.float32, 0.0),
        (HEADS, 1024, jnp.bfloat16, 0.0),
    ]
    DECODE_LENGTHS = [1, 16, 17, 100, 333, 512, 1000, 1024]
    # the gated delta rule at the hybrid serving cell's head sizes: every
    # bucket a prompt of 256..3,584 tokens takes, each with a masked tail
    DELTA_HEADS = (30, 96, 192)
    DELTA_BLOCKS = [(256, 256), (512, 300), (1024, 1000), (2048, 1536),
                    (4096, 3584)]
    # the state-space, expert and grouped-query serving cell's sizes: 32
    # query heads over 2 key-value heads of 128 (a pool row of 256 lanes);
    # 64 Mamba-2 heads of 64 in 8 groups, state 128, chunk 128, the buckets
    # a prompt of 32..1,024 takes; 64 held of 128 experts of 1,856, top 6,
    # at a decode step's 64 tokens and at a prefill's 1,024
    GQA_HEADS = (32, 2, 128)
    SSM_SIZES = (64, 64, 8, 128, 128)
    SSM_BLOCKS = [(32, 32), (64, 40), (256, 200), (1024, 777)]
    BANK_SIZES, BANK_TOKENS = (2688, 128, 64, 6, 1856, 3712), [64, 1024]
    # the window-attention serving cell's sizes: 72 query heads over 8
    # key-value heads of 128, window 512 (a ring of 32 pages), 32 slots; the
    # shortest and the longest bucket a prompt of 128..6,144 takes a banded
    # prefill at; a full layer's YaRN at the cell's last position
    WINDOW_SIZES, WINDOW_BUCKETS, YARN_POSITION = \
        (72, 8, 128, 512, 32), [1024, 8192], 8191
    YARN = dict(theta=500000.0, dim=64, scaling="yarn", factor=128.0,
                original_max_position_embeddings=8192,
                attention_factor=1.4852030263919618)
HEAD_DIM = HIDDEN // HEADS
PAGE = 16
NEW_TOKENS = 8
STEPS_PER_EPOCH, EPOCHS = 4, 3
STEPS_PER_DISPATCH = 2

# tolerances, stated once
KERNEL_TOL = 2e-2      # max |kernel - reference| / max |reference|; bf16 has
#                        8 mantissa bits and f32 dots on the MXU default to
#                        bf16 passes, so 2e-2 is "same numbers", a wrong
#                        kernel is off by O(1)
SERVE_LOGP_TOL = 0.1   # a generated token's reference log-prob may trail the
#                        reference argmax by this much (near-ties between two
#                        lowerings); a wrong token trails by the spread of the
#                        logits, several times this
LOSS_TOL = 1e-3        # relative, four-chip vs one-chip epoch loss (bf16)
DROP_TOL = 0.25        # relative, four-chip vs one-chip loss DECREASE over
#                        the run — the part of the loss training moved


def log(msg=""):
    print(msg, flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def mosaic_calls(lowered_text: str) -> int:
    return lowered_text.count("tpu_custom_call")


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def dense_attention(q, k, v, *, causal, keep=None, rate=0.0):
    """Plain f32 attention over folded (b*h, s, d) operands; `keep` is the
    library's own dropout oracle (attention_dropout_mask)."""
    with jax.default_matmul_precision("highest"):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(q.shape[-1])
        if causal:
            sq, sk = s.shape[-2:]
            s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if keep is not None:
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
        return jnp.einsum("bqk,bkd->bqd", p, v)


def phase_kernels(ctx):
    from flexflow_tpu.kernels.attention import (
        attention_dropout_mask,
        flash_attention_folded,
        flash_supported,
    )
    from flexflow_tpu.kernels.decode import (
        paged_decode_reference,
        paged_flash_decode,
    )

    interpret = REHEARSAL  # compiled on the chip, always
    rng = np.random.RandomState(0)
    for bh, seq, dtype, rate in FLASH_SHAPES:
        assert flash_supported(seq, seq)
        q, k, v, do = (jnp.asarray(rng.randn(bh, seq, HEAD_DIM), dtype)
                       for _ in range(4))
        seeds = jnp.array([0x1234, 0xBEEF], jnp.uint32) if rate else None
        keep = (attention_dropout_mask(seeds, rate, bh, seq, seq)
                if rate else None)

        def ours(q, k, v):
            return flash_attention_folded(q, k, v, True, interpret,
                                          dropout=rate, seeds=seeds)

        def ref(q, k, v):
            return dense_attention(q, k, v, causal=True, keep=keep, rate=rate)

        out, vjp = jax.vjp(jax.jit(ours), q, k, v)
        grads = jax.jit(vjp)(do)
        out_r, vjp_r = jax.vjp(jax.jit(ref), q, k, v)
        grads_r = jax.jit(vjp_r)(do.astype(jnp.float32))
        errs = [rel_err(out, out_r)] + [
            rel_err(g, gr) for g, gr in zip(grads, grads_r)]
        name = (f"flash fwd+bwd bh={bh} {seq}x{seq} d{HEAD_DIM} "
                f"{jnp.dtype(dtype).name} dropout={rate}")
        log(f"  {name}: rel err out/dq/dk/dv = "
            + "/".join(f"{e:.1e}" for e in errs))
        assert all(np.isfinite(e) and e < KERNEL_TOL for e in errs), name
        ctx["kernels"].append(name)

    pages_per_slot = MAX_LEN // PAGE
    slots = len(DECODE_LENGTHS)
    for dtype in (jnp.float32, jnp.bfloat16):
        q = jnp.asarray(rng.randn(slots, HEADS, HEAD_DIM), dtype)
        # the pool in the cache's own layout: a page is PAGE positions of
        # all heads
        kp, vp = (jnp.asarray(
            rng.randn(slots * pages_per_slot, PAGE, HEADS, HEAD_DIM), dtype)
            for _ in range(2))
        # scattered physical pages, ragged lengths (one token .. full)
        table = jnp.asarray(rng.permutation(slots * pages_per_slot)
                            .reshape(slots, pages_per_slot), jnp.int32)
        lengths = jnp.asarray(DECODE_LENGTHS, jnp.int32)
        out = jax.jit(lambda *a: paged_flash_decode(
            *a, interpret=interpret))(q, kp, vp, table, lengths)
        with jax.default_matmul_precision("highest"):
            want = paged_decode_reference(q, kp, vp, table, lengths)
        err = rel_err(out, want)
        name = (f"paged_flash_decode slots={slots} heads={HEADS} "
                f"d{HEAD_DIM} max_len={MAX_LEN} page={PAGE} "
                f"{jnp.dtype(dtype).name}")
        log(f"  {name}: rel err = {err:.1e}")
        assert np.isfinite(err) and err < KERNEL_TOL, name
        ctx["kernels"].append(name)

    # the gated delta rule (ops/linear_attention.py): the chunked form on a
    # bucket whose tail is masked, against the recurrence over the real
    # tokens alone, from a state that is not zero
    from flexflow_tpu.ops.linear_attention import (delta_rule_chunked,
                                                   delta_rule_step)

    h, dk, dv = DELTA_HEADS

    def recurrence(S, q, k, v, g, beta):
        def token(S, c):
            o, S = delta_rule_step(S, *c)
            return S, o
        S, o = jax.lax.scan(token, S, tuple(
            jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), S

    for bucket, real in DELTA_BLOCKS:
        q, k = (rng.randn(1, bucket, h, dk).astype(np.float32)
                for _ in range(2))
        q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        v = rng.randn(1, bucket, h, dv).astype(np.float32)
        live = (np.arange(bucket) < real)[None, :, None]
        beta = np.where(live, 2.0 * rng.rand(1, bucket, h), 0.0) \
            .astype(np.float32)
        g = np.where(live, np.log(rng.uniform(0.9, 0.999, (1, bucket, h))),
                     0.0).astype(np.float32)
        S0 = jnp.asarray(0.1 * rng.randn(1, h, dv, dk), jnp.float32)
        o, S = jax.jit(delta_rule_chunked)(S0, q, k, v, g, beta)
        o_r, S_r = jax.jit(recurrence)(
            S0, *(a[:, :real] for a in (q, k, v, g, beta)))
        errs = [rel_err(S, S_r)] + (
            [rel_err(o[:, :real], o_r)] if real else [])
        name = (f"delta_rule_chunked heads={h} dk={dk} dv={dv} bucket="
                f"{bucket} real={real}")
        log(f"  {name}: rel err state/out = "
            + "/".join(f"{e:.1e}" for e in errs))
        assert all(np.isfinite(e) and e < KERNEL_TOL for e in errs), name
        ctx["kernels"].append(name)

    # grouped-query heads in the paged kernel: a pool row holds the
    # key-value heads alone and a query head reads its group's lanes
    hq, hkv, d = GQA_HEADS
    q = jnp.asarray(rng.randn(slots, hq, d), jnp.bfloat16)
    kp, vp = (jnp.asarray(rng.randn(slots * pages_per_slot, PAGE, hkv, d),
                          jnp.bfloat16) for _ in range(2))
    out = jax.jit(lambda *a: paged_flash_decode(
        *a, interpret=interpret))(q, kp, vp, table, lengths)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_reference(q, kp, vp, table, lengths)
    err = rel_err(out, want)
    name = (f"paged_flash_decode grouped slots={slots} heads={hq} "
            f"kv_heads={hkv} d{d} max_len={MAX_LEN} page={PAGE} bfloat16")
    log(f"  {name}: rel err = {err:.1e}")
    assert np.isfinite(err) and err < KERNEL_TOL, name
    ctx["kernels"].append(name)

    # the Mamba-2 recurrence (ops/state_space.py): the chunked form on a
    # bucket whose tail is masked (dt = 0), against the one-token step over
    # the real tokens alone, from a state that is not zero
    from flexflow_tpu.ops.state_space import ssd_chunked, ssm_step

    h, p, g, n, chunk = SSM_SIZES

    def ssm_recurrence(S, x, B, C, dt, A):
        def token(S, c):
            y, S = ssm_step(S, *c, A)
            return S, y
        S, y = jax.lax.scan(token, S, tuple(
            jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt)))
        return jnp.moveaxis(y, 0, 1), S

    A = -np.exp(rng.uniform(-1.0, 1.0, h)).astype(np.float32)
    for bucket, real in SSM_BLOCKS:
        x = rng.randn(1, bucket, h, p).astype(np.float32)
        B, C = (rng.randn(1, bucket, g, n).astype(np.float32) / np.sqrt(n)
                for _ in range(2))
        live = (np.arange(bucket) < real)[None, :, None]
        dt = np.where(live, rng.uniform(0.001, 0.1, (1, bucket, h)), 0.0) \
            .astype(np.float32)
        S0 = jnp.asarray(0.1 * rng.randn(1, h, p, n), jnp.float32)
        y, S = jax.jit(ssd_chunked, static_argnums=6)(
            S0, x, B, C, dt, A, min(chunk, bucket))
        y_r, S_r = jax.jit(ssm_recurrence)(
            S0, *(a[:, :real] for a in (x, B, C, dt)), A)
        errs = [rel_err(S, S_r), rel_err(y[:, :real], y_r)]
        name = (f"ssd_chunked heads={h} p={p} groups={g} state={n} bucket="
                f"{bucket} real={real}")
        log(f"  {name}: rel err state/out = "
            + "/".join(f"{e:.1e}" for e in errs))
        assert all(np.isfinite(e) and e < KERNEL_TOL for e in errs), name
        ctx["kernels"].append(name)

    # the expert bank (ops/moe.py): router, the sorted grouped product over
    # the held experts and the shared expert, against every held expert
    # applied to every token under the router's own mask
    from flexflow_tpu.ff_types import DataType, OperatorType
    from flexflow_tpu.ops.moe import ExpertBankParams, route
    from flexflow_tpu.ops.registry import FwdCtx, get_op_def

    e, experts, held, top_k, width, shared = BANK_SIZES
    bank = ExpertBankParams(experts, 0, held, top_k, width, shared, 2.5)
    op = get_op_def(OperatorType.OP_EXPERT_BANK)
    dtype = jnp.float32 if REHEARSAL else jnp.bfloat16
    w = {spec.name: jnp.asarray(
        rng.randn(*spec.shape) / np.sqrt(spec.shape[-2] if len(spec.shape) > 1
                                         else 1e30), dtype)
         for spec in op.weights(bank, [(1, e)], [DataType.DT_FLOAT])}

    def dense_bank(w, x):
        with jax.default_matmul_precision("highest"):
            f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
            x = x.astype(jnp.float32)
            ids, gate = route(bank, f32["router"], f32["b_corr"], x)
            mask = jnp.sum(jax.nn.one_hot(ids, experts) * gate[..., None], 1)
            act = lambda t: jnp.square(jnp.maximum(t, 0.0))  # noqa: E731
            y = (jnp.einsum(
                "ntf,nfe->nte", act(jnp.einsum("te,nef->ntf", x, f32["w_up"])),
                f32["w_down"]) * mask.T[:held, :, None]).sum(0)
            return y + act(x @ f32["shared_up"]) @ f32["shared_down"]

    for tokens in BANK_TOKENS:
        x = jnp.asarray(rng.randn(tokens, e), dtype)
        (y,) = jax.jit(lambda w, x: op.forward(
            bank, w, [x], FwdCtx(training=False)))(w, x)
        err = rel_err(y, jax.jit(dense_bank)(w, x))
        name = (f"expert_bank hidden={e} held={held}/{experts} top{top_k} "
                f"width={width} tokens={tokens} {jnp.dtype(dtype).name}")
        log(f"  {name}: rel err = {err:.1e}")
        assert np.isfinite(err) and err < KERNEL_TOL, name
        ctx["kernels"].append(name)

    # window attention (ops/attention.py): the paged kernel on a ring pool,
    # rings full and filling; the banded prefill over a bucket, behind a
    # ring's worth of positions below 0 as a first block meets them; YaRN's
    # angles at the last position
    from flexflow_tpu.kernels.attention import _chunk_scan
    from flexflow_tpu.ops.attention import RotaryParams, apply_rotary

    heads, kv, d, window, slots = WINDOW_SIZES
    ring_pages = window // PAGE
    dtype = jnp.float32 if REHEARSAL else jnp.bfloat16
    q = jnp.asarray(rng.randn(slots, heads, d), dtype)
    kp, vp = (jnp.asarray(rng.randn(slots * ring_pages, PAGE, kv, d), dtype)
              for _ in range(2))
    table = jnp.asarray(rng.permutation(slots * ring_pages)
                        .reshape(slots, ring_pages), jnp.int32)
    # min(t + 1, ring): half the slots past the window, the rest on the way
    lengths = jnp.asarray([window if i % 2 else 1 + (i * 37) % window
                           for i in range(slots)], jnp.int32)
    out = jax.jit(lambda *a: paged_flash_decode(
        *a, interpret=interpret))(q, kp, vp, table, lengths)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_reference(q, kp, vp, table, lengths)
    err = rel_err(out, want)
    name = (f"paged_flash_decode ring slots={slots} heads={heads}/{kv} "
            f"d{d} ring={window} page={PAGE} {jnp.dtype(dtype).name}")
    log(f"  {name}: rel err = {err:.1e}")
    assert np.isfinite(err) and err < KERNEL_TOL, name
    ctx["kernels"].append(name)

    for bucket in WINDOW_BUCKETS:
        q = jnp.asarray(rng.randn(1, bucket, heads, d), dtype)
        k, v = (jnp.asarray(rng.randn(1, window + bucket, heads, d), dtype)
                for _ in range(2))
        out = jax.jit(lambda q, k, v: _chunk_scan(
            q, k, v, causal=True, chunk_size=min(256, window), q_offset=0,
            kv_offset=-window, window=window)[0])(q, k, v)
        errs = []
        # the first rows, rows across a block's edge, the last rows
        for lo in sorted({0, bucket // 2 - 3, bucket - min(128, bucket)}):
            hi = min(lo + 128, bucket)
            with jax.default_matmul_precision("highest"):
                f32 = jnp.float32
                rows = jnp.arange(lo, hi)[:, None]
                cols = jnp.arange(-window, bucket)[None, :]
                seen = (cols <= rows) & (cols > rows - window) & (cols >= 0)
                sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi].astype(f32),
                                k.astype(f32)) / math.sqrt(d)
                pr = jax.nn.softmax(jnp.where(seen, sc, -1e30), -1)
                want = jnp.einsum("bhqk,bkhd->bqhd", pr, v.astype(f32))
            errs.append(rel_err(out[:, lo:hi], want))
        name = (f"banded prefill bucket={bucket} heads={heads} d{d} "
                f"window={window} {jnp.dtype(dtype).name}")
        log(f"  {name}: rel err of the row blocks = "
            + "/".join(f"{e:.1e}" for e in errs))
        assert all(np.isfinite(e) and e < KERNEL_TOL for e in errs), name
        ctx["kernels"].append(name)

    rope = RotaryParams(**YARN)
    x = rng.randn(1, 2, 4, d).astype(np.float32)
    at = np.array([YARN_POSITION - 1, YARN_POSITION])
    got = np.asarray(jax.jit(lambda x, p: apply_rotary(rope, x, p))(
        jnp.asarray(x), jnp.asarray(at)))
    from flexflow_tpu.ops.attention import rotary_table

    inv, factor = rotary_table(rope, d)
    ang = at[:, None].astype(np.float64) * inv.astype(np.float64)
    cos, sin = (np.concatenate([f(ang), f(ang)], -1)[None, :, None, :] * factor
                for f in (np.cos, np.sin))
    rot = 2 * len(inv)
    xr = x[..., :rot].astype(np.float64)
    half = np.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    want = np.concatenate([xr * cos + half * sin, x[..., rot:]], -1)
    err = rel_err(got, want)
    name = f"yarn rotary dim={rot} of d{d} at position {YARN_POSITION}"
    log(f"  {name}: rel err = {err:.1e}")
    assert np.isfinite(err) and err < KERNEL_TOL, name
    ctx["kernels"].append(name)


# ---------------------------------------------------------------------------
# phase: trainer
# ---------------------------------------------------------------------------
def train_data():
    rng = np.random.RandomState(0)
    n = BATCH * STEPS_PER_EPOCH
    x = rng.randn(n, SEQ, HIDDEN).astype(np.float32)
    y = rng.randn(n, SEQ, HIDDEN).astype(np.float32)
    return x, y


def run_trainer(ctx, name, *, chips, steps_per_dispatch=1, search_budget=-1):
    """The headline encoder through the normal entry points. Returns the
    per-epoch mean squared error (PerfMetrics, what fit() reports)."""
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)
    from flexflow_tpu.models.transformer import build_transformer

    t0 = time.perf_counter()
    cfg = FFConfig()
    cfg.batch_size = BATCH
    cfg.workersPerNode = chips
    cfg.allow_mixed_precision = True
    cfg.iterations_per_dispatch = steps_per_dispatch
    cfg.search_budget = search_budget
    cfg.only_data_parallel = False
    model = FFModel(cfg)
    build_transformer(model, batch_size=BATCH, seq_length=SEQ,
                      hidden_size=HIDDEN, num_heads=HEADS, num_layers=LAYERS)
    model.compile(optimizer=SGDOptimizer(lr=0.01),
                  loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
                  metrics=[MetricsType.METRICS_MEAN_SQUARED_ERROR])
    ex = model.executor
    mesh_shape = {k: int(v) for k, v in ex.mesh.shape.items()}
    chip = model._build_cost_model().machine.chip
    log(f"  mesh {mesh_shape} over {ex.mesh.devices.size} device(s); "
        f"strategy {model.strategy_provenance.get('source')}; search priced "
        f"with chip spec '{chip.name}' ({chip.peak_flops_bf16 / 1e12:.0f} "
        f"TFLOP/s bf16, {chip.hbm_bandwidth / 1e9:.0f} GB/s)")
    # (at toy widths the search may well prefer one device: a rehearsal
    # only logs the mesh)
    spans = ex.mesh.devices.size == chips
    assert spans or REHEARSAL, (ex.mesh.devices.size, chips)

    x, y = train_data()
    losses = []
    for epoch in range(EPOCHS):
        # fit() prints the reference's ELAPSED/THROUGHPUT line whatever
        # `verbose` says; over 4 steps with compilation inside, it is not
        # a rate anyone should quote, so it is passed on labelled as such
        said = io.StringIO()
        with contextlib.redirect_stdout(said):
            pm = model.fit(x, y, epochs=1, verbose=False)
        for line in said.getvalue().splitlines():
            log(f"  [fit() printed, information only] {line}")
        assert pm.train_all == len(x), (pm.train_all, len(x))
        losses.append(pm.mse_loss / pm.train_rows)
    log(f"  {EPOCHS} epochs x {STEPS_PER_EPOCH} steps, "
        f"{steps_per_dispatch} step(s) per dispatch: epoch mse = "
        + " ".join(f"{v:.8f}" for v in losses))
    assert all(np.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    # what the step lowered to: the fused Pallas kernels, not a fallback
    in_pt = ex.input_pts[0]
    bx = [ex.shard_batch(in_pt, x[:BATCH])]
    by = ex.put_replicated(y[:BATCH])
    key = ex.put_replicated(jax.random.PRNGKey(0))
    text = ex.build_train_step().lower(model.state, bx, by, key).as_text()
    calls = mosaic_calls(text)
    log(f"  lowered train step: {calls} Mosaic custom call(s) "
        f"(flash forward + backward for {LAYERS} layers = {2 * LAYERS})")
    if not REHEARSAL:
        assert calls == 2 * LAYERS, calls

    if chips > 1 and spans:
        devices = set(ex.mesh.devices.flat)
        assert len(devices) == chips
        for leaf in jax.tree_util.tree_leaves(model.state.params):
            assert set(leaf.sharding.device_set) == devices, leaf.sharding
        assert set(bx[0].sharding.device_set) == devices, bx[0].sharding
        if not REHEARSAL:
            in_use = {d.id: d.memory_stats()["bytes_in_use"] for d in devices}
            log(f"  bytes in use per device: {in_use}")
            assert all(v > 0 for v in in_use.values()), in_use
    ctx["trainer"][name] = {
        "mesh": mesh_shape, "epoch_mse": losses,
        "strategy": model.strategy_provenance.get("source"),
        "searched_cost": getattr(model, "searched_cost", None),
        "mosaic_calls": calls,
    }
    log(f"  wall time {time.perf_counter() - t0:.1f} s "
        f"(information: set-up, compilation and {EPOCHS * STEPS_PER_EPOCH} "
        f"steps together)")
    return losses


def agree_with_one_chip(ctx, name):
    """Same seed, same global batches: the four-chip loss must be the
    one-chip loss up to bf16 reduction order."""
    one = ctx["trainer"]["one_chip"]["epoch_mse"]
    four = ctx["trainer"][name]["epoch_mse"]
    worst = max(abs(a - b) / abs(b) for a, b in zip(four, one))
    drop1, drop4 = one[0] - one[-1], four[0] - four[-1]
    drop_err = abs(drop4 - drop1) / abs(drop1)
    log(f"  vs one chip: worst epoch-loss rel diff {worst:.2e} "
        f"(tol {LOSS_TOL}); loss decrease {drop4:.3e} vs {drop1:.3e}, "
        f"rel diff {drop_err:.2e} (tol {DROP_TOL})")
    assert worst < LOSS_TOL and drop_err < DROP_TOL


# ---------------------------------------------------------------------------
# phase: server
# ---------------------------------------------------------------------------
def build_lm(model):
    """examples/python/decoder_lm.py build_lm, at serving width."""
    from flexflow_tpu import ActiMode, AggrMode, DataType

    ids = model.create_tensor((SLOTS, MAX_LEN), DataType.DT_INT32)
    t = model.embedding(ids, VOCAB, HIDDEN, AggrMode.AGGR_MODE_NONE)
    for _ in range(LAYERS):
        t = model.multihead_attention(t, t, t, HIDDEN, HEADS, causal=True)
        t = model.layer_norm(t)
        t = model.dense(t, HIDDEN, ActiMode.AC_MODE_RELU)
    return model.softmax(model.dense(t, VOCAB))


def phase_server(ctx):
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)
    from flexflow_tpu.runtime.serving import (AdmissionQueue,
                                              ContinuousBatcher,
                                              GenerationRequest,
                                              ServingConfig)

    t0 = time.perf_counter()
    cfg = FFConfig()
    cfg.batch_size = SLOTS
    cfg.workersPerNode = 1
    model = FFModel(cfg)
    build_lm(model)
    model.compile(SGDOptimizer(lr=0.01),
                  LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [MetricsType.METRICS_ACCURACY])
    model.compile_decode()
    scfg = ServingConfig(max_len=MAX_LEN, slots=SLOTS, page_size=PAGE,
                         precompile=True, default_deadline_s=900.0)
    queue = AdmissionQueue(max_depth=64)
    batcher = ContinuousBatcher(model, scfg, queue).start()
    log(f"  decode_strategy_active = {batcher.decode_strategy_active}")
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32) for n in PROMPT_LENS]
    try:
        reqs = []
        for p in prompts:
            r = GenerationRequest(p, NEW_TOKENS, deadline_s=900.0)
            queue.offer(r)
            reqs.append(r)
        deadline = time.monotonic() + 900.0
        while not all(r.done() for r in reqs):
            # a dead serve thread fails the phase now, not at the deadline
            if batcher.dead:
                raise RuntimeError(
                    f"serve thread died: {batcher.death_cause!r}"
                ) from batcher.death_cause
            if time.monotonic() > deadline:
                raise TimeoutError("requests unanswered after 900 s")
            time.sleep(0.05)
        outs = [r.result(timeout=1.0) for r in reqs]
    finally:
        batcher.stop(timeout=60.0)
    assert not batcher.dead, batcher.death_cause
    log(f"  {len(outs)} requests answered in "
        f"{time.perf_counter() - t0:.1f} s wall (information: includes the "
        f"searches and {2 + int(math.log2(MAX_LEN))} compilations); "
        f"batcher stats {batcher.stats}")
    assert batcher.decode_strategy_active, (
        "the decode-searched strategy did not fit; serving fell back to "
        "the training lowering")
    assert batcher.stats["finished"] == len(prompts), batcher.stats
    # on the chip the decode step owns its caches and appends in place
    assert batcher.stats["decode_caches_donated"] == int(not REHEARSAL), \
        batcher.stats

    # shape, range, prompt kept
    for p, o in zip(prompts, outs):
        assert o.shape == (len(p) + NEW_TOKENS,), (o.shape, len(p))
        assert np.array_equal(o[:len(p)], p)
        assert o.min() >= 0 and o.max() < VOCAB

    # teacher-forced against the training graph's full causal forward: at
    # every generated position the emitted token must be the reference
    # argmax, or within SERVE_LOGP_TOL of it in log-probability
    ids = np.zeros((SLOTS, MAX_LEN), np.int32)
    for row, o in enumerate(outs[:SLOTS]):
        ids[row, :len(o)] = o
    probs = model.executor.build_forward()(
        model.state.params, [jnp.asarray(ids)], model.state.net_state)
    assert probs.shape == (SLOTS, MAX_LEN, VOCAB), probs.shape
    exact = total = 0
    worst = 0.0
    for row, (p, o) in enumerate(list(zip(prompts, outs))[:SLOTS]):
        pos = np.arange(len(p) - 1, len(o) - 1)  # predicts o[len(p):]
        logp = np.log(np.maximum(np.asarray(probs[row, pos], np.float32),
                                 1e-30))
        assert np.all(np.isfinite(logp))
        gen = o[len(p):]
        gap = logp.max(-1) - logp[np.arange(len(gen)), gen]
        exact += int(np.sum(logp.argmax(-1) == gen))
        total += len(gen)
        worst = max(worst, float(gap.max()))
    log(f"  against the full forward: {exact}/{total} tokens are the "
        f"reference argmax; worst log-prob gap {worst:.3e} "
        f"(tol {SERVE_LOGP_TOL})")
    assert worst < SERVE_LOGP_TOL

    # what the batched decode step lowered to
    dex = model.decode_executor
    init_b, step_b = dex.build_decode(SLOTS, MAX_LEN,
                                      assume_causal=scfg.assume_causal)
    params = model.state.params
    caches = jax.eval_shape(init_b, params, ())
    text = step_b.lower(
        params, caches, jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        [jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32)]).as_text()
    calls = mosaic_calls(text)
    log(f"  lowered batched decode step: {calls} Mosaic custom call(s) "
        f"(paged decode for {LAYERS} layers = {LAYERS})")
    if not REHEARSAL:
        assert calls == LAYERS, calls
    ctx["server"] = {
        "requests": len(outs), "prompt_lens": PROMPT_LENS,
        "argmax_agreement": f"{exact}/{total}", "worst_logp_gap": worst,
        "decode_strategy_active": True, "mosaic_calls": calls,
        "stats": dict(batcher.stats),
    }


# ---------------------------------------------------------------------------
# phase: a loop region at the looped serving cell's sizes
# ---------------------------------------------------------------------------
LOOP_CELL = "serve-ouro2.6b-chat-saturated"
LOOP_PROMPT = 300 if not REHEARSAL else 21   # in the bucket of 512 (32)
LOOP_DECODES = 4


def phase_loop(ctx):
    """The looped serving cell's programs at its own sizes (the whole model:
    48 layers, 4 steps): the paged kernel on the stacked pool read through
    each step's table against the dense reference on that step's strip, then
    one prefill bucket inserted into the batch and decode steps through
    every step's cache, their logits judged by the plain reference as the
    cell's `correct` judges them (the reference logit of the program's
    token against the reference's best, under the cell's limit)."""
    from flexflow_tpu.kernels.decode import (paged_decode_reference,
                                             paged_flash_decode,
                                             paged_view_of_cache)
    from flexflow_tpu.parallel import decode
    from perfbench.harness import runctx, serve, spec

    cell = spec.cell(LOOP_CELL, rehearsal=REHEARSAL)
    builder, ref = spec.family(cell.config)
    z, sv = ref.sizes(cell.config), cell.params["serving"]
    slots, steps, max_len = sv["slots"], z["steps"], sv["max_len"]
    kv, hd = z["kv_heads"], z["head_dim"]
    rng = np.random.RandomState(5)
    k, v = (jnp.asarray(rng.randn(slots, steps, max_len, kv * hd),
                        jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.randn(slots, z["heads"], hd), jnp.bfloat16)
    lengths = jnp.asarray(rng.randint(1, max_len + 1, slots), jnp.int32)
    worst = 0.0
    for u in range(steps):
        kp, vp, table = paged_view_of_cache(k, v, PAGE, step=jnp.int32(u))
        got = jax.jit(lambda *a: paged_flash_decode(
            *a, interpret=REHEARSAL))(q, kp, vp, table, lengths)
        k1, v1, t1 = paged_view_of_cache(k[:, u], v[:, u], PAGE)
        want = paged_decode_reference(
            q, k1.reshape(-1, PAGE, kv, hd), v1.reshape(-1, PAGE, kv, hd),
            t1, lengths)
        worst = max(worst, rel_err(got, want))
    log(f"  paged kernel on the stacked pool {list(kp.shape)}, each of "
        f"{steps} steps: worst rel err {worst:.2e} (tol {KERNEL_TOL})")
    assert worst < KERNEL_TOL

    sc = serve.ServeCell(cell, builder, ref, runctx.Spans())
    sc.build()
    sc.load_seed(41)
    ex, params = sc.model.executor, sc.model.state.params
    bucket = 1 << (LOOP_PROMPT - 1).bit_length()
    prompt = rng.randint(0, z["vocab"], LOOP_PROMPT).astype(np.int32)
    init1, step1 = ex.build_decode(1, max_len)
    initB, stepB = sc.model.decode_executor.build_decode(slots, max_len)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :LOOP_PROMPT] = prompt
    logits, strip = step1(params, decode.compiled_init(init1)(params, ()),
                          jnp.int32(0), [jnp.asarray(padded)],
                          jnp.int32(LOOP_PROMPT), jnp.int32(LOOP_PROMPT - 1))
    tokens = list(prompt) + [int(np.argmax(np.asarray(logits)[0, -1]))]
    passes = int(strip["prefill_counters"]["loop_prefill_passes"])
    caches = decode.insert_row(initB(params, ()), strip, 0,
                               donate=ex.donates_buffers())
    del strip
    for i in range(LOOP_DECODES):
        t = np.full((slots,), len(tokens) - 1, np.int32)
        toks = np.zeros((slots, 1), np.int32)
        toks[0, 0] = tokens[-1]
        logits, caches = stepB(params, caches, jnp.asarray(t),
                               [jnp.asarray(toks)])
        tokens.append(int(np.argmax(np.asarray(logits)[0, 0])))
    loop_passes = int(caches["counters"]["loop_passes"])
    log(f"  prefill of {LOOP_PROMPT} tokens in a bucket of {bucket} "
        f"({passes} loop passes), {LOOP_DECODES} decode steps "
        f"({loop_passes} loop passes each), tokens {tokens[-5:]}")
    assert passes == loop_passes == steps
    del caches, logits
    sc.free()
    row = {"prompt": prompt, "tokens": np.asarray(tokens, np.int32)}
    gap = float(serve.logit_gaps(ref, cell.config, 41, [row])[0].max())
    limit = cell.params["limits"]["worst_logit_gap"]
    log(f"  against the plain reference: worst logit gap {gap:.4f} "
        f"(the cell's limit {limit})")
    assert gap < limit
    ctx["loop"] = {"paged_stacked_rel_err": worst, "worst_logit_gap": gap,
                   "loop_passes": loop_passes}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def main() -> int:
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"jax {jax.__version__}")
    log(f"platform {device['platform']}")
    log(f"device_kind {device['kind']}")
    log(f"device_count {device['count']}")
    if device["platform"] != "tpu" and not REHEARSAL:
        print("chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); this check only means something "
              "on the chip", file=sys.stderr)
        return 2
    if REHEARSAL:
        log("CPU REHEARSAL at toy widths with the Pallas interpreter: this "
            "debugs chip_smoke.py itself and proves NOTHING about the chip")

    logging.basicConfig(level=logging.WARNING)  # a failed native build shows
    import flexflow_tpu.obs as obs
    from flexflow_tpu import native
    from flexflow_tpu.config import enable_compile_cache
    from flexflow_tpu.kernels.attention import pallas_compiled

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    log(f"compile cache {cache_dir}: {entries_before} entries before "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    log(f"native library (MCMC simulator, data loader): "
        f"{'built from these sources' if native.available() else 'BUILD FAILED — Python fallbacks'}"
        f"; compile()'s search itself is the Python DP search "
        f"(search/dp_search.py) either way")
    for var in ("FF_ATTENTION_IMPL", "FF_DECODE_IMPL"):
        assert os.environ.get(var, "auto") == "auto", (
            f"{var} is set: the smoke checks the default dispatch")
    assert pallas_compiled() or REHEARSAL

    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out", "chip_smoke")
    tel = obs.start(obs.TelemetryConfig(dir=os.path.join(out_dir,
                                                         "telemetry")))
    ctx = {"kernels": [], "trainer": {}, "server": None, "loop": None}
    phases = [
        ("kernels", lambda: phase_kernels(ctx)),
        ("train one chip, 1 step per dispatch",
         lambda: run_trainer(ctx, "one_chip", chips=1)),
        (f"train one chip, {STEPS_PER_DISPATCH} steps per dispatch",
         lambda: run_trainer(ctx, "one_chip_scan", chips=1,
                             steps_per_dispatch=STEPS_PER_DISPATCH)),
        ("serve one chip", lambda: phase_server(ctx)),
        ("serve a loop region", lambda: phase_loop(ctx)),
    ]
    if device["count"] >= 4:
        def four(name, **kw):
            run_trainer(ctx, name, chips=4, **kw)
            agree_with_one_chip(ctx, name)

        phases += [
            ("train four chips, data parallel",
             lambda: four("four_chip_dp")),
            ("train four chips, searched (search_budget 20)",
             lambda: four("four_chip_searched", search_budget=20)),
        ]
    failed = []
    try:
        for name, fn in phases:
            log(f"== {name}")
            t0 = time.perf_counter()
            try:
                fn()
                log(f"== {name}: ok ({time.perf_counter() - t0:.1f} s wall, "
                    "information)")
            except Exception:  # every phase runs; any failure fails the run
                traceback.print_exc()
                sys.stderr.flush()
                failed.append(name)
                log(f"== {name}: FAILED")
        fallbacks = {
            name: sum(r["value"] for r in tel.metrics.snapshot()
                      if r["name"] == name)
            for name in ("ff_attention_fallback_total",
                         "ff_decode_fallback_total")
        }
    finally:
        obs.finish()
    log(f"fallback counters: {fallbacks}")
    if any(fallbacks.values()):
        failed.append(f"fallback counters not zero: {fallbacks}")
    if "one_chip" in ctx["trainer"] and "one_chip_scan" in ctx["trainer"]:
        a = ctx["trainer"]["one_chip"]["epoch_mse"]
        b = ctx["trainer"]["one_chip_scan"]["epoch_mse"]
        diff = max(abs(p - q) / abs(p) for p, q in zip(a, b))
        log(f"1 vs {STEPS_PER_DISPATCH} steps per dispatch: worst "
            f"epoch-loss rel diff {diff:.2e} (tol {LOSS_TOL})")
        if not diff < LOSS_TOL:
            failed.append("dispatch paths disagree")
    entries_after = cache_entries(cache_dir)
    log(f"compile cache {cache_dir}: {entries_after} entries after "
        f"({entries_after - entries_before} added)")

    summary = {
        "ok": not failed and not REHEARSAL,
        "device": device,
        "jax": jax.__version__,
        "phases": [n for n, _ in phases],
        "failed": failed,
        "kernels_checked": len(ctx["kernels"]),
        "trainer": ctx["trainer"],
        "server": ctx["server"],
        "loop": ctx["loop"],
        "fallback_counters": fallbacks,
        "compile_cache": {"dir": cache_dir, "before": entries_before,
                          "after": entries_after},
        "claim": None,
    }
    if REHEARSAL:
        summary["rehearsal"] = "CPU, toy widths: proves nothing about the chip"
    log("summary " + json.dumps(summary))
    if REHEARSAL:  # no result line: there is no chip result to state
        log(f"rehearsal {'FAILED' if failed else 'passed'}")
        return 1 if failed else 0
    # the last line is the contract's: exactly these keys, nothing beside them
    print(json.dumps({"ok": not failed, "device": device}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
