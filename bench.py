"""Benchmark driver: trains the reference's headline Transformer benchmark
config (examples/cpp/Transformer defaults: hidden 1024, 16 heads, 12 layers,
seq 512; batch 8 per scripts/osdi22ae/bert.sh) and prints ONE JSON line with
per-chip training throughput.

Runs on whatever jax.devices() provides (one real TPU chip under the driver).
Mixed precision (bf16 compute, f32 master weights) is on — the TPU-native
equivalent of the reference's f32 cuDNN path, since bf16 is the MXU's native
input type.
"""
import json
import os
import time

import numpy as np


def device_fields() -> dict:
    """Where the number was taken, as JAX reports it — every result line
    carries it, so a CPU run can never be read as a chip's."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
    }


def phase_breakdown(model, x, y, key, *, repeats: int, fetch):
    """Per-phase seconds per step: fwd (forward only), bwd (grad step
    minus forward), opt+sync (full train step minus grad step). Measured
    through separately jitted programs over the same batch — the split
    is approximate (XLA fuses differently per program) but stable enough
    to see which phase a perf round moved."""
    import numpy as np

    ex = model.executor

    def timed(fn, *args):
        out = fn(*args)
        fetch(out)
        out = fn(*args)  # second warmup absorbs relayout recompiles
        fetch(out)
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        fetch(out)
        return (time.perf_counter() - t0) / repeats

    fwd = ex.build_forward()
    grad = ex.build_grad_step()
    step = ex.build_train_step(donate=False)
    state = model.state
    fwd_s = timed(lambda: fwd(state.params, [x]))
    grad_s = timed(lambda: grad(state.params, [x], y))
    step_s = timed(lambda: step(state, [x], y, key))
    # the implicit data-parallel grad collectives are the sync phase; on
    # one chip they are zero and the remainder is the optimizer update.
    # Multi-chip: estimated statically (ring all-reduce wire bytes of
    # every replicated weight gradient over ICI) — the jitted step fuses
    # the collectives, so they can't be timed separately.
    sync_s = 0.0
    d = ex.mesh.shape.get("data", 1) if ex.mesh is not None else 1
    if d > 1:
        try:
            from flexflow_tpu.search.cost_model import op_weight_bytes

            machine = model._build_cost_model().machine
            wire = sum(
                2.0 * (d - 1) / d * op_weight_bytes(op)
                for op in model.graph.topo_order()
                if op.weights and not op.is_parallel_op
            )
            sync_s = wire / machine.ici_bandwidth
        except Exception:
            sync_s = 0.0
    return {
        "fwd": round(fwd_s, 6),
        "bwd": round(max(0.0, grad_s - fwd_s), 6),
        "opt": round(max(0.0, step_s - grad_s - sync_s), 6),
        "sync": round(sync_s, 6),
    }


def decode_bench():
    """FF_BENCH_WORKLOAD=decode: serving throughput, not training.

    Builds a CPU-sized decoder-only LM, searches BOTH strategies
    (compile() with the training objective, compile_decode() with the
    HBM-roofline decode objective) and drives the continuous-batching
    loop end to end — admission, prefill, batched single-token decode —
    counting generated tokens. The headline is tokens/s/chip; like the
    zoo series the absolute number is a trend line, so the regression
    gate treats it warn-only until the driver publishes a baseline."""
    import jax

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
    from flexflow_tpu.runtime.serving import (
        AdmissionQueue,
        ContinuousBatcher,
        GenerationRequest,
        ServingConfig,
    )

    smoke = bool(os.environ.get("FF_BENCH_SMOKE"))
    vocab, hidden, heads, layers, max_len = 64, 64, 4, 2, 32
    prompt_len = 4
    cfg = FFConfig()
    cfg.batch_size = 2
    cfg.search_budget = 1
    model = FFModel(cfg)
    ids = model.create_tensor((2, max_len), DataType.DT_INT32)
    t = model.embedding(ids, vocab, hidden, AggrMode.AGGR_MODE_NONE)
    for _ in range(layers):
        t = model.multihead_attention(t, t, t, hidden, heads, causal=True)
        t = model.dense(t, hidden, ActiMode.AC_MODE_RELU)
    t = model.softmax(model.dense(t, vocab))
    model.compile(SGDOptimizer(lr=0.01),
                  LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [MetricsType.METRICS_ACCURACY])
    model.compile_decode()

    def run_round(n_req, new_tokens):
        q = AdmissionQueue(max_depth=max(16, n_req))
        b = ContinuousBatcher(
            model,
            ServingConfig(max_len=max_len, slots=4, page_size=8,
                          precompile=False, default_deadline_s=600.0),
            q,
        ).start()
        rng = np.random.RandomState(0)
        try:
            t0 = time.perf_counter()
            reqs = []
            for _ in range(n_req):
                prompt = rng.randint(0, vocab, prompt_len).astype(np.int32)
                r = GenerationRequest(prompt, new_tokens, deadline_s=600.0)
                q.offer(r)
                reqs.append(r)
            toks = sum(len(r.result(timeout=600.0)) - prompt_len
                       for r in reqs)
            return toks, time.perf_counter() - t0, b.decode_strategy_active
        finally:
            b.stop()

    n_req, new_tokens = (2, 4) if smoke else (16, 16)
    run_round(n_req, new_tokens)  # warmup: jit-compiles prefill + step
    toks, elapsed, active = run_round(n_req, new_tokens)

    n_chips = max(1, len(jax.devices()))
    tokens_per_sec_per_chip = toks / elapsed / n_chips
    print(
        json.dumps(
            {
                "metric": "decode_tokens_throughput",
                "value": round(tokens_per_sec_per_chip, 3),
                "unit": "tokens/s/chip",
                "phases_s_per_step": None,
                "decode_strategy_active": bool(active),
                "smoke": smoke,
                "n_chips": n_chips,
                **device_fields(),
            }
        )
    )


def main():
    import jax

    from flexflow_tpu import (
        FFConfig,
        FFModel,
        LossType,
        MetricsType,
        SGDOptimizer,
    )
    from flexflow_tpu.models.transformer import build_transformer

    # FF_BENCH_WORKLOAD selects the zoo series (docs/models.md):
    #   transformer (default) — the reference's headline config
    #   moe                   — top-k gated expert FFN blocks (CPU-sized)
    #   longctx               — the encoder at long seq, small batch
    #   decode                — continuous-batching serving loop under the
    #                           decode-searched strategy (tokens/s/chip)
    # The zoo series sizes are CPU-scale smoke shapes: their value is the
    # per-workload trend line (and the regression gate treats series
    # without a published baseline as warn-only), not absolute numbers.
    workload = os.environ.get("FF_BENCH_WORKLOAD", "transformer")
    if workload == "decode":
        return decode_bench()
    cfg = FFConfig()
    cfg.allow_mixed_precision = True
    labels = None
    if workload == "moe":
        from flexflow_tpu.models import build_moe_transformer

        batch, seq = 8, 16
        cfg.batch_size = batch
        model = FFModel(cfg)
        build_moe_transformer(
            model, batch_size=batch, seq_length=seq, hidden_size=64,
            num_heads=4, num_layers=2, num_experts=4, top_k=2,
        )
        loss = LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY
        metrics = []
        labels = (batch, seq, 1)
    elif workload == "longctx":
        from flexflow_tpu.models import build_long_context_transformer

        batch, seq = 2, 512
        cfg.batch_size = batch
        model = FFModel(cfg)
        build_long_context_transformer(
            model, batch_size=batch, seq_length=seq, hidden_size=64,
            num_heads=4, num_layers=2,
        )
        loss = LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY
        metrics = []
        labels = (batch, seq, 1)
    elif workload == "transformer":
        batch = 8
        seq, hidden, heads, layers = 512, 1024, 16, 12
        cfg.batch_size = batch
        model = FFModel(cfg)
        build_transformer(
            model,
            batch_size=batch,
            seq_length=seq,
            hidden_size=hidden,
            num_heads=heads,
            num_layers=layers,
        )
        loss = LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE
        metrics = [MetricsType.METRICS_MEAN_SQUARED_ERROR]
    else:
        raise SystemExit(
            f"bench: FF_BENCH_WORKLOAD={workload!r} "
            "(want transformer|moe|longctx|decode)"
        )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=loss,
        metrics=metrics,
    )
    ex = model.executor
    in_pt = ex.input_pts[0]
    rng = np.random.RandomState(0)
    x = ex.shard_batch(in_pt, rng.randn(*in_pt.material_shape()).astype(np.float32))
    if labels is not None:
        y = jax.numpy.asarray(rng.randint(0, 10, labels).astype(np.int32))
    else:
        y = jax.numpy.asarray(
            rng.randn(*in_pt.material_shape()).astype(np.float32))
    key = jax.random.PRNGKey(0)

    state = model.state

    def sync(st):
        jax.block_until_ready(st.params)

    # Measure through the multi-step scan driver (executor.build_train_scan
    # — the Legion trace-replay analog): per-step host dispatch is folded
    # into one XLA program, so the number reflects device throughput, not
    # host dispatch latency. The reference's bench likewise replays a
    # Legion trace per iteration (flexflow_cffi.py:2093-2102).
    scan = ex.build_train_scan()
    smoke = bool(os.environ.get("FF_BENCH_SMOKE"))
    spd = 2 if smoke else 50  # steps per dispatch
    xs = [jax.numpy.broadcast_to(x, (spd,) + x.shape)]
    ys = jax.numpy.broadcast_to(y, (spd,) + y.shape)
    keys = jax.random.split(key, spd)

    # warmup: TWO calls, not one — the first compiles against the
    # init-time param layouts, and its donated output comes back in the
    # executable's preferred layouts, which triggers ONE more compile on
    # the next call; the second warmup absorbs it so the timed loop only
    # measures steady-state execution.
    for _ in range(2):
        state, partials = scan(state, xs, ys, keys)
    sync(state)

    chunks = 1 if smoke else 3
    iters = spd * chunks
    t0 = time.perf_counter()
    for _ in range(chunks):
        state, partials = scan(state, xs, ys, keys)
    sync(state)
    elapsed = time.perf_counter() - t0

    n_chips = max(1, len(jax.devices()))
    samples_per_sec_per_chip = batch * iters / elapsed / n_chips

    # per-phase breakdown (fwd/bwd/opt/sync) — measured AFTER the headline
    # number so its extra compiles can't perturb the timed loop. The scan
    # donated the state it was handed (off the CPU), so the model must hold
    # the live one: model.state's first buffers are gone.
    model.state = state
    phases = phase_breakdown(
        model, x, y, jax.random.PRNGKey(1),
        repeats=2 if smoke else 10, fetch=jax.block_until_ready,
    )

    print(
        json.dumps(
            {
                "metric": f"{workload}_train_throughput",
                "value": round(samples_per_sec_per_chip, 3),
                "unit": "samples/s/chip",
                "phases_s_per_step": phases,
                "smoke": smoke,
                "n_chips": n_chips,
                **device_fields(),
            }
        )
    )


if __name__ == "__main__":
    main()
