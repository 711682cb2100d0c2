"""Shared throughput-measurement harness for the benchmark scripts.

One discipline for every bench (bench.py documents the reasoning): batches
pre-staged on device, steps fused through the scan driver (the Legion
trace-replay analog) so per-step host dispatch is amortized, and a scalar
probe that depends on every parameter leaf is fetched to end the timed
window.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def run_throughput(build, *, metric: str, batch: int, label_classes: int,
                   spd: int = 10, chunks: int = 4, mixed: bool = True,
                   label_shape=None) -> float:
    """build(model, batch) adds layers to a fresh FFModel. Prints the
    one-line JSON record and returns samples/s/chip."""
    import jax

    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )

    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.allow_mixed_precision = mixed
    model = FFModel(cfg)
    build(model, batch)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_ACCURACY],
    )
    ex = model.executor
    rng = np.random.RandomState(0)
    xs = []
    for pt in ex.input_pts:
        shape = pt.material_shape()
        if pt.data_type.name.startswith("DT_INT"):
            arr = rng.randint(0, 1000, shape).astype(np.int32)
        else:
            arr = rng.rand(*shape).astype(np.float32)
        xs.append(ex.shard_batch(pt, arr))
    y = jax.numpy.asarray(
        rng.randint(0, label_classes,
                    label_shape or (batch, 1)).astype(np.int32)
    )
    state = model.state
    probe = jax.jit(
        lambda params: sum(
            leaf.reshape(-1)[0].astype(jax.numpy.float32)
            for leaf in jax.tree_util.tree_leaves(params)
        )
    )

    def sync(st):
        return float(np.asarray(probe(st.params)))

    scan = ex.build_train_scan()
    stacked = [jax.numpy.broadcast_to(x, (spd,) + x.shape) for x in xs]
    ys = jax.numpy.broadcast_to(y, (spd,) + y.shape)
    keys = jax.random.split(jax.random.PRNGKey(0), spd)
    # two warmups: the second absorbs the donated-layout recompile
    for _ in range(2):
        state, _ = scan(state, stacked, ys, keys)
    sync(state)
    t0 = time.perf_counter()
    for _ in range(chunks):
        state, _ = scan(state, stacked, ys, keys)
    sync(state)
    dt = time.perf_counter() - t0
    iters = spd * chunks
    n_chips = max(1, len(jax.devices()))
    sps = batch * iters / dt / n_chips
    print(json.dumps({
        "metric": metric,
        "value": round(sps, 2),
        "unit": "samples/s/chip",
        "vs_baseline": None,
    }))
    return sps
