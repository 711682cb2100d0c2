"""Where does the bench step spend time? Times the full bench model and
ablations (attention-only stack, dense-only stack) through the scan driver
so per-step dispatch latency is amortized. Prints one JSON line per variant."""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def run(tag: str, *, layers=12, attention=True, mlp=True, impl="auto",
        spd=20, chunks=3):
    os.environ["FF_ATTENTION_IMPL"] = impl
    import jax

    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.ff_types import ActiMode, DataType

    batch, seq, hidden, heads = 8, 512, 1024, 16
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.allow_mixed_precision = True
    model = FFModel(cfg)
    t = model.create_tensor((batch, seq, hidden), DataType.DT_FLOAT)
    for _ in range(layers):
        if attention:
            t = model.multihead_attention(
                t, t, t, hidden, heads, hidden // heads, hidden // heads
            )
        if mlp:
            t = model.dense(t, hidden, ActiMode.AC_MODE_RELU, use_bias=False)
            t = model.dense(t, hidden, ActiMode.AC_MODE_NONE, use_bias=False)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[MetricsType.METRICS_MEAN_SQUARED_ERROR],
    )
    ex = model.executor
    in_pt = ex.input_pts[0]
    rng = np.random.RandomState(0)
    x = ex.shard_batch(in_pt, rng.randn(*in_pt.material_shape()).astype(np.float32))
    y = jax.numpy.asarray(rng.randn(*in_pt.material_shape()).astype(np.float32))
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
    xs = [jax.numpy.broadcast_to(x, (spd,) + x.shape)]
    ys = jax.numpy.broadcast_to(y, (spd,) + y.shape)
    keys = jax.random.split(jax.random.PRNGKey(0), spd)
    for _ in range(2):
        state, _ = scan(state, xs, ys, keys)
    sync(state)
    t0 = time.perf_counter()
    for _ in range(chunks):
        state, _ = scan(state, xs, ys, keys)
    sync(state)
    dt = time.perf_counter() - t0
    iters = spd * chunks
    print(json.dumps({
        "tag": tag, "impl": impl,
        "ms_per_step": round(1e3 * dt / iters, 3),
        "samples_per_s": round(batch * iters / dt, 2),
    }), flush=True)


if __name__ == "__main__":
    import multiprocessing as mp

    # each variant in its own process: FF_ATTENTION_IMPL is read at trace
    # time and jit caches are per-process
    for tag, kw in [
        ("full_auto", {}),
        ("full_flash", {"impl": "flash"}),
        ("full_chunked", {"impl": "chunked"}),
        ("attn_only", {"mlp": False}),
        ("mlp_only", {"attention": False}),
    ]:
        p = mp.Process(target=run, args=(tag,), kwargs=kw)
        p.start()
        p.join()
