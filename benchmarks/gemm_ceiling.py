"""Substantiate the XLA gemm ceiling the bench analysis leans on.

BASELINE.md's attainable-step estimate prices the transformer bench's
projection/FFN gemms at "XLA's observed ~175 TF/s ceiling" — this
artifact MEASURES that number on the current device for exactly the
bench config's gemm shapes (hidden 1024, seq 512, batch 8 → m = 4096
rows), bf16 inputs with f32 accumulation, using the same
scan-differencing methodology as the calibrated microbenchmarks
(search/measure.py — additive carries are invalid for linear ops, the
elementwise sin tie prevents XLA from hoisting the matmul).

Run ON A REAL CHIP from the repo root (no PYTHONPATH):
    python benchmarks/gemm_ceiling.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import numpy as np


def main():
    import jax

    from flexflow_tpu.ff_types import ActiMode, DataType, OperatorType
    from flexflow_tpu.ops.linear import LinearParams
    from flexflow_tpu.pcg.machine_view import MachineView
    from flexflow_tpu.pcg.op import PCGOp
    from flexflow_tpu.pcg.parallel_tensor import ParallelDim, ParallelTensor
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.measure import OperatorMeasurer

    peak_tf = MachineModel().chip.peak_flops_bf16 / 1e12
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    meas = OperatorMeasurer(repeats=256, compute_dtype=jax.numpy.bfloat16)
    view = MachineView(start_device_id=0, dim=(1,), stride=(1,))

    # the bench transformer's per-layer gemm shapes (m = batch*seq = 4096)
    shapes = [
        ("proj_1024x1024", 4096, 1024, 1024),   # q/k/v/o projections (x4)
        ("ffn_up_1024x4096", 4096, 1024, 4096),  # FFN in (x1)
        ("ffn_dn_4096x1024", 4096, 4096, 1024),  # FFN out (x1)
    ]
    results = []
    for name, m, k, n in shapes:
        x = ParallelTensor(dims=[ParallelDim(size=m, degree=1),
                                 ParallelDim(size=k, degree=1)],
                           data_type=DataType.DT_FLOAT)
        op = PCGOp(OperatorType.OP_LINEAR,
                   LinearParams(out_channels=n, use_bias=False,
                                activation=ActiMode.AC_MODE_NONE),
                   [x], name=f"gemm_{name}")
        w = ParallelTensor(dims=[ParallelDim(size=k, degree=1),
                                 ParallelDim(size=n, degree=1)],
                           data_type=DataType.DT_FLOAT, owner_op=op)
        op.weights.append(w)
        op.weight_names.append("kernel")
        op.weight_tags = [("in_channel", "out_channel")]
        out = ParallelTensor(dims=[ParallelDim(size=m, degree=1),
                                   ParallelDim(size=n, degree=1)],
                             data_type=DataType.DT_FLOAT, owner_op=op)
        op.outputs.append(out)

        fwd_s, bwd_s = meas(op, view)
        fl = 2.0 * m * k * n
        # backward of a linear = dgrad + wgrad, 2x the forward flops; a
        # rate above ~1.2x peak is differencing noise (the scan carry
        # only ties the forward output — bwd can be hoisted), report null
        bwd_tf = (round(2 * fl / bwd_s / 1e12, 1)
                  if bwd_s == bwd_s and bwd_s > 0 else None)
        if bwd_tf is not None and bwd_tf > 1.2 * peak_tf:
            bwd_tf = None
        rec = {
            "shape": name, "m": m, "k": k, "n": n,
            "fwd_us": round(fwd_s * 1e6, 1),
            "bwd_us": round(bwd_s * 1e6, 1),
            "fwd_tflops": round(fl / fwd_s / 1e12, 1),
            "bwd_tflops": bwd_tf,
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)

    # per-layer gemm budget for the bench config: 4 projections + 2 FFN
    layer_fwd = 4 * results[0]["fwd_us"] + results[1]["fwd_us"] + \
        results[2]["fwd_us"]
    flops_fwd = (4 * 2.0 * 4096 * 1024 * 1024
                 + 2 * 2.0 * 4096 * 1024 * 4096)
    print(json.dumps({
        "metric": "xla_gemm_ceiling",
        "per_layer_gemm_fwd_us": round(layer_fwd, 1),
        "weighted_fwd_tflops": round(flops_fwd / (layer_fwd * 1e-6) / 1e12,
                                     1),
        "unit": "TF/s",
    }), flush=True)
    chain()


def chain(l_short: int = 8, l_long: int = 32, iters: int = 40):
    """Sustained rate for a DEPENDENT chain of the bench's actual gemm
    class — every gemm in the bench model is m=4096, k/n=1024 (the FFN is
    hidden->hidden 1024, NOT 4096-wide; the isolated single-gemm rows
    above overstate this class via cross-iteration pipelining, flagged in
    BASELINE.md). Chained gemms serialize like the model's layers do, so
    this is the honest in-context ceiling for the step's gemm budget.

    Methodology (the naive version of this measurement reported a
    physically-inconsistent 53 TF/s): the carry tie-in must be CHEAP — a
    whole-tensor sin tie costs ~0.5 ms/iteration of VPU transcendentals
    and swamps the gemm delta — so only one (8,128) tile is perturbed
    nonlinearly; and per-iteration overhead is cancelled by DIFFERENCING
    two chain depths (median of 5 runs, which suppresses host dispatch
    jitter)."""
    import statistics
    import time

    import jax
    import jax.numpy as jnp

    h = 1024
    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(8, 512, h), jnp.bfloat16)  # bench act shape

    def tie(a, c):
        tile = a[:, :8, :128].astype(jnp.float32)
        pert = jnp.sin(tile + c) * 1e-30 + tile
        return a.at[:, :8, :128].set(pert.astype(a.dtype))

    def fwd_chain(x, ws):
        hcur = x
        for i, W in enumerate(ws):
            hcur = jnp.dot(hcur, W, preferred_element_type=jnp.float32)
            if i % 2 == 0:  # alternate relu like the model's FFN-in layers
                hcur = jax.nn.relu(hcur)
            hcur = hcur.astype(x.dtype)
        return hcur

    def timed(layers, mode):
        Ws = [jnp.asarray(rng.randn(h, h) * 0.03, jnp.bfloat16)
              for _ in range(layers)]
        if mode == "fwd":
            def body(c, _):
                out = fwd_chain(tie(x0, c), Ws)
                return c + out.astype(jnp.float32).sum() * 1e-9, ()
        else:
            def body(c, _):
                def loss(ws):
                    return fwd_chain(tie(x0, c), ws).astype(
                        jnp.float32).sum()
                gs = jax.grad(loss)(Ws)
                return c + sum(
                    g.astype(jnp.float32).sum() for g in gs) * 1e-9, ()

        def fn(c0):
            c, _ = jax.lax.scan(body, c0, None, length=iters)
            return c

        jfn = jax.jit(fn)
        float(jfn(jnp.float32(0.0)))  # compile+warm
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(jfn(jnp.float32(1.0)))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    for mode, eq in (("fwd", 1), ("fwdbwd", 3)):
        d = timed(l_long, mode) - timed(l_short, mode)
        per_gemm = d / iters / (l_long - l_short) / eq
        print(json.dumps({
            "metric": f"gemm_chain_{mode}",
            "layers_differenced": [l_short, l_long],
            "per_gemm_equiv_us": round(per_gemm * 1e6, 2),
            "sustained_tflops": round(
                2.0 * 4096 * h * h / per_gemm / 1e12, 1),
        }), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "chain":
        chain()
    else:
        main()
