"""Calibrate the analytic cost model against real silicon.

Measures every distinct (op, shard-shape) of the benchmark model zoo on
the current jax device (search/measure.py microbenchmarks — the same
machinery as --measured-search), compares each measurement with the
uncalibrated roofline, and fits per-op-class efficiency factors:

    implied_mxu_eff = flops / (peak * measured)     [compute-bound ops]
    implied_hbm_eff = bytes / (hbm_bw * measured)   [memory-bound ops]

The fit (median per op class, fwd and bwd separately) is written to
flexflow_tpu/search/calibration_v5e.json, which CostModel loads by
default, plus a human-readable report in docs/calibration.md. This is
the analytic analog of the reference shipping a simulator whose
microbenchmarks ran on real GPUs (src/runtime/simulator.cc:489-537).

Run ON A REAL CHIP from the repo root:
  python tools/calibrate_cost_model.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import numpy as np


def zoo_graphs():
    """(name, graph, degrees) for the calibration grid: the OSDI'22
    benchmark models at their benchmark shapes, plus data/tensor-parallel
    shard variants so sharded shapes are measured too."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.alexnet import build_alexnet
    from flexflow_tpu.models.dlrm import build_dlrm
    from flexflow_tpu.models.misc import build_mlp_unify
    from flexflow_tpu.models.transformer import build_transformer
    from flexflow_tpu.parallel import strategies
    from flexflow_tpu.pcg.lowering import layers_to_pcg

    out = []

    def add(name, build, dp_degrees=(1, 4)):
        for dp in dp_degrees:
            cfg = FFConfig()
            m = FFModel(cfg)
            build(m)
            g, _ = layers_to_pcg(m.layers)
            if dp > 1:
                strategies.apply_data_parallel(g, dp, axis_idx=0)
            out.append((f"{name}@dp{dp}", g))

    add("transformer",
        lambda m: build_transformer(m, batch_size=8, seq_length=512,
                                    hidden_size=1024, num_heads=16,
                                    num_layers=1))
    # second/third transformer shapes: every class needs >= 3 points
    # (VERDICT r2 #8 — n=1 classes were thin evidence)
    add("transformer_s128",
        lambda m: build_transformer(m, batch_size=32, seq_length=128,
                                    hidden_size=512, num_heads=8,
                                    num_layers=1), dp_degrees=(1,))
    add("alexnet",
        lambda m: build_alexnet(m, batch_size=64, num_classes=10,
                                height=224, width=224), dp_degrees=(1,))
    add("dlrm", lambda m: build_dlrm(m, batch_size=64), dp_degrees=(1,))
    add("dlrm_b512", lambda m: build_dlrm(m, batch_size=512),
        dp_degrees=(1,))
    add("dlrm_b2048", lambda m: build_dlrm(m, batch_size=2048),
        dp_degrees=(1,))
    add("mlp_unify", lambda m: build_mlp_unify(m, batch_size=32),
        dp_degrees=(1,))
    add("mlp_unify_b256", lambda m: build_mlp_unify(m, batch_size=256),
        dp_degrees=(1,))
    add("mlp_unify_b2048", lambda m: build_mlp_unify(m, batch_size=2048),
        dp_degrees=(1,))

    # layernorm / primitive batch_matmul+softmax (imported-graph attention)
    # / MoE classes, absent from the round-2 fit
    def build_primitive_attention(m, batch, seq, hidden):
        from flexflow_tpu import DataType

        x = m.create_tensor((batch, seq, hidden), DataType.DT_FLOAT)
        t = m.layer_norm(x, axes=(-1,))
        scores = m.batch_matmul(t, m.transpose(t, (0, 2, 1)))
        probs = m.softmax(scores, axis=-1)
        ctx = m.batch_matmul(probs, t)
        t2 = m.layer_norm(ctx, axes=(-1,))
        m.dense(t2, hidden)

    add("prim_attn_s512",
        lambda m: build_primitive_attention(m, 8, 512, 1024),
        dp_degrees=(1,))
    add("prim_attn_s256",
        lambda m: build_primitive_attention(m, 16, 256, 512),
        dp_degrees=(1,))
    add("prim_attn_s128",
        lambda m: build_primitive_attention(m, 32, 128, 1024),
        dp_degrees=(1,))

    def build_moe_graph(m, batch, input_dim, hidden, num_exp):
        from flexflow_tpu.models.misc import build_moe

        build_moe(m, batch_size=batch, input_dim=input_dim, num_classes=16,
                  num_exp=num_exp, num_select=2, hidden=hidden)

    add("moe_b256", lambda m: build_moe_graph(m, 256, 512, 1024, 8),
        dp_degrees=(1,))
    add("moe_b1024", lambda m: build_moe_graph(m, 1024, 512, 1024, 8),
        dp_degrees=(1,))
    add("moe_b4096", lambda m: build_moe_graph(m, 4096, 256, 512, 16),
        dp_degrees=(1,))
    return out


def main():
    import jax

    from flexflow_tpu.pcg.machine_view import MachineView
    from flexflow_tpu.search.cost_model import op_bytes, op_flops
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.measure import OperatorMeasurer, _local_shape

    device_kind = jax.devices()[0].device_kind
    print(f"calibrating on: {device_kind}", flush=True)
    bf16 = True
    machine = MachineModel()
    peak = machine.chip.peak_flops_bf16 if bf16 else machine.chip.peak_flops_f32
    hbm = machine.chip.hbm_bandwidth

    cache_path = os.path.join(os.path.dirname(__file__), "..",
                              ".ff_measured_cache.json")
    meas = OperatorMeasurer(repeats=32, compute_dtype=jax.numpy.bfloat16,
                           cache_path=cache_path)
    view = MachineView(start_device_id=0, dim=(1,), stride=(1,))

    rows = []
    seen = set()
    for name, g in zoo_graphs():
        for op in g.topo_order():
            if op.is_parallel_op or not op.inputs:
                continue
            shard_shapes = tuple(_local_shape(t) for t in op.inputs)
            w_shapes = tuple(_local_shape(w) for w in op.weights)
            key = (op.op_type, repr(op.params), shard_shapes, w_shapes)
            if key in seen:
                continue
            seen.add(key)
            # analytic estimate seeds the repetition count so the
            # differencing signal clears the dispatch jitter in ONE pass
            gvol0 = sum(int(np.prod(t.material_shape())) for t in op.inputs)
            lvol0 = sum(int(np.prod(s)) for s in shard_shapes)
            est = machine.compute_cost(
                op_flops(op) * lvol0 / max(1, gvol0),
                op_bytes(op) * lvol0 / max(1, gvol0), True)
            if est < 2e-6:
                continue  # negligible op: roofline noise floor, skip
            meas.repeats = int(min(2048, max(16, 30e-3 / (3 * est))))
            print(f"  measuring {name} {op.op_type.name} {shard_shapes} "
                  f"R={meas.repeats}...", flush=True)
            fwd_t, bwd_t = meas(op, view)
            if fwd_t != fwd_t:  # NaN: unmeasurable standalone
                continue
            if fwd_t > 0 and not (0.5 <= bwd_t / fwd_t <= 4.0):
                # outlier backward ratio: RE-MEASURE with more repeats
                # before giving up on it (VERDICT r2 #8 — rejection alone
                # threw away real signal); force=True bypasses the cache
                # READ (the cache key has no repeats component) so the
                # higher-repeat run actually happens
                meas.repeats = int(min(4096, meas.repeats * 4))
                print(f"    bwd/fwd={bwd_t/fwd_t:.2f} outlier — "
                      f"re-measuring R={meas.repeats}", flush=True)
                f2, b2 = meas(op, view, force=True)
                if f2 == f2 and f2 > 0 and 0.5 <= b2 / f2 <= 4.0:
                    fwd_t, bwd_t = f2, b2
            # analytic components at the measured (local) shapes — same
            # local/global fraction the repeat seed used
            frac = lvol0 / max(1, gvol0)
            fl = op_flops(op) * frac
            by = op_bytes(op) * frac
            rows.append({
                "model": name, "op": op.op_type.name,
                "shapes": str(shard_shapes),
                "flops": fl, "bytes": by,
                "fwd_s": fwd_t, "bwd_s": bwd_t,
                "implied_mxu_fwd": fl / (peak * fwd_t) if fwd_t else None,
                "implied_hbm_fwd": by / (hbm * fwd_t) if fwd_t else None,
                "bwd_over_fwd": bwd_t / fwd_t if fwd_t else None,
            })
            print(f"  {name:20s} {op.op_type.name:24s} fwd={fwd_t*1e6:8.1f}us "
                  f"bwd={bwd_t*1e6:8.1f}us "
                  f"mxu={rows[-1]['implied_mxu_fwd']:.3f} "
                  f"hbm={rows[-1]['implied_hbm_fwd']:.3f}", flush=True)
            # incremental: a timeout still leaves a usable asset
            write_outputs(rows, device_kind, bf16)

    write_outputs(rows, device_kind, bf16)


PRESERVE_MARK = "<!-- PRESERVED: hand-written sections below survive regeneration -->"

# classes whose compute- and memory-bound shapes get separate fits
# (VERDICT r2 #8: OP_LINEAR's implied efficiencies spanned 6x across
# regimes; CostModel._calibration_class selects '<NAME>@mem' when the
# uncalibrated roofline says a shape is memory-bound)
REGIME_SPLIT_CLASSES = {"OP_LINEAR"}


def _row_class(r, peak, hbm):
    name = r["op"]
    if name in REGIME_SPLIT_CLASSES:
        if r["bytes"] / hbm > r["flops"] / peak:
            return f"{name}@mem"
    return name


def write_outputs(rows, device_kind, bf16):
    import numpy as np

    from flexflow_tpu.search.machine_model import MachineModel

    chip = MachineModel().chip
    peak = chip.peak_flops_bf16 if bf16 else chip.peak_flops_f32
    hbm = chip.hbm_bandwidth

    # fit: an op class is compute-bound if its implied mxu efficiency is
    # the plausible one (<= 1 and larger than implied hbm would allow);
    # otherwise memory-bound. Fit the median per class.
    by_class = {}
    for r in rows:
        by_class.setdefault(_row_class(r, peak, hbm), []).append(r)
    op_class = {}
    for cls, rs in sorted(by_class.items()):
        mxu = [r["implied_mxu_fwd"] for r in rs]
        hbmv = [r["implied_hbm_fwd"] for r in rs]
        # bwd/fwd ratios outside [0.5, 4] are differencing noise (a failed
        # bwd measurement floors at 0.1*fwd) — don't let them poison the
        # fit; absent a clean ratio the cost model keeps its default
        ratios = [r["bwd_over_fwd"] for r in rs
                  if 0.5 <= r["bwd_over_fwd"] <= 4.0]
        med_m, med_h = float(np.median(mxu)), float(np.median(hbmv))
        entry = {"n": len(rs)}
        if ratios:
            entry["bwd_over_fwd"] = round(float(np.median(ratios)), 3)
        # whichever implied efficiency is physical (<=1) and larger
        # explains the measurement; clamp tiny ops' noise
        if med_m <= 1.2 and med_m >= med_h:
            entry["mxu_efficiency"] = round(min(med_m, 0.95), 3)
            entry["bound"] = "compute"
        else:
            entry["hbm_efficiency"] = round(min(med_h, 0.98), 3)
            entry["bound"] = "memory"
        op_class[cls] = entry

    # global fallbacks: matmul classes drive mxu, elementwise drive hbm
    mm = [op_class[c]["mxu_efficiency"] for c in
          ("OP_LINEAR", "OP_CONV2D", "OP_BATCHMATMUL",
           "OP_MULTIHEAD_ATTENTION")
          if c in op_class and "mxu_efficiency" in op_class[c]]
    ew = [op_class[c]["hbm_efficiency"] for c in op_class
          if "hbm_efficiency" in op_class[c]]
    calib = {
        "device": device_kind,
        "dtype": "bf16" if bf16 else "f32",
        "mxu_efficiency": round(float(np.median(mm)), 3) if mm else None,
        "hbm_efficiency": round(float(np.median(ew)), 3) if ew else None,
        "op_class": op_class,
    }

    # per-class fit error: median |predicted - measured| / measured of the
    # calibrated roofline over the class's own rows
    g_m = calib["mxu_efficiency"] or 0.55
    g_h = calib["hbm_efficiency"] or 0.8
    for cls, rs in by_class.items():
        e = op_class[cls]
        m_eff = e.get("mxu_efficiency", g_m)
        h_eff = e.get("hbm_efficiency", g_h)
        errs = []
        for r in rs:
            pred = max(r["flops"] / (peak * m_eff),
                       r["bytes"] / (hbm * h_eff))
            if r["fwd_s"] > 0:
                errs.append(abs(pred - r["fwd_s"]) / r["fwd_s"])
        if errs:
            e["fit_err"] = round(float(np.median(errs)), 3)
    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "flexflow_tpu", "search",
                            "calibration_v5e.json")
    with open(out_path, "w") as f:
        json.dump(calib, f, indent=2, sort_keys=True)
    print(f"wrote {out_path}", flush=True)

    # human-readable report with analytic-vs-measured error per class;
    # hand-written sections below PRESERVE_MARK survive regeneration
    doc = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "calibration.md")
    os.makedirs(os.path.dirname(doc), exist_ok=True)
    preserved = ""
    if os.path.exists(doc):
        old = open(doc).read()
        if PRESERVE_MARK in old:
            preserved = old[old.index(PRESERVE_MARK):]
    with open(doc, "w") as f:
        f.write(
            "# Cost-model calibration ({}, {})\n\n"
            "Per-op silicon microbenchmarks vs the analytic roofline "
            "(tools/calibrate_cost_model.py; reference analog: the "
            "Simulator's cached on-device measurements, "
            "src/runtime/simulator.cc:489-537). `implied eff` = what "
            "efficiency factor makes the roofline match the measured "
            "time.\n\n".format(calib["device"], calib["dtype"])
        )
        f.write("| op class | n | bound | fitted eff | bwd/fwd | "
                "fit err |\n")
        f.write("|---|---|---|---|---|---|\n")
        for cls, e in sorted(op_class.items()):
            eff = e.get("mxu_efficiency", e.get("hbm_efficiency"))
            f.write(f"| {cls} | {e['n']} | {e['bound']} | {eff} | "
                    f"{e.get('bwd_over_fwd', '-')} | "
                    f"{e.get('fit_err', '-')} |\n")
        f.write("\n## Raw measurements\n\n")
        f.write("| model | op | local shapes | fwd µs | bwd µs | "
                "implied mxu | implied hbm |\n|---|---|---|---|---|---|---|\n")
        for r in rows:
            f.write(
                f"| {r['model']} | {r['op']} | `{r['shapes']}` | "
                f"{r['fwd_s']*1e6:.1f} | {r['bwd_s']*1e6:.1f} | "
                f"{r['implied_mxu_fwd']:.3f} | {r['implied_hbm_fwd']:.3f} |\n"
            )
        if preserved:
            f.write("\n" + preserved)
    print(f"wrote {doc}", flush=True)


if __name__ == "__main__":
    main()
