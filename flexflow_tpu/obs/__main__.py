"""Telemetry artifact CLI.

Usage:
    python -m flexflow_tpu.obs trace    <events.jsonl> [-o trace.json]
    python -m flexflow_tpu.obs summary  <events.jsonl>
    python -m flexflow_tpu.obs prom     <metrics.jsonl> [-o metrics.prom]
    python -m flexflow_tpu.obs requests <events.jsonl> [--slowest K]
    python -m flexflow_tpu.obs explain  [--top N] [--in-situ] [shape flags]
    python -m flexflow_tpu.obs calibrate inspect <store.json>
    python -m flexflow_tpu.obs calibrate prune   <store.json> --max-age-h H
    python -m flexflow_tpu.obs calibrate diff    <a.json> <b.json>

``trace`` converts a structured event log to Chrome-trace JSON (open at
https://ui.perfetto.dev). ``summary`` schema-validates the log and
prints per-category/event counts plus step/search aggregates — and,
when the log carries a step-observatory capture, the overlap-
realization/HBM numbers, per-collective hidden/exposed attribution and
the measured-vs-simulated per-op drift from the overlay file.
``prom`` re-renders the last metrics.jsonl snapshot as Prometheus text.
``requests`` reconstructs per-request lifecycles from the serving
flight recorder's events (cat "requests"): stage breakdown, top-K
slowest, shed and requeue causes. ``explain`` compiles the benchmark
Transformer (CPU-sized by default; pass --seq/--hidden/... for the real
bench shape on a TPU host), joins the cost model against on-device
profile_ops measurements and prints the miscalibrated-op kernel
worklist — each perf round starts from this list (docs/performance.md).
``calibrate`` inspects/maintains a persistent cost-model calibration
store (obs/calibration.py).

This module is a CLI entry point: bare print() is its job (fflint FFL201
allowlists __main__ modules).
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import Counter

from .tracer import lanes_from_events, read_events_jsonl, to_chrome_trace


def _cmd_trace(args) -> int:
    events, problems = read_events_jsonl(args.events)
    for p in problems:
        print(f"warning: {p}", file=sys.stderr)
    out = args.output or "trace.json"
    with open(out, "w") as f:
        json.dump(to_chrome_trace(events,
                                  lane_names=lanes_from_events(events)), f)
    print(f"wrote {out}: {len(events)} event(s) "
          f"({len(problems)} malformed line(s) skipped)")
    return 0


def _cmd_summary(args) -> int:
    events, problems = read_events_jsonl(args.events)
    if problems:
        for p in problems:
            print(f"schema: {p}", file=sys.stderr)
    by_name = Counter((e["cat"], e["name"]) for e in events)
    print(f"{args.events}: {len(events)} event(s), "
          f"{len(problems)} malformed line(s)")
    for (cat, name), n in sorted(by_name.items()):
        print(f"  {cat:<12} {name:<24} {n}")
    steps = [e for e in events
             if e["name"] == "step" and e["ph"] == "X"]
    if steps:
        total = sum(e["dur"] for e in steps)
        print(f"steps: {len(steps)}, total {total:.3f}s, "
              f"mean {total / len(steps) * 1e3:.2f}ms")
    mcmc = [e for e in events if e["name"] == "mcmc_iter"]
    if mcmc:
        acc = sum(1 for e in mcmc if e.get("args", {}).get("accept"))
        print(f"mcmc: {len(mcmc)} proposal(s), {acc} accepted "
              f"({100.0 * acc / len(mcmc):.1f}%)")
    cands = [e for e in events if e["name"] == "xfer_candidate"]
    if cands:
        best = sum(1 for e in cands if e.get("args", {}).get("best"))
        print(f"substitutions: {len(cands)} candidate(s), "
              f"{best} improved the best strategy")
    _summarize_step_profile(args.events, events)
    return 1 if problems else 0


def _summarize_step_profile(events_path: str, events) -> None:
    """Step-observatory section of ``summary``: the capture's headline
    numbers (overlap realization, HBM accuracy), per-collective
    hidden/exposed attribution, and — when the overlay file sits next to
    the event log — the measured-vs-simulated per-op drift."""
    import os

    from .step_profile import MEASURED_CAT, OVERLAY_FILE

    sp = next((e for e in events
               if e["name"] == "step_profile" and e["cat"] == MEASURED_CAT),
              None)
    if sp is None:
        return
    a = sp.get("args", {})
    print("step observatory (obs.capture_step_profile):")
    rr = a.get("realized_ratio")
    print(f"  mode {a.get('mode')}/{a.get('backend')}, "
          f"fused step {float(a.get('step_wall_s', 0)) * 1e3:.3f} ms "
          f"(serial {float(a.get('serial_step_wall_s', 0)) * 1e3:.3f} ms)")
    if rr is not None:
        print(f"  overlap realization: {float(rr):.2f} measured vs "
              f"{float(a.get('assumed_efficiency', 1.0)):.2f} assumed "
              f"(hidden {float(a.get('hidden_sync_s', 0)) * 1e3:.3f} of "
              f"{float(a.get('total_sync_s', 0)) * 1e3:.3f} ms sync)")
    acc = a.get("hbm_static_accuracy")
    if acc is not None:
        print(f"  HBM: measured peak {int(a.get('hbm_peak_bytes', 0))} B "
              f"({a.get('hbm_source')}), static accuracy {float(acc):.2f}")
    syncs = [e for e in events
             if e["cat"] == MEASURED_CAT and e["ph"] == "X"
             and e["name"].endswith(".grad_sync")]
    for e in syncs:
        sa = e.get("args", {})
        print(f"  {e['name']:<34} {sa.get('collective', '?'):<28} "
              f"hidden {float(sa.get('hidden_s', 0)) * 1e3:>8.3f} ms  "
              f"exposed {float(sa.get('exposed_s', 0)) * 1e3:>8.3f} ms")
    overlay = os.path.join(os.path.dirname(os.path.abspath(events_path)),
                           OVERLAY_FILE)
    if not os.path.exists(overlay):
        return
    with open(overlay) as f:
        tr = json.load(f).get("traceEvents", [])
    pid_names = {e["pid"]: e["args"]["name"] for e in tr
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
    sim: dict = {}
    meas: dict = {}
    for e in tr:
        if e.get("ph") != "X":
            continue
        group = pid_names.get(e.get("pid"))
        name = e["name"].removesuffix(".bwd")
        if name.endswith(".grad_sync"):
            continue
        bucket = sim if group == "simulated" else (
            meas if group == "measured" else None)
        if bucket is not None:
            # dur is µs in the overlay; one span per device — keep max
            bucket[name] = max(bucket.get(name, 0.0), e.get("dur", 0.0))
    both = sorted(set(sim) & set(meas),
                  key=lambda n: abs(meas[n] - sim[n]), reverse=True)
    if both:
        print(f"  measured-vs-simulated drift ({OVERLAY_FILE}, worst 5):")
        print(f"    {'op':<28} {'sim ms':>9} {'meas ms':>9} {'drift':>7}")
        for n in both[:5]:
            s, m = sim[n] / 1e3, meas[n] / 1e3
            drift = (m / s) if s > 0 else float("inf")
            print(f"    {n[:28]:<28} {s:>9.4f} {m:>9.4f} {drift:>6.2f}x")


def _cmd_prom(args) -> int:
    from .metrics import MetricsRegistry

    reg = MetricsRegistry()
    with open(args.metrics) as f:
        records = [json.loads(line) for line in f if line.strip()]
    # keep only the newest snapshot per (name, labels)
    latest = {}
    for r in records:
        latest[(r["name"], tuple(sorted(r["labels"].items())))] = r
    for r in latest.values():
        labels = dict(r["labels"])
        if r["kind"] == "counter":
            reg.counter(r["name"], **labels).inc(r["value"])
        elif r["kind"] == "gauge":
            reg.gauge(r["name"], **labels).set(r["value"])
        else:  # histogram snapshots only carry aggregates; re-emit sum
            h = reg.histogram(r["name"], **labels)
            h.sum, h.count = r.get("sum", 0.0), r.get("count", 0)
    text = reg.to_prometheus()
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        print(f"wrote {args.output}")
    else:
        print(text, end="")
    return 0


def _cmd_requests(args) -> int:
    from .request_trace import REQUEST_CAT

    events, problems = read_events_jsonl(args.events)
    for p in problems:
        print(f"warning: {p}", file=sys.stderr)
    lanes = {tid: name for (cat, name), tid
             in lanes_from_events(events).items() if cat == REQUEST_CAT}
    reqs: dict = {}
    for e in events:
        if e.get("cat") != REQUEST_CAT:
            continue
        rid = e.get("args", {}).get("request")
        if rid is None:
            continue  # lane metadata etc.
        reqs.setdefault(rid, []).append(e)
    if not reqs:
        print(f"{args.events}: no request events (cat={REQUEST_CAT!r}); "
              "was the session started with request_sample_rate > 0?")
        return 1
    rows = []
    shed_causes: Counter = Counter()
    requeues = 0
    for rid, evs in reqs.items():
        stages = {"queue": 0.0, "prefill": 0.0, "decode": 0.0}
        replicas = set()
        sheds = []
        gens = []
        tokens = None
        done = False
        for e in evs:
            name, a = e["name"], e.get("args", {})
            if e["ph"] == "X" and name in stages:
                stages[name] += float(e.get("dur", 0.0))
            if name == "shed":
                sheds.append((a.get("reason"), a.get("stage")))
                shed_causes[a.get("reason")] += 1
            elif name == "requeue":
                gens.append(a.get("generation"))
            elif name == "complete":
                done = True
                tokens = a.get("tokens")
            tid = int(e.get("tid", 0))
            if tid in lanes and lanes[tid] != "admission":
                replicas.add(lanes[tid])
        requeues += len(gens)
        ts = [float(e["ts"]) for e in evs]
        spans = [float(e["ts"]) + float(e.get("dur", 0.0)) for e in evs]
        rows.append({
            "request": rid, "total_s": max(spans) - min(ts),
            "stages": stages, "replicas": sorted(replicas),
            "sheds": sheds, "requeue_generations": gens,
            "completed": done, "tokens": tokens,
        })
    rows.sort(key=lambda r: r["total_s"], reverse=True)
    n_done = sum(1 for r in rows if r["completed"])
    print(f"{args.events}: {len(rows)} traced request(s), "
          f"{n_done} completed, {requeues} requeue(s), "
          f"{sum(shed_causes.values())} shed(s)")
    if shed_causes:
        print("  shed causes: " + ", ".join(
            f"{k}={v}" for k, v in shed_causes.most_common()))
    k = max(1, args.slowest)
    print(f"slowest {min(k, len(rows))} (stage seconds):")
    print(f"  {'request':<14} {'total':>8} {'queue':>8} {'prefill':>8} "
          f"{'decode':>8}  outcome")
    for r in rows[:k]:
        st = r["stages"]
        if r["completed"]:
            outcome = f"completed tokens={r['tokens']}"
        elif r["sheds"]:
            reason, stage = r["sheds"][-1]
            outcome = f"shed {reason}@{stage}"
        else:
            outcome = "in flight"
        if r["requeue_generations"]:
            outcome += (f" (requeued x{len(r['requeue_generations'])}"
                        f" gen={r['requeue_generations']})")
        if r["replicas"]:
            outcome += " on " + ",".join(r["replicas"])
        print(f"  {r['request'][:14]:<14} {r['total_s']:>8.4f} "
              f"{st['queue']:>8.4f} {st['prefill']:>8.4f} "
              f"{st['decode']:>8.4f}  {outcome}")
    return 0


def _cmd_calibrate(args) -> int:
    from .calibration import DEFAULT_MAX_AGE_S, CalibrationStore

    if args.action == "inspect":
        store = CalibrationStore(args.store)
        s = store.summary()
        print(json.dumps(s, indent=2, sort_keys=True, default=str))
        bad = store.problems(max_age_s=args.max_age_h * 3600.0
                             if args.max_age_h else DEFAULT_MAX_AGE_S)
        if bad:
            print("unusable for THIS process:", file=sys.stderr)
            for b in bad:
                print(f"  - {b}", file=sys.stderr)
            return 1
        print("usable: fingerprint/backend match, entries fresh")
        return 0
    if args.action == "prune":
        store = CalibrationStore(args.store)
        if args.max_age_h is None:
            print("prune: --max-age-h is required", file=sys.stderr)
            return 2
        n = store.prune(args.max_age_h * 3600.0)
        if n:
            store.save()
        print(f"pruned {n} entr{'y' if n == 1 else 'ies'}; "
              f"{len(store.ops)} remain")
        return 0
    # diff
    a, b = CalibrationStore(args.store), CalibrationStore(args.other)
    delta = a.diff(b)
    if not delta:
        print("stores agree on every shared key")
        return 0
    for d in delta:
        if d["status"] == "changed":
            print(f"  ~ {d['op_type']:<22} x{d['ratio']:.3f} "
                  f"({d['total_s_a'] * 1e3:.4f} -> "
                  f"{d['total_s_b'] * 1e3:.4f} ms)  {d['key'][:60]}")
        else:
            side = "a only" if d["status"] == "only_in_a" else "b only"
            print(f"  {side:>8}: {d['op_type']:<22} {d['key'][:60]}")
    print(f"{len(delta)} difference(s)")
    return 0


def _cmd_explain(args) -> int:
    from .. import (
        FFConfig,
        FFModel,
        LossType,
        MetricsType,
        SGDOptimizer,
    )
    from ..models.transformer import build_transformer
    from .explain import explain_strategy

    cfg = FFConfig()
    cfg.batch_size = args.batch
    cfg.allow_mixed_precision = args.bf16
    model = FFModel(cfg)
    build_transformer(
        model, batch_size=args.batch, seq_length=args.seq,
        hidden_size=args.hidden, num_heads=args.heads,
        num_layers=args.layers,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[MetricsType.METRICS_MEAN_SQUARED_ERROR],
    )
    prof = None
    if args.in_situ:
        import numpy as np

        from .step_profile import capture_step_profile

        rng = np.random.RandomState(0)
        in_pt = model.executor.input_pts[0]
        x = rng.rand(*in_pt.material_shape()).astype(np.float32)
        y = rng.rand(*in_pt.material_shape()).astype(np.float32)
        prof = capture_step_profile(model, x, y, batch_size=args.batch)
        print(f"in-situ capture: mode={prof.mode}, "
              f"fused step {prof.step_wall_s * 1e3:.3f} ms, "
              f"realized overlap {prof.realized_ratio}")
    exp = explain_strategy(model, repeats=args.repeats, step_profile=prof)
    print(exp.summary(args.top))
    print(f"kernel worklist (top {args.top} by |simulated - measured|):")
    for w in exp.worklist(args.top):
        verdict = ("cost model optimistic — fuse/speed up this kernel"
                   if w["ratio"] > 1.0 else
                   "cost model pessimistic — recalibrate this class")
        print(f"  #{w['rank']} {w['name']} [{w['op_type']}] "
              f"meas {w['meas_total_s'] * 1e3:.4f} ms vs "
              f"sim {w['sim_total_s'] * 1e3:.4f} ms "
              f"(x{w['ratio']:.2f}) — {verdict}")
    return 0


def _fleet_domains(args):
    if not getattr(args, "domains", None):
        return None
    import json as _json

    from ..runtime.fault_domains import FaultDomainMap

    with open(args.domains) as f:
        return FaultDomainMap.from_json(_json.load(f))


def _cmd_fleet(args) -> int:
    import time as _time

    from .fleet import FleetAggregator

    agg = FleetAggregator(args.spool_dir, staleness_s=args.staleness,
                          death_s=args.death,
                          fault_domains=_fleet_domains(args))
    while True:
        view = agg.aggregate()
        if args.prom:
            with open(args.prom, "w") as f:
                f.write(view.to_prometheus())
        if args.watch:
            print("\033[2J\033[H", end="")
        print(view.table())
        corrupt = [r for r in view.records if r.error]
        for r in corrupt:
            print(f"CORRUPT {r.process}: {r.error}")
        if not args.watch:
            return 1 if corrupt else 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


def _cmd_forensics(args) -> int:
    import json as _json
    import os as _os

    from . import flight_recorder as fr

    entries, problems = fr.read_index(args.dir)
    if args.validate:
        entries, problems = fr.validate_dir(args.dir)
        for msg in problems:
            print(f"PROBLEM: {msg}")
        print(f"{len(entries)} bundle(s) indexed, "
              f"{len(problems)} problem(s)")
        return 1 if problems else 0
    if args.show:
        if not entries:
            print("no forensics bundles indexed")
            return 1
        if args.show == "latest":
            rec = entries[-1]
        else:
            hits = [e for e in entries if e.get("file") == args.show
                    or args.show in (e.get("file") or "")]
            if not hits:
                print(f"no bundle matches {args.show!r}")
                return 1
            rec = hits[-1]
        payload = fr.read_bundle(_os.path.join(rec["_dir"], rec["file"]))
        if args.json:
            print(_json.dumps(payload, indent=2, sort_keys=True))
            return 0
        err = payload.get("error") or {}
        print(f"bundle:  {rec['file']}")
        print(f"process: {payload.get('process')} "
              f"(pid {payload.get('pid')})")
        print(f"reason:  {payload.get('reason')}"
              + (f" — {err.get('type')}: {err.get('message')}" if err
                 else ""))
        events = payload.get("events") or []
        print(f"events:  {len(events)} in ring"
              + (f"; tail: " + ", ".join(
                  str(e.get("name")) for e in events[-8:]) if events
                 else ""))
        metrics = payload.get("metrics") or {}
        for series in sorted(metrics):
            pts = metrics[series]
            vals = [v for _, v in pts[-5:]]
            print(f"metric:  {series} ({len(pts)} samples; recent "
                  + ", ".join(f"{v:.4g}" for v in vals) + ")")
        for name in sorted(payload.get("state") or {}):
            print(f"state:   {name}")
        if payload.get("extra"):
            blob = _json.dumps(payload["extra"], sort_keys=True)
            print(f"extra:   {blob[:300]}")
        return 0
    for rec in entries:
        print(f"{rec.get('unixtime', 0):.3f} {rec.get('process', '?'):<16} "
              f"{rec.get('reason', '?'):<24} {rec.get('file')}")
    for msg in problems:
        print(f"PROBLEM: {msg}")
    if not entries and not problems:
        print("no forensics bundles indexed")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m flexflow_tpu.obs",
        description=__doc__.split("\n\n")[0],
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("trace", help="events.jsonl -> Chrome/Perfetto trace")
    t.add_argument("events")
    t.add_argument("-o", "--output")
    s = sub.add_parser("summary", help="validate + summarize an event log")
    s.add_argument("events")
    m = sub.add_parser("prom", help="metrics.jsonl -> Prometheus text")
    m.add_argument("metrics")
    m.add_argument("-o", "--output")
    r = sub.add_parser(
        "requests",
        help="per-request stage breakdown + slowest/shed/requeue report "
             "from the serving flight recorder's events",
    )
    r.add_argument("events")
    r.add_argument("--slowest", type=int, default=10,
                   help="how many slowest requests to detail")
    c = sub.add_parser(
        "calibrate",
        help="inspect/prune/diff a persistent cost-model calibration "
             "store (obs/calibration.py)",
    )
    c.add_argument("action", choices=("inspect", "prune", "diff"))
    c.add_argument("store", help="calibration store JSON path")
    c.add_argument("other", nargs="?",
                   help="second store (diff only)")
    c.add_argument("--max-age-h", type=float, default=None,
                   help="staleness horizon in hours (inspect verdict / "
                        "prune cutoff)")
    e = sub.add_parser(
        "explain",
        help="print the miscalibrated-op kernel worklist for the "
             "benchmark Transformer on this host's device",
    )
    e.add_argument("--top", type=int, default=3)
    e.add_argument("--batch", type=int, default=2)
    e.add_argument("--seq", type=int, default=64)
    e.add_argument("--hidden", type=int, default=128)
    e.add_argument("--heads", type=int, default=4)
    e.add_argument("--layers", type=int, default=2)
    e.add_argument("--repeats", type=int, default=1)
    e.add_argument("--bf16", action="store_true")
    e.add_argument("--in-situ", action="store_true",
                   help="also capture a step profile of the fused jitted "
                        "step and join its per-op seconds into the rows")
    fl = sub.add_parser(
        "fleet",
        help="aggregate a fleet spool directory (obs/fleet.py): live "
             "table, merged ff_fleet_* Prometheus page, staleness "
             "classification",
    )
    fl.add_argument("spool_dir")
    fl.add_argument("--prom", help="write the merged Prometheus page here")
    fl.add_argument("--watch", action="store_true",
                    help="refresh the table until interrupted")
    fl.add_argument("--interval", type=float, default=2.0)
    fl.add_argument("--staleness", type=float, default=10.0,
                    help="spool age (s) after which a process is stale")
    fl.add_argument("--death", type=float, default=30.0,
                    help="spool age (s) after which a process is dead")
    fl.add_argument("--domains",
                    help="FaultDomainMap JSON (to_json) mapping spool "
                         "process names to slices")
    fo = sub.add_parser(
        "forensics",
        help="inspect flight-recorder forensics bundles "
             "(obs/flight_recorder.py): list the index, --show one "
             "bundle, --validate everything",
    )
    fo.add_argument("dir",
                    help="forensics dir (or the telemetry dir holding "
                         "one)")
    fo.add_argument("--show",
                    help="bundle file name (or 'latest') to detail")
    fo.add_argument("--json", action="store_true",
                    help="with --show: dump the raw payload JSON")
    fo.add_argument("--validate", action="store_true",
                    help="integrity-check every indexed bundle; exit 1 "
                         "on any problem")
    args = p.parse_args(argv)
    if args.cmd == "calibrate" and args.action == "diff" \
            and not args.other:
        p.error("calibrate diff needs two store paths")
    return {"trace": _cmd_trace, "summary": _cmd_summary,
            "prom": _cmd_prom, "requests": _cmd_requests,
            "calibrate": _cmd_calibrate, "explain": _cmd_explain,
            "fleet": _cmd_fleet,
            "forensics": _cmd_forensics}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
