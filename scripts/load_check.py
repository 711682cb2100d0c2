#!/usr/bin/env python
"""Sustained-load serving harness (ROADMAP Open item 3 / docs/serving.md).

Drives a ReplicaSet of continuous-batching replicas through a 10x
offered-load ramp on the virtual CPU mesh, kills one replica mid-ramp,
and asserts the overload-robustness contract:

  1. **bounded tail latency for admitted work** — p99 latency of
     admitted requests during/after the ramp stays within
     ``--p99-factor`` (default 3x) of the pre-ramp p99;
  2. **zero silent drops** — every request the generator offered either
     returns tokens or raises a TYPED shed/deadline error; nothing
     hangs, nothing vanishes;
  3. **failover completes** — the killed replica's in-flight work is
     requeued onto its sibling and a replacement comes back through the
     elastic-restore path (checkpoint resharded onto the live
     topology), so the run ends at full replica strength;
  4. **(with --telemetry-dir) the flight recorder is coherent** — the
     session's events.jsonl is schema-valid, at least one sampled
     request carries the full queue -> admit -> prefill -> decode ->
     complete lifecycle, and when the kill fired, some requeued request
     finished under its ORIGINAL trace id with exactly one complete
     event (obs/request_trace.py).

With ``--shared-prefix`` the ramp is replaced by the KV-dedup A/B
check (docs/serving.md "Prefix sharing"): the same burst of sessions —
one long block-aligned common prompt prefix, unique tails — is served
twice from an identically starved page pool, sharing off then on, and
the run asserts >= ``--share-factor`` (default 5x) the concurrent
sessions in the same HBM budget, ``ff_kv_pages_shared > 0`` at peak,
token-exact output vs ``incremental_generate`` in BOTH phases, and a
zero-violation ``PagePool.audit()`` per phase.
scripts/kvshare_check.sh runs this leg in CI.

Exit 0 with a JSON summary on stdout when all criteria hold; exit 1
(with the failed criterion) otherwise. scripts/serving_check.sh runs
this on 8- and 4-device CPU meshes in CI; scripts/obs_check.sh runs the
telemetry-enabled leg.
"""
import argparse
import json
import os
import sys
import tempfile
import threading
import time

# a CPU-mesh harness by construction (counts and CPU wall clock, never a
# device rate): the platform is pinned like tests/conftest.py pins it
os.environ["JAX_PLATFORMS"] = "cpu"
# honor JAX_NUM_CPU_DEVICES like tests/conftest.py: virtual CPU mesh size
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("JAX_NUM_CPU_DEVICES", "8")
).strip()
# runnable as `python scripts/load_check.py` from a source checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices",
                  int(os.environ.get("JAX_NUM_CPU_DEVICES", "8")))


def build_model_fn(args):
    from flexflow_tpu import (ActiMode, AggrMode, DataType, FFConfig,
                              FFModel, LossType, MetricsType, SGDOptimizer)

    def model_fn():
        cfg = FFConfig()
        cfg.batch_size = 2
        cfg.search_budget = args.search_budget
        m = FFModel(cfg)
        ids = m.create_tensor((2, args.max_len), DataType.DT_INT32)
        t = m.embedding(ids, args.vocab, args.hidden, AggrMode.AGGR_MODE_NONE)
        for _ in range(args.layers):
            t = m.multihead_attention(t, t, t, args.hidden, args.heads,
                                      causal=True)
            t = m.dense(t, args.hidden, ActiMode.AC_MODE_RELU)
        t = m.softmax(m.dense(t, args.vocab))
        m.compile(SGDOptimizer(lr=0.01),
                  LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [MetricsType.METRICS_ACCURACY])
        if args.decode_strategy:
            # disaggregated prefill/decode (docs/serving.md): the
            # batched decode step lowers from the decode-objective
            # strategy; the harness asserts the same typed-accounting
            # invariants either way
            m.compile_decode()
        return m

    return model_fn


class Record:
    __slots__ = ("req", "phase", "submit_error")

    def __init__(self, req, phase, submit_error=None):
        self.req = req
        self.phase = phase
        self.submit_error = submit_error


def offered_load(rs, args, records, stop_evt, killed_evt, fi, kill_info):
    """Open-loop generator: warm at the base rate, ramp to ramp x base,
    cool back down. The replica kill fires mid-ramp."""
    from flexflow_tpu.runtime.serving import RequestShedError

    rng = np.random.RandomState(args.seed)
    phases = [("warm", args.warm_s, args.base_rate),
              ("ramp", args.ramp_s, args.base_rate * args.ramp),
              ("post", args.post_s, args.base_rate)]
    for phase, dur, rate in phases:
        t_end = time.monotonic() + dur
        period = 1.0 / rate
        while time.monotonic() < t_end and not stop_evt.is_set():
            if (phase == "ramp" and not killed_evt.is_set()
                    and time.monotonic() > t_end - dur * (1 - args.kill_at)):
                # kill the BUSIEST replica, and only once it provably has
                # in-flight work: criterion 4's "requeued request finishes
                # under its original trace id" needs the victim to strand
                # something, and an idle victim mid-tick would make the
                # whole check flaky. If every replica is momentarily idle
                # this retries next loop iteration.
                with rs._lock:
                    busy = sorted(
                        ((r.batcher.active_slots, name)
                         for name, r in rs._replicas.items()
                         if r.batcher.thread_alive()), reverse=True)
                if busy and busy[0][0] > 0:
                    victim = busy[0][1]
                    fi.inject("replica_death", replica=victim)
                    kill_info["victim"] = victim
                    killed_evt.set()
                    print(f"[load_check] injected replica_death on "
                          f"{victim}", file=sys.stderr)
            plen = int(rng.randint(2, args.max_prompt + 1))
            prompt = rng.randint(0, args.vocab, plen).astype(np.int32)
            new = int(rng.randint(2, args.max_new + 1))
            try:
                req = rs.submit(prompt, max_new_tokens=new,
                                deadline_s=args.deadline_s)
                records.append(Record(req, phase))
            except RequestShedError as e:
                records.append(Record(None, phase, submit_error=e))
            time.sleep(period)


def verify_request_trace(tel_dir, *, expect_requeue):
    """Criterion 4: reconstruct per-request lifecycles from the finished
    session's events.jsonl and judge the flight-recorder contract.
    Returns (verdict-dict-for-summary, failure-strings)."""
    from flexflow_tpu.obs.tracer import read_events_jsonl

    failures = []
    events_path = os.path.join(tel_dir, "events.jsonl")
    trace_path = os.path.join(tel_dir, "trace.json")
    events, problems = read_events_jsonl(events_path)
    if problems:
        failures.append(
            f"events.jsonl has {len(problems)} schema-invalid line(s): "
            + "; ".join(problems[:3])
        )
    by_req = {}
    for e in events:
        if e.get("cat") != "requests":
            continue
        rid = e.get("args", {}).get("request")
        if rid is not None:
            by_req.setdefault(rid, []).append(e["name"])
    lifecycle = ("queue", "admit", "prefill", "decode", "complete")
    full = [rid for rid, names in by_req.items()
            if all(s in names for s in lifecycle)]
    requeued_ok = [
        rid for rid, names in by_req.items()
        if "requeue" in names and names.count("complete") == 1
    ]
    double_complete = [rid for rid, names in by_req.items()
                       if names.count("complete") > 1]
    verdict = {
        "traced_requests": len(by_req),
        "full_lifecycle": len(full),
        "requeued_completed": len(requeued_ok),
        "schema_problems": len(problems),
        "perfetto_trace": trace_path,
    }
    if not by_req:
        failures.append("telemetry enabled but no request events recorded")
    elif not full:
        failures.append(
            "no traced request carries the full queue->admit->prefill->"
            "decode->complete lifecycle"
        )
    if double_complete:
        failures.append(
            f"{len(double_complete)} request(s) completed more than once "
            f"in the trace: {double_complete[:3]}"
        )
    if expect_requeue and not requeued_ok:
        failures.append(
            "replica kill fired but no requeued request finished under "
            "its original trace id"
        )
    if not os.path.exists(trace_path):
        failures.append(f"missing Perfetto export {trace_path}")
    else:
        with open(trace_path) as f:
            tr = json.load(f)
        if "traceEvents" not in tr:
            failures.append("trace.json is not Chrome-trace shaped")
    return verdict, failures


def verify_fleet(args, *, expected_requests, victim, killed):
    """The --fleet-spool criteria (obs/fleet.py, docs/observability.md
    "Fleet observatory"): judged from the spool directory and the
    finished telemetry session AFTER the ReplicaSet has stopped.

      a. the cross-process rollup **conserves request counts** — the
         fleet-summed ``ff_serving_requests_total`` equals the client's
         completed count (warmup + offered load), i.e. the killed
         replica's final tally survived in its terminal spool;
      b. the killed replica's spool reads as **stale or dead**, never
         live (its death spool declares the terminal status);
      c. when the autoscaler added capacity, the ``replica_scale_up``
         event names the **anomaly** the sentinel blamed it on;
      d. a ``replica_death`` **forensics bundle** names the victim and
         passes ``validate_bundle``.
    Returns (verdict-dict-for-summary, failure-strings)."""
    from flexflow_tpu.obs import flight_recorder as fr
    from flexflow_tpu.obs.fleet import FleetAggregator

    failures = []
    agg = FleetAggregator(args.fleet_spool, staleness_s=5.0, death_s=15.0)
    view = agg.aggregate()
    states = view.states()
    total = view.counter_total("ff_serving_requests_total")
    corrupt = [r.process for r in view.records if r.error is not None]
    verdict = {
        "spooled_processes": len(view.records),
        "states": states,
        "requests_total": total,
        "expected_requests": expected_requests,
        "corrupt_spools": corrupt,
    }
    if corrupt:
        failures.append(f"corrupt spool file(s): {corrupt}")
    if not view.records:
        failures.append("fleet spool dir has no spools at all")
    # (a) counter conservation across the kill
    if total != expected_requests:
        failures.append(
            f"fleet rollup lost requests: ff_serving_requests_total sums "
            f"to {total:.0f} across spools but the client saw "
            f"{expected_requests} completions"
        )
    # (b) the victim's terminal spool classifies stale/dead, not live
    if killed and victim is not None:
        vstate = states.get(victim)
        if vstate is None:
            failures.append(
                f"killed replica {victim} left no spool behind")
        elif vstate not in ("stale", "dead"):
            failures.append(
                f"killed replica {victim} classified {vstate!r}, "
                "expected stale/dead")
        verdict["victim"] = victim
        verdict["victim_state"] = vstate
    # (c) anomaly-attributed scale-up, from the finished events.jsonl
    if args.telemetry_dir:
        from flexflow_tpu.obs.tracer import read_events_jsonl

        events, _ = read_events_jsonl(
            os.path.join(args.telemetry_dir, "events.jsonl"))
        ups = [e for e in events if e.get("name") == "replica_scale_up"]
        tagged = [e for e in ups if e.get("args", {}).get("anomaly")]
        verdict["scale_ups"] = len(ups)
        verdict["scale_up_anomalies"] = sorted(
            {e["args"]["anomaly"] for e in tagged})
        if ups and not tagged:
            failures.append(
                f"{len(ups)} replica_scale_up event(s) but none carries "
                "the anomaly tag that motivated it")
        if args.expect_scale_up and not ups:
            failures.append(
                "fleet leg expected the overload ramp to trigger a "
                "replica_scale_up but none fired")
    # (d) a valid replica_death forensics bundle naming the victim
    if killed and args.telemetry_dir:
        entries, index_problems = fr.read_index(args.telemetry_dir)
        failures.extend(index_problems)
        deaths = [e for e in entries
                  if e.get("reason") == "replica_death"]
        verdict["forensics_bundles"] = len(entries)
        verdict["replica_death_bundles"] = len(deaths)
        named = []
        for e in deaths:
            path = os.path.join(e["_dir"], e["file"])
            problems = fr.validate_bundle(path)
            if problems:
                failures.append(
                    f"replica_death bundle {e['file']} invalid: "
                    + "; ".join(problems[:3]))
                continue
            payload = fr.read_bundle(path)
            if payload.get("extra", {}).get("replica") == victim:
                named.append(e["file"])
        if not deaths:
            failures.append(
                "replica kill fired but no replica_death forensics "
                "bundle was dumped")
        elif victim is not None and not named:
            failures.append(
                f"no replica_death bundle names the victim {victim}")
    return verdict, failures


def run_shared_prefix(args):
    """The --shared-prefix A/B criterion: identical starved pool, the
    same same-prefix session burst, sharing off vs on. The geometry is
    chosen so one session needs `blocks+1` pages unshared but only ONE
    page once the prefix is published: prefix = `blocks` full pages,
    and the unique tail plus every decoded token fit inside a single
    extra page."""
    from flexflow_tpu.runtime.serving import (AdmissionQueue,
                                              ContinuousBatcher,
                                              GenerationRequest,
                                              ServingConfig,
                                              incremental_generate)

    ps = args.page_size
    if ps < 4:
        print("[load_check] --shared-prefix needs --page-size >= 4",
              file=sys.stderr)
        return 1
    blocks = 8                      # shared prefix: 8 full pages
    plen = blocks * ps + 2          # + 2-token unique tail
    max_new = ps - 2                # decode stays inside the tail page
    args.max_len = (blocks + 1) * ps
    pages_per = blocks + 1          # unshared worst case per session
    num_pages = args.num_pages or 2 * pages_per + 2  # fits TWO unshared
    slots = max(args.slots, 12)
    sessions = slots + 4            # more offered than can ever run

    import jax

    ndev = len(jax.devices())
    print(f"[load_check] shared-prefix A/B: {ndev} device(s), "
          f"{num_pages}-page pool, {pages_per} pages/session unshared, "
          f"{sessions} sessions offered", file=sys.stderr)
    model = build_model_fn(args)()
    rng = np.random.RandomState(args.seed)
    prefix = rng.randint(0, args.vocab, blocks * ps).astype(np.int32)
    prompts = [np.concatenate([prefix, np.array(
        [(i // args.vocab) % args.vocab, i % args.vocab], np.int32)])
        for i in range(sessions)]
    refs = [incremental_generate(model, p[None], max_new_tokens=max_new)[0]
            for p in prompts]

    phases = {}
    failures = []
    for label, share in (("unshared", False), ("shared", True)):
        cfg = ServingConfig(
            max_len=args.max_len, slots=slots, page_size=ps,
            num_pages=num_pages, share_prefixes=share, precompile=False,
            max_queue_depth=sessions + 4,
            default_deadline_s=args.deadline_s,
        )
        q = AdmissionQueue(max_depth=sessions + 4)
        b = ContinuousBatcher(model, cfg, q).start()
        peak = {"sessions": 0, "pages_shared": 0}
        poll_stop = threading.Event()

        def poll(b=b, peak=peak, poll_stop=poll_stop):
            while not poll_stop.is_set():
                peak["sessions"] = max(peak["sessions"], b.active_slots)
                peak["pages_shared"] = max(peak["pages_shared"],
                                           b.pool.pages_shared)
                time.sleep(0.001)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        try:
            reqs = [GenerationRequest(p.copy(), max_new,
                                      deadline_s=args.deadline_s)
                    for p in prompts]
            for r in reqs:
                q.offer(r)
            outs = [r.result(timeout=300.0) for r in reqs]
        finally:
            poll_stop.set()
            poller.join(timeout=2.0)
            report = b.pool.audit()
            pool_stats = dict(b.pool.stats)
            b.stop()
        exact = sum(1 for o, ref in zip(outs, refs)
                    if np.array_equal(o, ref))
        phases[label] = {
            "peak_concurrent_sessions": peak["sessions"],
            "peak_pages_shared": peak["pages_shared"],
            "exact_outputs": exact,
            "prefix_hits": pool_stats["prefix_hits"],
            "cow": pool_stats["cow"],
            "accounting_errors": pool_stats["accounting_errors"],
            "audit_violations": len(report.violations),
            "pages_resident_at_end": report.pages_resident,
        }
        if exact != sessions:
            failures.append(
                f"{label}: only {exact}/{sessions} outputs exact vs "
                f"incremental_generate")
        if not report.ok:
            failures.append(
                f"{label}: pool audit found {len(report.violations)} "
                f"violation(s); first: {report.violations[0].kind}")
        if report.pages_resident:
            failures.append(
                f"{label}: {report.pages_resident} page(s) leaked after "
                f"the burst drained")

    ratio = (phases["shared"]["peak_concurrent_sessions"]
             / max(1, phases["unshared"]["peak_concurrent_sessions"]))
    summary = {
        "devices": ndev,
        "geometry": {"page_size": ps, "prefix_blocks": blocks,
                     "prompt_len": plen, "max_new": max_new,
                     "num_pages": num_pages, "slots": slots,
                     "sessions_offered": sessions,
                     "pages_per_session_unshared": pages_per},
        "phases": phases,
        "concurrency_ratio": round(ratio, 2),
        "required_ratio": args.share_factor,
    }
    if ratio < args.share_factor:
        failures.append(
            f"sharing sustained only {ratio:.2f}x the unshared concurrent "
            f"sessions (need >= {args.share_factor}x in the same "
            f"{num_pages}-page budget)")
    if phases["shared"]["peak_pages_shared"] <= 0:
        failures.append("ff_kv_pages_shared never rose above 0 with "
                        "sharing on")
    if phases["shared"]["prefix_hits"] < 1:
        failures.append("no admission attached a shared prefix")
    if phases["unshared"]["prefix_hits"] or phases["unshared"][
            "peak_pages_shared"]:
        failures.append("sharing leaked into the share_prefixes=False "
                        "control phase")

    print(json.dumps(summary, indent=2, default=str))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2, default=str)
    if failures:
        for f_ in failures:
            print(f"[load_check] FAIL: {f_}", file=sys.stderr)
        return 1
    print("[load_check] OK", file=sys.stderr)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscaler ceiling (default: --replicas, i.e. "
                         "no scale-up headroom); the fleet leg sets this "
                         "above --replicas so the overload ramp provokes "
                         "an anomaly-attributed replica_scale_up")
    ap.add_argument("--fleet-spool", type=str, default=None,
                    help="fleet spool directory (obs/fleet.py): every "
                         "replica's counters are spooled per autoscale "
                         "tick and once more with a terminal status at "
                         "death/drain; adds the fleet criteria — counter "
                         "conservation through the kill, stale/dead "
                         "classification of the victim, anomaly-tagged "
                         "scale-ups, and a valid replica_death forensics "
                         "bundle (needs --telemetry-dir for the last two)")
    ap.add_argument("--expect-scale-up", action="store_true",
                    help="with --fleet-spool: fail unless the ramp "
                         "actually triggered a replica_scale_up")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=32)
    ap.add_argument("--max-prompt", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV page-pool size per replica (default: covers "
                         "slots x max_len); small values exercise "
                         "admission backpressure")
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--search-budget", type=int, default=2)
    ap.add_argument("--decode-strategy", action="store_true",
                    help="compile_decode() each replica model: serve the "
                         "batched decode step from the decode-objective "
                         "strategy (docs/serving.md)")
    ap.add_argument("--base-rate", type=float, default=6.0,
                    help="pre-ramp offered load, requests/s")
    ap.add_argument("--ramp", type=float, default=10.0,
                    help="offered-load multiplier during the ramp")
    ap.add_argument("--warm-s", type=float, default=4.0)
    ap.add_argument("--ramp-s", type=float, default=6.0)
    ap.add_argument("--post-s", type=float, default=3.0)
    ap.add_argument("--kill-at", type=float, default=0.4,
                    help="fraction into the ramp to kill a replica")
    ap.add_argument("--deadline-s", type=float, default=8.0)
    ap.add_argument("--queue-depth", type=int, default=24)
    ap.add_argument("--p99-factor", type=float, default=3.0)
    ap.add_argument("--p99-floor-s", type=float, default=0.25,
                    help="pre-ramp p99 floor so CPU timing noise cannot "
                         "make the 3x bound vacuously tight")
    # generous on the CPU harness: every replica shares ONE process, so a
    # sibling's restart (strategy search + XLA compile, GIL-heavy) can
    # legitimately stall live iterations for seconds — a tight watchdog
    # here false-positives into cascading failovers. Production replicas
    # run in separate processes and use tight timeouts.
    ap.add_argument("--health-timeout-s", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", type=str, default=None,
                    help="also write the summary JSON to this path")
    ap.add_argument("--no-kill", action="store_true",
                    help="skip the replica kill (latency-only run)")
    ap.add_argument("--telemetry-dir", type=str, default=None,
                    help="run under a telemetry session writing to this "
                         "dir and verify the request flight recorder "
                         "(criterion 4)")
    ap.add_argument("--artifact-store", type=str, default=None,
                    help="persistent strategy store dir "
                         "(runtime/artifact_store.py): replica/spare "
                         "builds boot from cached strategies; adds the "
                         "cold-start criterion — at least one cache hit, "
                         "no corrupt entries (docs/artifact_cache.md)")
    ap.add_argument("--request-sample-rate", type=float, default=1.0,
                    help="head-based request trace sampling rate for the "
                         "telemetry session")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="run the KV prefix-sharing A/B criterion instead "
                         "of the load ramp: >= --share-factor x concurrent "
                         "sessions in the same page budget with sharing "
                         "on, exact outputs, zero audit violations")
    ap.add_argument("--share-factor", type=float, default=5.0,
                    help="required concurrent-session multiplier for "
                         "--shared-prefix")
    args = ap.parse_args()

    if args.shared_prefix:
        return run_shared_prefix(args)

    from flexflow_tpu.runtime.resilience import FaultInjector, InferenceTimeout
    from flexflow_tpu.runtime.serving import ReplicaSet, RequestShedError, \
        ServingConfig

    import jax

    ndev = len(jax.devices())
    print(f"[load_check] {ndev} device(s), {args.replicas} replica(s), "
          f"{args.slots} slot(s) each", file=sys.stderr)

    telemetry = None
    if args.telemetry_dir:
        import flexflow_tpu.obs as obs
        from flexflow_tpu import TelemetryConfig

        telemetry = obs.start(TelemetryConfig(
            dir=args.telemetry_dir,
            request_sample_rate=args.request_sample_rate,
        ))
        print(f"[load_check] telemetry session -> {args.telemetry_dir} "
              f"(request_sample_rate={args.request_sample_rate})",
              file=sys.stderr)

    store = None
    if args.artifact_store:
        from flexflow_tpu.runtime.artifact_store import ArtifactStore

        store = ArtifactStore(args.artifact_store)
        print(f"[load_check] artifact store -> {args.artifact_store} "
              f"({len(store.entries())} entries)", file=sys.stderr)

    fi = FaultInjector()
    cfg = ServingConfig(
        max_len=args.max_len, slots=args.slots, page_size=args.page_size,
        num_pages=args.num_pages, max_queue_depth=args.queue_depth,
        default_deadline_s=args.deadline_s,
    )
    ckpt_dir = tempfile.mkdtemp(prefix="ff_load_check_ckpt_")
    rs = ReplicaSet(
        build_model_fn(args), cfg, replicas=args.replicas,
        max_replicas=args.max_replicas,
        ckpt_dir=ckpt_dir, fault_injector=fi,
        health_timeout_s=args.health_timeout_s,
        restart_backoff_s=0.1,
        # a warm spare makes failover a checkpoint-restore instead of an
        # in-process rebuild — on the shared-core CPU harness a rebuild's
        # strategy search would starve the surviving replicas mid-ramp
        warm_spares=1,
        artifact_store=store,
        fleet_spool_dir=args.fleet_spool,
    ).start()

    # jit warmup: run a few requests through every replica so the decode
    # executables (and prefill buckets) are compiled BEFORE the measured
    # warm phase — compile time is a cold-start cost, not serving latency,
    # and leaving it in would inflate the pre-ramp p99 the bound hangs off
    wrng = np.random.RandomState(args.seed + 1)

    def warm_req():
        plen = int(wrng.randint(2, args.max_prompt + 1))
        return rs.submit(wrng.randint(0, args.vocab, plen).astype(np.int32),
                         max_new_tokens=args.max_new, deadline_s=120.0)

    n_warm = 2 * args.replicas * args.slots
    if args.max_replicas and args.max_replicas > args.replicas:
        # with scale-up headroom, the jit-warmup flood must stay below
        # the autoscale queue threshold — a warmup-triggered scale-up
        # would fire before the anomaly sentinel has any baseline, and
        # the fleet criterion wants the RAMP's scale-up, blamed on a
        # real anomaly
        wave = max(1, rs.scale_up_queue_depth - 1)
        warmups = []
        for i in range(0, n_warm, wave):
            batch = [warm_req() for _ in range(min(wave, n_warm - i))]
            for w in batch:
                w.wait(timeout=120.0)
            warmups.extend(batch)
    else:
        warmups = [warm_req() for _ in range(n_warm)]
    warm_completed = 0
    for w in warmups:
        w.wait(timeout=120.0)
        try:
            w.result(timeout=0.5)
            warm_completed += 1
        except BaseException:
            pass  # shed warmups don't count toward conservation
    print("[load_check] warmup done, starting offered load",
          file=sys.stderr)

    records = []
    stop_evt = threading.Event()
    killed_evt = threading.Event()
    kill_info = {}
    if args.no_kill:
        killed_evt.set()
    gen = threading.Thread(
        target=offered_load,
        args=(rs, args, records, stop_evt, killed_evt, fi, kill_info),
        daemon=True,
    )
    t_run0 = time.monotonic()
    gen.start()
    gen.join(timeout=args.warm_s + args.ramp_s + args.post_s + 60.0)
    stop_evt.set()

    # -- account for EVERY offered request (criterion 2) -----------------
    lat = {"warm": [], "ramp": [], "post": []}
    counts = {"offered": 0, "completed": 0, "shed_submit": 0,
              "shed_typed": 0, "hung_or_silent": 0, "untyped_error": 0}
    shed_reasons = {}
    wait_budget = time.monotonic() + 90.0
    for rec in records:
        counts["offered"] += 1
        if rec.req is None:  # shed synchronously at submit — typed
            counts["shed_submit"] += 1
            reason = getattr(rec.submit_error, "reason", "unknown")
            shed_reasons[reason] = shed_reasons.get(reason, 0) + 1
            continue
        try:
            rec.req.result(timeout=max(0.5, wait_budget - time.monotonic()))
            counts["completed"] += 1
            lat[rec.phase].append(rec.req.finished_t - rec.req.submitted_t)
        except RequestShedError as e:
            counts["shed_typed"] += 1
            reason = getattr(e, "reason", "unknown")
            shed_reasons[reason] = shed_reasons.get(reason, 0) + 1
        except InferenceTimeout:
            counts["hung_or_silent"] += 1
        except BaseException as e:
            counts["untyped_error"] += 1
            print(f"[load_check] UNTYPED failure: {type(e).__name__}: {e}",
                  file=sys.stderr)
    t_run = time.monotonic() - t_run0

    # criterion 3 needs the replacement replica live before we judge
    if not args.no_kill:
        t_wait = time.monotonic() + 30.0
        while (rs.replica_count() < args.replicas
               and time.monotonic() < t_wait):
            time.sleep(0.1)

    def p99(xs):
        return float(np.percentile(xs, 99)) if xs else float("nan")

    pre_p99 = p99(lat["warm"])
    load_p99 = p99(lat["ramp"] + lat["post"])
    bound = args.p99_factor * max(pre_p99, args.p99_floor_s)
    summary = {
        "devices": ndev,
        "counts": counts,
        "shed_reasons": shed_reasons,
        "latency_s": {
            "pre_ramp_p99": round(pre_p99, 4),
            "under_load_p99": round(load_p99, 4),
            "bound": round(bound, 4),
            "admitted_warm": len(lat["warm"]),
            "admitted_ramp": len(lat["ramp"]),
            "admitted_post": len(lat["post"]),
        },
        "failover": {
            "killed": killed_evt.is_set() and not args.no_kill,
            "restarts": rs.stats["restarts"],
            "requeued": rs.stats["requeued"],
            "spares_used": rs.stats["spares_used"],
            "replicas_at_end": rs.replica_count(),
            "elastic_ckpt": True,
        },
        "run_seconds": round(t_run, 2),
        "replica_stats": rs.aggregate_stats(),
    }
    cold = rs.stats["cold_start_s"]
    summary["cold_start"] = {
        "builds": len(cold),
        "p95_s": round(float(np.percentile(cold, 95)), 4) if cold
        else None,
        "max_s": round(max(cold), 4) if cold else None,
        "artifact_store": bool(store),
        "cache_counts": dict(store.counts) if store else None,
    }

    failures = []
    # criterion 1: bounded tail latency for admitted requests
    if not lat["warm"]:
        failures.append("no pre-ramp completions to baseline p99 against")
    elif lat["ramp"] + lat["post"] and not load_p99 <= bound:
        failures.append(
            f"admitted p99 under load {load_p99:.3f}s exceeds bound "
            f"{bound:.3f}s (pre-ramp p99 {pre_p99:.3f}s x "
            f"{args.p99_factor})"
        )
    # criterion 2: zero silent drops or hangs
    if counts["hung_or_silent"] or counts["untyped_error"]:
        failures.append(
            f"silent/hung/untyped requests: {counts['hung_or_silent']} hung, "
            f"{counts['untyped_error']} untyped"
        )
    if counts["completed"] == 0:
        failures.append("no requests completed at all")
    # criterion 3: the killed replica came back (elastic restore path)
    if not args.no_kill:
        if not killed_evt.is_set():
            failures.append("replica kill never fired")
        if rs.stats["restarts"] < 1:
            failures.append("killed replica was not restarted")
        if rs.replica_count() < args.replicas:
            failures.append(
                f"replica strength {rs.replica_count()} < "
                f"{args.replicas} at end"
            )
    # cold-start criterion (with --artifact-store): replica builds hit
    # the strategy cache instead of re-searching, and nothing corrupted
    if store is not None:
        if store.counts.get("hit", 0) < 1:
            failures.append(
                "artifact store attached but no replica build hit the "
                f"strategy cache (counts: {store.counts})"
            )
        if store.counts.get("corrupt", 0):
            failures.append(
                f"artifact store reported {store.counts['corrupt']} "
                "corrupt entr(ies) during the run"
            )

    rs.stop()

    # criterion 4: the request flight recorder is coherent
    if telemetry is not None:
        import flexflow_tpu.obs as obs

        obs.finish()  # flush events.jsonl + trace.json
        verdict, trace_failures = verify_request_trace(
            args.telemetry_dir,
            expect_requeue=killed_evt.is_set() and not args.no_kill,
        )
        summary["trace"] = verdict
        failures.extend(trace_failures)

    # fleet criteria (with --fleet-spool): counter conservation through
    # the kill, victim classification, anomaly-attributed scale-ups, and
    # a valid replica_death forensics bundle. Judged after obs.finish()
    # so events.jsonl is flushed.
    if args.fleet_spool:
        fleet_verdict, fleet_failures = verify_fleet(
            args,
            expected_requests=warm_completed + counts["completed"],
            victim=kill_info.get("victim"),
            killed=killed_evt.is_set() and not args.no_kill,
        )
        summary["fleet"] = fleet_verdict
        failures.extend(fleet_failures)

    print(json.dumps(summary, indent=2, default=str))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2, default=str)
    if failures:
        for f_ in failures:
            print(f"[load_check] FAIL: {f_}", file=sys.stderr)
        return 1
    print("[load_check] OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
