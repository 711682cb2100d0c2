#!/usr/bin/env python3
"""Phase-aware bench-regression gate: the headline throughput against
a BASELINE.json published number in the history directory (where there
is one), and each bench phase (fwd/bwd/opt/sync
seconds per step, bench.py's phases_s_per_step) against the previous
committed round — with the regression attributed to the phase that moved.

Reads the measurement from (first match wins):
  --bench-json FILE   a bench.py JSON line, or a driver BENCH_r*.json
                      artifact (the {"parsed": {...}} wrapper)
  stdin ("-")         a bench.py JSON line piped in
  BENCH_r*.json       the newest committed round artifact in the repo root

Exit code is 1 on any regression (headline below tolerance, or a phase
slower than its per-phase tolerance vs the previous round) unless
--warn-only, which downgrades every failure to a GitHub Actions
::warning:: annotation and exits 0. Phases missing on either side (old
rounds predate phases_s_per_step) skip silently — the headline gate
still applies.

Usage:
  python scripts/bench_regression.py                      # newest round
  python bench.py | python scripts/bench_regression.py -  # fresh run
  python scripts/bench_regression.py --tolerance 0.10 \
      --phase-tolerance fwd=0.10 --phase-tolerance sync=0.30
  python scripts/bench_regression.py --warn-only          # never fails
"""
import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PHASES = ("fwd", "bwd", "opt", "sync")
# opt/sync are the smallest slices of the step and the noisiest to time
# (the sync estimate is static on one chip) — give them more headroom
DEFAULT_PHASE_TOLERANCES = {"fwd": 0.15, "bwd": 0.15,
                            "opt": 0.25, "sync": 0.25}


def load_measurement(src):
    """-> (doc, where): the bench.py JSON dict from a line file, driver
    artifact, stdin, or the newest committed round."""
    if src == "-":
        doc = json.loads(sys.stdin.read())
        where = "stdin"
    elif src:
        with open(src) as f:
            doc = json.load(f)
        where = src
    else:
        rounds = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
        if not rounds:
            return None, None
        with open(rounds[-1]) as f:
            doc = json.load(f)
        where = os.path.basename(rounds[-1])
    if "parsed" in doc:  # driver artifact wraps the bench line
        doc = doc["parsed"] or {}
    return doc, where


def load_baseline(history_dir, metric, backend=None, smoke=False):
    """Published baseline for EXACTLY this metric on this backend, from a
    BASELINE.json in the history directory (the repo itself carries none
    any more). A series without a published number is warn-only: the
    caller skips the headline gate instead of comparing it against
    another workload's baseline.

    A round only gates against published.<metric>@<backend> — a number
    from one machine is never the baseline of another — and
    FF_BENCH_SMOKE rounds scope one step further
    (<metric>@<backend>+smoke): smoke shapes amortize warmup differently,
    so they never compare against full-run numbers."""
    try:
        with open(os.path.join(history_dir, "BASELINE.json")) as f:
            published = json.load(f).get("published", {}) or {}
    except (OSError, ValueError):
        return None
    key = f"{metric}@{backend}" if backend else metric
    if smoke:
        key += "+smoke"
    v = published.get(key)
    if isinstance(v, (int, float)) and v > 0:
        return float(v)
    return None


def previous_phases(where, history_dir=REPO, metric=None, backend=None,
                    smoke=False):
    """The newest committed round OTHER than the one under test that
    carries phases_s_per_step for the SAME metric and backend ->
    (phases dict, round label) or (None, None). Rounds that predate the
    metric field count as transformer rounds, and a round without a
    backend field only matches a measurement without one — comparing a
    CPU moe round's phases against a TPU round's would attribute a
    hardware/workload difference to a code change."""
    try:
        from flexflow_tpu.obs.step_profile import load_bench_history
    except ImportError:
        return None, None

    history = load_bench_history(history_dir)
    want_metric = metric or "transformer_train_throughput"
    for r in reversed(history):
        if where and os.path.basename(r["path"]) == os.path.basename(where):
            continue
        if (r.get("metric") or "transformer_train_throughput") != want_metric:
            continue
        if r.get("backend") != backend:
            continue
        if bool(r.get("smoke")) != bool(smoke):
            continue
        if isinstance(r.get("phases"), dict):
            return r["phases"], f"r{r['round']:02d}"
    return None, None


def parse_phase_tolerances(pairs):
    tol = dict(DEFAULT_PHASE_TOLERANCES)
    for pair in pairs or ():
        name, _, frac = pair.partition("=")
        if name not in PHASES or not frac:
            raise SystemExit(
                f"bench_regression: bad --phase-tolerance {pair!r} "
                f"(want one of {'/'.join(PHASES)}=FRACTION)")
        tol[name] = float(frac)
    return tol


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="phase-aware bench vs baseline/previous-round gate")
    ap.add_argument("bench_json", nargs="?", default=None,
                    help="bench JSON line file, driver artifact, or - for "
                         "stdin (default: newest BENCH_r*.json)")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="allowed fractional headline drop below baseline "
                         "(default 0.15)")
    ap.add_argument("--phase-tolerance", action="append", metavar="PH=FRAC",
                    help="per-phase allowed fractional slowdown vs the "
                         "previous round, e.g. fwd=0.10 (repeatable; "
                         f"defaults {DEFAULT_PHASE_TOLERANCES})")
    ap.add_argument("--history-dir", default=REPO,
                    help="directory holding the BENCH_r*.json round "
                         "artifacts the phase gate compares against "
                         "(default: repo root)")
    ap.add_argument("--warn-only", action="store_true",
                    help="downgrade regressions to ::warning:: annotations "
                         "and exit 0")
    args = ap.parse_args(argv)
    phase_tol = parse_phase_tolerances(args.phase_tolerance)

    doc, where = load_measurement(args.bench_json)
    if doc is None:
        print("bench_regression: no measurement found "
              "(no BENCH_r*.json rounds); nothing to compare")
        return 0
    value = doc.get("value")
    if not isinstance(value, (int, float)) or value <= 0:
        print(f"bench_regression: no usable value in {where}; "
              "nothing to compare")
        return 0
    metric = doc.get("metric", "transformer_train_throughput")
    backend = doc.get("backend")
    smoke = bool(doc.get("smoke"))
    failures = []

    # ---- headline gate: throughput vs the published baseline ----------
    baseline = load_baseline(args.history_dir, metric, backend, smoke)
    if baseline is None:
        # absent series are warn-only, never a failure: annotate so the
        # missing baseline is visible in the Actions summary and move on
        scope = f"{metric}@{backend}" if backend else metric
        if smoke:
            scope += "+smoke"
        print(f"::warning title=bench baseline::BASELINE.json has no "
              f"published value for {scope}; headline gate skipped "
              "(new series stay warn-only until a baseline is recorded "
              "on this backend)")
    else:
        ratio = value / baseline
        line = (f"bench_regression: {metric} = {value:.3f} vs baseline "
                f"{baseline:.3f} ({where}); ratio {ratio:.3f}, "
                f"tolerance -{args.tolerance:.0%}")
        if ratio < 1.0 - args.tolerance:
            failures.append(line)
        else:
            print(f"{line} — OK")

    # ---- phase gate: seconds per step vs the previous round -----------
    cur_phases = doc.get("phases_s_per_step")
    if not isinstance(cur_phases, dict):
        print(f"bench_regression: {where} has no phases_s_per_step; "
              "skipping the phase gate")
    else:
        prev, prev_label = previous_phases(where, args.history_dir,
                                           metric, backend, smoke)
        if prev is None:
            print("bench_regression: no previous round carries "
                  "phases_s_per_step; skipping the phase gate")
        else:
            grew = {}
            for ph in PHASES:
                a, b = prev.get(ph), cur_phases.get(ph)
                if not isinstance(a, (int, float)) or a <= 0 \
                        or not isinstance(b, (int, float)):
                    continue
                r = b / a
                line = (f"bench_regression: phase {ph} = {b * 1e3:.3f} ms "
                        f"vs {a * 1e3:.3f} ms ({prev_label}); ratio "
                        f"{r:.3f}, tolerance +{phase_tol[ph]:.0%}")
                if b > a:
                    grew[ph] = b - a
                if r > 1.0 + phase_tol[ph]:
                    failures.append(line)
                else:
                    print(f"{line} — OK")
            if grew:
                total = sum(grew.values())
                dominant = max(grew, key=grew.get)
                print(f"bench_regression: step grew {total * 1e3:.3f} ms; "
                      f"dominant phase {dominant} "
                      f"({grew[dominant] / total:.0%} of the growth)")

    for line in failures:
        print(f"::warning title=bench regression::{line}")
    if failures and not args.warn_only:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
