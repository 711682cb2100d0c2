"""Readings for the limits of `correct`, many seeds in ONE process (set-up is
paid once): for every seed the numbers a run of the cell compares, read from
the sound program; for the control seeds the same numbers with the reference
in the next precision down (the configuration's `dtype_policy.control`) put
in the program's place; for a training cell also with half of the batch left
out and with a state left unchanged. Every reading goes through
`check.Checks` with the cell's own committed limits, and its line says
whether it came out `correct`: the program has to, every stand-in must not.
PERF.md records what these readings were and the limits set from them; the
benchmark's own runs never call this.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--seconds 15] [--rehearsal]

One JSON line per reading on standard output.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import check, runctx, serve, spec, traffic, train  # noqa: E402
from perfbench.run import find_device  # noqa: E402


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def emit(cell, kind, seed, numbers, **more):
    """One reading, judged by the cell's own limits as a run would be."""
    checks = check.Checks(cell.params["limits"])
    for name, value in numbers.items():
        if name in checks.limits:
            checks.add(name, value)
    failed = [n for n, r in checks.rows.items()
              if not r["value"] <= r["limit"]]
    print(json.dumps(dict(kind=kind, seed=seed, correct=checks.correct,
                          failed=failed, **numbers, **more)), flush=True)


def train_readings(cell, builder, ref, args):
    tc = train.TrainCell(cell, builder, ref, runctx.Spans())
    tc.build()
    control = cell.config["dtype_policy"]["control"]
    half = cell.mix["batch"] // 2
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        tc.load_seed(seed)
        if seed in args.seeds:
            program = tc.first_steps(seed)
        tc.free()
        reference = tc.reference_steps(seed)
        if seed in args.seeds:
            emit(cell, "program", seed,
                 check.train_numbers(program, reference))
        if seed in args.control_seeds:
            for kind, stood_in in (
                    ("control_" + control, tc.reference_steps(seed, control)),
                    ("fault_half_batch", tc.reference_steps(seed, rows=half)),
                    ("fault_state_unchanged",
                     tc.reference_steps(seed, stuck=True))):
                emit(cell, kind, seed,
                     check.train_numbers(stood_in, reference))


def serve_readings(cell, builder, ref, args):
    sc = serve.ServeCell(cell, builder, ref, runctx.Spans())
    sc.build()
    control = cell.config["dtype_policy"]["control"]
    vocab = ref.sizes(cell.config)["vocab"]
    tracer, models = runctx.Tracer(False), {}
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        sc.load_seed(seed)
        sc.start()
        schedule = traffic.serve_schedule(cell.mix, vocab, seed, args.seconds)
        w = sc.window(schedule, args.seconds, tracer)
        sc.stop(w.rows)
        tab = serve.table(w.rows)
        picks = serve.served_rows(tab)
        sc.free(keep_model=True)
        for kind, precision in (
                [("program", "f32")] * (seed in args.seeds)
                + [("control_" + control, control)]
                * (seed in args.control_seeds)):
            gaps = serve.logit_gaps(ref, cell.config, seed, picks,
                                    precision=precision, models=models)
            emit(cell, kind, seed, serve.numbers(tab, gaps, vocab),
                 requests=len(tab), compared_requests=len(picks),
                 compared_tokens=sum(len(g) for g in gaps),
                 median_logit_gap=float(sorted(
                     x for g in gaps for x in g)[sum(map(len, gaps)) // 2]))


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.argv = sys.argv[:1]
    cell = spec.cell(args.workload, rehearsal=args.rehearsal)
    find_device(cell.chips, args.rehearsal)
    builder, ref = spec.family(cell.config)
    {"train": train_readings, "serve": serve_readings}[cell.kind](
        cell, builder, ref, args)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
