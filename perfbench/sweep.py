"""Find a serving cell's knee, once: ONE process and one set-up, a window at
each of several fixed rates of the cell's own mix, lowest first, each from an
empty server (no pre-roll) and drained before the next. The knee is the
highest rate at which the backlog does not grow through the window (the wait
for a slot in its second half is no longer than in its first); the mix file
records what was swept and what held. The benchmark's runs never search for
a rate.

    python3 perfbench/sweep.py --workload <cell> --rates 2,3,4,5,6 --seconds 30

One JSON line per rate on standard output.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import runctx, serve, spec, traffic, window  # noqa: E402
from perfbench.run import find_device  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(r) for r in s.split(",")])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.argv = sys.argv[:1]
    cell = spec.cell(args.workload, rehearsal=args.rehearsal)
    find_device(cell.chips, args.rehearsal)
    builder, ref = spec.family(cell.config)
    sc = serve.ServeCell(cell, builder, ref, runctx.Spans())
    sc.build()
    sc.load_seed(args.seed)
    sc.start()
    vocab = ref.sizes(cell.config)["vocab"]
    for i, rate in enumerate(args.rates):
        mix = spec.overlay(cell.mix, {"arrival": {"rate_per_s": rate},
                                      "preroll": None})
        schedule = traffic.serve_schedule(mix, vocab, args.seed + i,
                                          args.seconds)
        w = sc.window(schedule, args.seconds, runctx.Tracer(False))
        at_close = [r["req"].done() for r in w.rows if r["req"] is not None]
        t0 = time.monotonic()
        sc.drain([r["req"] for r in w.rows if r["req"] is not None], 600.0)
        drain_s = time.monotonic() - t0
        tab = serve.table(w.rows)
        half = w.t_open + (w.t_close - w.t_open) / 2

        def wait_p95(rows):
            return 1e3 * (window.percentile(
                [t["admitted"] - t["due"] for t in rows
                 if t["admitted"] is not None], 95) or float("nan"))

        print(json.dumps({
            "rate_per_s": rate, "requests": len(tab),
            "failed": sum(1 for t in tab if t["failed"]),
            "offered_tokens_per_s":
                sum(o for _, _, o in schedule) / args.seconds,
            **window.serve_metrics(tab, w.t_open, w.t_close),
            "queue_wait_p95_first_half_ms":
                wait_p95([t for t in tab if t["due"] <= half]),
            "queue_wait_p95_second_half_ms":
                wait_p95([t for t in tab if t["due"] > half]),
            "unfinished_at_close": at_close.count(False),
            "drain_s": drain_s,
            "iterations_in_window": w.stats["iterations"],
            "ms_per_iteration": 1e3 * (w.t_close - w.t_open)
                / max(1, w.stats["iterations"]),
        }), flush=True)
    sc.stop()
    sc.free()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
