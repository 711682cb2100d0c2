"""perfbench: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result; the numbers that decided
`correct` stand beside their limits in it and as the last lines of standard
error. Without a TPU whose kind is in harness/peaks.py, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
`--rehearsal` walks the same code at the tiny sizes each file carries for it,
on whatever backend is there, for the tests: it prints NO result line.

This file knows two kinds of cell, `train` and `serve`, and no cell,
configuration, mix or metric by name: those are files found by the names in
BENCHMARK.json (see README.md).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import peaks, runctx, spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    return ap.parse_args(argv)


def find_device(chips, rehearsal):
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearsal:
        return device, None
    if device["platform"] != "tpu":
        raise SystemExit(f"perfbench: JAX found no TPU (platform "
                         f"{device['platform']!r}); a rate means something "
                         "only on the chip")
    if device["count"] < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chip(s), "
                         f"{device['count']} found")
    return device, peaks.of(device["kind"])


def program_counters(trace_on):
    """In a traced run a telemetry session of the program is open through
    set-up, where every path is traced and so every fallback is counted; it
    is closed before the window, which runs as a user's would."""
    if not trace_on:
        return None
    import flexflow_tpu.obs as obs

    return obs.start(obs.TelemetryConfig(
        dir=os.path.join(runctx.OUT_DIR, "telemetry"), flight_recorder=False,
        anomaly_detection=False))


def main(argv):
    args = parse(argv)
    # FFConfig() reads sys.argv the way the reference's does
    sys.argv = sys.argv[:1]
    cell = spec.cell(args.workload, rehearsal=args.rehearsal)
    device, peak = find_device(cell.chips, args.rehearsal)
    from flexflow_tpu.config import enable_compile_cache

    from perfbench.harness import serve, train

    cache_dir = enable_compile_cache()
    cached_before = runctx.cache_entries(cache_dir)
    runctx.say(f"perfbench {cell.name} seed {args.seed} seconds "
               f"{args.seconds} trace {args.trace} on {device}; compile "
               f"cache {cache_dir}: {cached_before} entries")
    ctx = runctx.RunCtx(T_PROCESS, bool(args.trace))
    ctx.spans.seconds["import_s"] = time.perf_counter() - T_PROCESS
    ctx.facts.update(cell=cell, device=device, peaks=peak,
                     compile_cache_cold=cached_before == 0)
    builder, ref = spec.family(cell.config)
    tel = program_counters(bool(args.trace))
    kind = {"train": train, "serve": serve}[cell.kind]

    def close_session():
        if tel is not None:
            import flexflow_tpu.obs as obs

            ctx.facts["program_counters"] = {
                r["name"] + "".join(f"{{{k}={v}}}" for k, v in
                                    sorted(r.get("labels", {}).items())):
                r["value"] for r in tel.metrics.snapshot()
                if r["name"].endswith("_fallback_total")}
            obs.finish()

    ctx.before_window = close_session
    checks = kind.run(cell, builder, ref, args, ctx)
    checks.add("compiles_in_window", ctx.facts["compiles_in_window"])
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    ctx.facts["memory_peak_bytes"] = ctx.memory_peak_bytes
    per_layer = breakdown = None
    if args.trace:
        summary = ctx.tracer.summary()
        ctx.facts.update(trace=summary, spans=ctx.spans.seconds,
                         trace_window_s=ctx.tracer.window_s)
        device["busy_s"] = summary.busy_s
        device["window_s"] = ctx.tracer.window_s
        breakdown = {"device_ops": summary.top_ops(),
                     "idle_gaps": summary.top_gaps()}
        per_layer = {}
        for m in cell.per_layer:
            value = spec.reader(m["name"])(ctx.facts)
            if value is not None:
                per_layer[m["name"]] = value
        runctx.say(f"program counters {ctx.facts.get('program_counters')}")
    runctx.say(f"compile cache: {runctx.cache_entries(cache_dir)} entries "
               f"after; spans {ctx.spans.seconds}")
    line = runctx.result_line(cell, ctx, device, checks, per_layer, breakdown)
    for name, value in dict(ctx.end_to_end, **(per_layer or {})).items():
        runctx.say(f"metric {name} {value}")
    checks.report()
    if args.rehearsal:
        runctx.say("rehearsal: no result line")
        return 0 if checks.correct else 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
