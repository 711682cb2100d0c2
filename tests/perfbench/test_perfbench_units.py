"""perfbench's arithmetic, generator, counts and files, on the CPU."""
import json
import math
import os
import re
import statistics
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import check, runctx, spec, traffic, window  # noqa: E402
from perfbench.models import decoder_lm_ref as ref  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def config(name):
    return spec.load_json("configs", name + ".json")


# -- counts of operations and bytes, against hand-computed values ------------
@pytest.mark.parametrize("name,params,matmul,flops_1024", [
    # 24 x 12 h^2 + h x vocab; + wte, wpe, biases, layernorms
    ("gpt2-medium", 406_212_608, 24 * 12 * 1024 ** 2 + 1024 * 50257,
     3 * (2 * (24 * 12 * 1024 ** 2 + 1024 * 50257)
          + 4 * 1024 * 24 * 1025 / 2)),
    ("opt-1.3b", 1_418_567_680, 24 * 12 * 2048 ** 2 + 2048 * 50272,
     3 * (2 * (24 * 12 * 2048 ** 2 + 2048 * 50272)
          + 4 * 2048 * 24 * 1025 / 2)),
])
def test_counts(name, params, matmul, flops_1024):
    cfg = config(name)
    c = ref.counts(cfg)
    assert c["params"] == params
    assert c["matmul_params"] == matmul
    assert ref.train_flops_per_token(cfg, 1024) == pytest.approx(flops_1024)


def test_param_count_by_hand():
    # gpt2-medium: embeddings + 24 blocks + final layernorm + untied head
    h, v, f = 1024, 50257, 4096
    block = 4 * h * h + h + 2 * h * f + f + h + 4 * h
    assert ref.counts(config("gpt2-medium"))["params"] == \
        v * h + 1024 * h + 24 * block + 2 * h + h * v


def test_decode_bytes_and_forward_flops():
    cfg = config("opt-1.3b")
    c = ref.counts(cfg)
    # one slot with 100 live positions: matrices once + 2 x 24 x 2048 x 100 values
    assert ref.decode_step_bytes(cfg, [100]) == \
        2 * (c["matmul_params"] + 2 * 24 * 2048 * 100)
    # one token at position 9, head computed: body + 10 keys of attention
    assert ref.forward_flops(cfg, [9], 1) == \
        2 * c["matmul_params"] + 4 * 2048 * 24 * 10


# -- the generator ------------------------------------------------------------
MIX = spec.load_json("traffic", "chat-saturated.json")
NO_PREROLL = dict(MIX, preroll=None)


def sizes(schedule):
    return sorted((len(p), o) for _, p, o in schedule)


def test_schedule_reproduces_from_its_seed():
    a = traffic.serve_schedule(MIX, 50272, 2 ** 31 + 17, 30.0)
    b = traffic.serve_schedule(MIX, 50272, 2 ** 31 + 17, 30.0)
    assert len(a) == len(b)
    for (d1, p1, o1), (d2, p2, o2) in zip(a, b):
        assert d1 == d2 and o1 == o2 and np.array_equal(p1, p2)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    a = traffic.serve_schedule(NO_PREROLL, 50272, 2 ** 31 + 17, 60.0)
    c = traffic.serve_schedule(NO_PREROLL, 50272, 5, 60.0)
    assert len(a) == len(c) == round(MIX["arrival"]["rate_per_s"] * 60.0)
    assert sorted(len(p) for _, p, _ in a) == sorted(len(p) for _, p, _ in c)
    assert sorted(o for _, _, o in a) == sorted(o for _, _, o in c)
    # the first arrival is at 0, the gap before it closes the window
    gaps = [np.diff([d for d, _, _ in s] + [60.0]) for s in (a, c)]
    assert np.allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in c]
    assert not np.allclose(gaps[0], gaps[1])
    assert a[0][0] == 0.0 and a[-1][0] < 60.0


def test_the_order_spreads_long_and_short_evenly():
    rng = traffic.rng_of(11, 4)
    sizes = np.arange(70)
    out = traffic.spread_out(rng, sizes)
    assert sorted(out) == list(sizes)
    eighth = np.searchsorted([70 * k // 8 for k in range(1, 8)], out, "right")
    for k in range(0, 64, 8):  # every run of eight holds one of each eighth
        assert sorted(eighth[k:k + 8]) == list(range(8))
    assert list(traffic.spread_out(traffic.rng_of(12, 4), sizes)) != list(out)
    a = traffic.serve_schedule(MIX, 50272, 3, 51.0)
    work = [sum(len(p) for _, p, _ in a[k:k + 24]) for k in (0, 24, 46)]
    assert max(work) < 1.25 * min(work)


def test_the_mix_keeps_its_published_shape():
    a = traffic.serve_schedule(MIX, 50272, 7, 51.0)
    lens = [len(p) for _, p, _ in a]
    outs = [o for _, _, o in a]
    assert min(lens) >= 32 and max(lens) <= 768
    assert min(outs) >= 16 and max(outs) <= 256
    assert 200 <= statistics.median(lens) <= 320
    assert 75 <= statistics.median(outs) <= 120
    assert all(len(p) + o <= 1024 for _, p, o in a)


def test_preroll_starts_the_load_before_the_window_with_a_backlog():
    pre = MIX["preroll"]
    a = traffic.serve_schedule(MIX, 50272, 3, 51.0)
    due = [d for d, _, _ in a]
    assert due == sorted(due) and due[-1] < 51.0
    assert due[:pre["backlog"]] == [-pre["seconds"]] * pre["backlog"]
    stream = round(MIX["arrival"]["rate_per_s"] * (pre["seconds"] + 51.0))
    assert len(a) == pre["backlog"] + stream
    # the stream's rate is the mix's over pre-roll and window together
    assert sum(1 for d in due if d >= 0.0) == pytest.approx(
        MIX["arrival"]["rate_per_s"] * 51.0, abs=6)


def test_a_distribution_no_mix_here_uses_is_refused():
    mix = dict(MIX, arrival={"rate_per_s": 4.0,
                             "gap": {"dist": "gamma", "cv": 3.0}})
    with pytest.raises(ValueError, match="unknown distribution"):
        traffic.serve_schedule(mix, 1000, 3, 20.0)


def test_train_batches_rows_all_differ_and_labels_shift():
    mix = spec.load_json("traffic", "pretrain-1024x4.json")
    x, y = traffic.train_batches(mix, 50257, 2 ** 31 + 5)
    assert x.shape == (11, 4, 1024) and y.shape == (11, 4, 1024, 1)
    assert np.array_equal(x[..., 1:], y[..., :-1, 0])
    assert len({r.tobytes() for r in x.reshape(-1, 1024)}) == 44
    x2, _ = traffic.train_batches(mix, 50257, 2 ** 31 + 5)
    assert np.array_equal(x, x2)


# -- percentile and window arithmetic ------------------------------------------
def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert window.percentile(vals, 95) == 95
    assert window.percentile([3.0], 95) == 3.0
    assert window.percentile([], 95) is None
    assert window.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 100], 95) == 100


def req(at_open, at_close, prompt=10, first=0.1, fin=None, ok=False):
    return {"due": 0.0, "offered": 0.001, "first_token": first,
            "finished": fin, "prompt_tokens": prompt, "ok": ok,
            "out_tokens": at_close if ok else 0,
            "produced_at_open": at_open, "produced_at_close": at_close}


def test_serve_rate_counts_every_token_produced_inside_the_window():
    rows = [req(0, 10, fin=3.0, ok=True),   # admitted and finished inside
            req(40, 100, fin=7.0, ok=True),  # was running at the open
            req(0, 25),                     # still running at the close
            req(0, 0),                      # queued all through
            req(30, 30, fin=-1.0, ok=True)]  # finished in the pre-roll
    m = window.serve_metrics(rows, 10.0, 30.0)
    assert m == {"serve_out_tokens_per_s": pytest.approx((10 + 60 + 25) / 20.0)}
    # a request's first token comes from its prefill, the rest from decode
    assert [window.decode_tokens(r) for r in rows] == [9, 60, 24, 0, 0]


def test_pooled_time_per_token_shows_a_stall_in_proportion():
    facts = {"t_open": 0.0, "t_close": 20.0, "requests": [
        req(0, 11, first=1.0, fin=3.0, ok=True),     # 10 gaps in 2 s
        req(0, 11, first=1.0, fin=12.0, ok=True),    # stalled: 10 gaps in 11 s
        req(0, 11, first=1.0, fin=25.0, ok=True),    # finished after the close
        req(0, 5)]}
    assert spec.reader("tpot_pooled_ms")(facts) == pytest.approx(1e3 * 13 / 20)
    facts["requests"] = facts["requests"][2:]
    assert spec.reader("tpot_pooled_ms")(facts) is None


def test_generator_lateness_is_of_the_requests_due_in_the_window():
    rows = [dict(req(0, 1), due=float(i), offered=i + 0.001 * i)
            for i in range(-5, 20)]
    facts = {"t_open": 0.0, "requests": rows}
    assert spec.reader("gen_lateness_p95_ms")(facts) == pytest.approx(18.0)


def test_slot_occupancy_and_serve_mfu_on_known_numbers():
    cell = spec.cell(next(w["name"] for w in BENCH["workloads"]
                          if spec.cell(w["name"]).kind == "serve"))
    rows = [req(0, 3), req(5, 7, prompt=20)]
    facts = {"cell": cell, "requests": rows, "stats": {"iterations": 2},
             "serving": {"slots": 4}, "window_s": 2.0,
             "peaks": {"flops_bf16": 1e12}}
    # 2 + 2 decode tokens over 2 iterations of 4 slots
    assert spec.reader("slot_occupancy")(facts) == pytest.approx(50.0)
    # the first: 10 prompt tokens through prefill with one head, then tokens
    # fed at positions 10 and 11; the second: fed at positions 24 and 25
    cfg = cell.config
    want = ref.forward_flops(cfg, range(10), 1) \
        + ref.forward_flops(cfg, [10, 11], 2) \
        + ref.forward_flops(cfg, [24, 25], 2)
    assert spec.reader("serve_mfu")(facts) == pytest.approx(
        100.0 * want / (2.0 * 1e12))


def test_train_rate_counts_all_tokens_over_all_time():
    (rate,) = window.train_metrics(4096 * 10, 1.0, 3.0, 1).values()
    assert rate == 20480.0
    (rate,) = window.train_metrics(4096 * 10, 1.0, 3.0, 4).values()
    assert rate == 5120.0


# -- the comparison -------------------------------------------------------------
def test_worst_norm_gap_measures_against_leaf_or_median():
    want = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    got = {"a": 1.1, "b": 2.0, "tiny": 2e-9}
    gap, leaf = check.worst_norm_gap(got, want)
    assert leaf == "a" and gap == pytest.approx(0.1)  # tiny: against the median
    gap, leaf = check.worst_norm_gap({"a": float("nan"), "b": 2, "tiny": 0}, want)
    assert leaf == "a" and math.isnan(gap)


def test_train_numbers_leave_idle_leaves_out_of_the_update_by_rule():
    ref_r = {"loss": [10.0], "grad": {"a": 1.0, "b": 1.0, "idle": 1e-6},
             "moved": {"a": 1.0, "b": 1.0, "idle": 1.0}}
    prog = {"loss": [10.1], "grad": {"a": 1.0, "b": 1.02, "idle": 1e-6},
            "moved": {"a": 1.0, "b": 1.0, "idle": 5.0}}
    n = check.train_numbers(prog, ref_r)
    assert n["loss_gap_step1"] == pytest.approx(0.01)
    assert n["grad_norm_gap"] == pytest.approx(0.02)
    assert n["update_norm_gap"] == 0.0


def test_checks_refuse_a_number_without_a_limit_and_fail_on_nan():
    c = check.Checks({"x": 1.0})
    with pytest.raises(KeyError):
        c.add("y", 0.0)
    assert not c.correct  # nothing compared is not correct
    c.add("x", float("nan"))
    assert not c.correct
    c.add("x", 0.5)
    assert c.correct


def test_result_line_has_the_contract_keys_and_the_checks_last():
    serve = next(w["name"] for w in BENCH["workloads"]
                 if spec.cell(w["name"]).kind == "serve")
    cell = spec.cell(serve)
    ctx = runctx.RunCtx(0.0, False)
    ctx.attempted, ctx.failed = 20, 1
    ctx.end_to_end.update({m["name"]: 1.5 for m in cell.end_to_end},
                          not_declared=3.0)
    checks = check.Checks({"x": 1.0})
    checks.add("x", 0.5)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = json.loads(runctx.result_line(cell, ctx, device, checks, None, None))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}
    assert line["checks"] == {"x": {"value": 0.5, "limit": 1.0}}
    name = cell.per_layer[0]["name"]
    traced = json.loads(runctx.result_line(
        cell, ctx, device, checks, {name: 2.0}, {"device_ops": [], "idle_gaps": []}))
    assert list(traced)[-2:] == ["breakdown", "checks"]
    # a reader that found nothing leaves its metric out of the line
    assert set(traced["metrics"]) == {m["name"] for m in cell.end_to_end} | {name}


# -- the files --------------------------------------------------------------------
def test_every_data_file_loads():
    for sub in ("configs", "traffic", "workloads"):
        for name in os.listdir(os.path.join(spec.BENCH_DIR, sub)):
            assert name.endswith(".json"), name
            spec.load_json(sub, name)
    for name in os.listdir(os.path.join(spec.BENCH_DIR, "metrics")):
        if name.endswith(".py"):
            assert callable(spec.reader(name[:-3]))


def test_benchmark_json_names_units_and_references():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert "workloads" not in moved or w in moved["workloads"], \
                (m["name"], w)
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = spec.cell(w["name"])
        assert cell.kind in ("train", "serve")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for c in BENCH["configs"]:
        assert c["file"].startswith("perfbench/") and c["reduced"] == []
        assert json.load(open(os.path.join(REPO, c["file"])))["source"] == \
            c["source"]
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for root, _, files in os.walk(os.path.join(REPO, path)):
            if "__pycache__" in root or "/.out" in root:
                continue
            for f in files:
                if not f.endswith(".pyc"):
                    assert allowed.match(os.path.relpath(
                        os.path.join(root, f), REPO)), f


def test_every_roofline_stands_beside_a_whole_step_mfu_moving_the_same_metric():
    mfus = [m for m in BENCH["per_layer"] if "mfu" in re.split(r"[_.]", m["name"])]
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            for w in m["workloads"]:
                assert any(f["moves"] == m["moves"] and w in f["workloads"]
                           for f in mfus), (m["name"], w)


def test_run_py_names_no_cell_config_mix_or_metric():
    text = open(os.path.join(spec.BENCH_DIR, "run.py")).read()
    names = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[g]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    for n in names:
        assert n not in text, n


def test_off_the_tpu_a_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=REPO,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
