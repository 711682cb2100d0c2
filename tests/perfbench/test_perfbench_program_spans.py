"""What the program wrote into the profiler's slice, reduced on fixtures
with known numbers: `ff.` host spans with their arguments, device busy and
idle time under each, scope paths of device operations; the twelve readers
built on them; and `None` from every one of them on a slice (or counters)
of a program that marks nothing."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import program_spans, runctx, spec, trace  # noqa: E402

FIXTURES = os.path.join(spec.BENCH_DIR, "fixtures")
US = 1e-6
BENCH = spec.benchmark()
CELL = {spec.cell(w["name"]).kind: w["name"] for w in BENCH["workloads"]}


def fixture(name):
    return os.path.join(FIXTURES, name + ".xplane.txt")


@pytest.fixture(scope="module")
def serve_slice():
    return program_spans.read(fixture("ff_serve_slice"))


@pytest.fixture(scope="module")
def train_slice():
    return program_spans.read(fixture("ff_train_slice"))


# -- the reduction ----------------------------------------------------------------
def test_host_spans_carry_their_arguments_and_their_thread(serve_slice):
    s = serve_slice
    assert all(sp.name.startswith("ff.") for sp in s.spans)  # no perfbench.offer
    assert {sp.thread for sp in s.spans} == {"ff-serve-replica0/77"}
    (admit,) = [sp for sp in s.spans if sp.name == "ff.serve.admit"]
    assert admit.args == {"request": "r1", "slot": 3, "prompt_len": 200,
                          "bucket": 256}
    assert [sp.args["iteration"] for sp in s.spans
            if sp.name == "ff.serve.decode"] == [40, 41]
    assert (admit.end_ns - admit.start_ns) * 1e-9 == pytest.approx(300 * US)


def test_busy_and_idle_under_each_span(serve_slice):
    s = serve_slice
    assert s.busy_s == pytest.approx(550 * US)
    assert s.idle_s == pytest.approx(450 * US)
    assert s.idle_named_s == pytest.approx(450 * US)
    assert s.count("ff.serve.decode") == 2 and s.count("ff.absent") == 0
    assert s.busy_under("ff.serve.decode") == pytest.approx(400 * US)
    assert s.idle_under("ff.serve.decode") == pytest.approx(200 * US)
    assert s.busy_under("ff.serve.admit") == pytest.approx(150 * US)
    assert s.idle_under("ff.serve.admit") == pytest.approx(150 * US)
    assert s.busy_under("ff.serve.prefill") == pytest.approx(120 * US)
    assert s.idle_under("ff.serve.idle") == pytest.approx(100 * US)
    assert s.busy_under("ff.absent") is None
    assert s.idle_under("ff.absent") is None


def test_the_innermost_span_wins_where_they_nest(serve_slice):
    u = serve_slice.under
    # the decode span's five parts cover it: nothing is left to itself
    assert u["ff.serve.decode"]["idle_self"] == pytest.approx(0.0)
    assert u["ff.serve.decode"]["busy_self"] == pytest.approx(0.0)
    assert u["ff.serve.decode.wait"]["busy_self"] == pytest.approx(400 * US)
    assert u["ff.serve.decode.fetch"]["idle_self"] == pytest.approx(60 * US)
    # admit 300 us: prefill 150 and insert 60 inside it, 90 its own
    assert u["ff.serve.admit"]["busy_self"] == pytest.approx(0.0)
    assert u["ff.serve.admit"]["idle_self"] == pytest.approx(90 * US)
    assert u["ff.serve.prefill"]["idle_self"] == pytest.approx(30 * US)
    assert u["ff.serve.insert"]["busy_self"] == pytest.approx(30 * US)
    # the innermost attribution sums to the whole
    assert sum(v["idle_self"] for v in u.values()) == pytest.approx(450 * US)
    assert sum(v["busy_self"] for v in u.values()) == pytest.approx(550 * US)


def test_a_waiting_span_ends_with_the_device(serve_slice):
    assert serve_slice.lag_after_device("ff.serve.decode.wait") == [
        pytest.approx(0.0), pytest.approx(0.0)]


def test_the_table_says_where_the_idle_time_lies(serve_slice, capsys):
    program_spans.read(fixture("ff_serve_slice")).report()
    err = capsys.readouterr().err
    assert "ff.serve.admit" in err and "ff.serve.decode.fetch" in err
    assert "100.0% of the idle time under a named ff. span" in err
    rows = serve_slice.table()
    assert rows[0][0] == "ff.serve.idle"  # most idle to itself first


def test_scope_paths_of_device_operations(train_slice):
    s = train_slice
    assert len(s.scopes) == 5  # the copy has none
    flash = next(v for k, v in s.scopes.items() if k.startswith("%ff_flash_fwd"))
    assert flash == "jit(step)/jvp(ff.fwd)/h0_attn/ff_flash_fwd/pallas_call"
    assert s.has_scopes()
    assert s.scope_seconds("ff.fwd", outside="transpose(") == \
        pytest.approx(600 * US)
    assert s.scope_seconds("transpose(jvp(ff.fwd))") == pytest.approx(800 * US)
    assert s.scope_seconds("ff.loss") == pytest.approx(200 * US)
    assert s.scope_seconds("ff.opt") == pytest.approx(400 * US)
    assert s.scope_seconds("h0_attn") == pytest.approx(1200 * US)
    assert s.unscoped_seconds() == pytest.approx(100 * US)
    assert s.busy_s == pytest.approx(1900 * US)


def test_idle_at_the_boundary_of_a_fit_call(train_slice):
    s = train_slice
    assert s.idle_s == pytest.approx(410 * US)
    assert s.idle_under("ff.fit.fold") == pytest.approx(250 * US)
    assert s.idle_under("ff.fit.sync") == pytest.approx(40 * US)
    assert s.idle_under("ff.fit.feed") == pytest.approx(60 * US)
    assert s.idle_named_s == pytest.approx(350 * US)
    assert s.count("ff.train.step") == 2
    assert [sp.args for sp in s.spans if sp.name == "ff.train.step"] == [
        {"step_num": 0}, {"step_num": 1}]


def test_a_program_that_marks_nothing_has_empty_tables():
    s = program_spans.read(fixture("two_steps"))
    assert s.spans == [] and s.under == {} and s.scopes == {}
    assert s.busy_s == pytest.approx(1800 * US)
    assert not s.has_scopes() and s.scope_seconds("ff.opt") is None


def test_the_recorded_v5e_slice_reads_too():
    # PR 24's recorded slice: names cut, no stats kept, no ff. span
    s = program_spans.read(os.path.join(
        FIXTURES, "train_two_steps_v5e.xplane.txt.gz"))
    assert s.spans == [] and not s.has_scopes() and s.busy_s > 0.18


def test_wire_reader_takes_a_stat_by_reference_too():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace('''
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%a = f32[] add()" stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 2 value { id: 2 name: "%b = f32[] add()" stats { metadata_id: 3 str_value: "other" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(step)/ff.opt/add:" } }
  stat_metadata { key: 3 value { id: 3 name: "hlo_category" } }
}''')
    assert program_spans.op_scopes(raw) == {
        "%a = f32[] add()": "jit(step)/ff.opt/add"}
    assert program_spans.op_scopes(b"") == {}


# -- the twelve readers -------------------------------------------------------------
def serve_facts(spans):
    """A window of 20 iterations and 4 admissions, with the fixture's
    slice; two requests whose token stamps lie 0.25 s apart but for one
    gap of 0.6 s, an admission's stall."""
    class Req:
        def __init__(self, t0, n, stall_at):
            self.token_t = [t0 + 0.25 * i + (0.35 if i > stall_at else 0.0)
                            for i in range(n)]

    stats = {"iterations": 20, "admitted": 4, "admit_s": 0.8,
             "decode_prepare_s": 0.02, "decode_dispatch_s": 0.04,
             "decode_wait_s": 4.0, "decode_fetch_s": 0.1,
             "decode_sample_s": 0.04, "prefill_tokens": 600,
             "prefill_bucket_tokens": 1000}
    requests = [{"row": {"req": Req(100.0, 600, 10)}},
                {"row": {"req": Req(100.1, 600, 300)}},
                {"row": {"req": None}}]
    return dict(cell=spec.cell(CELL["serve"]), stats=stats,
                requests=requests, t_open=100.0, t_close=400.0,
                trace=trace.reduce(fixture("ff_serve_slice")),
                program_spans=spans)


def train_facts(spans):
    return dict(cell=spec.cell(CELL["train"]), program_spans=spans,
                trace=trace.reduce(fixture("ff_train_slice")))


@pytest.fixture
def session_files(tmp_path, monkeypatch):
    """The telemetry session's files as the program writes them."""
    monkeypatch.setattr(runctx, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "telemetry")
    with open(tmp_path / "telemetry" / "events.jsonl", "w") as f:
        for name, dur in (("ff.compile.search", 2.5),
                          ("ff.compile.decode_search", 60.0),
                          ("ff.compile.decode_search", 4.5)):
            f.write(json.dumps({"ts": 1.0, "ph": "X", "name": name,
                                "cat": "compile", "dur": dur, "tid": 0,
                                "args": {}}) + "\n")
    with open(tmp_path / "telemetry" / "metrics.jsonl", "w") as f:
        for program, value in (("train_step", 1.0), ("decode_step", 5.0),
                               ("train_step", 2.0), ("train_scan", 1.0)):
            f.write(json.dumps({"time": 0, "name": "ff_program_traces_total",
                                "kind": "counter", "value": value,
                                "labels": {"program": program}}) + "\n")
    return tmp_path


SERVE_READINGS = [
    ("decode_host_ms", 10.0),            # 0.2 s of host phases / 20
    ("decode_idle_ms", 0.1),             # 200 us idle under 2 decode spans
    ("admission_stall_ms", 200.0),       # 0.8 s / 4
    ("admission_device_ms", 0.15),       # 150 us busy under 1 admit span
    ("prefill_padding_share", 40.0),     # 1 - 600 / 1000
    ("decode_gap_p99_ms", 250.0),        # 2 stalls of 0.6 s in 1,198 gaps
]
TRAIN_READINGS = [
    ("fwd_ms", 0.3), ("bwd_ms", 0.4),
    ("optimizer_share", 100.0 * 400 / 1900),
    ("fit_boundary_idle_ms", 0.35),      # 250 + 40 + 60 us over 1 fit() call
]


@pytest.mark.parametrize("metric,value", SERVE_READINGS)
def test_serve_reader_on_known_numbers(metric, value, serve_slice, capsys):
    assert spec.reader(metric)(serve_facts(serve_slice)) == pytest.approx(value)
    err = capsys.readouterr().err
    if metric == "decode_gap_p99_ms":
        assert "1198 gaps pooled" in err
    if metric == "decode_idle_ms":
        assert "ff.serve.decode.wait ends 0.000 ms" in err


@pytest.mark.parametrize("metric,value", TRAIN_READINGS)
def test_train_reader_on_known_numbers(metric, value, capsys):
    spans = program_spans.read(fixture("ff_train_slice"))
    assert spec.reader(metric)(train_facts(spans)) == pytest.approx(value)
    err = capsys.readouterr().err
    if metric == "fwd_ms":  # 100 of 1,900 us under no scope; the loss apart
        assert "under no ff. scope: 5.26%" in err
        assert "ff.loss 0.050 ms forward + 0.050 ms backward" in err
    if metric == "fit_boundary_idle_ms":
        assert "85.4% of the idle time under a named ff. span" in err


def test_decode_gap_tail_shows_the_stall_the_mean_hides(serve_slice):
    facts = serve_facts(serve_slice)
    for r in facts["requests"][:2]:  # 22 stalls: over 1% of the gaps
        r["row"]["req"].token_t = [
            100.0 + 0.25 * i + 0.35 * (i // 50) for i in range(600)]
    assert spec.reader("decode_gap_p99_ms")(facts) == pytest.approx(600.0)
    facts["t_close"] = 150.0  # a window that holds under 1,000 gaps
    assert spec.reader("decode_gap_p99_ms")(facts) is None


def test_set_up_readers_read_the_sessions_files(session_files):
    assert spec.reader("decode_search_s")({}) == pytest.approx(64.5)
    assert spec.reader("step_builds")({}) == 3  # last snapshot: 2 + 1


@pytest.mark.parametrize("metric", [m for m, _ in SERVE_READINGS]
                         + ["decode_search_s"])
def test_serve_reader_is_silent_on_a_program_that_marks_nothing(
        metric, tmp_path, monkeypatch):
    monkeypatch.setattr(runctx, "OUT_DIR", str(tmp_path))  # no session files
    class OldReq:
        pass
    facts = dict(
        cell=spec.cell(CELL["serve"]), t_open=0.0, t_close=51.0,
        stats={"iterations": 190, "admitted": 28, "prefills": 28},
        requests=[{"row": {"req": OldReq()}}, {"row": {"req": None}}],
        trace=trace.reduce(fixture("two_steps")),
        program_spans=program_spans.read(fixture("two_steps")))
    assert spec.reader(metric)(facts) is None


@pytest.mark.parametrize("metric", [m for m, _ in TRAIN_READINGS]
                         + ["step_builds"])
def test_train_reader_is_silent_on_a_program_that_marks_nothing(
        metric, tmp_path, monkeypatch):
    monkeypatch.setattr(runctx, "OUT_DIR", str(tmp_path))
    facts = dict(cell=spec.cell(CELL["train"]),
                 trace=trace.reduce(fixture("two_steps")),
                 program_spans=program_spans.read(fixture("two_steps")))
    assert spec.reader(metric)(facts) is None


def test_of_reads_the_runs_slice_once_and_is_none_without_one(
        tmp_path, monkeypatch):
    monkeypatch.setattr(runctx, "OUT_DIR", str(tmp_path))
    facts = {}
    assert program_spans.of(facts) is None and "program_spans" in facts
    from jax.profiler import ProfileData

    run = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    os.makedirs(run)
    with open(fixture("ff_train_slice")) as f, \
            open(run / "vm.xplane.pb", "wb") as out:
        out.write(ProfileData.text_proto_to_serialized_xspace(f.read()))
    facts = {}
    spans = program_spans.of(facts)
    assert spans.count("ff.fit.sync") == 1 and program_spans.of(facts) is spans


def test_every_new_metric_has_its_entry_and_its_reader():
    mine = [m for m, _ in SERVE_READINGS + TRAIN_READINGS] \
        + ["decode_search_s", "step_builds"]
    assert len(mine) == 12
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in mine:
        assert entries[name]["better"] == "lower"
        assert len(entries[name]["workloads"]) == 1
        assert callable(spec.reader(name))
    assert [m["name"] for m in BENCH["per_layer"]][-12:] == [
        "decode_host_ms", "decode_idle_ms", "admission_stall_ms",
        "admission_device_ms", "prefill_padding_share", "decode_gap_p99_ms",
        "decode_search_s", "step_builds", "fwd_ms", "bwd_ms",
        "optimizer_share", "fit_boundary_idle_ms"]


# -- a slice recorded on the chip (PR 25) -------------------------------------------
@pytest.fixture(scope="module")
def v5e_boundary():
    return program_spans.read(os.path.join(
        FIXTURES, "ff_train_boundary_v5e.xplane.txt.gz"))


def test_recorded_slice_kernels_carry_their_names_and_scopes(v5e_boundary):
    s = v5e_boundary
    fwd = [k for k in s.scopes if k.startswith("%ff_flash_fwd")]
    bwd = [k for k in s.scopes if k.startswith("%ff_flash_bwd")]
    assert len(fwd) == len(bwd) == 24  # one a layer, the two steps alike
    assert s.scopes[fwd[0]].startswith("jit(step)/jvp(ff.fwd)/h")
    assert s.scopes[fwd[0]].endswith(".attn/ff_flash_fwd/pallas_call")
    assert all(s.scopes[k].startswith("jit(step)/transpose(jvp(ff.fwd))/h")
               for k in bwd)
    # the rooflines still find them by layout among the Mosaic calls
    summary = trace.reduce(os.path.join(
        FIXTURES, "ff_train_boundary_v5e.xplane.txt.gz"))
    seconds, calls, _ = summary.kernel("bf16[64,1024,64]")
    # 2 steps x 24 layers x (forward + backward), and the third step's first
    assert calls == 97 and seconds == pytest.approx(0.031635, rel=1e-3)


def test_recorded_slice_splits_a_step_by_scope(v5e_boundary):
    s, steps = v5e_boundary, 2
    fwd = s.scope_seconds("ff.fwd", outside="transpose(") / steps
    bwd = s.scope_seconds("transpose(jvp(ff.fwd))") / steps
    opt = s.scope_seconds("ff.opt") / steps
    assert 1e3 * fwd == pytest.approx(24.84, abs=0.7)  # the third step's 2 ms
    assert 1e3 * bwd == pytest.approx(60.47, abs=0.05)
    assert 1e3 * opt == pytest.approx(2.228, abs=0.01)
    assert s.unscoped_seconds() / s.busy_s == pytest.approx(0.0505, abs=0.003)
    # a fusion carries one scope: the weights' Adam update runs inside the
    # fusion of their gradient's matmul and reads as backward
    kinds = dict(s.scope_kinds("transpose(jvp(ff.fwd))"))
    assert 1e3 * kinds["%divide_subtract_fusion"] / steps == \
        pytest.approx(21.07, abs=0.05)
    assert dict(s.scope_kinds("ff.opt")).keys() == {"%divide_subtract_fusion"}


def test_recorded_slice_names_the_idle_time_at_a_fit_boundary(v5e_boundary):
    s = v5e_boundary
    assert 1e3 * s.idle_under("ff.fit.fold") == pytest.approx(14.17, abs=0.01)
    assert 1e3 * s.idle_under("ff.fit.sync") == pytest.approx(1.15, abs=0.01)
    assert 1e3 * s.idle_under("ff.fit.feed") == pytest.approx(1.83, abs=0.01)
    assert 1e3 * s.idle_under("ff.train.step") == pytest.approx(0.98, abs=0.01)
    assert s.idle_named_s / s.idle_s == pytest.approx(0.961, abs=0.002)
    facts = train_facts(s)
    facts["trace"] = trace.reduce(os.path.join(
        FIXTURES, "ff_train_boundary_v5e.xplane.txt.gz"))
    assert spec.reader("fit_boundary_idle_ms")(facts) == \
        pytest.approx(17.15, abs=0.02)
    # the fold's wait ends with the device: host and device share a clock
    fold = next(sp for sp in s.spans if sp.name == "ff.fit.fold")
    assert fold.args == {"epoch": 0, "steps": 8}
