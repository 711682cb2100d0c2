"""The readers of an admission's parts: three from the serve loop's always-on
counters (`admission_host_ms`, `prefill_wait_ms`, `insert_programs`) and two
from the traced slice (`insert_device_ms`, `prefill_device_ms`), on
hand-made counters, on a hand-made slice with known numbers, on a slice cut
from a v5e run, and silent on what an older program writes."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import program_runs, program_spans, runctx, spec  # noqa: E402

FIXTURES = os.path.join(spec.BENCH_DIR, "fixtures")
US = 1e-6

# two admissions back to back, then a decode iteration, on one chip (times in
# us): admission a (0-180) prefills on the device 0-100 and dispatches its
# insert at 120-180, whose three eager updates run 130-170 and 210-230, the
# last under admission b's prefill (200-345, its own program 240-340); b's
# insert is dispatched at 350-360 and its two updates run 380-420, under the
# decode iteration (370-530) whose step runs 420-520
SLICE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 11 offset_ps: 130000000 duration_ps: 20000000 }
    events { metadata_id: 11 offset_ps: 150000000 duration_ps: 20000000 }
    events { metadata_id: 11 offset_ps: 210000000 duration_ps: 20000000 }
    events { metadata_id: 10 offset_ps: 240000000 duration_ps: 100000000 }
    events { metadata_id: 11 offset_ps: 380000000 duration_ps: 20000000 }
    events { metadata_id: 11 offset_ps: 400000000 duration_ps: 20000000 }
    events { metadata_id: 12 offset_ps: 420000000 duration_ps: 100000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 130000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 145000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 150000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 165000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 210000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 225000000 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 240000000 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 380000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 395000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 400000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 415000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 420000000 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.9 = f32[1,1,50272]{2,1,0} fusion(bf16[1,256,2048]{2,1,0} %x), kind=kOutput" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.1 = bf16[16,1024,2048]{2,1,0} copy(bf16[16,1024,2048]{2,1,0} %p0)" } }
  event_metadata { key: 3 value { id: 3 name: "%dynamic-update-slice.1 = bf16[16,1024,2048]{2,1,0} dynamic-update-slice(bf16[16,1024,2048]{2,1,0} %copy.1, bf16[1,1024,2048]{2,1,0} %p1, s32[] %p2)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.7 = f32[16,1,50272]{2,1,0} fusion(bf16[16,1,2048]{2,1,0} %x), kind=kOutput" } }
  event_metadata { key: 10 value { id: 10 name: "jit_step(9)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_dynamic_update_slice(3)" } }
  event_metadata { key: 12 value { id: 12 name: "jit_step(7)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "ff-serve-replica0/77" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 180000000 stats { metadata_id: 1 str_value: "a" } }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 110000000 stats { metadata_id: 1 str_value: "a" } }
    events { metadata_id: 3 offset_ps: 120000000 duration_ps: 60000000 stats { metadata_id: 1 str_value: "a" } }
    events { metadata_id: 1 offset_ps: 190000000 duration_ps: 170000000 stats { metadata_id: 1 str_value: "b" } }
    events { metadata_id: 2 offset_ps: 200000000 duration_ps: 145000000 stats { metadata_id: 1 str_value: "b" } }
    events { metadata_id: 3 offset_ps: 350000000 duration_ps: 10000000 stats { metadata_id: 1 str_value: "b" } }
    events { metadata_id: 4 offset_ps: 370000000 duration_ps: 160000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "ff.serve.admit" } }
  event_metadata { key: 2 value { id: 2 name: "ff.serve.prefill" } }
  event_metadata { key: 3 value { id: 3 name: "ff.serve.insert" } }
  event_metadata { key: 4 value { id: 4 name: "ff.serve.decode" } }
  stat_metadata { key: 1 value { id: 1 name: "request" } }
}'''


@pytest.fixture(scope="module")
def slice_path(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("slice") / "vm.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SLICE))
    return str(path)


def traced_facts(path):
    return {"program_spans": program_spans.read(path),
            "program_runs": program_runs.read(path)}


# -- the counters -------------------------------------------------------------------
def counters():
    """A window of 10 admissions, 2 of them replayed from the memo."""
    return {"admitted": 10, "prefills": 10, "prefill_skips": 2,
            "admit_s": 2.0, "prefill_wait_s": 1.2, "insert_programs": 260}


COUNTED = [
    ("admission_host_ms", 80.0),  # (2.0 - 1.2) s / 10
    ("prefill_wait_ms", 150.0),   # 1.2 s / 8 computed
    ("insert_programs", 26.0),    # 260 / 10
]


@pytest.mark.parametrize("metric,value", COUNTED)
def test_counter_reader_on_known_numbers(metric, value):
    assert spec.reader(metric)({"stats": counters()}) == pytest.approx(value)


def test_the_host_part_and_the_wait_add_up_to_the_stall():
    facts = {"stats": counters()}
    s = facts["stats"]
    computed = s["prefills"] - s["prefill_skips"]
    whole = spec.reader("admission_host_ms")(facts) \
        + spec.reader("prefill_wait_ms")(facts) * computed / s["admitted"]
    assert whole == pytest.approx(spec.reader("admission_stall_ms")(facts))


@pytest.mark.parametrize("metric", [m for m, _ in COUNTED])
def test_counter_reader_is_silent_on_an_older_programs_counters(metric):
    # what the serve loop counted before it split the admission
    old = {"iterations": 190, "admitted": 28, "prefills": 28,
           "prefill_skips": 0, "admit_s": 3.1, "prefill_s": 2.0,
           "insert_s": 0.9}
    assert spec.reader(metric)({"stats": old}) is None


@pytest.mark.parametrize("metric", [m for m, _ in COUNTED])
def test_counter_reader_is_silent_with_nothing_admitted(metric):
    empty = dict(counters(), admitted=0, prefills=0, prefill_skips=0)
    assert spec.reader(metric)({"stats": empty}) is None


# -- the slice --------------------------------------------------------------------
def test_program_runs_are_read_by_name_and_start(slice_path):
    runs = program_runs.read(slice_path)
    assert [program_runs.base_name(n) for n, _, _ in runs] == [
        "jit_step", "jit_dynamic_update_slice", "jit_dynamic_update_slice",
        "jit_dynamic_update_slice", "jit_step", "jit_dynamic_update_slice",
        "jit_dynamic_update_slice", "jit_step"]
    assert [[s * 1e-3, e * 1e-3] for s, e in program_runs.inserts(runs)] == [
        [130, 150], [150, 170], [210, 230], [380, 400], [400, 420]]


def test_insert_device_ms_counts_the_inserts_wherever_they_ran(slice_path):
    facts = traced_facts(slice_path)
    # five updates of 20 us over two inserts: the one under the next prefill
    # and the two under the decode iteration count to the insert
    assert spec.reader("insert_device_ms")(facts) == pytest.approx(0.05)
    # the insert spans themselves see 40 us of the 100
    spans = facts["program_spans"]
    assert spans.busy_under("ff.serve.insert") == pytest.approx(40 * US)


def test_prefill_device_ms_leaves_out_an_earlier_inserts_tail(slice_path):
    facts = traced_facts(slice_path)
    spans = facts["program_spans"]
    # 100 us busy under a's prefill, 120 under b's of which 20 are a's insert
    assert spans.busy_under("ff.serve.prefill") == pytest.approx(220 * US)
    assert program_runs.busy_under_less_inserts(
        spans, facts["program_runs"], "ff.serve.prefill") == \
        pytest.approx(200 * US)
    assert spec.reader("prefill_device_ms")(facts) == pytest.approx(0.1)


def test_the_insert_span_carries_the_request(slice_path):
    spans = program_spans.read(slice_path).spans
    assert [(s.name, s.args["request"]) for s in spans
            if s.name != "ff.serve.decode"] == [
        ("ff.serve.admit", "a"), ("ff.serve.prefill", "a"),
        ("ff.serve.insert", "a"), ("ff.serve.admit", "b"),
        ("ff.serve.prefill", "b"), ("ff.serve.insert", "b")]


@pytest.mark.parametrize("metric", ["insert_device_ms", "prefill_device_ms"])
def test_traced_reader_is_silent_on_a_program_that_marks_nothing(metric):
    path = os.path.join(FIXTURES, "two_steps.xplane.txt")
    assert spec.reader(metric)(traced_facts(path)) is None


@pytest.mark.parametrize("metric", ["insert_device_ms", "prefill_device_ms"])
def test_traced_reader_is_silent_without_a_slice(metric, tmp_path, monkeypatch):
    monkeypatch.setattr(runctx, "OUT_DIR", str(tmp_path))
    assert spec.reader(metric)({}) is None


def test_of_reads_the_runs_slice_once(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(runctx, "OUT_DIR", str(tmp_path))
    run = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    os.makedirs(run)
    (run / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(SLICE))
    facts = {}
    runs = program_runs.of(facts)
    assert len(runs) == 8 and program_runs.of(facts) is runs


# -- a slice recorded on the chip -----------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(FIXTURES, "ff_admit_slice_v5e.xplane.txt.gz")
    return traced_facts(path)


def test_recorded_slice_holds_two_admissions_and_a_decode(recorded):
    spans = recorded["program_spans"]
    for name, n in (("ff.serve.admit", 2), ("ff.serve.admit.reserve", 2),
                    ("ff.serve.prefill", 2), ("ff.serve.prefill.init", 2),
                    ("ff.serve.prefill.dispatch", 2),
                    ("ff.serve.prefill.wait", 2), ("ff.serve.prefill.fetch", 2),
                    ("ff.serve.insert", 2)):
        assert spans.count(name) == n, name
    assert spans.count("ff.serve.decode") >= 1
    admits = [s.args["request"] for s in spans.spans
              if s.name == "ff.serve.admit"]
    inserts = [s.args["request"] for s in spans.spans
               if s.name == "ff.serve.insert"]
    assert inserts == admits and len(set(admits)) == 2


def test_recorded_insert_is_one_program_a_leaf(recorded):
    runs = recorded["program_runs"]
    # 48 per-slot leaves (24 layers' keys and values) an insert, each a
    # program of its own; the batch-1 cache is made leaf by leaf as well
    assert len(program_runs.inserts(runs)) == 2 * 48
    assert sum(1 for n, _, _ in runs
               if program_runs.base_name(n) == "jit_broadcast_in_dim") == 2 * 48


def test_recorded_readers(recorded):
    assert spec.reader("insert_device_ms")(recorded) == \
        pytest.approx(10.3814, abs=1e-3)
    assert spec.reader("prefill_device_ms")(recorded) == \
        pytest.approx(10.4992, abs=1e-3)


def test_recorded_insert_ran_under_its_own_span(recorded):
    # the host's dispatch of each update outlasts the device's work for it:
    # none of the insert runs under the next prefill or decode, so there the
    # admission's device time is its prefill's and its insert's
    spans = recorded["program_spans"]
    insert_s = program_runs.insert_seconds(spans, recorded["program_runs"])
    assert spans.busy_under("ff.serve.insert") == pytest.approx(insert_s)
    assert program_runs.busy_under_less_inserts(
        spans, recorded["program_runs"], "ff.serve.prefill") == \
        pytest.approx(spans.busy_under("ff.serve.prefill"))
    assert spec.reader("insert_device_ms")(recorded) \
        + spec.reader("prefill_device_ms")(recorded) == \
        pytest.approx(spec.reader("admission_device_ms")(recorded))


def test_recorded_prefill_idle_is_its_parts(recorded):
    under = recorded["program_spans"].under
    whole = under["ff.serve.prefill"]["idle_under"]
    assert under["ff.serve.prefill"]["idle_self"] < 0.01 * whole
    # most of it is the host making the batch-1 cache, leaf by leaf
    assert under["ff.serve.prefill.init"]["idle_self"] > 0.85 * whole


def test_an_insert_cut_by_the_slices_start_is_left_out(tmp_path):
    # a slice that begins inside admission a's insert holds no span of it:
    # a's updates are not counted against b's one span
    from jax.profiler import ProfileData

    a_insert = ('    events { metadata_id: 3 offset_ps: 120000000 duration_ps: '
                '60000000 stats { metadata_id: 1 str_value: "a" } }\n')
    assert a_insert in SLICE
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        SLICE.replace(a_insert, "")))
    facts = traced_facts(str(path))
    assert facts["program_spans"].count("ff.serve.insert") == 1
    assert spec.reader("insert_device_ms")(facts) == pytest.approx(0.04)
