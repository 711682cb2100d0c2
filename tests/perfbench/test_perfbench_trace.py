"""The reduction from a profiler trace to metrics, on fixtures with known
numbers: a synthetic XSpace written out by hand, and a slice recorded on the
chip (TPU v5e) of the training cell's own step."""
import dataclasses
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import peaks, spec, trace  # noqa: E402

FIXTURES = os.path.join(spec.BENCH_DIR, "fixtures")
US = 1e-6


@pytest.fixture(scope="module")
def two_steps():
    return trace.reduce(os.path.join(FIXTURES, "two_steps.xplane.txt"))


def test_busy_idle_and_programs(two_steps):
    s = two_steps
    assert s.chips == 1
    assert s.busy_s == pytest.approx(1800 * US)
    assert (s.last_ns - s.first_ns) * 1e-9 == pytest.approx(3000 * US)
    assert s.modules == {"jit_step(1)": [pytest.approx(2000 * US), 2]}
    # four 100 us gaps inside the steps are below nothing: min gap is 50 us
    gaps = s.top_gaps()
    assert gaps[0] == ["sleep", pytest.approx(1000 * US)]
    assert gaps[1] == ["fit", pytest.approx(200 * US)]


def test_kernel_time_by_name(two_steps):
    seconds, calls = two_steps.op_seconds(trace.MOSAIC_CALL)
    assert calls == 4 and seconds == pytest.approx(1000 * US)
    top = two_steps.top_ops(2)
    assert top[0][0].startswith("%fusion = bf16[4,1024,1024]")
    assert top[0][1] == pytest.approx(600 * US)
    assert top[1][0].startswith("%transpose_jvp___ = ")
    assert top[1][1] == pytest.approx(600 * US)
    assert all(len(name) <= 100 for name, _ in two_steps.top_ops())


def test_a_kernel_is_known_by_the_layout_it_works_on(two_steps, capsys):
    seconds, calls, programs = two_steps.kernel("bf16[64,1024,64]")
    assert calls == 4 and seconds == pytest.approx(1000 * US)
    assert programs == {"jit_step(1)"}
    assert capsys.readouterr().err == ""
    # Mosaic calls ran, none on this layout: nothing, and it says so
    assert two_steps.kernel("bf16[32,1024,16,64]") is None
    assert "4 Mosaic call(s) in the trace, none on bf16[32,1024,16,64]" \
        in capsys.readouterr().err
    bare = dataclasses.replace(two_steps, ops={
        k: v for k, v in two_steps.ops.items() if "custom-call" not in k})
    assert bare.kernel("bf16[64,1024,64]") is None
    assert capsys.readouterr().err == ""


def test_a_program_is_found_by_its_executions(two_steps):
    assert two_steps.program() == (pytest.approx(2000 * US), 2)
    assert two_steps.program(executions=3) == two_steps.program()
    assert two_steps.program(executions=40) is None
    assert two_steps.program(names={"jit_other(2)"}) is None


def facts_for(cell_name, summary, **more):
    cell = spec.cell(cell_name)
    return dict(cell=cell, trace=summary, peaks=peaks.of("TPU v5 lite"), **more)


def test_trace_readers_on_known_numbers(two_steps):
    train = next(w["name"] for w in spec.benchmark()["workloads"]
                 if spec.cell(w["name"]).kind == "train")
    facts = facts_for(train, two_steps, trace_window_s=3000 * US)
    assert spec.reader("device_idle_share.train")(facts) == pytest.approx(40.0)
    assert spec.reader("train_step_device_ms")(facts) == pytest.approx(0.9)
    # gpt2-medium at 4 x 1,024: 7 B H S^2 d = 30.06 GFLOP a layer bounds it
    # (0.1526 ms at 197 TFLOP/s against 0.1229 ms for 100.7 MB at 819 GB/s)
    flops = 7 * 4 * 16 * 1024 * 1024 * 64
    moved = 12 * 4 * 16 * 1024 * 64 * 2
    assert flops / 197e12 > moved / 819e9
    want = 100.0 * 24 * 2 * (flops / 197e12) / (1000 * US)
    assert spec.reader("flash_attn_roofline")(facts) == pytest.approx(want)


SERVE = next(w["name"] for w in spec.benchmark()["workloads"]
             if spec.cell(w["name"]).kind == "serve")
SERVING = {"slots": 16, "max_len": 1024, "page_size": 16}


def test_decode_step_readers_on_known_numbers(two_steps):
    """A slice of two iterations, one slot occupied, fed at positions 10 and
    11 (11 and 12 keys), each with the head; the fixture's program takes
    1,000 us an execution."""
    facts = facts_for(SERVE, two_steps, serving=SERVING,
                      traced={"iterations": 2, "positions": [[10], [11]]})
    matmul = 24 * 12 * 2048 ** 2 + 2048 * 50272
    flops = 2 * matmul + 4 * 2048 * 24 * 11.5
    assert spec.reader("decode_step_mfu")(facts) == pytest.approx(
        100.0 * flops / (1000 * US * 197e12))
    need = 2 * (matmul + 2 * 24 * 2048 * 11.5)
    assert spec.reader("decode_step_hbm_share")(facts) == pytest.approx(
        100.0 * need / 819e9 / (1000 * US))
    # the slice's Mosaic calls work on another layout than the paged cache
    assert spec.reader("paged_decode_roofline")(facts) is None
    # no program ran once an iteration: silent, never 0
    facts["traced"] = {"iterations": 40, "positions": [[10], [11]]}
    assert spec.reader("decode_step_mfu")(facts) is None
    # nothing decoded in the slice
    facts["traced"] = {"iterations": 2, "positions": [[], []]}
    assert spec.reader("decode_step_mfu")(facts) is None
    assert spec.reader("decode_step_hbm_share")(facts) is None


def test_paged_decode_roofline_on_known_numbers(two_steps):
    """The fixture's four Mosaic calls, 1,000 us together, relabelled as
    calls on the paged cache of the serving cell; 600 live positions."""
    paged = dataclasses.replace(two_steps, ops={
        k.replace("bf16[64,1024,64]{2,1,0} %", "bf16[32,1024,16,64]{3,2,1,0} %"):
        v for k, v in two_steps.ops.items()}, op_programs={})
    facts = facts_for(SERVE, paged, serving=SERVING,
                      traced={"iterations": 2,
                              "positions": [[99, 199, 299], [99, 199, 299]]})
    moved = 2 * 600 * 2048 * 2  # keys and values, bf16
    assert spec.reader("paged_decode_roofline")(facts) == pytest.approx(
        100.0 * 4 * (moved / 819e9) / (1000 * US))


def test_a_reader_with_nothing_to_read_returns_nothing(two_steps):
    bare = dataclasses.replace(two_steps, ops={}, modules={})
    train = spec.benchmark()["workloads"][0]["name"]
    facts = facts_for(train, bare, trace_window_s=1.0, stats={}, requests=[],
                      serving=SERVING, traced=None)
    for name in ("flash_attn_roofline", "paged_decode_roofline",
                 "train_step_device_ms", "decode_step_hbm_share",
                 "decode_step_mfu"):
        assert spec.reader(name)(facts) is None


def test_a_trace_without_a_tpu_plane_is_refused(tmp_path):
    p = tmp_path / "host_only.xplane.txt"
    p.write_text('planes { id: 1 name: "/host:CPU" }\n')
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce(str(p))


def test_recorded_slice_of_the_training_step():
    """Two consecutive steps of the training cell recorded on a TPU v5e
    (PR 24): 91.8 ms of device time a step, 96 Mosaic calls (flash forward
    and backward of 24 layers, twice) taking 15.7 ms a step."""
    s = trace.reduce(os.path.join(FIXTURES, "train_two_steps_v5e.xplane.txt.gz"))
    assert s.chips == 1
    seconds, steps = s.program()
    assert steps == 2 and seconds == pytest.approx(0.183688, rel=1e-4)
    assert s.busy_s == pytest.approx(0.183574, rel=1e-4)
    assert s.busy_s <= seconds  # operations lie inside their program
    kernel_s, calls = s.op_seconds(trace.MOSAIC_CALL)
    assert calls == 96 and kernel_s == pytest.approx(0.031446, rel=1e-4)
    assert s.top_gaps() == []  # the two steps run back to back
    train = next(w["name"] for w in spec.benchmark()["workloads"]
                 if spec.cell(w["name"]).kind == "train")
    facts = facts_for(train, s, trace_window_s=0.19)
    assert spec.reader("train_step_device_ms")(facts) == pytest.approx(91.79, rel=1e-3)
    assert spec.reader("flash_attn_roofline")(facts) == pytest.approx(23.29, rel=1e-3)
    assert spec.reader("device_idle_share.train")(facts) == pytest.approx(
        100 * (1 - 0.183574 / 0.19), rel=1e-3)
