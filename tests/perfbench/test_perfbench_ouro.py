"""The Ouro family (48 layers run four steps over one copy of their weights,
a loop region in the program): its counts against the published size and
hand-computed values, its plain reference against the program at the
configuration's rehearsal sizes on the CPU, through the batcher and its
stacked caches, and the loop readers on a trace with known numbers and on
one recorded on the chip."""
import gzip
import itertools
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import peaks, program_spans, runctx, serve, spec, trace  # noqa: E402
from perfbench.models import ouro_lm_ref as ref  # noqa: E402

CELL = "serve-ouro2.6b-chat-saturated"
CONFIG = spec.load_json("configs", "ouro-2.6b.json")
READERS = ("loop_decode_roofline", "loop_prefill_roofline",
           "loop_paged_decode_roofline")


# -- counts, against the published size ----------------------------------------
def test_parameter_counts_by_hand():
    h, f, v = 2048, 5632, 49152
    layer = 4 * h * h + 3 * h * f + 4 * h
    assert layer == 51_388_416
    c = ref.counts(CONFIG)
    assert c["layer_params"] == layer
    assert c["params"] == 48 * layer + 2 * v * h + h == 2_667_972_608
    # 5.34 GB held once in bfloat16; written out 4 x 48 layers, 20.1 GB
    assert round(2 * c["params"] / 1e9, 2) == 5.34
    assert round(2 * (4 * 48 * layer + 2 * v * h) / 1e9, 1) == 20.1
    assert c["pass_matmul_params"] == 48 * (4 * h * h + 3 * h * f)
    z = ref.sizes(CONFIG)
    assert (z["layers"], z["steps"], z["heads"], z["kv_heads"],
            z["head_dim"]) == (48, 4, 16, 16, 128)


def test_a_decode_step_reads_every_layer_once_a_step():
    """The configuration's arithmetic: 4 x 4.93 GB of matrices a decode
    step, the head once, and every step's keys and values, 1.5 MiB a
    position over 48 layers and 4 steps; the operations count each step's
    pass."""
    c = ref.counts(CONFIG)
    passes = 2 * 4 * c["pass_matmul_params"]
    assert round(passes / 4 / 1e9, 2) == 4.93
    assert round(passes / 1e9, 1) == 19.7
    per_position = 4 * 48 * 2 * 2048 * 2
    assert per_position == 1_572_864
    live = [100, 500, 900, 1000]
    assert ref.decode_step_bytes(CONFIG, live) == \
        passes + 2 * c["head_params"] + per_position * sum(live)
    assert ref.loop_decode_bytes(CONFIG, live) == \
        passes + per_position * sum(live)
    assert ref.forward_flops(CONFIG, [99], 1) == \
        2 * 4 * c["pass_matmul_params"] + 4 * 4 * 48 * 16 * 128 * 100 \
        + 2 * c["head_params"]


def test_the_program_holds_four_steps_of_keys_and_values_a_slot():
    """What the batcher holds a slot and reserves (runtime/kvcache.py), at
    the rehearsal sizes in float32: 3 layers x 4 steps of 64 values a
    position, k and v."""
    cell = spec.cell(CELL, rehearsal=True)
    sc = serve.ServeCell(cell, *spec.family(cell.config), runctx.Spans())
    sc.build()
    from flexflow_tpu.runtime.kvcache import KVCacheConfig, slot_reservation_bytes

    row = 2 * 64 * 4
    kv = KVCacheConfig(num_pages=64, page_size=16)
    assert slot_reservation_bytes(sc.model, kv, 20) == 3 * 4 * 32 * row
    init1, _ = sc.model.executor.build_decode(1, 64)
    caches = init1(sc.model.state.params, ())
    assert {name: leaves[0].shape for name, leaves in caches["mha"].items()} \
        == {f"h{i}.attn": (1, 4, 64, 64) for i in range(3)}
    assert set(caches["counters"]) == {"attn_full_positions_read",
                                       "loop_passes"}
    assert "loop_prefill_passes" in caches["prefill_counters"]
    sc.free()


def test_init_reproduces_and_stores_the_configurations_type():
    small = spec.overlay(CONFIG, CONFIG["rehearsal"])
    a, b = ref.init(small, 2 ** 31 + 5), ref.init(small, 2 ** 31 + 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert a["h2.attn.wq"].shape == (64, 4, 16)
    assert a["h0.gate.kernel"].shape == (64, 176)
    assert {k for k in a if k.startswith("h0.")} == {
        f"h0.{k}" for k in ref.layer_shapes(ref.sizes(small))}
    assert ref.sizes(CONFIG)["weights"] == np.dtype("bfloat16")


# -- the program against the reference, rehearsal sizes, float32 ----------------
@pytest.fixture(scope="module")
def built():
    cell = spec.cell(CELL, rehearsal=True)
    sc = serve.ServeCell(cell, *spec.family(cell.config), runctx.Spans())
    sc.build()
    sc.load_seed(11)
    yield cell, sc
    sc.free()


def test_full_forward_logits_agree_with_the_reference(built):
    """The program's full forward (probabilities) against the reference's
    logits through a softmax. Tolerance 2e-5: float32 round-off through 12
    layer passes reads 1e-6 and less; the bfloat16 control moves the
    probabilities by 1e-4 and more."""
    import jax
    import jax.numpy as jnp

    cell, sc = built
    sv = cell.params["serving"]
    ids = np.random.RandomState(3).randint(
        0, cell.config["vocab_size"], (sv["slots"], sv["max_len"]), np.int32)
    got = np.asarray(sc.model.executor.build_forward()(
        sc.model.state.params, [jnp.asarray(ids)]))
    params = ref.init(cell.config, 11)
    want = np.asarray(jax.nn.softmax(
        ref.Reference(cell.config).logits(params, jnp.asarray(ids)), -1))
    low = np.asarray(jax.nn.softmax(
        ref.Reference(cell.config, "bf16").logits(params, jnp.asarray(ids)),
        -1))
    print("probability gap: program", np.abs(got - want).max(),
          "control", np.abs(low - want).max())
    assert np.abs(got - want).max() < 2e-5
    assert np.abs(low - want).max() > 1e-4


def serve_prompts(sc, prompts, outs):
    sc.start()  # serves two warm-up requests of its own
    warm = dict(sc.batcher.stats)
    reqs = [sc._offer(np.asarray(p, np.int32), o)
            for p, o in zip(prompts, outs)]
    assert sc.drain(reqs, 600.0)
    rows = [{"prompt": np.asarray(p, np.int32),
             "tokens": np.asarray(r.result(timeout=1.0))}
            for p, r in zip(prompts, reqs)]
    stats = {k: v - warm[k] if k.startswith(("iterations", "loop_", "attn_"))
             else v for k, v in sc.batcher.stats.items()}
    sc.batcher.stop(timeout=60.0)
    return rows, stats


def test_prefill_then_decode_through_every_steps_cache(built):
    """More requests than slots, in buckets longer than the prompts, so that
    slots sit at different positions and each stacked cache is used again
    after another occupant: against the reference's full forward at every
    served position. Every decode step ran the body four times, and the
    slots hold four steps' keys and values. Tolerance 2e-5 in the logit
    gap: float32 round-off; the bfloat16 control reads 1e-4 and more."""
    cell, sc = built
    rng = np.random.RandomState(7)
    lengths = [30, 9, 21, 3, 14, 6]
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in lengths]
    rows, stats = serve_prompts(sc, prompts, [20, 30, 12, 40, 25, 9])
    assert stats["loop_passes"] == 4 * stats["iterations"] > 0
    slots = cell.params["serving"]["slots"]
    loop_bytes = slots * 4 * 3 * 64 * 2 * 64 * 4
    assert stats["kv_cache_bytes_loop"] == stats["kv_cache_bytes"] \
        == loop_bytes
    gaps = serve.logit_gaps(ref, cell.config, 11, rows)
    assert max(float(g.max()) for g in gaps) < 2e-5
    low = serve.logit_gaps(ref, cell.config, 11, rows, precision="bf16")
    assert max(float(g.max()) for g in low) > 1e-4


def test_a_reference_with_fewer_steps_is_not_the_program(built):
    """The planted fault: a loop that ran three steps where the program ran
    four; the served tokens' gap shows it."""
    cell, sc = built
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in (17, 8)]
    rows, _ = serve_prompts(sc, prompts, [10, 10])
    other = dict(cell.config, total_ut_steps=3)
    gaps = serve.logit_gaps(ref, other, 11, rows)
    assert max(float(g.max()) for g in gaps) > 1e-3


# -- the three readers on a trace with known numbers ----------------------------
SLICE = os.path.join(spec.BENCH_DIR, "fixtures", "ff_loop_slice.xplane.txt")
US = 1e-6
POSITIONS = [[100, 200, 300, 400], [101, 201, 301, 401]]


def facts_of(path=SLICE, **more):
    facts = dict(cell=spec.cell(CELL), trace=trace.reduce(path),
                 program_spans=program_spans.read(path),
                 peaks=peaks.of("TPU v5 lite"),
                 serving={"slots": 4, "max_len": 1024, "page_size": 16},
                 stats={"iterations": 100},
                 traced={"iterations": 2, "positions": POSITIONS})
    facts.update(more)
    return facts


def test_readers_on_known_numbers():
    facts = facts_of()
    bw, fl = 819e9, 197e12
    # a decode step: 38,000 us under ff.loop; every step's matrices and
    # keys and values at the slice's positions, bytes bound it
    least = np.mean([ref.loop_decode_bytes(CONFIG, [p + 1 for p in ps]) / bw
                     for ps in POSITIONS])
    assert ref.forward_flops(CONFIG, POSITIONS[0], 0) / fl < least
    assert spec.reader("loop_decode_roofline")(facts) == pytest.approx(
        100.0 * least / (38000 * US))
    # the admission's 300 real tokens through 4 steps of 48 layers:
    # operations bound it; 140,000 us under ff.loop inside the admit span
    flops = ref.forward_flops(CONFIG, range(300), 0)
    assert flops / fl > 2 * 4 * ref.counts(CONFIG)["pass_matmul_params"] / bw
    assert spec.reader("loop_prefill_roofline")(facts) == pytest.approx(
        100.0 * (flops / fl) / (140000 * US))
    # 16 kernel calls on the stacked pool of 20 us, each one step's live
    # keys and values of one layer (4 slots, 1,006 live positions on average)
    moved = 2 * 1006 * 2048 * 2
    assert spec.reader("loop_paged_decode_roofline")(facts) == pytest.approx(
        100.0 * 16 * (moved / bw) / (320 * US))
    for name in READERS:
        assert 0.0 < spec.reader(name)(facts) < 100.0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_where_the_program_has_no_loop(name):
    """The parent's program (and a cell of another family) marks no
    `ff.loop` scope and keeps no stacked pool: the reader returns nothing
    and does not raise."""
    other = os.path.join(spec.BENCH_DIR, "fixtures", "ff_serve_slice.xplane.txt")
    assert spec.reader(name)(facts_of(other)) is None
    facts = facts_of(cell=spec.cell("serve-opt1.3b-saturated"))
    assert spec.reader(name)(facts) is None
    facts = facts_of(other)
    facts["program_spans"] = None  # a run with no slice
    facts["traced"] = None
    assert spec.reader(name)(facts) is None


# -- the three readers on a slice recorded on the chip --------------------------
RECORDED = os.path.join(spec.BENCH_DIR, "fixtures",
                        "ff_loop_slice_v5e.xplane.txt.gz")


@pytest.fixture(scope="module")
def recorded():
    """The recorded slice's facts; its header gives the slots' positions in
    the decode iterations it holds."""
    with gzip.open(RECORDED, "rt") as f:
        head = "".join(itertools.takewhile(lambda l: l.startswith("#"), f))
    positions = json.loads(re.search(r"positions (\[\[.*?\]\]);", head)[1])
    return facts_of(RECORDED, traced={"iterations": len(positions),
                                      "positions": positions})


def program_seconds(span, prefix="jit_step("):
    """Chip 0's run of the program named `prefix...` inside `span`."""
    plane = next(p for p in trace.load(RECORDED).planes
                 if p.name == "/device:TPU:0")
    runs = [d for name, s, d in trace._line_events(plane, trace.MODULES_LINE)
            if name.startswith(prefix)
            and span.start_ns <= s and s + d <= span.end_ns]
    assert len(runs) == 1
    return runs[0] * 1e-9


def test_a_recorded_iteration_is_mostly_its_loop(recorded):
    """On the chip the `while` and its body's operations are events of one
    line, one inside the other: the time under `ff.loop` is their union,
    so it is never more than the program's run and is most of it (the
    embedding and the head are the rest), and a decode iteration calls the
    paged kernel on the stacked pool once a layer a step."""
    spans = program_spans.of(recorded)
    loop = spec.module("metrics", "loop_decode_roofline.py")
    z = ref.sizes(CONFIG)
    layout = "bf16[%d,16,%d]" % (4 * z["steps"] * 1024 // 16,
                                 z["kv_heads"] * z["head_dim"])
    decodes = loop.loop_seconds(spans, "ff.serve.decode")
    assert len(decodes) == 3
    for span, seconds in decodes:
        assert 0.85 * program_seconds(span) < seconds <= program_seconds(span)
        calls = [n for n, s, e in spans._under(loop.SCOPE)
                 if span.start_ns <= s and e <= span.end_ns
                 and layout in n and re.search(trace.MOSAIC_CALL, n)]
        assert len(calls) == z["layers"] * z["steps"]
    (span, seconds), = loop.loop_seconds(spans, "ff.serve.admit")
    assert 0.7 * program_seconds(span) < seconds <= program_seconds(span)


@pytest.mark.parametrize("name, whole_slice", [
    ("loop_decode_roofline", 90.71), ("loop_prefill_roofline", 57.08),
    ("loop_paged_decode_roofline", 76.27)])
def test_readers_on_a_recorded_slice(recorded, name, whole_slice):
    """Each reader reads the recorded slice under 100%, near what it read on
    the whole 4 s slice the piece was cut from (one admission here, not the
    slice's every admission)."""
    value = spec.reader(name)(recorded)
    assert 0.0 < value < 100.0
    assert value == pytest.approx(whole_slice, abs=5.0)


def test_the_cell_is_as_specified():
    cell = spec.cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.params["serving"] == {
        "max_len": 1024, "slots": 4, "page_size": 16, "deadline_s": 900.0,
        "queue_depth": 512, "search_budget": -1}
    mix, chat = cell.mix, spec.load_json("traffic", "chat-saturated.json")
    assert mix["prompt_len"] == chat["prompt_len"]
    assert mix["output_len"] == chat["output_len"]
    assert mix["preroll"] == {"seconds": 10.0, "backlog": 6}
    assert cell.config["reduced"] == [] and cell.config["total_ut_steps"] == 4
    assert "four re-reads" in cell.why
    knee = mix["knee"]
    assert knee["side"] == "above" and len(knee["sweep"]) >= 3
    assert mix["arrival"]["rate_per_s"] == pytest.approx(
        1.25 * knee["ceiling_rate_per_s"], rel=0.02)
