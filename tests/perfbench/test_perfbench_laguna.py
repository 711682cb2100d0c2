"""The Laguna family (window and full attention layers with head counts of
their own, rotary embeddings, a per-head gate, a softmax-routed gated expert
bank): its counts against hand-computed values, its plain reference against
the program at the configuration's rehearsal sizes on the CPU, through the
batcher and its ring caches, and the window-attention readers on a trace with
known numbers."""
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import peaks, program_spans, runctx, serve, spec, trace  # noqa: E402
from perfbench.models import laguna_lm_ref as ref  # noqa: E402

CELL = "serve-lagunas-code-saturated"
CONFIG = spec.load_json("configs", "laguna-s-2.1.json")
READERS = ("window_decode_roofline", "full_decode_roofline",
           "window_prefill_roofline", "attn_share")
F, S = ref.FULL, ref.SLIDING


# -- counts, against the issue's own arithmetic --------------------------------
def test_parameter_counts_by_hand():
    h, v, d = 3072, 100352, 128
    full = 2 * h * 48 * d + 2 * h * 8 * d + h * 48     # wq, wo; wk, wv; wg
    sliding = 2 * h * 72 * d + 2 * h * 8 * d + h * 72
    expert = 3 * h * 1024
    shared, router, dense = 3 * h * 1024, h * 256, 3 * h * 12288
    assert round(full / 1e6, 2) == 44.19 and round(sliding / 1e6, 1) == 63.1
    assert round(expert / 1e6, 3) == round(shared / 1e6, 3) == 9.437
    assert round(router / 1e6, 3) == 0.786 and round(dense / 1e6, 2) == 113.25
    z = ref.sizes(CONFIG)
    assert (z["experts"], z["held"], z["held_from"], z["top_k"]) == \
        (256, 8, 0, 10)
    assert [k[0] for k in z["layer_types"]] == [F, S, S, S] * 3 + [F]
    assert [k[2] for k in z["layer_types"]] == [48, 72, 72, 72] * 3 + [48]
    assert [k[1] for k in z["layer_types"]] == [ref.DENSE] + [ref.SPARSE] * 12
    c = ref.counts(CONFIG)
    assert c["head_params"] == h * v
    fixed = 4 * full + 9 * sliding + dense + 12 * (shared + router) + h * v
    assert c["fixed_matmul_params"] == fixed
    # of 8 held experts a token's 10 choices among 256 touch 0.3125, expected
    assert c["matmul_params"] == fixed + int(round(12 * 0.3125 * expert))
    vectors = 13 * 2 * h + h + 12 * 256      # norm scales, the router's bias
    assert c["params"] == fixed + 12 * 8 * expert + v * h + vectors
    # the issue's 2,503.5M as cut (5.01 GB in bfloat16), 117.6B whole
    assert round(c["params"] / 1e6, 1) == 2503.5
    assert round(2 * c["params"] / 1e9, 2) == 5.01
    whole = 12 * (full + 2 * h) + 36 * (sliding + 2 * h) + dense \
        + 47 * (256 * expert + shared + router + 256) + 2 * v * h + h
    assert c["whole_params"] == whole
    assert round(whole / 1e9, 1) == 117.6


def test_decode_bytes_and_operations_count_the_work():
    c, z = ref.counts(CONFIG), ref.sizes(CONFIG)
    # 32 tokens of top 10 among 256 touch 5.76 of the 8 held experts
    assert round(ref.experts_touched(z, 32), 2) == 5.76
    assert ref.experts_touched(z, 1) == pytest.approx(0.3125)
    # a sliding layer's live keys stop at the window
    assert ref.keys_seen(z, S, 100) == 101 and ref.keys_seen(z, S, 600) == 512
    assert ref.keys_seen(z, F, 600) == 601
    # two slots, 100 and 3,000 live positions: the matrices outside the
    # routed experts once, the distinct experts two tokens touch in 12
    # layers, keys and values at 8 x 128 a position: all of them in the 4
    # full layers, min(live, 512) in the 9 sliding ones
    expert = 3 * 3072 * 1024
    assert ref.decode_step_bytes(CONFIG, [100, 3000]) == pytest.approx(
        2 * (c["fixed_matmul_params"]
             + 12 * ref.experts_touched(z, 2) * expert
             + 2 * 8 * 128 * (4 * 3100 + 9 * (100 + 512))))
    # a full batch at 3,000 positions: rings 0.60 GB, full layers 1.57 GB of
    # the step's 6.1 GB; as max_len leaves the rings' 9 layers would be 3.5
    full = ref.decode_step_bytes(CONFIG, [3000] * 32)
    rings = 2 * 2 * 8 * 128 * 9 * 32 * 512
    assert round(rings / 1e9, 2) == 0.60 and round(full / 1e9, 1) == 6.1
    # one token at position 600 with the head: matrices (0.3125 experts a
    # layer), 601 keys in 4 layers of 48 heads, 512 in 9 layers of 72
    assert ref.forward_flops(CONFIG, [600], 1) == \
        2 * c["matmul_params"] + 4 * 128 * (4 * 48 * 601 + 9 * 72 * 512)


def test_yarn_and_default_frequencies_by_the_formulas():
    z = ref.sizes(CONFIG)
    full, sliding = ref.rope_of(z, F), ref.rope_of(z, S)
    assert (full["dim"], sliding["dim"]) == (64, 128)
    assert full["attention_factor"] == pytest.approx(0.1 * math.log(128) + 1)
    inv, factor = ref.inv_freq(sliding)
    assert factor == 1.0 and inv[1] == pytest.approx(10000 ** (-2 / 128))
    inv, factor = ref.inv_freq(full)
    extra = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    c = lambda n: 64 * math.log(8192 / (2 * math.pi * n)) / (2 * math.log(5e5))
    assert (math.floor(c(32)), math.ceil(c(1))) == (9, 18)
    np.testing.assert_allclose(inv[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], extra[18:] / 128, rtol=1e-6)
    np.testing.assert_allclose(
        inv[13], extra[13] / 128 * (4 / 9) + extra[13] * (5 / 9), rtol=1e-6)
    # the program's table is the reference's
    from flexflow_tpu.ops.attention import RotaryParams, rotary_table

    for rope in (full, sliding):
        got, f = rotary_table(RotaryParams(**rope), 128)
        np.testing.assert_allclose(got, ref.inv_freq(rope)[0], rtol=1e-6)
        assert f == pytest.approx(ref.inv_freq(rope)[1])


def test_the_programs_slot_state_is_rings_beside_full_leaves():
    """What the program's batcher holds a slot (runtime/kvcache.py): a ring
    of the window in each sliding layer, max_len positions in each full
    one, at the key-value heads' width; at the rehearsal sizes in float32."""
    cell = spec.cell(CELL, rehearsal=True)
    builder, family_ref = spec.family(cell.config)
    sc = serve.ServeCell(cell, builder, family_ref, runctx.Spans())
    sc.build()
    from flexflow_tpu.runtime.kvcache import (KVCacheConfig, kv_page_bytes,
                                              recurrent_slot_bytes,
                                              slot_reservation_bytes)

    row = 2 * 2 * 8 * 4                     # k and v, 2 heads of 8, float32
    assert recurrent_slot_bytes(sc.model) == 0
    assert kv_page_bytes(sc.model, 16) == 5 * 16 * row  # while rings grow
    kv = KVCacheConfig(num_pages=64, page_size=16)
    # 2 full layers keep every page, 3 sliding ones a ring of 16
    assert slot_reservation_bytes(sc.model, kv, 16) == 5 * 16 * row
    assert slot_reservation_bytes(sc.model, kv, 200) == \
        (2 * 208 + 3 * 16) * row
    init1, _ = sc.model.executor.build_decode(1, 256)
    caches = init1(sc.model.state.params, ())
    shapes = {name: leaves[0].shape for name, leaves in caches["mha"].items()}
    assert shapes == {"h0.attn": (1, 256, 16), "h1.attn": (1, 16, 16),
                      "h2.attn": (1, 16, 16), "h3.attn": (1, 16, 16),
                      "h4.attn": (1, 256, 16)}
    assert set(caches["counters"]) == {
        "attn_window_positions_read", "attn_full_positions_read",
        "moe_assignments_held", "moe_assignments_elsewhere",
        "moe_experts_touched", "moe_expert_load_max"}
    sc.free()


def test_init_reproduces_and_keeps_the_routers_width():
    small = spec.overlay(CONFIG, CONFIG["rehearsal"])
    a, b = ref.init(small, 2 ** 31 + 5), ref.init(small, 2 ** 31 + 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert np.all(np.asarray(a["h1.moe.b_corr"]) == 0)
    # the router keeps the published width; the bank holds its share
    assert a["h1.moe.router"].shape == (32, 8)
    assert a["h1.moe.w_gate"].shape == a["h1.moe.w_up"].shape == (4, 32, 24)
    assert a["h1.attn.wq"].shape == (32, 6, 8)
    assert a["h0.attn.wq"].shape == (32, 4, 8) and "h0.gate.kernel" in a
    assert a["h1.attn.wg"].shape == (32, 6)
    # the full-size file stores bfloat16 and the reference casts it up
    assert ref.sizes(CONFIG)["weights"] == np.dtype("bfloat16")


# -- the program against the reference, rehearsal sizes, float32 ----------------
@pytest.fixture(scope="module")
def built():
    cell = spec.cell(CELL, rehearsal=True)
    builder, family_ref = spec.family(cell.config)
    sc = serve.ServeCell(cell, builder, family_ref, runctx.Spans())
    sc.build()
    sc.load_seed(11)
    yield cell, sc
    sc.free()


def test_full_forward_logits_agree_with_the_reference(built):
    """The program's full forward (probabilities) against the reference's
    logits through a softmax, 256 positions through windows of 16.
    Tolerance 2e-5: float32 round-off through five attention layers and
    four expert layers reads 1e-6; the bfloat16 control moves the
    probabilities by 1e-3 and more."""
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
    assert np.abs(low - want).max() > 1e-3


def serve_prompts(sc, prompts, outs):
    """Serve `prompts` through the batcher, a request a prompt, and return
    the served rows in the form `serve.logit_gaps` takes."""
    sc.start()  # serves two warm-up requests of its own
    warm = dict(sc.batcher.stats)
    reqs = [sc._offer(np.asarray(p, np.int32), o)
            for p, o in zip(prompts, outs)]
    assert sc.drain(reqs, 600.0)
    rows = [{"prompt": np.asarray(p, np.int32),
             "tokens": np.asarray(r.result(timeout=1.0))}
            for p, r in zip(prompts, reqs)]
    stats = {k: v - warm[k]
             if k.startswith(("prefill_", "iterations", "moe_a", "attn_"))
             else v for k, v in sc.batcher.stats.items()}
    sc.batcher.stop(timeout=60.0)
    return rows, stats


def test_prefill_then_decode_past_the_window_through_rings(built):
    """Prompts longer than the window (16) in buckets longer than the
    prompts (every prefill has a masked tail that the rings must not
    store), more requests than slots so that slots sit at different
    positions and each ring is used again after another occupant, outputs
    that run two windows past the prompt: against the reference's full
    forward at every served position. Tolerance 2e-5 in the logit gap:
    float32 round-off; the bfloat16 control reads 1e-4 and more."""
    cell, sc = built
    rng = np.random.RandomState(7)
    lengths = [150, 97, 130, 5, 33, 70, 17, 21]  # 3 slots: long ones first
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in lengths]
    rows, stats = serve_prompts(sc, prompts, [40, 36, 9, 50, 34, 8, 45, 11])
    assert stats["prefill_masked_tokens"] == \
        sum(sc.batcher._bucket(n) - n for n in lengths) > 0
    slots, row = cell.params["serving"]["slots"], 2 * 2 * 8 * 4
    # 3 sliding layers keep a ring of 16, 2 full layers 256 positions
    assert stats["kv_cache_bytes_window"] == slots * 3 * 16 * row
    assert stats["kv_cache_bytes_full"] == slots * 2 * 256 * row
    assert stats["kv_cache_bytes"] == slots * (3 * 16 + 2 * 256) * row
    # a step never reads more than a ring a slot and sliding layer
    assert 0 < stats["attn_window_positions_read"] <= \
        stats["iterations"] * slots * 3 * 16
    assert stats["attn_full_positions_read"] > \
        stats["attn_window_positions_read"]
    gaps = serve.logit_gaps(ref, cell.config, 11, rows)
    assert max(float(g.max()) for g in gaps) < 2e-5
    low = serve.logit_gaps(ref, cell.config, 11, rows, precision="bf16")
    assert max(float(g.max()) for g in low) > 1e-4


def test_a_reference_with_another_window_is_not_the_program(built):
    """The planted fault: the reference is told a window of 12 where the
    program keeps 16; the served tokens' gap shows it."""
    cell, sc = built
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in (37, 81)]
    rows, _ = serve_prompts(sc, prompts, [10, 10])
    other = dict(cell.config, sliding_window=12)
    gaps = serve.logit_gaps(ref, other, 11, rows)
    assert max(float(g.max()) for g in gaps) > 1e-3


# -- the four readers on a trace with known numbers -----------------------------
SLICE = os.path.join(spec.BENCH_DIR, "fixtures",
                     "ff_window_attn_slice.xplane.txt")
US = 1e-6


def facts_of(path=SLICE, **more):
    facts = dict(cell=spec.cell(CELL), trace=trace.reduce(path),
                 program_spans=program_spans.read(path),
                 peaks=peaks.of("TPU v5 lite"),
                 serving={"slots": 32, "max_len": 8192, "page_size": 16},
                 stats={"iterations": 100},
                 traced={"iterations": 2,
                         "positions": [[100] * 8 + [3000] * 24,
                                       [101] * 8 + [3001] * 24]})
    facts.update(more)
    return facts


def test_readers_on_known_numbers():
    facts = facts_of()
    # ring pool: 8 slots at 101.5 live positions on average, 24 full rings
    # of 512; keys and values at 8 x 128, 2 bytes: bytes bound it. 18 calls
    # (2 steps x 9 sliding layers) of 100 us
    live = 8 * 101.5 + 24 * 512
    moved = 2 * 8 * 128 * live * 2
    assert 4 * 72 * 128 * live / 197e12 < moved / 819e9
    assert spec.reader("window_decode_roofline")(facts) == pytest.approx(
        100.0 * 18 * (moved / 819e9) / (1800 * US))
    # full pool: every live position, 8 calls of 600 us
    live = 8 * 101.5 + 24 * 3001.5
    moved = 2 * 8 * 128 * live * 2
    assert spec.reader("full_decode_roofline")(facts) == pytest.approx(
        100.0 * 8 * (moved / 819e9) / (4800 * US))
    # the admission's 3,000 real tokens through 9 sliding layers of 72
    # heads: 512 x 513 / 2 + 2,488 x 512 keys seen; operations bound it;
    # 60,000 us under ff.attn.window inside the admit span
    keys = 512 * 513 // 2 + (3000 - 512) * 512
    flops = 4 * 72 * 128 * keys
    assert flops / 197e12 > 2 * 3000 * 128 * (2 * 72 + 2 * 8) / 819e9
    assert spec.reader("window_prefill_roofline")(facts) == pytest.approx(
        100.0 * 9 * (flops / 197e12) / (60000 * US))
    # under the decode spans 28,000 us busy; rope 200 + 9 x 100 + 4 x 600 +
    # gate 100 a step under ff.attn.
    assert spec.reader("attn_share")(facts) == pytest.approx(
        100.0 * 7200 / 28000)
    for name in READERS:
        assert 0.0 < spec.reader(name)(facts) < 100.0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_where_the_program_has_no_such_scope(name):
    """The parent's program (and a cell of another family) marks no
    `ff.attn` scope and keeps no ring: the reader returns nothing and does
    not raise."""
    other = os.path.join(spec.BENCH_DIR, "fixtures", "ff_serve_slice.xplane.txt")
    assert spec.reader(name)(facts_of(other)) is None
    facts = facts_of(other, cell=spec.cell("serve-opt1.3b-saturated"))
    assert spec.reader(name)(facts) is None
    facts["program_spans"] = None  # a run with no slice
    facts["traced"] = None
    assert spec.reader(name)(facts) is None


def test_the_cell_is_the_issues_table():
    cell = spec.cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.params["serving"] == {
        "max_len": 8192, "slots": 32, "page_size": 16, "deadline_s": 900.0,
        "queue_depth": 512, "search_budget": -1}
    mix = cell.mix
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.9, "min": 128, "max": 6144}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    assert mix["preroll"] == {"seconds": 20.0, "backlog": 48}
    knee = mix["knee"]
    assert knee["side"] == "above" and len(knee["sweep"]) >= 3
    assert mix["arrival"]["rate_per_s"] == pytest.approx(
        1.25 * knee["ceiling_rate_per_s"], rel=0.02)
    # every published key, as published, but for the cut
    cfg, pub = cell.config, cell.config["published"]
    assert cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
        "num_attention_heads_per_layer", "num_experts"] == list(pub)
    assert (pub["num_hidden_layers"], pub["num_experts"]) == (48, 256)
    assert pub["layer_types"] == [F, S, S, S] * 12
    assert pub["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == pub[key][:13]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["experts_held_from"]) == (13, 8, 0)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["vocab_size"],
            cfg["intermediate_size"]) == \
        (3072, 128, 8, 512, 1024, 10, 100352, 12288)
    assert cfg["kinds"] == ["serve"] and cfg["family"] == "laguna_lm"
    # the catalog's row, key for key, but for the reduced ones
    import json
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-S-2.1")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert (pub if key in pub else cfg)[key] == value, key
    # the cell is listed under every serving metric Nemotron's cell is
    listed = {m["name"] for m in spec.benchmark()["per_layer"]
              if CELL in m["workloads"]}
    assert listed == {"search_s", "compile_s", "serve_mfu", "decode_step_mfu",
                      "decode_step_hbm_share", "tpot_pooled_ms",
                      "slot_occupancy", "gen_lateness_p95_ms",
                      "device_idle_share.serve", "peak_hbm_share.serve"}
    # the new readers come as files, their entries with the queued PR
    assert not {m["name"] for m in spec.benchmark()["per_layer"]} \
        & set(READERS)
    assert len(spec.benchmark()["workloads"][-1]["why"]) <= 200
    from perfbench.harness import traffic

    sched = traffic.serve_schedule(mix, 100352, 2 ** 31 + 3, 51.0)
    assert all(len(p) + o <= 8192 for _, p, o in sched)
    assert max(len(p) for _, p, _ in sched) == 6144
    dues = [due for due, _, _ in sched]
    assert dues[:48] == [-20.0] * 48 and dues[49] > -20.0  # the backlog
    assert max(max(p) for _, p, _ in sched) > 100000  # ids over all 100,352
