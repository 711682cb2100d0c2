"""The Nemotron-H family (Mamba-2, expert and grouped-query attention layers):
its counts against hand-computed values, its plain reference against the
program at the configuration's rehearsal sizes on the CPU, and the expert-bank
and Mamba-2 readers on a trace with known numbers."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import peaks, program_spans, runctx, serve, spec, trace  # noqa: E402
from perfbench.models import nemotron_h_lm_ref as ref  # noqa: E402

CELL = "serve-nemotron3nano-reason-saturated"
CONFIG = spec.load_json("configs", "nemotron-3-nano-30b-a3b.json")
READERS = ("moe_decode_roofline", "ssm_decode_roofline", "moe_share")


# -- counts, against the issue's own arithmetic --------------------------------
def test_parameter_counts_by_hand():
    h, v = 2688, 131072
    mamba = h * 10304 + 4096 * h                 # in- and out-projection
    mamba_rest = 4 * 6144 + 6144 + 3 * 64 + 4096  # taps, bias, vectors, norm
    attn = 2 * h * 32 * 128 + 2 * h * 2 * 128
    expert = 2 * h * 1856
    shared, router = 2 * h * 3712, h * 128
    z = ref.sizes(CONFIG)
    assert (z["experts"], z["held"], z["held_from"], z["top_k"]) == \
        (128, 64, 0, 6)
    assert ref.ssm_inner(z) == 4096 and ref.ssm_conv_channels(z) == 6144
    c = ref.counts(CONFIG)
    assert c["head_params"] == h * v
    assert c["fixed_matmul_params"] == \
        6 * mamba + 2 * attn + 6 * (shared + router) + h * v
    # of 64 held experts a token's 6 choices among 128 touch 3, expected
    assert c["matmul_params"] == c["fixed_matmul_params"] + 6 * 3 * expert
    assert c["params"] == c["fixed_matmul_params"] + 6 * 64 * expert \
        + v * h + 6 * mamba_rest + 6 * 128 + 14 * h + h
    # 38.74M a Mamba-2 layer, 23.40M an attention layer (each with its
    # pre-norm), 9.978M a routed expert; 4.937B held here
    assert round((mamba + mamba_rest + h) / 1e6, 2) == 38.74
    assert round((attn + h) / 1e6, 2) == 23.40
    assert round(expert / 1e6, 3) == 9.978
    assert round(c["params"] / 1e9, 3) == 4.937
    # the whole published model by the same arithmetic: 31.58B
    whole = 23 * (mamba + mamba_rest + h) + 6 * (attn + h) \
        + 23 * (128 * expert + shared + router + 128 + h) + 2 * v * h + h
    assert round(whole / 1e9, 2) == 31.58


def test_decode_bytes_count_the_work():
    c = ref.counts(CONFIG)
    z = ref.sizes(CONFIG)
    state = 6 * (4 * 64 * 64 * 128 + 2 * 3 * 6144)  # S float32 + the tail
    assert ref.slot_state_bytes(CONFIG) == state
    assert round(state / 1e6, 1) == 12.8
    # 64 tokens of top 6 among 128 touch 61.0 of the 64 held experts
    assert round(ref.experts_touched(z, 64), 1) == 61.0
    assert ref.experts_touched(z, 1) == pytest.approx(3.0)
    # two slots, 100 and 300 live positions: the matrices outside the routed
    # experts once, the distinct experts two tokens touch in 6 layers, keys
    # and values of the 2 attention layers at 2 x 128 a position, both
    # slots' state read and written
    expert = 2 * 2688 * 1856
    assert ref.decode_step_bytes(CONFIG, [100, 300]) == pytest.approx(
        2 * (c["fixed_matmul_params"]
             + 6 * ref.experts_touched(z, 2) * expert
             + 2 * 2 * 2 * 128 * 400) + 2 * 2 * state)
    # a full batch at 1,000 positions: the issue's 10.6 GB, experts 71%
    full = ref.decode_step_bytes(CONFIG, [1000] * 64)
    assert round(full / 1e9, 1) == 10.6
    # one token at position 9 with the head: matrices (3 experts a layer),
    # 10 keys in 2 layers over 32 query heads, 6 states updated and read
    assert ref.forward_flops(CONFIG, [9], 1) == \
        2 * c["matmul_params"] + 4 * 32 * 128 * 2 * 10 \
        + 6 * (6 * 64 * 64 * 128 + 2 * 4 * 6144)


def test_the_programs_slot_state_is_the_references_count():
    """What the program's batcher holds a slot (runtime/kvcache.py): the
    recurrent state `decode_step_bytes` counts, and keys and values at the
    key-value heads' width, at the rehearsal sizes in float32."""
    cell = spec.cell(CELL, rehearsal=True)
    builder, family_ref = spec.family(cell.config)
    sc = serve.ServeCell(cell, builder, family_ref, runctx.Spans())
    sc.build()
    from flexflow_tpu.runtime.kvcache import (kv_page_bytes,
                                              recurrent_slot_bytes)

    want = ref.slot_state_bytes(cell.config, bytes_per_value=4)
    assert recurrent_slot_bytes(sc.model) == want > 0
    # one attention layer of the four: 2 key-value heads of 8, float32
    assert kv_page_bytes(sc.model, 16) == 16 * 2 * 2 * 8 * 4
    init1, _ = sc.model.executor.build_decode(1, 64)
    caches = init1(sc.model.state.params, ())
    held = sum(leaf.nbytes for state in caches["recurrent"].values()
               for leaf in state)
    assert held == want
    assert set(caches["counters"]) == {
        "moe_assignments_held", "moe_assignments_elsewhere",
        "moe_experts_touched", "moe_expert_load_max"}
    sc.free()


def test_init_reproduces_and_spans_the_stated_decay():
    small = spec.overlay(CONFIG, CONFIG["rehearsal"])
    a, b = ref.init(small, 2 ** 31 + 5), ref.init(small, 2 ** 31 + 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    rate = np.log1p(np.exp(np.asarray(a["h0.mixer.dt_bias"], np.float64)))
    decay = np.exp(-np.exp(np.asarray(a["h0.mixer.A_log"], np.float64)) * rate)
    assert decay.max() == pytest.approx(0.999, abs=1e-4)
    assert decay.min() == pytest.approx(0.9, abs=1e-4)
    assert CONFIG["assumed"]["decay_at_zero_input"] == list(ref.DECAY_SPAN)
    assert np.all(np.asarray(a["h0.mixer.D"]) == 1)
    assert np.all(np.asarray(a["h1.mixer.b_corr"]) == 0)
    # the router keeps the published width; the bank holds its share
    assert a["h1.mixer.router"].shape == (32, 8)
    assert a["h1.mixer.w_up"].shape == (4, 32, 24)
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
    logits through a softmax. Tolerance 2e-5: float32 round-off through a
    chunked Mamba-2 layer, two expert layers and an attention layer reads
    2e-6; the bfloat16 control moves the probabilities by 1e-3 and more (a
    rounded router score flips a token's expert)."""
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
    stats = {k: v - warm[k] if k.startswith(("prefill_", "iterations", "moe_a"))
             else v for k, v in sc.batcher.stats.items()}
    sc.batcher.stop(timeout=60.0)
    return rows, stats


def test_prefill_then_decode_with_padded_prompts_and_a_reused_slot(built):
    """Prompt lengths that are no powers of two (every prefill has a masked
    tail, three of them longer than a 16-token chunk), more requests than
    slots so that slots sit at different positions and each is used again
    after a LONGER occupant, against the reference's full forward at every
    served position. Tolerance 2e-5 in the logit gap: float32 round-off; the
    bfloat16 control reads 1e-3 and more."""
    cell, sc = built
    rng = np.random.RandomState(7)
    lengths = [150, 97, 130, 5, 33, 70, 3, 21]  # 3 slots: long ones first
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in lengths]
    rows, stats = serve_prompts(sc, prompts, [12, 20, 9, 30, 14, 8, 25, 11])
    assert stats["prefill_masked_tokens"] == \
        sum(sc.batcher._bucket(n) - n for n in lengths) > 0
    slots = cell.params["serving"]["slots"]
    assert stats["recurrent_state_bytes"] == slots * ref.slot_state_bytes(
        cell.config, bytes_per_value=4)
    # keys and values of the one attention layer at 2 key-value heads of 8
    assert stats["kv_cache_bytes"] == slots * 256 * 2 * 2 * 8 * 4
    # every decode step routes every slot's row through the 2 expert layers
    assert stats["moe_assignments_held"] + stats["moe_assignments_elsewhere"] \
        == stats["iterations"] * slots * 2 * 2
    assert 0 < stats["moe_expert_load_max"] <= slots
    gaps = serve.logit_gaps(ref, cell.config, 11, rows)
    assert max(float(g.max()) for g in gaps) < 2e-5
    low = serve.logit_gaps(ref, cell.config, 11, rows, precision="bf16")
    assert max(float(g.max()) for g in low) > 1e-4


def test_a_bank_that_computes_an_expert_it_does_not_hold_is_not_correct(
        built, monkeypatch):
    """The planted fault: the reference is told the chip holds experts 2..5
    where the program holds 0..3; the partial sums differ and the served
    tokens' gap shows it."""
    cell, sc = built
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in (37, 81)]
    rows, _ = serve_prompts(sc, prompts, [10, 10])
    shifted = dict(cell.config, experts_held_from=2)
    gaps = serve.logit_gaps(ref, shifted, 11, rows)
    assert max(float(g.max()) for g in gaps) > 1e-3


# -- the three readers on a trace with known numbers ----------------------------
SLICE = os.path.join(spec.BENCH_DIR, "fixtures", "ff_moe_ssm_slice.xplane.txt")
US = 1e-6


def facts_of(path=SLICE, **more):
    facts = dict(cell=spec.cell(CELL), trace=trace.reduce(path),
                 program_spans=program_spans.read(path),
                 peaks=peaks.of("TPU v5 lite"),
                 serving={"slots": 64},
                 # 100 iterations of the window: 61 experts touched and 190
                 # assignments held a layer a step
                 stats={"iterations": 100, "moe_experts_touched": 100 * 6 * 61,
                        "moe_assignments_held": 100 * 6 * 190},
                 traced={"iterations": 2,
                         "positions": [list(range(100, 164)),
                                       list(range(101, 163))]})
    facts.update(more)
    return facts


def test_readers_on_known_numbers():
    facts = facts_of()
    # one expert layer's step: 61 experts' two matrices, the shared expert
    # and the router at 2 bytes; bytes bound it. 2 steps x 12,000 us under
    # `ff.moe` inside the decode spans (the admission's 6,000 us are not)
    fixed = 2688 * (2 * 3712 + 128)
    moved = 2 * (2 * 2688 * 1856 * 61 + fixed)
    flops = 4 * 2688 * 1856 * 190 + 2 * fixed * 64
    assert flops / 197e12 < moved / 819e9
    assert spec.reader("moe_decode_roofline")(facts) == pytest.approx(
        100.0 * 2 * 6 * (moved / 819e9) / (24000 * US))
    # 63 slots occupied on average, 6 Mamba-2 layers, 2 steps: each call
    # reads and writes 63 x 64 x 64 x 128 x 4 B; 6,000 us under ff.ssm.step
    state = 64 * 64 * 128
    moved = 2 * 4 * state * 63
    assert 6 * state * 63 / 197e12 < moved / 819e9
    assert spec.reader("ssm_decode_roofline")(facts) == pytest.approx(
        100.0 * 2 * 6 * (moved / 819e9) / (6000 * US))
    assert spec.reader("moe_share")(facts) == pytest.approx(
        100.0 * 24000 / 40000)
    for name in READERS:
        assert 0.0 < spec.reader(name)(facts) < 100.0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_where_the_program_has_no_such_scope(name):
    """The parent's program (and a cell of another family) marks no `ff.moe`
    or `ff.ssm` scope and counts no expert: the reader returns nothing and
    does not raise."""
    other = os.path.join(spec.BENCH_DIR, "fixtures", "ff_serve_slice.xplane.txt")
    facts = facts_of(other, stats={"iterations": 100})
    assert spec.reader(name)(facts) is None
    facts = facts_of(other)
    assert spec.reader(name)(facts) is None
    facts["program_spans"] = None  # a run with no slice
    assert spec.reader(name)(facts) is None


def test_the_cell_is_the_issues_table():
    cell = spec.cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.params["serving"] == {
        "max_len": 4096, "slots": 64, "page_size": 16, "deadline_s": 900.0,
        "queue_depth": 512, "search_budget": -1}
    mix = cell.mix
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    assert mix["output_len"] == {"dist": "lognormal", "median": 640,
                                 "sigma": 0.6, "min": 128, "max": 1536}
    assert mix["preroll"] == {"seconds": 25.0, "backlog": 96}
    knee = mix["knee"]
    assert knee["side"] == "above" and len(knee["sweep"]) >= 4
    assert mix["arrival"]["rate_per_s"] == pytest.approx(
        1.25 * knee["ceiling_rate_per_s"], rel=0.02)
    # every published key, as published, but for the cut
    cfg, pub = cell.config, cell.config["published"]
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts"]
    assert pub == {"num_hidden_layers": 52, "n_routed_experts": 128,
                   "hybrid_override_pattern":
                   "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert cfg["hybrid_override_pattern"] == \
        pub["hybrid_override_pattern"][:14] == "MEMEM*EMEMEM*E"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["experts_held_from"]) == (14, 64, 0)
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_key_value_heads"],
            cfg["mamba_num_heads"], cfg["ssm_state_size"]) == \
        (2688, 1856, 6, 2, 64, 128)
    assert cfg["kinds"] == ["serve"] and cfg["family"] == "nemotron_h_lm"
    # the cell is listed under every serving metric PR 28's cell is, and
    # not under the paged-decode kernel's (its reader wants a hidden-wide pool)
    listed = {m["name"] for m in spec.benchmark()["per_layer"]
              if CELL in m["workloads"]}
    assert listed == {"search_s", "compile_s", "serve_mfu", "decode_step_mfu",
                      "decode_step_hbm_share", "tpot_pooled_ms",
                      "slot_occupancy", "gen_lateness_p95_ms",
                      "device_idle_share.serve", "peak_hbm_share.serve"}
    from perfbench.harness import traffic

    sched = traffic.serve_schedule(mix, 131072, 2 ** 31 + 3, 51.0)
    assert all(len(p) + o <= 4096 for _, p, o in sched)
    dues = [due for due, _, _ in sched]
    assert dues[:96] == [-25.0] * 96 and dues[97] > -25.0  # the backlog
    assert max(max(p) for _, p, _ in sched) > 130000  # ids over all 131,072
