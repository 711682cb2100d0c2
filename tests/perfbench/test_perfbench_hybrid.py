"""The hybrid family (gated delta-rule layers among full-attention layers):
its counts against hand-computed values, its plain reference against the
program at the configuration's rehearsal sizes on the CPU, and the three
linear-attention readers on a trace with known numbers."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.harness import peaks, program_spans, runctx, serve, spec, trace  # noqa: E402
from perfbench.models import hybrid_lm_ref as ref  # noqa: E402

CELL = "serve-olmohybrid-docs-saturated"
CONFIG = spec.load_json("configs", "olmo-hybrid-7b.json")


# -- counts, against the issue's own arithmetic --------------------------------
def test_parameter_counts_by_hand():
    h, f, v = 3840, 11008, 100352
    linear = 2 * h * 30 * 96 + 3 * h * 30 * 192 + 2 * h * 30  # matrices
    linear_rest = 4 * 11520 + 30 + 30 + 192                  # taps, vectors
    full = 4 * h * h
    mlp = 3 * h * f
    c = ref.counts(CONFIG)
    assert c["head_params"] == h * v
    assert c["matmul_params"] == 9 * linear + 3 * full + 12 * mlp + h * v
    assert c["params"] == c["matmul_params"] + v * h + 9 * linear_rest \
        + 3 * 2 * h + 12 * 2 * h + h
    # 88.7M a linear layer, 59.0M a full one, 126.8M a gated MLP; 3.27B
    assert round(linear / 1e6, 1) == 88.7  # + 46,332 taps and vectors
    assert round(full / 1e6, 1) == 59.0 and round(mlp / 1e6, 1) == 126.8
    assert round(c["params"] / 1e9, 2) == 3.27


def test_decode_bytes_hold_both_kinds_of_state():
    c = ref.counts(CONFIG)
    state = 9 * (4 * 30 * 192 * 96 + 2 * 3 * 11520)  # S float32 + the tail
    assert ref.slot_state_bytes(CONFIG) == state
    # two slots, 100 and 300 live positions: matrices once, keys and values
    # of the 3 full layers, both slots' state read and written
    assert ref.decode_step_bytes(CONFIG, [100, 300]) == \
        2 * (c["matmul_params"] + 2 * 3 * 3840 * 400) + 2 * 2 * state
    # one token at position 9 with the head: matrices, 10 keys in 3 layers,
    # 9 states updated and read
    assert ref.forward_flops(CONFIG, [9], 1) == \
        2 * c["matmul_params"] + 4 * 3840 * 3 * 10 \
        + 9 * (6 * 30 * 192 * 96 + 2 * 4 * 11520)


def test_the_programs_slot_state_is_the_references_count():
    """What the program's batcher holds of recurrent state a slot
    (runtime/kvcache.py) is what `decode_step_bytes` counts, at the
    rehearsal sizes in float32."""
    cell = spec.cell(CELL, rehearsal=True)
    builder, family_ref = spec.family(cell.config)
    sc = serve.ServeCell(cell, builder, family_ref, runctx.Spans())
    sc.build()
    from flexflow_tpu.runtime.kvcache import (KVCacheConfig, kv_page_bytes,
                                              recurrent_slot_bytes,
                                              slot_reservation_bytes)

    want = ref.slot_state_bytes(cell.config, bytes_per_value=4)
    assert recurrent_slot_bytes(sc.model) == want > 0
    init1, _ = sc.model.executor.build_decode(1, 64)
    caches = init1(sc.model.state.params, ())
    held = sum(leaf.nbytes for state in caches["recurrent"].values()
               for leaf in state)
    assert held == want
    kv = KVCacheConfig(num_pages=12, page_size=16)
    assert slot_reservation_bytes(sc.model, kv, 40) == \
        3 * kv_page_bytes(sc.model, 16) + want
    sc.free()


def test_init_reproduces_and_spans_the_stated_decay():
    a = ref.init(dict(CONFIG, **CONFIG["rehearsal"]), 2 ** 31 + 5)
    b = ref.init(dict(CONFIG, **CONFIG["rehearsal"]), 2 ** 31 + 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    alpha = np.exp(-np.log1p(np.exp(np.asarray(a["h0.mixer.dt_bias"],
                                               np.float64))))
    assert alpha.max() == pytest.approx(0.999, abs=1e-4)
    assert alpha.min() == pytest.approx(0.9, abs=1e-4)
    assert CONFIG["assumed"]["decay_at_zero_input"] == list(ref.ALPHA_SPAN)
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
    logits through a softmax. Tolerance 5e-5: float32 round-off through
    three chunked layers of key size 16 reads 8e-6 (a 64-wide triangular
    system at that size is the worst conditioned the op meets); the
    bfloat16 control moves the probabilities by 5.5e-3."""
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
    assert np.abs(got - want).max() < 5e-5
    assert np.abs(low - want).max() > 1e-3


def serve_prompts(sc, cell, prompts, outs):
    """Serve `prompts` through the batcher, a request a prompt, and return
    the served rows in the form `serve.logit_gaps` takes."""
    sc.start()  # serves two warm-up requests of its own
    warm = sc.batcher.stats["prefill_masked_tokens"]
    reqs = [sc._offer(np.asarray(p, np.int32), o)
            for p, o in zip(prompts, outs)]
    assert sc.drain(reqs, 600.0)
    rows = [{"prompt": np.asarray(p, np.int32),
             "tokens": np.asarray(r.result(timeout=1.0))}
            for p, r in zip(prompts, reqs)]
    stats = dict(sc.batcher.stats)
    stats["prefill_masked_tokens"] -= warm
    sc.batcher.stop(timeout=60.0)
    return rows, stats


def test_prefill_then_decode_with_padded_prompts_and_a_reused_slot(built):
    """Prompt lengths that are no powers of two (every prefill has a masked
    tail, two of them longer than a 64-token chunk), more requests than
    slots so that slots sit at different positions and each is used again
    after a LONGER occupant, against the reference's full forward at every
    served position. Tolerance 2e-5 in the logit gap: float32 round-off;
    the bfloat16 control reads 1e-3."""
    cell, sc = built
    rng = np.random.RandomState(7)
    lengths = [150, 97, 130, 5, 33, 70, 3, 21]  # 3 slots: long ones first
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in lengths]
    rows, stats = serve_prompts(sc, cell, prompts, [12, 20, 9, 30, 14, 8, 25, 11])
    assert stats["prefill_masked_tokens"] == \
        sum(sc.batcher._bucket(n) - n for n in lengths) > 0
    assert stats["recurrent_state_bytes"] == \
        cell.params["serving"]["slots"] * ref.slot_state_bytes(
            cell.config, bytes_per_value=4)
    assert stats["kv_cache_bytes"] > 0
    gaps = serve.logit_gaps(ref, cell.config, 11, rows)
    assert max(float(g.max()) for g in gaps) < 2e-5
    low = serve.logit_gaps(ref, cell.config, 11, rows, precision="bf16")
    assert max(float(g.max()) for g in low) > 1e-4


def test_a_padded_tail_that_touches_the_state_is_not_correct(
        built, monkeypatch):
    """The planted fault: the prefill step is not told the prompt's length,
    so a bucket's padding runs through the recurrent state as if real."""
    from flexflow_tpu.runtime.serving import ContinuousBatcher

    cell, sc = built
    step1 = {}

    def forget_valid(self, *a, **kw):
        if not step1:
            step1["real"] = self._step1
            self._step1 = lambda p, c, t, toks, valid=None, row=None: \
                step1["real"](p, c, t, toks, None, row)
        return prefill(self, *a, **kw)

    prefill = ContinuousBatcher._prefill
    monkeypatch.setattr(ContinuousBatcher, "_prefill", forget_valid)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cell.config["vocab_size"], n) for n in (37, 81)]
    rows, _ = serve_prompts(sc, cell, prompts, [10, 10])
    gaps = serve.logit_gaps(ref, cell.config, 11, rows)
    assert max(float(g.max()) for g in gaps) > 1e-3


# -- the three readers on a trace with known numbers ----------------------------
SLICE = os.path.join(spec.BENCH_DIR, "fixtures", "ff_linear_attn_slice.xplane.txt")
US = 1e-6


def facts_of(path=SLICE, **more):
    return dict(cell=spec.cell(CELL), trace=trace.reduce(path),
                program_spans=program_spans.read(path),
                peaks=peaks.of("TPU v5 lite"),
                traced={"iterations": 2, "positions": [list(range(100, 108)),
                                                       list(range(101, 107))]},
                **more)


def test_linear_attn_readers_on_known_numbers():
    facts = facts_of()
    # 7 slots occupied on average, 9 linear layers, 2 steps: each call reads
    # and writes 7 x 30 x 192 x 96 x 4 B; 1,800 us under ff.linear_attn.step
    state = 30 * 192 * 96
    moved = 2 * 4 * state * 7
    assert 6 * state * 7 / 197e12 < moved / 819e9  # bytes bound it
    assert spec.reader("linear_attn_decode_roofline")(facts) == pytest.approx(
        100.0 * 2 * 9 * (moved / 819e9) / (1800 * US))
    # 200 real tokens of a 256 bucket: q, k, v, gate, output at 2 B and the
    # state once, 9 layers; 1,000 us under ff.linear_attn.scan
    moved = 2 * 200 * 30 * (2 * 96 + 3 * 192) + 4 * state
    assert 6 * state * 200 / 197e12 < moved / 819e9
    assert spec.reader("linear_attn_prefill_roofline")(facts) == pytest.approx(
        100.0 * 9 * (moved / 819e9) / (1000 * US))
    assert spec.reader("linear_attn_share")(facts) == pytest.approx(
        100.0 * 3800 / 8000)
    for name in ("linear_attn_decode_roofline", "linear_attn_prefill_roofline",
                 "linear_attn_share"):
        assert spec.reader(name)(facts) < 100.0


@pytest.mark.parametrize("name", ["linear_attn_decode_roofline",
                                  "linear_attn_prefill_roofline",
                                  "linear_attn_share"])
def test_linear_attn_reader_is_silent_where_the_program_has_no_such_scope(name):
    """The parent's program (and a cell of another family) marks no
    `ff.linear_attn.` scope: the reader returns nothing and does not raise."""
    other = os.path.join(spec.BENCH_DIR, "fixtures", "ff_serve_slice.xplane.txt")
    facts = facts_of(other)
    assert spec.reader(name)(facts) is None
    facts["program_spans"] = None  # a run with no slice
    assert spec.reader(name)(facts) is None


def test_the_cell_is_the_issues_table():
    cell = spec.cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.params["serving"] == {
        "max_len": 4096, "slots": 8, "page_size": 16, "deadline_s": 900.0,
        "queue_depth": 512, "search_budget": -1}
    mix = cell.mix
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.6, "min": 256, "max": 3584}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 32, "max": 384}
    assert mix["preroll"] == {"seconds": 15.0, "backlog": 12}
    knee = mix["knee"]
    assert knee["side"] == "above" and len(knee["sweep"]) == 6
    assert mix["arrival"]["rate_per_s"] == pytest.approx(
        1.25 * knee["ceiling_rate_per_s"], rel=0.02)
    # every published key, as published, but for the cut
    assert cell.config["layer_types"] == \
        cell.config["published"]["layer_types"][:12]
    assert cell.config["kinds"] == ["serve"]
    from perfbench.harness import traffic

    sched = traffic.serve_schedule(mix, 100352, 2 ** 31 + 3, 51.0)
    assert all(len(p) + o <= 4096 for _, p, o in sched)
    assert max(max(p) for _, p, _ in sched) > 100000  # ids over all 100,352
