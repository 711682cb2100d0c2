"""The serving kind end to end at the tiny sizes its files carry, on the
CPU: a sound run, the control (the program in lower precision than the
configuration states), the timed path broken underneath (a token altered
where it is produced), and prefill then decode through the batcher against
the plain reference's full forward."""
import json

import numpy as np
import pytest
import runs_common as rc

from perfbench.harness import runctx, serve, spec, traffic


def test_rehearsal_is_correct_and_prints_no_result_line(capsys):
    rc.sound_run_is_correct_and_prints_no_result_line("serve", capsys)


def test_program_in_lower_precision_than_stated_is_not_correct(
        monkeypatch, capsys):
    rc.program_in_lower_precision_than_stated_is_not_correct(
        "serve", monkeypatch, capsys)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from flexflow_tpu.runtime.serving import GenerationRequest

    finish = GenerationRequest._finish

    def altered(self, *, tokens=None, **kw):
        if tokens is not None and len(tokens) > len(self.prompt) + 2:
            tokens = np.array(tokens)
            at = len(self.prompt) + 1
            tokens[at] = (tokens[at] + 1) % 61
        return finish(self, tokens=tokens, **kw)

    monkeypatch.setattr(GenerationRequest, "_finish", altered)
    assert rc.rehearse("serve") == 1
    assert "check worst_logit_gap" in capsys.readouterr().err


class SliceStub:
    """Stands in for the profiler's slice: on over the window's second half."""
    t_start = None

    def maybe_start(self, elapsed, seconds):
        if elapsed >= seconds / 2:
            self.t_start = elapsed

    def stop(self):
        pass


@pytest.mark.parametrize("config_name", rc.CONFIGS)
def test_prefill_then_decode_agree_with_the_reference_full_forward(config_name):
    cell = rc.variant_cell("serve", config_name)
    builder, ref = spec.family(cell.config)
    sc = serve.ServeCell(cell, builder, ref, runctx.Spans())
    sc.build()
    sc.load_seed(5)
    sc.start()
    vocab = ref.sizes(cell.config)["vocab"]
    schedule = traffic.serve_schedule(cell.mix, vocab, 5, 2.0)
    w = sc.window(schedule, 2.0, SliceStub())
    sc.stop(w.rows)
    tab = serve.table(w.rows)
    sc.free()
    # the slice saw decode iterations, each with its slots' positions
    assert 0 < w.traced["iterations"] <= w.stats["iterations"]
    assert any(w.traced["positions"]) and all(
        0 < p < cell.params["serving"]["max_len"]
        for ps in w.traced["positions"] for p in ps)
    assert not any(t["failed"] for t in tab)
    served = [t for t in tab if t["served_tokens"] > 0]
    assert len({t["prompt_tokens"] for t in served}) > 1  # slots at different positions
    assert w.t_close - w.t_open >= 2.0 and w.rows[0]["due"] < w.t_open
    assert sum(t["produced_at_close"] - t["produced_at_open"] for t in tab) \
        <= sum(t["served_tokens"] for t in tab)
    gaps = serve.logit_gaps(ref, cell.config, 5, [t["row"] for t in served])
    assert serve.numbers(tab, gaps, vocab) == {
        "failed_requests": 0, "wrong_answers": 0,
        "worst_logit_gap": pytest.approx(0.0, abs=2e-5)}


def test_a_request_cut_short_or_given_another_prompt_is_a_wrong_answer():
    prompt = np.arange(5, dtype=np.int32)

    def entry(tokens, asked=3, ok=True):
        row = {"prompt": prompt, "tokens": np.asarray(tokens)}
        return {"row": row, "prompt_tokens": 5, "asked_tokens": asked, "ok": ok,
                "served_tokens": len(tokens) - 5}

    assert not serve.wrong_answer(entry([0, 1, 2, 3, 4, 9, 9, 9]), 10)
    assert not serve.wrong_answer(entry([0, 1, 2, 3, 4, 9], ok=False), 10)
    assert serve.wrong_answer(entry([0, 1, 2, 3, 4, 9, 9]), 10)      # short
    assert serve.wrong_answer(entry([0, 1, 2, 3, 4, 9, 9, 9, 9], ok=False), 10)
    assert serve.wrong_answer(entry([0, 1, 2, 3, 5, 9, 9, 9]), 10)   # prompt
    assert serve.wrong_answer(entry([0, 1, 2, 3, 4, 9, 10, 9]), 10)  # range


def readings(module, argv, capsys):
    assert module.main(argv) == 0
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


def test_calibrate_judges_program_and_control_by_the_cells_limits(capsys):
    from perfbench import calibrate

    rows = readings(calibrate, ["--workload", rc.CELLS["serve"], "--seeds",
                                "3", "--control-seeds", "3", "--seconds", "2",
                                "--rehearsal"], capsys)
    assert [(r["kind"], r["correct"]) for r in rows] == [
        ("program", True), ("control_bf16", False)]
    assert rows[1]["failed"] == ["worst_logit_gap"]


def test_sweep_reports_each_rate_from_an_empty_server(capsys):
    from perfbench import sweep

    rows = readings(sweep, ["--workload", rc.CELLS["serve"], "--rates", "2,4",
                            "--seconds", "1.5", "--rehearsal"], capsys)
    assert [r["rate_per_s"] for r in rows] == [2.0, 4.0]
    assert [r["requests"] for r in rows] == [3, 6]
    assert all(r["failed"] == 0 and r["serve_out_tokens_per_s"] > 0
               for r in rows)
