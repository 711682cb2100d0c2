"""The training kind end to end at the tiny sizes its files carry, on the
CPU: a sound run, the control (the program in lower precision than the
configuration states), the timed path broken underneath (a step that returns
its state unchanged; half of the batch left out), and the plain reference
against the builder through FFModel."""
import numpy as np
import pytest
import runs_common as rc

from perfbench.harness import runctx, spec, train


def test_rehearsal_is_correct_and_prints_no_result_line(capsys):
    rc.sound_run_is_correct_and_prints_no_result_line("train", capsys)


def test_program_in_lower_precision_than_stated_is_not_correct(
        monkeypatch, capsys):
    rc.program_in_lower_precision_than_stated_is_not_correct(
        "train", monkeypatch, capsys)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    from flexflow_tpu import FFModel

    fit = FFModel.fit

    def stuck(self, *a, **kw):
        before = self.state  # the CPU step does not donate its state
        pm = fit(self, *a, **kw)
        self.state = before
        return pm

    monkeypatch.setattr(FFModel, "fit", stuck)
    assert rc.rehearse("train") == 1
    err = capsys.readouterr().err
    assert "check grad_norm_gap value 1 " in err
    assert "check update_norm_gap value 1 " in err


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, capsys):
    """The mean is taken over the rest: the first half of every batch stands
    in for the second."""
    from flexflow_tpu import FFModel

    fit = FFModel.fit

    def halved(self, x, y, batch_size, **kw):
        def half(a):
            a = a.reshape(-1, batch_size, *a.shape[1:]).copy()
            a[:, batch_size // 2:] = a[:, :batch_size // 2]
            return a.reshape(-1, *a.shape[2:])
        return fit(self, half(x), half(y), batch_size=batch_size, **kw)

    monkeypatch.setattr(FFModel, "fit", halved)
    assert rc.rehearse("train") == 1
    assert "check grad_norm_gap" in capsys.readouterr().err


@pytest.mark.parametrize("config_name", rc.CONFIGS)
def test_reference_agrees_on_forward_loss_gradients_and_adam(config_name):
    import jax
    import jax.numpy as jnp

    cell = rc.variant_cell("train", config_name)
    builder, ref = spec.family(cell.config)
    tc = train.TrainCell(cell, builder, ref, runctx.Spans())
    tc.build()
    tc.load_seed(11)
    # forward probabilities against the reference's full-forward logits
    probs = tc.model.executor.build_forward()(
        tc.model.state.params, [jnp.asarray(tc.x[0])],
        tc.model.state.net_state)
    want = jax.nn.softmax(ref.Reference(cell.config).logits(
        ref.init(cell.config, 11), jnp.asarray(tc.x[0])), axis=-1)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(want),
                               rtol=2e-4, atol=1e-7)
    program = tc.first_steps(11)
    reference = tc.reference_steps(11)
    for a, b in zip(program["loss"], reference["loss"]):
        assert a == pytest.approx(b, rel=1e-6)
    for k, v in reference["grad"].items():
        assert program["grad"][k] == pytest.approx(v, rel=1e-4, abs=1e-9), k
    for k, v in reference["moved"].items():
        assert program["moved"][k] == pytest.approx(v, rel=1e-3), k


def test_calibrate_judges_program_control_and_faults_by_the_cells_limits(capsys):
    import json

    from perfbench import calibrate

    assert calibrate.main(["--workload", rc.CELLS["train"], "--seeds", "3",
                           "--control-seeds", "3", "--rehearsal"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    assert [(r["kind"], r["correct"]) for r in rows] == [
        ("program", True), ("control_bf16", False),
        ("fault_half_batch", False), ("fault_state_unchanged", False)]
    assert "update_norm_gap" in rows[3]["failed"]
