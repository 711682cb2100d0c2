"""What the two files of rehearsal runs share (one file a kind, so that the
suite's workers run them side by side)."""
import dataclasses
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import run  # noqa: E402
from perfbench.harness import spec  # noqa: E402

BENCH = spec.benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
CELLS = {spec.cell(w["name"]).kind: w["name"] for w in BENCH["workloads"]}
SECONDS = {"train": "0.5", "serve": "3"}


def rehearse(kind, seed=2 ** 31 + 7):
    return run.main(["--workload", CELLS[kind], "--seed", str(seed),
                     "--seconds", SECONDS[kind], "--trace", "0",
                     "--rehearsal"])


def sound_run_is_correct_and_prints_no_result_line(kind, capsys):
    assert rehearse(kind) == 0
    out, err = capsys.readouterr()
    assert not any(line.startswith("{") for line in out.splitlines())
    assert "correct True" in err and "rehearsal: no result line" in err


def program_in_lower_precision_than_stated_is_not_correct(
        kind, monkeypatch, capsys):
    """The control: the rehearsal sizes state float32; the program's own
    mixed-precision path (bfloat16 compute) switched on stands in."""
    from flexflow_tpu import FFModel

    compile_ = FFModel.compile

    def lower(self, *a, **kw):
        self.config.allow_mixed_precision = True
        return compile_(self, *a, **kw)

    monkeypatch.setattr(FFModel, "compile", lower)
    assert rehearse(kind) == 1
    assert "correct False" in capsys.readouterr().err


def variant_cell(kind, config_name):
    """The cell of `kind` with the named configuration's tiny sizes in it."""
    cell = spec.cell(CELLS[kind], rehearsal=True)
    cfg = spec.load_json("configs", config_name + ".json")
    cfg = spec.overlay(cfg, cfg["rehearsal"])
    if kind == "serve":  # room for the serving cell's tiny max_len
        for key in ("n_positions", "max_position_embeddings"):
            if key in cfg:
                cfg[key] = cell.params["serving"]["max_len"]
    return dataclasses.replace(cell, config=cfg)
