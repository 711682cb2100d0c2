"""The new per-layer metrics that need no device, read off the two kinds of
cell at their rehearsal sizes on the CPU and printed: the serve loop's
always-on counters and per-token stamps, and what the telemetry session
that a traced run holds open through set-up wrote down."""
import numpy as np
import pytest
import runs_common as rc
from test_perfbench_serve_runs import SliceStub

import flexflow_tpu.obs as obs
from perfbench.harness import runctx, serve, spec, traffic, train


@pytest.fixture
def session(tmp_path, monkeypatch):
    """perfbench's own session (run.py program_counters), in a directory of
    the test's."""
    monkeypatch.setattr(runctx, "OUT_DIR", str(tmp_path))
    tel = obs.start(obs.TelemetryConfig(
        dir=str(tmp_path / "telemetry"), flight_recorder=False,
        anomaly_detection=False))
    yield tel
    obs.finish()


def test_serve_rehearsal_prints_the_program_metrics(session, capsys):
    cell = spec.cell(rc.CELLS["serve"], rehearsal=True)
    builder, ref = spec.family(cell.config)
    sc = serve.ServeCell(cell, builder, ref, runctx.Spans())
    sc.build()
    sc.load_seed(5)
    sc.start()
    obs.finish()  # before the window, as run.py does
    vocab = ref.sizes(cell.config)["vocab"]
    w = sc.window(traffic.serve_schedule(cell.mix, vocab, 5, 2.0), 2.0,
                  SliceStub())
    sc.stop(w.rows)
    tab = serve.table(w.rows)
    sc.free()
    facts = dict(cell=cell, stats=w.stats, requests=tab, t_open=w.t_open,
                 t_close=w.t_close)
    got = {name: spec.reader(name)(facts) for name in (
        "decode_host_ms", "admission_stall_ms", "prefill_padding_share",
        "decode_gap_p99_ms", "decode_search_s")}
    with capsys.disabled():
        print("\nserve rehearsal (CPU, tiny; no rate):",
              {k: None if v is None else round(v, 4) for k, v in got.items()})
    assert 0 < got["decode_host_ms"] < 1e3 * w.stats["decode_s"] \
        / w.stats["iterations"]
    assert got["admission_stall_ms"] > 0
    assert 0 <= got["prefill_padding_share"] < 50  # buckets are powers of 2
    assert got["decode_search_s"] > 0
    # a window of seconds holds tens of gaps: counted, said, and no tail
    assert got["decode_gap_p99_ms"] is None
    assert "gaps pooled" in capsys.readouterr().err
    stamps = [t["row"]["req"].token_t for t in tab if t["ok"]]
    assert stamps and all(len(s) == t["out_tokens"] and s == sorted(s)
                          for s, t in zip(stamps, (t for t in tab if t["ok"])))
    # the window's delta of every phase counter is there for the readers
    assert {"admit_s", "prefill_s", "insert_s", "decode_wait_s",
            "idle_s"} <= set(w.stats)


def test_train_rehearsal_prints_the_program_metrics(session, capsys):
    cell = spec.cell(rc.CELLS["train"], rehearsal=True)
    builder, ref = spec.family(cell.config)
    tc = train.TrainCell(cell, builder, ref, runctx.Spans())
    tc.build()
    tc.load_seed(7)
    program = tc.first_steps(7)
    obs.finish()
    builds = spec.reader("step_builds")({})
    with capsys.disabled():
        print("\ntrain rehearsal (CPU, tiny):", {"step_builds": builds})
    # fit() builds the step's program for the first state (host scalars)
    # and again for its own outputs: PERF.md section 5; one is the least
    assert builds in (1, 2)
    assert all(np.isfinite(program["loss"]))
    tc.free()
