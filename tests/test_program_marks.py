"""The program marks its own hot path (ISSUE 25): the `obs.mark` seam and
its two sinks, the serve loop's always-on phase counters and per-token
stamps, the named scopes and kernel names of the step's program, and the
trace-time counter of program builds."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu.obs as obs
from flexflow_tpu.runtime.serving import (
    AdmissionQueue,
    ContinuousBatcher,
    GenerationRequest,
    ServingConfig,
)
from test_serving import SEQ, build_lm


@pytest.fixture(scope="module")
def lm():
    return build_lm()


def session(tmp_path):
    return obs.session(obs.TelemetryConfig(
        dir=str(tmp_path), flight_recorder=False, anomaly_detection=False))


def host_span_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("ff."):
                        names.setdefault(e.name, []).append(dict(e.stats))
    return names


# -- the seam ---------------------------------------------------------------
def test_mark_without_session_or_profiler_records_nothing():
    assert obs.active() is None
    assert obs.span("a") is obs.span("b")  # the one null span, as before
    counters = {"phase_s": 0.0}
    with obs.mark("ff.test.phase", into=(counters, "phase_s"), request="r1") as m:
        time.sleep(0.002)
        m.set(slot=3)
    assert m.dur >= 0.002 and counters["phase_s"] == m.dur
    assert m.args == {"request": "r1", "slot": 3}
    assert m._tracer is None  # nothing to write to


def test_mark_with_a_session_writes_the_x_event(tmp_path):
    with session(tmp_path) as tel:
        with obs.mark("ff.test.outer", cat="serving", request="r1") as outer:
            outer.set(slot=2)
            with obs.mark("ff.test.idle", session=False):
                pass
        with obs.mark("ff.train.step", cat="train", step_num=7):
            pass
        events = {e["name"]: e for e in tel.tracer.events if e["ph"] == "X"}
    assert set(events) == {"ff.test.outer", "ff.train.step"}
    e = events["ff.test.outer"]
    assert e["cat"] == "serving" and e["args"] == {"request": "r1", "slot": 2}
    assert e["dur"] == outer.dur and not obs.validate_event(e)
    assert events["ff.train.step"]["args"] == {"step": 7}


def test_mark_is_on_a_host_plane_of_the_profilers_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.mark("ff.test.traced", request="r9", iteration=4) as m:
            m.set(slot=1)
            jnp.ones((8, 8)).sum().block_until_ready()
        with obs.mark("ff.train.step", step_num=3):
            pass
    finally:
        jax.profiler.stop_trace()
    names = host_span_names(str(tmp_path))
    assert names["ff.test.traced"] == [
        {"request": "r9", "iteration": 4, "slot": 1}]
    assert names["ff.train.step"][0]["step_num"] == 3


# -- the serve loop -----------------------------------------------------------
def serve(lm, prompts, max_new):
    q = AdmissionQueue(max_depth=16)
    b = ContinuousBatcher(lm, ServingConfig(
        max_len=SEQ, slots=2, page_size=4, precompile=False), q).start()
    reqs = [GenerationRequest(np.asarray(p, np.int32), max_new,
                              deadline_s=120.0) for p in prompts]
    try:
        for r in reqs:
            q.offer(r)
        for r in reqs:
            r.result(timeout=120.0)
        time.sleep(0.05)  # a few idle sleeps
    finally:
        b.stop()
    return b, reqs


def test_serve_loop_phase_counters_and_token_stamps(lm):
    b, reqs = serve(lm, [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]], max_new=5)
    s = b.stats
    assert s["admitted"] == 3 and s["iterations"] > 0
    assert s["admit_s"] >= s["prefill_s"] + s["insert_s"] > 0
    parts = [s[k] for k in ("decode_prepare_s", "decode_dispatch_s",
                            "decode_wait_s", "decode_fetch_s",
                            "decode_sample_s")]
    assert all(p > 0 for p in parts) and s["decode_s"] >= sum(parts)
    # what the iterations fetched: 2 slots' ids, this graph counts nothing
    assert s["decode_fetch_bytes"] == 8 * s["iterations"]
    assert s["idle_s"] > 0
    # prompts of 3, 5 and 2 tokens in buckets of 4, 8 and 2
    assert (s["prefill_tokens"], s["prefill_bucket_tokens"]) == (10, 14)
    for r in reqs:
        generated = len(r.tokens) - len(r.prompt)
        assert len(r.token_t) == generated == 5
        assert r.token_t[0] == r.first_token_t
        assert r.token_t == sorted(r.token_t)
        assert r.token_t[-1] <= r.finished_t


def test_admission_parts_add_up_to_the_admission(lm):
    b, reqs = serve(lm, [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]], max_new=3)
    s = b.stats
    parts = [s[k] for k in ("prefill_init_s", "prefill_dispatch_s",
                            "prefill_wait_s", "prefill_fetch_s")]
    assert all(p > 0 for p in parts) and s["prefill_s"] >= sum(parts)
    assert s["admit_reserve_s"] > 0
    assert s["admit_s"] >= s["admit_reserve_s"] + s["prefill_s"] + s["insert_s"]


def test_insert_programs_count_the_per_slot_leaves_written(lm):
    """Every per-slot leaf is written by one program an admission, and a
    computed prefill's batch-1 cache is made by one more: the counts do
    not grow with the leaves."""
    b, reqs = serve(lm, [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]], max_new=3)
    leaves = sum(len(jax.tree_util.tree_leaves(b._caches[sec]))
                 for sec in ("prefix", "mha", "recurrent"))
    assert leaves > 1 and b.stats["admitted"] == 3
    assert b.stats["insert_programs"] == 3
    assert b.stats["prefill_init_programs"] == 3 - b.stats["prefill_skips"]


def test_serve_loop_spans_are_on_the_serve_threads_line(lm, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        b, reqs = serve(lm, [[1, 2, 3]], max_new=3)
    finally:
        jax.profiler.stop_trace()
    names = host_span_names(str(tmp_path))
    for name in ("ff.serve.admit", "ff.serve.admit.reserve",
                 "ff.serve.prefill", "ff.serve.prefill.init",
                 "ff.serve.prefill.dispatch", "ff.serve.prefill.wait",
                 "ff.serve.prefill.fetch", "ff.serve.insert",
                 "ff.serve.decode", "ff.serve.decode.prepare",
                 "ff.serve.decode.dispatch", "ff.serve.decode.wait",
                 "ff.serve.decode.fetch", "ff.serve.decode.sample",
                 "ff.serve.idle"):
        assert name in names, name
    (admit,) = names["ff.serve.admit"]
    assert (admit["request"], admit["prompt_len"], admit["slot"],
            admit["bucket"]) == (reqs[0].id, 3, 0, 4)
    assert str(admit["skipped"]) in ("False", "0")
    assert names["ff.serve.prefill"][0]["request"] == reqs[0].id
    # every span of one admission carries the request's id
    assert names["ff.serve.insert"] == [{"request": reqs[0].id, "slot": 0}]
    for part in ("init", "dispatch", "wait", "fetch"):
        assert len(names[f"ff.serve.prefill.{part}"]) == 1, part
    assert [d["iteration"] for d in names["ff.serve.decode"]] == [0, 1]
    # each part once an iteration, with the pick on the device too
    for part in ("prepare", "dispatch", "wait", "fetch", "sample"):
        assert len(names[f"ff.serve.decode.{part}"]) == 2, part
    assert names["ff.serve.decode"][0]["occupancy"] == 1


# -- the step's program ---------------------------------------------------------
def train_args(m, batch=2, seq=SEQ):
    ex = m.executor
    x = ex.shard_batch(ex.input_pts[0], np.zeros((batch, seq), np.int32))
    y = ex.put_replicated(np.zeros((batch, seq, 1), np.int32))
    return m.state, [x], y, ex.put_replicated(jax.random.key(0))


def op_names(lowered):
    """The op_name of every operation of a lowered program's text."""
    import re

    text = lowered.as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def test_train_step_scopes_name_phases_and_pcg_ops(lm):
    step = jax.jit(lm.executor._make_step())
    names = op_names(step.lower(*train_args(lm)))
    joined = "\n".join(sorted(names))
    for scope in ("jit(step)/jvp(ff.fwd)/", "jit(step)/ff.opt/",
                  "jit(step)/jvp(ff.fwd)/ff.loss/",
                  "jit(step)/transpose(jvp(ff.fwd))/ff.loss/",
                  "jit(step)/ff.metrics/"):
        assert scope in joined, scope
    # a device operation names the graph node the search priced
    mha = next(op.name for op in lm.executor.topo
               if "attention" in op.op_type.name.lower())
    assert f"jit(step)/jvp(ff.fwd)/{mha}/" in joined
    assert f"jit(step)/transpose(jvp(ff.fwd))/{mha}/" in joined


def test_decode_step_scopes_and_kernel_names(lm, monkeypatch):
    _, step = lm.executor.build_decode(2, SEQ)
    caches = lm.executor.build_decode(2, SEQ)[0](lm.state.params, ())
    names = op_names(step.lower(lm.state.params, caches,
                                jnp.zeros((2,), jnp.int32),
                                [jnp.zeros((2, 1), jnp.int32)]))
    mha = next(op.name for op in lm.executor.topo
               if "attention" in op.op_type.name.lower())
    assert any(f"/ff.decode/{mha}/" in n for n in names)
    # the kernels carry their names into the program lowered for the TPU
    from flexflow_tpu.kernels.attention import flash_attention_folded
    from flexflow_tpu.kernels.decode import paged_flash_decode

    x = jax.ShapeDtypeStruct((16, 1024, 64), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: flash_attention_folded(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.export.export(jax.jit(grad), platforms=["tpu"])(
        x, x, x).mlir_module()
    assert "ff_flash_fwd" in text and "ff_flash_bwd" in text
    slots, heads, d, pages, page = 8, 16, 64, 64, 16
    pool = jax.ShapeDtypeStruct((heads, slots * pages, page, d), jnp.bfloat16)
    text = jax.export.export(jax.jit(paged_flash_decode), platforms=["tpu"])(
        jax.ShapeDtypeStruct((slots, heads, d), jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((slots, pages), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32)).mlir_module()
    assert "ff_paged_decode" in text


def traces(tel, program):
    return sum(r["value"] for r in tel.metrics.snapshot()
               if r["name"] == "ff_program_traces_total"
               and r["labels"].get("program") == program)


def test_program_traces_are_counted_once_per_build(tmp_path):
    m = build_lm()
    with session(tmp_path) as tel:
        step = jax.jit(m.executor._make_step())
        args = train_args(m)
        step(*args)
        step(*args)
        assert traces(tel, "train_step") == 1
        step(*train_args(m, batch=4))  # another shape: built again
        assert traces(tel, "train_step") == 2
        init, dstep = m.executor.build_decode(2, SEQ)
        t = jnp.zeros((2,), jnp.int32)
        for _ in range(2):  # the step consumes its caches: fresh ones each
            dstep(m.state.params, init(m.state.params, ()), t,
                  [jnp.zeros((2, 1), jnp.int32)])
        dstep(m.state.params, init(m.state.params, ()), jnp.int32(0),
              [jnp.zeros((2, 4), jnp.int32)])
        assert traces(tel, "decode_step") == 1
        assert traces(tel, "prefill") == 1
    # with no session the counter is a no-op and the step still traces
    jax.jit(m.executor._make_step())(*train_args(m, batch=8))


def test_fit_marks_feed_step_fold_and_sync(tmp_path):
    m = build_lm()
    x = np.zeros((4, SEQ), np.int32)
    y = np.zeros((4, SEQ, 1), np.int32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        m.fit(x, y, batch_size=2, epochs=1, verbose=False)
    finally:
        jax.profiler.stop_trace()
    names = host_span_names(str(tmp_path))
    assert [s["step_num"] for s in names["ff.train.step"]] == [0, 1]
    assert len(names["ff.fit.feed"]) == 2
    assert names["ff.fit.fold"] == [{"epoch": 0, "steps": 2}]
    assert names["ff.fit.sync"] == [{"step": 2}]


# -- obs/step_profile.py reads the same scopes ----------------------------------
def test_step_profile_maps_device_ops_by_their_per_op_scope(tmp_path):
    from jax.profiler import ProfileData

    from flexflow_tpu.obs import step_profile as sp

    hlo = '''
HloModule jit_step
ENTRY %main {
  %fusion.12 = bf16[4,16]{1,0} fusion(%p0), kind=kOutput, metadata={op_name="jit(step)/jvp(ff.fwd)/op_linear_2/dot_general" source_file="x.py"}
  %ff_flash_bwd.3 = bf16[4,16]{1,0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(ff.fwd))/op_multihead_attention_1/ff_flash_bwd/pallas_call"}
  %fusion.13 = f32[16]{0} fusion(%p1), kind=kLoop, metadata={op_name="jit(step)/ff.opt/sub"}
  ROOT %copy.1 = f32[16]{0} copy(%fusion.13)
}'''
    scopes = sp._instruction_scopes(hlo)
    assert scopes["fusion.12"].endswith("op_linear_2/dot_general")
    assert "copy.1" not in scopes and len(scopes) == 3
    path = tmp_path / "vm.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace('''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 11000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.12 = bf16[4,16]{1,0} fusion(%p0), kind=kOutput" } }
  event_metadata { key: 2 value { id: 2 name: "%ff_flash_bwd.3 = bf16[4,16]{1,0} custom-call(%fusion.12)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.13 = f32[16]{0} fusion(%p1), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.1 = f32[16]{0} copy(%fusion.13)" } }
}
planes { id: 2 name: "/host:CPU" }'''))
    events = sp._xplane_op_events(
        str(path), scopes, ["op_linear_2", "op_multihead_attention_1"])
    # the optimizer's fusion and the bare copy are under no PCG op's scope;
    # a fusion's NAME (%fusion.12) says nothing and is not matched on
    assert [(e["name"], e["ts"], e["dur"]) for e in events] == [
        ("op_linear_2", 0.0, pytest.approx(3e-6)),
        ("op_multihead_attention_1", pytest.approx(3e-6), pytest.approx(5e-6))]
    assert all(e["ph"] == "X" and e["cat"] == sp.MEASURED_CAT
               and e["args"]["source"] == "xla_trace" for e in events)
    # a trace with no device plane (the CPU's) maps nothing: the caller
    # falls back to the instrumented walk
    host_only = tmp_path / "cpu.xplane.pb"
    host_only.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/host:CPU" }'))
    assert sp._xplane_op_events(str(host_only), scopes, ["op_linear_2"]) == []
