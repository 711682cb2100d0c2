"""Static PCG analyzer tests (flexflow_tpu/analysis/): the typed
diagnostic model, the four pass families over seeded-defect PCGs —
each caught STATICALLY, with no device execution — a clean sweep over
the three searched zoo strategies from test_verify.py asserting zero
false positives, the substitution-rule lint + typed loader errors, the
`fit(lint=...)` knob, and the fflint project linter.

The broader mesh sweep runs standalone via scripts/analyze_check.sh."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from flexflow_tpu import (
    ActiMode,
    AdamOptimizer,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
    Severity,
    StaticAnalysisError,
    SubstitutionRuleError,
    analyze_graph,
    analyze_model,
)
from flexflow_tpu.analysis import analyze_rules_path, strategy_violations
from flexflow_tpu.analysis.diagnostics import AnalysisReport, Diagnostic
from flexflow_tpu.ff_types import OperatorType
from flexflow_tpu.ops.elementwise import ElementUnaryParams
from flexflow_tpu.ops.linear import LinearParams
from flexflow_tpu.ops.softmax import SoftmaxParams
from flexflow_tpu.parallel.parallel_ops import (
    CombineParams,
    ReductionParams,
    RepartitionParams,
)
from flexflow_tpu.pcg.graph import Graph
from flexflow_tpu.pcg.machine_view import MachineView
from flexflow_tpu.pcg.op import PCGOp
from flexflow_tpu.pcg.parallel_tensor import (
    ParallelDim,
    ParallelTensor,
    make_dims,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------
# graph-building helpers (no compile, no devices)
# ----------------------------------------------------------------------
def pt(sizes, degrees=None, replicas=None, dtype=DataType.DT_FLOAT):
    return ParallelTensor(dims=make_dims(sizes, degrees, replicas),
                          data_type=dtype)


def add_op(graph, op_type, params, inputs, out: ParallelTensor,
           view=None) -> PCGOp:
    op = PCGOp(op_type, params, inputs)
    out.owner_op = op
    op.outputs.append(out)
    op.machine_view = view
    graph.add_op(op)
    return op


def relu_params():
    return ElementUnaryParams(op_type=OperatorType.OP_RELU)


def view_over(start, n):
    return MachineView(start_device_id=start, dim=(n,), stride=(1,))


# ----------------------------------------------------------------------
# diagnostic model
# ----------------------------------------------------------------------
def test_diagnostic_model_and_report():
    rep = AnalysisReport()
    assert rep.ok and len(rep) == 0
    d = rep.add(Severity.ERROR, "FFA999", "boom", fix_hint="do less")
    rep.add(Severity.WARNING, "FFA998", "hmm")
    assert isinstance(d, Diagnostic)
    assert not rep.ok
    assert [x.code for x in rep.errors] == ["FFA999"]
    assert rep.by_code("FFA998")[0].severity is Severity.WARNING
    assert "1 error(s)" in rep.summary()
    assert "do less" in rep.summary()


# ----------------------------------------------------------------------
# structure pass / Graph.check_correctness (satellite regression)
# ----------------------------------------------------------------------
def test_check_correctness_flags_dangling_input():
    """Regression for the docstring promise of Graph.check_correctness:
    an op input whose producer was removed from the graph is dangling,
    not a graph input."""
    g = Graph()
    x = pt([8, 4])
    h = pt([8, 16])
    producer = add_op(g, OperatorType.OP_LINEAR, LinearParams(16), [x], h)
    y = pt([8, 16])
    add_op(g, OperatorType.OP_RELU, relu_params(), [h], y)
    assert g.check_correctness()
    # drop the producer but keep the consumer wired to its tensor
    g.ops = [op for op in g.ops if op is not producer]
    g._producer_cache = None
    assert not g.check_correctness()
    rep = analyze_graph(g, passes=("structure",))
    assert [d.code for d in rep.errors] == ["FFA001"]
    assert "dangling" in rep.errors[0].message


def test_structure_flags_invalid_dims_and_duplicates():
    g = Graph()
    x = pt([8, 4])
    bad = pt([8, 9], degrees=[1, 2])  # 9 % 2 != 0
    add_op(g, OperatorType.OP_RELU, relu_params(), [x], bad)
    rep = analyze_graph(g, passes=("structure",))
    assert "FFA002" in rep.codes()
    # duplicate producer
    g2 = Graph()
    t = pt([8, 4])
    add_op(g2, OperatorType.OP_RELU, relu_params(), [pt([8, 4])], t)
    op2 = PCGOp(OperatorType.OP_RELU, relu_params(), [pt([8, 4])])
    op2.outputs.append(t)
    g2.add_op(op2)
    rep2 = analyze_graph(g2, passes=("structure",))
    assert "FFA004" in rep2.codes()


def test_structure_flags_cycle():
    g = Graph()
    a = pt([8, 4])
    b = pt([8, 4])
    op1 = add_op(g, OperatorType.OP_RELU, relu_params(), [b], a)
    op2 = add_op(g, OperatorType.OP_RELU, relu_params(), [a], b)
    assert op1 and op2
    rep = analyze_graph(g, passes=("structure",))
    assert "FFA003" in rep.codes()
    assert not g.check_correctness()


# ----------------------------------------------------------------------
# sharding pass — seeded defects
# ----------------------------------------------------------------------
def test_sharding_flags_declared_vs_inferred_shape():
    g = Graph()
    x = pt([8, 4])
    out = pt([8, 32])  # linear says 16
    add_op(g, OperatorType.OP_LINEAR, LinearParams(16), [x], out)
    rep = analyze_graph(g, passes=("structure", "sharding"))
    assert "FFA101" in rep.codes()
    assert "(8, 32)" in rep.by_code("FFA101")[0].message


def test_sharding_flags_dtype_mismatch():
    g = Graph()
    x = pt([8, 4])
    out = pt([8, 16], dtype=DataType.DT_INT32)
    add_op(g, OperatorType.OP_LINEAR, LinearParams(16), [x], out)
    rep = analyze_graph(g, passes=("structure", "sharding"))
    assert "FFA102" in rep.codes()


def test_sharding_flags_degree_product_vs_devices():
    """Seeded defect: degree product exceeds the machine."""
    g = Graph()
    x = pt([32, 16], degrees=[8, 2])  # product 16
    out = pt([32, 16], degrees=[8, 2])
    add_op(g, OperatorType.OP_RELU, relu_params(), [x], out)
    rep = analyze_graph(g, num_devices=8)
    codes = [d.code for d in rep.errors]
    assert "FFA105" in codes
    assert "16" in rep.by_code("FFA105")[0].message


def test_sharding_flags_dropped_shard_on_rank_preserving_op():
    g = Graph()
    x = pt([32, 16], degrees=[4, 1])
    out = pt([32, 16])  # rewrite "lost" the batch shard
    add_op(g, OperatorType.OP_RELU, relu_params(), [x], out)
    rep = analyze_graph(g, passes=("structure", "sharding"))
    assert "FFA104" in [d.code for d in rep.errors]


def test_sharding_flags_parallel_op_degree_bookkeeping():
    g = Graph()
    x = pt([32, 16])
    out = pt([32, 16], degrees=[2, 1])  # combine must CLEAR the degree
    add_op(g, OperatorType.OP_COMBINE,
           CombineParams(combine_dim=0, combine_degree=2), [x], out)
    rep = analyze_graph(g, passes=("structure", "sharding"))
    assert "FFA104" in [d.code for d in rep.errors]


# ----------------------------------------------------------------------
# collectives pass — seeded defects
# ----------------------------------------------------------------------
def test_collectives_flag_wrong_reduction_axis():
    """Seeded defect: Reduction axis points at real data instead of the
    partial replica dim."""
    g = Graph()
    x = ParallelTensor(dims=[
        ParallelDim(size=2, degree=2, is_replica_dim=True),
        ParallelDim(size=32, degree=1),
        ParallelDim(size=16, degree=1),
    ])
    out = pt([32, 16])
    add_op(g, OperatorType.OP_REDUCTION,
           ReductionParams(reduction_dim=1, reduction_degree=2), [x], out)
    rep = analyze_graph(g, passes=("structure", "collectives"))
    assert "FFA202" in [d.code for d in rep.errors]
    assert "reduction_dim=0" in rep.by_code("FFA202")[0].fix_hint


def test_collectives_flag_reduction_with_nothing_to_reduce():
    g = Graph()
    x = pt([32, 16])
    out = pt([32, 16])
    add_op(g, OperatorType.OP_REDUCTION,
           ReductionParams(reduction_dim=0, reduction_degree=2), [x], out)
    rep = analyze_graph(g, passes=("structure", "collectives"))
    assert "FFA202" in [d.code for d in rep.errors]
    assert "nothing to" in rep.by_code("FFA202")[0].message


def test_collectives_flag_sharded_softmax_axis():
    """Seeded defect: the wrong-softmax-axis case PR 3 could only
    localize by RUNNING the differential verifier — caught statically:
    softmax over the (data-parallel sharded) batch axis."""
    g = Graph()
    x = pt([32, 3], degrees=[4, 1])
    out = pt([32, 3], degrees=[4, 1])
    add_op(g, OperatorType.OP_SOFTMAX, SoftmaxParams(dim=0), [x], out)
    rep = analyze_graph(g, passes=("structure", "collectives"))
    assert "FFA203" in [d.code for d in rep.errors]
    msg = rep.by_code("FFA203")[0].message
    assert "partitioned 4-way" in msg
    # the correct axis is clean
    g2 = Graph()
    x2 = pt([32, 3], degrees=[4, 1])
    out2 = pt([32, 3], degrees=[4, 1])
    add_op(g2, OperatorType.OP_SOFTMAX, SoftmaxParams(dim=-1), [x2], out2)
    assert analyze_graph(g2, passes=("structure", "collectives")).ok


def test_collectives_flag_cross_shard_order_mismatch():
    """Seeded defect: two collectives with no dependency ordering on
    PARTIALLY overlapping device sets — shards can issue them in
    different orders (static deadlock detection)."""
    g = Graph()
    src = pt([32, 16])
    fan = add_op(g, OperatorType.OP_RELU, relu_params(), [pt([32, 16])],
                 src, view=view_over(0, 1))
    assert fan
    a_out = pt([32, 16], degrees=[4, 1])
    add_op(g, OperatorType.OP_REPARTITION,
           RepartitionParams(repartition_dim=0, repartition_degree=4),
           [src], a_out, view=view_over(0, 4))     # devices 0-3
    b_out = pt([32, 16], degrees=[1, 4])
    add_op(g, OperatorType.OP_REPARTITION,
           RepartitionParams(repartition_dim=1, repartition_degree=4),
           [src], b_out, view=view_over(2, 4))     # devices 2-5: overlap
    rep = analyze_graph(g, num_devices=8,
                        passes=("structure", "collectives"))
    assert "FFA204" in [d.code for d in rep.errors]
    assert "[2, 3]" in rep.by_code("FFA204")[0].message
    # same-device-set independent collectives are fine
    g.ops[-1].machine_view = view_over(0, 4)
    rep2 = analyze_graph(g, num_devices=8,
                         passes=("structure", "collectives"))
    assert "FFA204" not in rep2.codes()


def test_collectives_flag_view_transition_without_repartition():
    g = Graph()
    x = pt([32, 16], degrees=[2, 1])
    h = pt([32, 16], degrees=[2, 1])
    add_op(g, OperatorType.OP_RELU, relu_params(), [x], h,
           view=view_over(0, 2))
    out = pt([32, 16], degrees=[4, 1])
    add_op(g, OperatorType.OP_RELU, relu_params(), [h], out,
           view=view_over(0, 4))
    rep = analyze_graph(g, passes=("structure", "collectives"))
    assert "FFA201" in [d.code for d in rep.errors]


def test_collectives_flag_dead_devices():
    g = Graph()
    x = pt([32, 16], degrees=[4, 1])
    out = pt([32, 16], degrees=[4, 1])
    add_op(g, OperatorType.OP_RELU, relu_params(), [x], out,
           view=view_over(6, 4))  # devices 6..9 of 8
    rep = analyze_graph(g, num_devices=8,
                        passes=("structure", "collectives"))
    assert "FFA205" in [d.code for d in rep.errors]


# ----------------------------------------------------------------------
# memory pass — seeded defect
# ----------------------------------------------------------------------
def big_linear_graph(view=None):
    g = Graph()
    x = pt([64, 1024])
    out = pt([64, 4096])
    op = add_op(g, OperatorType.OP_LINEAR, LinearParams(4096), [x], out,
                view=view)
    w = pt([1024, 4096])
    w.owner_op = op
    op.weights.append(w)
    op.weight_names.append("kernel")
    return g


def test_memory_flags_over_hbm_machine_view():
    """Seeded defect: a machine view that concentrates a strategy whose
    weights + optimizer state cannot fit the per-chip budget."""
    g = big_linear_graph(view=view_over(0, 1))
    # kernel: 1024*4096*4B = 16 MiB; Adam doubles state -> 64 MiB weights
    budget = 32 * 1024 * 1024
    rep = analyze_graph(g, num_devices=8, hbm_bytes=budget,
                        optimizer=AdamOptimizer(), passes=("memory",))
    assert "FFA301" in [d.code for d in rep.errors]
    assert "cannot fit" in rep.by_code("FFA301")[0].message
    # a large enough budget is clean (and still reports usage)
    rep2 = analyze_graph(g, num_devices=8, hbm_bytes=budget * 8,
                         optimizer=AdamOptimizer(), passes=("memory",))
    assert rep2.ok
    assert "FFA302" in rep2.codes()


def test_memory_inference_mode_skips_optimizer_slots():
    g = big_linear_graph(view=view_over(0, 1))
    budget = 32 * 1024 * 1024
    rep = analyze_graph(g, num_devices=8, hbm_bytes=budget,
                        optimizer=AdamOptimizer(), train=False,
                        passes=("memory",))
    assert rep.ok  # 16 MiB bare weights fit where 64 MiB training didn't


# ----------------------------------------------------------------------
# substitution-rule lint + typed loader errors (satellite)
# ----------------------------------------------------------------------
def _rule_json(dst_combine_degree=2, name="roundtrip"):
    return {"rule": [{
        "name": name,
        "srcOp": [{"type": "OP_LINEAR",
                   "input": [{"opId": -1, "tsId": 0}], "para": []}],
        "dstOp": [
            {"type": "OP_PARTITION", "input": [{"opId": -1, "tsId": 0}],
             "para": [{"key": "PM_PARALLEL_DIM", "value": 0},
                      {"key": "PM_PARALLEL_DEGREE", "value": 2}]},
            {"type": "OP_LINEAR", "input": [{"opId": 0, "tsId": 0}],
             "para": []},
            {"type": "OP_COMBINE", "input": [{"opId": 1, "tsId": 0}],
             "para": [{"key": "PM_PARALLEL_DIM", "value": 0},
                      {"key": "PM_PARALLEL_DEGREE",
                       "value": dst_combine_degree}]},
        ],
        "mappedOutput": [{"srcOpId": 0, "srcTsId": 0,
                          "dstOpId": 2, "dstTsId": 0}],
    }]}


def test_loader_accepts_sound_rule_and_rejects_unsound():
    from flexflow_tpu.search.substitution_loader import load_rule_collection

    rules = load_rule_collection(_rule_json(2))
    assert len(rules) == 1 and rules[0].supported
    with pytest.raises(SubstitutionRuleError) as ei:
        load_rule_collection(_rule_json(4, name="bad_degree"))
    assert "bad_degree" in str(ei.value)
    assert ei.value.field == "FFA402"


def test_loader_raises_typed_error_on_corrupt_fixture(tmp_path):
    from flexflow_tpu.search.substitution_loader import (
        load_rule_collection_from_path,
    )

    corrupt = _rule_json(2, name="corrupt_rule")
    del corrupt["rule"][0]["dstOp"][0]["input"][0]["tsId"]
    p = tmp_path / "corrupt.json"
    p.write_text(json.dumps(corrupt))
    with pytest.raises(SubstitutionRuleError) as ei:
        load_rule_collection_from_path(str(p))
    assert ei.value.rule == "corrupt_rule"
    assert "tsId" in ei.value.field
    # non-JSON is also a typed error, not a JSONDecodeError leak
    p2 = tmp_path / "broken.json"
    p2.write_text("{not json")
    with pytest.raises(SubstitutionRuleError):
        load_rule_collection_from_path(str(p2))


def test_rule_lint_flags_arity_and_a2a_params(tmp_path):
    bad = {"rule": [{
        "name": "fwd_ref",
        "srcOp": [{"type": "OP_RELU",
                   "input": [{"opId": 2, "tsId": 0}], "para": []}],
        "dstOp": [{"type": "OP_RELU",
                   "input": [{"opId": -1, "tsId": 0}], "para": []}],
        "mappedOutput": [{"srcOpId": 0, "srcTsId": 0,
                          "dstOpId": 5, "dstTsId": 0}],
    }]}
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    rep = analyze_rules_path(str(p))
    assert len(rep.by_code("FFA401")) >= 2  # forward ref + mapped range


def test_shipped_rule_collection_is_clean():
    from flexflow_tpu.search.substitution_loader import default_rules_path

    rep = analyze_rules_path(default_rules_path())
    assert rep.ok, rep.summary()


def test_analysis_cli_exit_codes(tmp_path):
    from flexflow_tpu.analysis.__main__ import main

    assert main([]) == 0  # shipped collection
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_rule_json(4, name="cli_bad")))
    assert main(["rules", str(bad)]) == 1


# ----------------------------------------------------------------------
# clean model-zoo sweep: zero false positives on searched strategies
# ----------------------------------------------------------------------
def searched_mlp():
    cfg = FFConfig()
    cfg.batch_size = 32
    cfg.search_budget = 4
    m = FFModel(cfg)
    x = m.create_tensor((32, 4), DataType.DT_FLOAT)
    t = m.dense(x, 32, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 32, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 3)
    t = m.softmax(t)
    m.compile(SGDOptimizer(lr=0.1),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [MetricsType.METRICS_ACCURACY])
    return m


def searched_cnn():
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.search_budget = 3
    m = FFModel(cfg)
    x = m.create_tensor((8, 3, 16, 16), DataType.DT_FLOAT)
    t = m.conv2d(x, 8, 3, 3, 1, 1, 1, 1, ActiMode.AC_MODE_RELU)
    t = m.pool2d(t, 2, 2, 2, 2, 0, 0)
    t = m.flat(t)
    t = m.dense(t, 4)
    t = m.softmax(t)
    m.compile(SGDOptimizer(lr=0.05),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [])
    return m


def searched_attention():
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.search_budget = 3
    m = FFModel(cfg)
    x = m.create_tensor((8, 16, 32), DataType.DT_FLOAT)
    t = m.multihead_attention(x, x, x, 32, 4)
    t = m.dense(t, 32, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 4)
    t = m.softmax(t)
    m.compile(SGDOptimizer(lr=0.05),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [])
    return m


@pytest.mark.parametrize("builder", [searched_mlp, searched_cnn,
                                     searched_attention])
def test_clean_zoo_sweep_zero_false_positives(builder):
    """The three searched zoo strategies from test_verify.py must come
    back with ZERO errors from the full pass stack."""
    m = builder()
    rep = analyze_model(m)
    assert rep.ok, rep.summary()
    # and through the raw validator-hook adapter too
    ndev = min(m.config.numWorkers, len(jax.devices()))
    assert strategy_violations(
        m.graph, getattr(m, "searched_views", None), ndev) == []


def test_validator_hook_runs_analyzer_on_compile():
    """compile() vets searched strategies through the analyzer via the
    register_strategy_validators hook — a seeded-defect graph mutation
    post-search is out of reach, so probe the hook wiring itself."""
    from flexflow_tpu import search as search_mod

    names = [f.__name__ for f in search_mod._STRATEGY_VALIDATORS]
    assert "_static_analysis_validator" in names


# ----------------------------------------------------------------------
# fit(lint=...) knob
# ----------------------------------------------------------------------
def lint_model():
    cfg = FFConfig()
    cfg.batch_size = 8
    m = FFModel(cfg)
    x = m.create_tensor((8, 4), DataType.DT_FLOAT)
    t = m.dense(x, 16, ActiMode.AC_MODE_RELU)
    t = m.dense(t, 3)
    t = m.softmax(t)
    m.compile(SGDOptimizer(lr=0.1),
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [])
    return m


def dataset(n=16):
    rng = np.random.RandomState(0)
    return (rng.randn(n, 4).astype(np.float32),
            rng.randint(0, 3, (n, 1)).astype(np.int32))


def _seed_softmax_defect(m):
    soft = [op for op in m.graph.ops
            if op.op_type == OperatorType.OP_SOFTMAX]
    assert soft
    # fit(lint) must catch this without ever dispatching a step, so the
    # defect only needs to be visible to the analyzer, not executable
    soft[0].params = dataclasses.replace(soft[0].params, dim=0)


def test_fit_lint_error_catches_seeded_defect_statically():
    m = lint_model()
    x, y = dataset()
    _seed_softmax_defect(m)
    with pytest.raises(StaticAnalysisError) as ei:
        m.fit(x, y, epochs=1, verbose=False, lint="error")
    assert ei.value.report.by_code("FFA203")
    assert not ei.value.report.ok


def test_fit_lint_warn_and_off_and_clean():
    m = lint_model()
    x, y = dataset()
    m.fit(x, y, epochs=1, verbose=False, lint="error")  # clean: no raise
    m2 = lint_model()
    _seed_softmax_defect(m2)
    m2.executor.invalidate_step_cache()
    with pytest.warns(UserWarning, match="FFA203"):
        m2.fit(x, y, epochs=1, verbose=False, lint="warn")
    m3 = lint_model()
    with pytest.raises(ValueError, match="lint"):
        m3.fit(x, y, epochs=1, verbose=False, lint="loud")


# ----------------------------------------------------------------------
# fflint (tools/fflint.py)
# ----------------------------------------------------------------------
sys.path.insert(0, os.path.join(REPO, "tools"))
from fflint import lint_source  # noqa: E402


def _codes(src):
    return [f.code for f in lint_source(src, "x.py")]


def test_fflint_bare_and_silent_except():
    assert _codes("try:\n    f()\nexcept:\n    pass\n") == ["FFL001"]
    assert _codes(
        "try:\n    f()\nexcept Exception:\n    pass\n") == ["FFL002"]
    # a handler that logs or falls back is fine
    assert _codes(
        "try:\n    f()\nexcept Exception:\n    x = 1\n") == []
    # pragma suppression
    assert _codes(
        "try:\n    f()\n"
        "except Exception:  # fflint: disable=FFL002\n    pass\n") == []


def test_fflint_asarray_on_device_get():
    assert _codes("a = np.asarray(jax.device_get(w))\n") == ["FFL101"]
    assert _codes("a = np.array(jax.device_get(w))\n") == ["FFL101"]
    assert _codes("a = np.array(jax.device_get(w), copy=True)\n") == []
    assert _codes("a = np.asarray(w)\n") == []  # host arrays untouched


def test_fflint_donated_reuse():
    bad = (
        "def run(self):\n"
        "    step = self.executor.build_train_step()\n"
        "    out = step(self.state, bx)\n"
        "    print(self.state.params)\n"
    )
    assert _codes(bad) == ["FFL102"]
    good = (
        "def run(self):\n"
        "    step = self.executor.build_train_step()\n"
        "    self.state, out = step(self.state, bx)\n"
        "    print(self.state.params)\n"
    )
    assert _codes(good) == []
    nodonate = (
        "def run(self):\n"
        "    step = self.executor.build_train_step(donate=False)\n"
        "    out = step(self.state, bx)\n"
        "    print(self.state.params)\n"
    )
    assert _codes(nodonate) == []


@pytest.mark.parametrize("source, codes", [
    # the decode step consumes what it is handed as `caches`
    ("def run(ex, params, toks):\n"
     "    init, step = ex.build_decode(2, 16)\n"
     "    caches = init(params, ())\n"
     "    logits, new = step(params, caches, 0, [toks])\n"
     "    return caches['mha']\n", ["FFL102"]),
    # handed over by keyword, over several lines
    ("def run(ex, params, toks):\n"
     "    init, step = ex.build_decode(2, 16)\n"
     "    caches = init(params, ())\n"
     "    logits, new = step(\n"
     "        params, t=0, batch_inputs=[toks],\n"
     "        caches=caches)\n"
     "    return caches\n", ["FFL102"]),
    # rebinding from the return value is the contract
    ("def run(ex, params, toks):\n"
     "    init, step = ex.build_decode(2, 16)\n"
     "    caches = init(params, ())\n"
     "    for t in range(4):\n"
     "        logits, caches = step(\n"
     "            params, caches, t, [toks])\n"
     "    return caches\n", []),
    # the weights are lent, not given: reading them again is fine
    ("def run(ex, params, toks):\n"
     "    init, step = ex.build_decode(2, 16)\n"
     "    logits, caches = step(params, init(params, ()), 0, [toks])\n"
     "    return params\n", []),
    # a step kept on self, built in one method and called in another
    ("class B:\n"
     "    def __init__(self, ex):\n"
     "        initB, stepB = ex.build_decode(2, 16)\n"
     "        self._initB, self._stepB = initB, stepB\n"
     "    def bad(self, params, toks):\n"
     "        logits, new = self._stepB(params, self._caches, 0, [toks])\n"
     "        return self._caches\n"
     "    def good(self, params, toks):\n"
     "        logits, self._caches = self._stepB(\n"
     "            params, self._caches, 0, [toks])\n"
     "        return self._caches\n", ["FFL102"]),
    # init_caches is no step: what it is handed stays the caller's
    ("def run(ex, params, xb):\n"
     "    init, step = ex.build_decode(2, 16)\n"
     "    caches = init(params, [xb])\n"
     "    return xb\n", []),
], ids=["read_again", "keyword_multiline", "rebound", "params_lent",
        "kept_on_self", "init_is_no_step"])
def test_fflint_donated_decode_caches(source, codes):
    assert _codes(source) == codes


def test_fflint_clean_on_final_tree_and_cli():
    """Acceptance: `python tools/fflint.py flexflow_tpu/` exits 0."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fflint.py"),
         os.path.join(REPO, "flexflow_tpu")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rules = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fflint.py"),
         "--list-rules"],
        capture_output=True, text=True,
    )
    assert "FFL101" in rules.stdout
