"""Precision-flow analysis pass (FFA7xx).

Abstract interpretation over dtypes: every tensor's *effective* dtype is
its precision annotation (`ParallelTensor.compute_dtype`, stamped by
`annotate_graph_precision` after the search picks a winner) falling back
to its declared `data_type`. A registry of per-op precision rules —
matmul/attention/reductions accumulate fp32 by default, elementwise
propagates the widest float input, explicit OP_CAST nodes change the
flow — re-derives the precision flow the executor will actually run, so
mixed-precision defects are rejected *before any device time is spent*
(the precision counterpart of the sharding pass's degree re-derivation).

Codes (docs/analysis.md):

  * FFA701 — dtype mismatch at an op boundary: two float inputs of one
    op carry different effective dtypes with no explicit cast (error —
    XLA would insert an implicit convert the author never audited);
  * FFA702 — low-precision accumulation: a reduction/matmul/Aggregate
    accumulating in a <=16-bit dtype without an fp32 accumulator
    (error — the MXU's fp32 accumulate is free, dropping it is never a
    win worth silent drift);
  * FFA703 — a gradient collective (Reduction / WeightShard
    reduce-scatter / the implicit data-parallel weight-grad sync)
    reduces in <=16-bit over a ring where rms error grows ~sqrt(p)
    (warning, names the degree);
  * FFA704 — loss-scale / step-guard range check: guard thresholds and
    loss-scale bounds vs the compute dtype's dynamic range (warning);
  * FFA705 — end-to-end static drift budget: per-op ulp-scaled
    quantization-error estimates accumulated along the longest PCG path
    vs a configurable budget (error when exceeded; the fix_hint names
    the op to promote). `runtime/verify.tolerance_from_budget` derives
    the differential verifier's tolerances from the same budget, so the
    static prediction and the runtime check share one knob
    (`FFConfig.precision_drift_budget`).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..ff_types import DataType, OperatorType
from .diagnostics import AnalysisReport, Severity

# Accumulated-error budget (relative, ulp-scaled units) a searched
# strategy may statically incur along its longest path. 0.25 clears the
# full bf16-compute/fp32-accum zoo with headroom while a 16-bit
# accumulator chain blows through it (FFConfig.precision_drift_budget
# overrides; verify.tolerance_from_budget consumes the same value).
DEFAULT_DRIFT_BUDGET = 0.25

# grad collectives over rings this wide get the FFA703 sqrt(p) warning
RING_DEGREE_THRESHOLD = 4

_FLOAT_DTYPES = frozenset({
    DataType.DT_HALF, DataType.DT_FLOAT, DataType.DT_DOUBLE,
    DataType.DT_BF16,
})
_LOW_PRECISION = frozenset({DataType.DT_HALF, DataType.DT_BF16})

# unit roundoff (eps/2 is one rounding's relative error bound)
_EPS = {
    DataType.DT_BF16: 2.0 ** -8,
    DataType.DT_HALF: 2.0 ** -11,
    DataType.DT_FLOAT: 2.0 ** -24,
    DataType.DT_DOUBLE: 2.0 ** -53,
}

# Ops that ACCUMULATE over a contraction/reduction width — the ops whose
# accumulator dtype matters (FFA702) and whose drift contribution scales
# with the reduction width (FFA705). OP_REDUCTION is the parallel
# partial-sum collective; its width is the reduction degree.
_ACCUMULATING = frozenset({
    OperatorType.OP_LINEAR, OperatorType.OP_CONV2D,
    OperatorType.OP_BATCHMATMUL, OperatorType.OP_MATMUL,
    OperatorType.OP_MULTIHEAD_ATTENTION, OperatorType.OP_GATED_DELTA_NET,
    OperatorType.OP_MAMBA2, OperatorType.OP_EXPERT_BANK,
    OperatorType.OP_AGGREGATE,
    OperatorType.OP_AGG_SPEC, OperatorType.OP_REDUCE_SUM,
    OperatorType.OP_REDUCE_MEAN, OperatorType.OP_MEAN,
    OperatorType.OP_POOL2D, OperatorType.OP_LAYERNORM,
    OperatorType.OP_BATCHNORM, OperatorType.OP_SOFTMAX,
    OperatorType.OP_REDUCTION,
})

# ops whose multiple inputs legitimately mix dtypes (int indices/routing
# state next to float payloads) — excluded from the FFA701 boundary check
# even for their float inputs, because the float legs are independent
# payloads, not operands of one arithmetic kernel
_MIXED_DTYPE_OK = frozenset({
    OperatorType.OP_WHERE,
})

# compute_dtype -> accum_dtype inference hook, keyed by OperatorType.
# A rule sees (op, in_flow: List[Optional[DataType]], default_compute)
# and returns (compute_dtype, accum_dtype) for the op's outputs — the
# registration point the int8/fp8 follow-up PR extends per quantized op.
_PRECISION_RULES: Dict[OperatorType, Callable] = {}


def register_precision_rule(op_type: OperatorType, fn: Callable) -> None:
    """Override the default precision inference for one op type."""
    _PRECISION_RULES[op_type] = fn


def _widest(dtypes: List[DataType]) -> Optional[DataType]:
    """Widest float dtype = smallest unit roundoff (f16 beats bf16:
    more mantissa bits; range is FFA704's business, not width's)."""
    floats = [d for d in dtypes if d in _FLOAT_DTYPES]
    if not floats:
        return None
    return min(floats, key=lambda d: _EPS[d])


def effective_dtype(t) -> DataType:
    return t.compute_dtype if t.compute_dtype is not None else t.data_type


def effective_accum_dtype(t) -> DataType:
    """The dtype the producing op accumulates in: the annotation, else
    the compute flow itself (no annotation = no fp32 master accum)."""
    return t.accum_dtype if t.accum_dtype is not None else effective_dtype(t)


def infer_op_precision(op, in_flow: List[Optional[DataType]],
                       default_compute: Optional[DataType]
                       ) -> Tuple[Optional[DataType], Optional[DataType]]:
    """Registry-driven (compute, accum) inference for one op.

    Defaults: OP_CAST sets the flow from its param; source ops start the
    flow at `default_compute`; everything else propagates the widest
    float input; accumulating ops get an fp32 accumulator."""
    rule = _PRECISION_RULES.get(op.op_type)
    if rule is not None:
        return rule(op, in_flow, default_compute)
    if op.op_type == OperatorType.OP_CAST:
        dt = op.params.dtype
        return (dt if dt in _FLOAT_DTYPES else None, None)
    known = [d for d in in_flow if d is not None]
    if not known:
        compute = default_compute
    else:
        compute = _widest(known)
    accum = None
    if op.op_type in _ACCUMULATING and compute in _LOW_PRECISION:
        accum = DataType.DT_FLOAT
    return compute, accum


def annotate_graph_precision(graph,
                             compute_dtype: Optional[DataType] = None
                             ) -> None:
    """Stamp `compute_dtype`/`accum_dtype` on every output tensor of the
    graph from the registry rules, starting the flow at `compute_dtype`
    (the executor's AMP dtype; None = full precision, which CLEARS any
    stale annotation so re-annotation is idempotent).

    Only activations (op outputs) are annotated — weights keep fp32
    master storage under AMP, so their memory accounting must stay at
    data_type width."""
    flow: Dict[int, Optional[DataType]] = {}
    for op in graph.topo_order():
        # graph-input tensors (no producing op) enter the executor
        # through its AMP entry cast, so their flow STARTS at the compute
        # dtype — declared f32 inputs do not keep the whole graph wide
        in_flow = []
        for t in op.inputs:
            if t.guid in flow:
                in_flow.append(flow[t.guid])
            elif t.data_type in _FLOAT_DTYPES:
                in_flow.append(compute_dtype if compute_dtype is not None
                               else t.data_type)
            else:
                in_flow.append(None)
        compute, accum = infer_op_precision(op, in_flow, compute_dtype)
        for t in op.outputs:
            if t.data_type not in _FLOAT_DTYPES:
                t.compute_dtype = None
                t.accum_dtype = None
                flow[t.guid] = None
                continue
            t.compute_dtype = (
                compute if compute is not None and compute != t.data_type
                else None
            )
            t.accum_dtype = accum
            flow[t.guid] = effective_dtype(t)


def _reduction_width(op) -> int:
    """Width of the op's accumulation: the contraction extent for
    matmul-likes, the declared degree for a partial-sum Reduction, the
    normalized axis for softmax/norms. 1 = nothing meaningful."""
    if op.op_type == OperatorType.OP_REDUCTION:
        return max(1, getattr(op.params, "reduction_degree", 1))
    if not op.inputs:
        return 1
    mat = op.inputs[0].material_shape()
    if not mat:
        return 1
    return max(1, mat[-1])


def estimate_drift(graph) -> Tuple[float, Dict[int, float]]:
    """(longest-path accumulated drift, per-op contribution by guid).

    Per-op contribution: one rounding in the compute dtype (eps/2) plus,
    for accumulating ops, a random-walk accumulation term
    eps(accum)/2 * sqrt(width). fp32 contributions (~6e-8) are counted
    but numerically negligible, so a full-precision graph's total is
    effectively zero."""
    contrib: Dict[int, float] = {}
    drift_at: Dict[int, float] = {}
    total = 0.0
    for op in graph.topo_order():
        base = max(
            (drift_at.get(t.guid, 0.0) for t in op.inputs), default=0.0
        )
        c = 0.0
        out = next((t for t in op.outputs
                    if effective_dtype(t) in _FLOAT_DTYPES), None)
        if out is not None:
            c = _EPS[effective_dtype(out)] / 2.0
            if op.op_type in _ACCUMULATING:
                acc = effective_accum_dtype(out)
                if acc in _FLOAT_DTYPES:
                    c += (_EPS[acc] / 2.0) * math.sqrt(_reduction_width(op))
        contrib[op.guid] = c
        here = base + c
        for t in op.outputs:
            drift_at[t.guid] = here
        total = max(total, here)
    return total, contrib


def _check_boundaries(graph, rep: AnalysisReport) -> None:
    """FFA701: float inputs of one op with differing effective dtypes."""
    for op in graph.topo_order():
        if len(op.inputs) < 2 or op.op_type in _MIXED_DTYPE_OK:
            continue
        seen: Dict[DataType, int] = {}
        for i, t in enumerate(op.inputs):
            dt = effective_dtype(t)
            if dt in _FLOAT_DTYPES:
                seen.setdefault(dt, i)
        if len(seen) > 1:
            names = ", ".join(
                f"input {i}: {dt.name}" for dt, i in sorted(
                    seen.items(), key=lambda kv: kv[1])
            )
            rep.add(
                Severity.ERROR, "FFA701",
                f"op boundary mixes float dtypes with no explicit cast "
                f"({names}) — XLA inserts an unaudited implicit convert "
                "whose direction (widen vs silently narrow) depends on "
                "operand order", op=op,
                fix_hint="insert an OP_CAST on the narrower operand "
                         "(model.cast) or annotate both sides to one "
                         "compute dtype",
            )


def _check_accumulation(graph, rep: AnalysisReport) -> None:
    """FFA702: accumulating op whose accumulator is <=16-bit."""
    for op in graph.topo_order():
        if op.op_type not in _ACCUMULATING or not op.outputs:
            continue
        out = op.outputs[0]
        if effective_dtype(out) not in _FLOAT_DTYPES:
            continue
        acc = effective_accum_dtype(out)
        if acc in _LOW_PRECISION:
            w = _reduction_width(op)
            rep.add(
                Severity.ERROR, "FFA702",
                f"{op.op_type.name} accumulates {w} terms in {acc.name} "
                "with no fp32 accumulator — relative error grows "
                f"~sqrt({w})*2^-{int(-math.log2(_EPS[acc]))} and the "
                "MXU's fp32 accumulate costs nothing", op=op,
                fix_hint="set accum_dtype=DT_FLOAT on the op's output "
                         "(the default precision rule does)",
            )


def _check_grad_collectives(graph, views, num_devices,
                            grad_dtype: Optional[DataType],
                            rep: AnalysisReport) -> None:
    """FFA703: low-precision reduction collectives over wide rings."""
    from .collectives import _view_of

    views = views or {}
    for op in graph.topo_order():
        if op.op_type == OperatorType.OP_REDUCTION:
            t = op.inputs[0] if op.inputs else None
            if t is None:
                continue
            dt = effective_dtype(t)
            p = max(1, getattr(op.params, "reduction_degree", 1))
            if dt in _LOW_PRECISION and p >= RING_DEGREE_THRESHOLD:
                rep.add(
                    Severity.WARNING, "FFA703",
                    f"partial-sum all-reduce over ring degree {p} in "
                    f"{dt.name}: rms reduction error grows ~sqrt({p}) "
                    "with the ring width", op=op,
                    fix_hint="reduce in fp32 (cast before the Reduction "
                             "or keep the partial outputs' accum fp32)",
                )
        elif op.op_type == OperatorType.OP_WEIGHT_SHARD:
            p = max(1, getattr(op.params, "shard_degree", 1))
            gdt = grad_dtype
            if gdt in _LOW_PRECISION and p >= RING_DEGREE_THRESHOLD:
                rep.add(
                    Severity.WARNING, "FFA703",
                    f"FSDP weight-grad reduce-scatter over ring degree "
                    f"{p} in {gdt.name}: rms reduction error grows "
                    f"~sqrt({p})", op=op,
                    fix_hint="force fp32 gradient storage "
                             "(FFConfig.bf16_grads=False) for this shard "
                             "degree",
                )
    # implicit data-parallel weight-grad sync: one aggregate warning —
    # every weight-carrying compute op syncs at the data degree, so
    # per-op repeats would just be noise
    if grad_dtype in _LOW_PRECISION:
        synced = [op for op in graph.topo_order()
                  if op.weights and not op.is_parallel_op]
        degrees = []
        for op in synced:
            v = _view_of(op, views)
            p = v.num_parts() if v is not None else (num_devices or 1)
            degrees.append(max(1, p))
        pmax = max(degrees, default=1)
        if pmax >= RING_DEGREE_THRESHOLD:
            rep.add(
                Severity.WARNING, "FFA703",
                f"{len(synced)} weight-grad all-reduce(s) ride the ring "
                f"at degree {pmax} in {grad_dtype.name}: rms reduction "
                f"error grows ~sqrt({pmax})",
                fix_hint="FFConfig.bf16_grads=False trades the wire "
                         "width back for fp32 reduction",
            )


def _check_guard_range(graph, step_guard, rep: AnalysisReport) -> None:
    """FFA704: loss-scale / step-guard bounds vs dtype dynamic range."""
    dtypes = set()
    for op in graph.topo_order():
        for t in op.outputs:
            dt = effective_dtype(t)
            if dt in _LOW_PRECISION:
                dtypes.add(dt)
    if not dtypes:
        return
    if DataType.DT_HALF in dtypes and (
            step_guard is None
            or getattr(step_guard, "init_loss_scale", 1.0) <= 1.0):
        rep.add(
            Severity.WARNING, "FFA704",
            "float16 compute without loss scaling (step guard absent or "
            "init_loss_scale <= 1): f16's dynamic range tops out at "
            "~6.5e4 and small gradients underflow its ~6e-5 smallest "
            "normal",
            fix_hint="fit(step_guard=StepGuardConfig("
                     "init_loss_scale=2**15)) or compute in bf16",
        )
    if step_guard is None:
        return
    init = float(getattr(step_guard, "init_loss_scale", 1.0))
    max_ls = getattr(step_guard, "max_loss_scale", None)
    max_ls = float(max_ls) if max_ls is not None else init
    min_ls = float(getattr(step_guard, "min_loss_scale", 0.0))
    for dt in sorted(dtypes):
        fi = np.finfo(dt.np_dtype)
        if max_ls > float(fi.max):
            rep.add(
                Severity.WARNING, "FFA704",
                f"loss-scale ceiling {max_ls:g} exceeds {dt.name}'s max "
                f"finite value {float(fi.max):g} — the scaled loss "
                "overflows before the guard can back off",
                fix_hint=f"cap max_loss_scale below {float(fi.max):g}",
            )
        if min_ls and min_ls < float(fi.tiny):
            rep.add(
                Severity.WARNING, "FFA704",
                f"min_loss_scale {min_ls:g} is below {dt.name}'s "
                f"smallest normal {float(fi.tiny):g} — backoff can park "
                "the scale in the subnormal range where the guard math "
                "itself flushes to zero",
                fix_hint=f"raise min_loss_scale to >= {float(fi.tiny):g}",
            )


def _check_drift_budget(graph, drift_budget: Optional[float],
                        rep: AnalysisReport) -> None:
    """FFA705: longest-path accumulated drift vs the budget."""
    budget = drift_budget if drift_budget is not None \
        else DEFAULT_DRIFT_BUDGET
    if budget <= 0:
        return
    total, contrib = estimate_drift(graph)
    if total <= budget:
        return
    worst_guid = max(contrib, key=lambda g: contrib[g])
    worst = next(op for op in graph.topo_order() if op.guid == worst_guid)
    rep.add(
        Severity.ERROR, "FFA705",
        f"static drift estimate {total:.4g} exceeds the budget "
        f"{budget:.4g} along the longest path; largest single "
        f"contribution {contrib[worst_guid]:.4g} from {worst.name} "
        f"({worst.op_type.name})", op=worst,
        fix_hint=f"promote {worst.name} (fp32 accum_dtype, or cast its "
                 "inputs up) or raise "
                 "FFConfig.precision_drift_budget if the tolerance "
                 "is intended",
    )


def precision_diagnostics(graph, views: Optional[Dict] = None,
                          num_devices: Optional[int] = None, *,
                          drift_budget: Optional[float] = None,
                          grad_dtype: Optional[DataType] = None,
                          step_guard=None) -> AnalysisReport:
    """Run the FFA7xx precision checks over a (possibly annotated) PCG.

    Un-annotated graphs analyze at their declared data_types — a pure
    fp32 graph is clean by construction, so the pass is safe in every
    pre-annotation hook (strategy validators, rule lint)."""
    rep = AnalysisReport()
    _check_boundaries(graph, rep)
    _check_accumulation(graph, rep)
    _check_grad_collectives(graph, views, num_devices, grad_dtype, rep)
    _check_guard_range(graph, step_guard, rep)
    _check_drift_budget(graph, drift_budget, rep)
    return rep
