"""Sharding / shape / dtype inference pass.

Re-derives every op's output ParallelTensorShape from its inputs via a
per-op rule table and flags declared-vs-inferred mismatches:

  * material shapes + dtypes come from the op registry's own `infer`
    (ops/registry.py) — the same rules lowering uses, so a declared
    output that disagrees is a corrupted rewrite, not a style issue;
  * parallel-op degree bookkeeping mirrors the runtime semantics
    (substitution_loader._infer_outputs): Repartition sets the dim's
    degree, Combine clears it, Reduction drops the partial replica dim,
    AllToAll exchanges gather/scatter dims;
  * degree propagation is checked only where it is unambiguous
    (rank-preserving elementwise/activation ops, Linear batch dims) —
    weight-sharding rewrites legitimately change channel-dim degrees.

Codes: FFA101 shape mismatch, FFA102 dtype mismatch, FFA103 invalid
ParallelDim, FFA104 degree/replica accounting, FFA105 degree product
exceeds devices.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from ..ff_types import OperatorType, PARALLEL_OP_TYPES
from .diagnostics import AnalysisReport, Severity

# Rank-preserving ops whose every output dim must carry its input dim's
# partition degree (a mismatch means a rewrite silently dropped or
# invented a shard): elementwise, activations, dropout, softmax.
_DEGREE_PRESERVING = frozenset(
    t for t in (
        OperatorType.OP_RELU, OperatorType.OP_SIGMOID, OperatorType.OP_TANH,
        OperatorType.OP_ELU, OperatorType.OP_GELU, OperatorType.OP_SILU,
        OperatorType.OP_LEAKYRELU,
        OperatorType.OP_DROPOUT, OperatorType.OP_SOFTMAX,
        OperatorType.OP_EW_ADD, OperatorType.OP_EW_MUL,
        OperatorType.OP_EW_SUB, OperatorType.OP_EW_DIV,
        OperatorType.OP_EW_MAX, OperatorType.OP_EW_MIN,
        OperatorType.OP_SCALAR_MULTIPLY, OperatorType.OP_SCALAR_ADD,
        OperatorType.OP_SCALAR_SUB, OperatorType.OP_SCALAR_TRUE_DIV,
        OperatorType.OP_EXP, OperatorType.OP_LOG, OperatorType.OP_SQRT,
        OperatorType.OP_RSQRT, OperatorType.OP_IDENTITY,
    )
)


def _dim_problems(t) -> List[str]:
    out = []
    for i, d in enumerate(t.dims):
        if d.degree < 1:
            out.append(f"dim {i}: degree {d.degree} < 1")
        elif d.size <= 0:
            out.append(f"dim {i}: size {d.size} <= 0")
        elif d.size % d.degree != 0:
            out.append(f"dim {i}: size {d.size} not divisible by "
                       f"degree {d.degree}")
        if d.is_replica_dim and d.size != d.degree:
            out.append(f"dim {i}: replica dim size {d.size} != "
                       f"degree {d.degree}")
    return out


def _expected_parallel_dims(op) -> Optional[List]:
    """Expected output dims of a parallel op (mirrors runtime semantics in
    substitution_loader._infer_outputs). None = cannot derive (leave to
    the structural validity checks)."""
    if not op.inputs:
        return None
    in_t = op.inputs[0]
    dims = [dataclasses.replace(d) for d in in_t.dims]
    p = op.params
    t = op.op_type
    if t == OperatorType.OP_REPARTITION:
        if not (0 <= p.repartition_dim < len(dims)):
            return None
        dims[p.repartition_dim].degree = p.repartition_degree
        return dims
    if t == OperatorType.OP_COMBINE:
        if not (0 <= p.combine_dim < len(dims)):
            return None
        dims[p.combine_dim].degree = 1
        return dims
    if t == OperatorType.OP_REDUCTION:
        if dims and dims[0].is_replica_dim:
            return dims[1:]
        return dims
    if t == OperatorType.OP_ALL_TO_ALL:
        g, s = p.gather_dim, p.scatter_dim
        if not (0 <= g < len(dims) and 0 <= s < len(dims)):
            return None
        dims[g].degree = 1
        dims[s].degree = p.degree
        return dims
    if t == OperatorType.OP_WEIGHT_SHARD:
        # identity on the activation path: WeightShard reshards parameter
        # STORAGE (the target op's weight dims), never the flowing tensor
        # (parallel/weight_sharding.py)
        return dims
    return None  # REPLICATE / PIPELINE / FUSED_PARALLEL: checked loosely


def sharding_diagnostics(graph, num_devices: Optional[int] = None
                         ) -> AnalysisReport:
    from ..ops.registry import has_op_def, get_op_def

    rep = AnalysisReport()
    for op in graph.topo_order():
        # -- dim validity on everything the op touches -------------------
        for kind, tensors in (("input", op.inputs), ("output", op.outputs),
                              ("weight", op.weights)):
            for i, t in enumerate(tensors):
                for prob in _dim_problems(t):
                    rep.add(
                        Severity.ERROR, "FFA103",
                        f"{kind} {i} {t.get_shape()!r}: {prob}", op=op,
                    )
        # -- degree product vs device count ------------------------------
        if num_devices:
            for i, t in enumerate(op.outputs):
                deg = t.get_total_degree()
                if deg > num_devices:
                    rep.add(
                        Severity.ERROR, "FFA105",
                        f"output {i} degree product {deg} exceeds "
                        f"{num_devices} device(s)", op=op,
                        fix_hint="re-search for the live device count "
                                 "(recompile_for_topology) or lower the "
                                 "requested parallel degrees",
                    )
        if not op.outputs:
            continue
        # -- parallel ops: full dims expectation -------------------------
        if op.op_type in PARALLEL_OP_TYPES:
            exp = _expected_parallel_dims(op)
            if exp is not None:
                decl = op.outputs[0].dims
                exp_sizes = [d.size for d in exp]
                decl_sizes = [d.size for d in decl]
                if exp_sizes != decl_sizes:
                    rep.add(
                        Severity.ERROR, "FFA101",
                        f"declared output sizes {decl_sizes} != inferred "
                        f"{exp_sizes} from input "
                        f"{op.inputs[0].get_shape()!r}", op=op,
                    )
                else:
                    for i, (de, dd) in enumerate(zip(exp, decl)):
                        if de.degree != dd.degree or \
                                de.is_replica_dim != dd.is_replica_dim:
                            rep.add(
                                Severity.ERROR, "FFA104",
                                f"output dim {i}: declared degree "
                                f"{dd.degree}{'r' if dd.is_replica_dim else ''}"
                                f" != inferred {de.degree}"
                                f"{'r' if de.is_replica_dim else ''} for "
                                f"{op.op_type.name}", op=op,
                            )
            continue
        # -- compute ops: registry shape/dtype inference ------------------
        if not has_op_def(op.op_type):
            continue
        d = get_op_def(op.op_type)
        in_shapes = [t.material_shape() for t in op.inputs]
        in_dtypes = [t.data_type for t in op.inputs]
        try:
            out_shapes, out_dtypes = d.infer(op.params, in_shapes, in_dtypes)
        except Exception as e:  # infer itself rejects the inputs
            rep.add(
                Severity.ERROR, "FFA101",
                f"shape inference failed for inputs {in_shapes}: {e}", op=op,
            )
            continue
        if len(out_shapes) != len(op.outputs):
            rep.add(
                Severity.ERROR, "FFA101",
                f"op declares {len(op.outputs)} outputs, rules infer "
                f"{len(out_shapes)}", op=op,
            )
            continue
        for i, (t, shape, dt) in enumerate(
                zip(op.outputs, out_shapes, out_dtypes)):
            if tuple(t.material_shape()) != tuple(shape):
                rep.add(
                    Severity.ERROR, "FFA101",
                    f"output {i} declared material shape "
                    f"{tuple(t.material_shape())} != inferred {tuple(shape)}",
                    op=op,
                )
            if t.data_type != dt:
                rep.add(
                    Severity.ERROR, "FFA102",
                    f"output {i} declared dtype {t.data_type.name} != "
                    f"inferred {dt.name}", op=op,
                )
        # -- degree propagation where unambiguous ------------------------
        _check_degree_propagation(op, rep)
    return rep


def _check_degree_propagation(op, rep: AnalysisReport) -> None:
    if not op.inputs or not op.outputs:
        return
    in_t, out_t = op.inputs[0], op.outputs[0]
    # compare MATERIAL dims only: a partial-sum output (row-parallel
    # linear — reduce_linear_partition / partition_experts_alltoall)
    # prepends a replica dim marking the pending Reduction, which must
    # not shift the positional batch-dim comparison
    in_dims = [d for d in in_t.dims if not d.is_replica_dim]
    out_dims = [d for d in out_t.dims if not d.is_replica_dim]
    if op.op_type in _DEGREE_PRESERVING:
        if len(in_dims) != len(out_dims):
            return
        for i, (di, do) in enumerate(zip(in_dims, out_dims)):
            if di.degree != do.degree:
                rep.add(
                    Severity.ERROR, "FFA104",
                    f"rank-preserving {op.op_type.name}: output dim {i} "
                    f"degree {do.degree} != input degree {di.degree} "
                    "(a rewrite dropped or invented a shard without a "
                    "parallel op)", op=op,
                )
    elif op.op_type == OperatorType.OP_LINEAR:
        # batch dims follow the input; the channel (last) dim may be
        # sharded by a column-parallel rewrite — but only with the weight
        # actually sharded to match. A contraction-sharded input (row
        # parallel) legitimately yields an unsharded-but-partial output,
        # so the shared last/contraction dim is excluded either way.
        n = min(len(in_dims), len(out_dims)) - 1
        for i in range(max(0, n)):
            if in_dims[i].degree != out_dims[i].degree:
                rep.add(
                    Severity.ERROR, "FFA104",
                    f"linear batch dim {i}: output degree "
                    f"{out_dims[i].degree} != input degree "
                    f"{in_dims[i].degree}", op=op,
                )
        if out_t.dims and out_t.dims[-1].degree > 1:
            w_sharded = any(
                dim.degree == out_t.dims[-1].degree
                for w in op.weights for dim in w.dims
            )
            if not w_sharded:
                rep.add(
                    Severity.WARNING, "FFA104",
                    f"linear output channel degree {out_t.dims[-1].degree} "
                    "with no matching sharded weight dim", op=op,
                )
