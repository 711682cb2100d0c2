"""Operator fusion pass.

TPU-native equivalent of FFModel::apply_fusion (reference:
src/runtime/model.cc:2495-2560, enabled by --fusion): packs maximal chains
of single-input/single-output non-parallel ops into one OP_FUSED node.

Under XLA this does not change the compiled program (XLA fuses anyway); it
exists for (a) PCG parity — searches and serializers see the same fused
graphs the reference produces, (b) fewer PCG nodes => faster search on deep
elementwise-heavy graphs, and (c) the attachment point for hand-written
Pallas mega-kernels.
"""
from __future__ import annotations

from typing import Dict, List

from ..ff_types import OperatorType
from ..ops.fused import FusedOpParams
from ..ops.registry import get_op_def, has_op_def
from .graph import Graph
from .op import PCGOp
from .parallel_tensor import ParallelDim, ParallelTensor

# ops safe to pack into a chain (single tensor in, single tensor out,
# no RNG requirement differences that change semantics when chained)
_FUSABLE = {
    OperatorType.OP_LINEAR,
    OperatorType.OP_RELU,
    OperatorType.OP_SIGMOID,
    OperatorType.OP_TANH,
    OperatorType.OP_GELU,
    OperatorType.OP_SILU,
    OperatorType.OP_ELU,
    OperatorType.OP_EXP,
    OperatorType.OP_SCALAR_MULTIPLY,
    OperatorType.OP_SCALAR_ADD,
    OperatorType.OP_SCALAR_SUB,
    OperatorType.OP_SCALAR_TRUE_DIV,
    OperatorType.OP_POW,
    OperatorType.OP_RSQRT,
    OperatorType.OP_SOFTMAX,
    OperatorType.OP_LAYERNORM,
    OperatorType.OP_FLAT,
    OperatorType.OP_RESHAPE,
    OperatorType.OP_IDENTITY,
}


def apply_fusion(graph: Graph) -> Graph:
    """Returns a new graph with fusable chains packed into OP_FUSED nodes."""
    topo = graph.topo_order()
    prod = graph.producers()
    consumers: Dict[int, List[PCGOp]] = {}
    for op in topo:
        for t in op.inputs:
            p = prod.get(t.guid)
            if p is not None:
                consumers.setdefault(p[0].guid, []).append(op)

    def fusable(op: PCGOp) -> bool:
        # a loop region's ops stay as they are: its body runs as one
        # program over the steps (FFModel.loop)
        return (
            op.loop is None
            and op.op_type in _FUSABLE
            and len(op.inputs) == 1
            and len(op.outputs) == 1
        )

    new_graph = Graph()
    consumed = set()
    for op in topo:
        if op.guid in consumed:
            continue
        if not fusable(op):
            new_graph.add_op(op)
            continue
        # grow the chain: next op must be the sole consumer and fusable
        chain = [op]
        cur = op
        while True:
            cons = consumers.get(cur.guid, [])
            if len(cons) != 1:
                break
            nxt = cons[0]
            if not fusable(nxt) or nxt.inputs[0].guid != cur.outputs[0].guid:
                break
            chain.append(nxt)
            cur = nxt
        if len(chain) == 1:
            new_graph.add_op(op)
            continue
        for c in chain:
            consumed.add(c.guid)
        fused = _make_fused(chain)
        new_graph.add_op(fused)
    return new_graph


def _make_fused(chain: List[PCGOp]) -> PCGOp:
    first, last = chain[0], chain[-1]
    steps = []
    for i, c in enumerate(chain):
        in_slot = 0 if i == 0 else 1 + (i - 1)  # slot of previous output
        steps.append((c.op_type, c.params, (in_slot,)))
    params = FusedOpParams(
        chain=tuple(steps),
        num_inputs=1,
        output_slots=(1 + len(chain) - 1,),
    )
    fused = PCGOp(
        OperatorType.OP_FUSED,
        params,
        [first.inputs[0]],
        name=f"fused_{first.name}__{last.name}",
        layer_guid=first.layer_guid,
    )
    out = last.outputs[0]
    out.owner_op = fused
    fused.outputs.append(out)
    # weights carried with step-qualified names (ops/fused.py looks them up
    # by the "step{i}/" prefix)
    fused.weight_tags = []
    for i, c in enumerate(chain):
        for w, name, tags in zip(
            c.weights, c.weight_names, getattr(c, "weight_tags", [()] * len(c.weights))
        ):
            w.owner_op = fused
            fused.weights.append(w)
            fused.weight_names.append(f"step{i}/{name}")
            fused.weight_tags.append(tags)
            fused.initializers[f"step{i}/{name}"] = c.initializers.get(
                name, "glorot_uniform"
            )
    return fused
