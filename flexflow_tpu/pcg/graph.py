"""The Parallel Computation Graph.

TPU-native equivalent of reference PCG::Graph (include/flexflow/graph.h:
293-377) and Edge (graph.h:31): a mutable DAG of PCGOp nodes connected by
ParallelTensors. The reference keeps explicit Edge sets keyed by Node; we
derive edges from tensor producer/consumer identity, and provide the same
structural operations the search needs: topo order, subgraph split
(sequence / horizontal), hashing, and dot export.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..ff_types import OperatorType
from .op import PCGOp
from .parallel_tensor import ParallelTensor


@dataclasses.dataclass(frozen=True)
class LoopMark:
    """An op's place in a loop region (FFModel.loop): the region's name and
    step count, and its role. The "entry" is an identity whose input is the
    region's source: the first step reads the source, every later step the
    value of the "exit", the op whose first output the region hands on."""

    name: str
    steps: int
    role: str = "body"  # "entry" | "body" | "exit"


@dataclasses.dataclass
class LoopRegion:
    """A loop region as the graph holds it: its ops in topological order,
    entry first, and the tensors at its edge."""

    name: str
    steps: int
    ops: List[PCGOp]
    entry: PCGOp
    exit: ParallelTensor

    @property
    def source(self) -> ParallelTensor:
        """What the first step's entry reads."""
        return self.entry.inputs[0]

    @property
    def body(self) -> List[PCGOp]:
        """The ops a step runs after the entry."""
        return [op for op in self.ops if op is not self.entry]


@dataclasses.dataclass(frozen=True)
class Edge:
    """reference: graph.h:31 Edge{srcOp,dstOp,srcIdx,dstIdx}"""

    src: PCGOp
    dst: PCGOp
    src_idx: int
    dst_idx: int

    def __hash__(self):
        return hash((self.src.guid, self.dst.guid, self.src_idx, self.dst_idx))


class Graph:
    """PCG container (reference: graph.h:293)."""

    def __init__(self, ops: Optional[List[PCGOp]] = None):
        self.ops: List[PCGOp] = list(ops) if ops else []
        # external inputs: ParallelTensors with no producer inside the graph
        self._producer_cache: Optional[Dict[int, Tuple[PCGOp, int]]] = None

    # -- loop regions --------------------------------------------------------
    def loops(self) -> List[LoopRegion]:
        """The graph's loop regions, from the marks on their ops; raises
        ValueError on a region that is not whole (loop_problems)."""
        problems = self.loop_problems()
        if problems:
            raise ValueError("; ".join(msg for _, msg in problems))
        return self._regions()

    def _regions(self) -> List[LoopRegion]:
        by_name: Dict[str, List[PCGOp]] = {}
        for op in self.topo_order():
            if op.loop is not None:
                by_name.setdefault(op.loop.name, []).append(op)
        out = []
        for name, ops in by_name.items():
            entries = [op for op in ops if op.loop.role == "entry"]
            exits = [op for op in ops if op.loop.role == "exit"]
            if len(entries) == 1 and len(exits) == 1 and exits[0].outputs:
                out.append(LoopRegion(name, ops[0].loop.steps, ops,
                                      entries[0], exits[0].outputs[0]))
        return out

    def loop_problems(self) -> List[Tuple[PCGOp, str]]:
        """What keeps a loop region from running as one body over its
        steps: one entry (an identity) and one exit each, one step count,
        an exit shaped as the source, and no tensor of the body read
        outside the region but the exit. A rewrite that breaks one fails
        check_correctness."""
        marked = [op for op in self.ops if op.loop is not None]
        if not marked:
            return []
        out: List[Tuple[PCGOp, str]] = []
        by_name: Dict[str, List[PCGOp]] = {}
        for op in marked:
            by_name.setdefault(op.loop.name, []).append(op)
        for name, ops in by_name.items():
            roles = [op.loop.role for op in ops]
            if roles.count("entry") != 1 or roles.count("exit") != 1:
                out.append((ops[0], f"loop {name!r} needs one entry and one "
                                    f"exit, has {roles.count('entry')} and "
                                    f"{roles.count('exit')}"))
                continue
            if len({op.loop.steps for op in ops}) != 1:
                out.append((ops[0], f"loop {name!r} has two step counts"))
            entry = next(op for op in ops if op.loop.role == "entry")
            exit_op = next(op for op in ops if op.loop.role == "exit")
            if entry.op_type != OperatorType.OP_IDENTITY \
                    or len(entry.inputs) != 1:
                out.append((entry, f"loop {name!r}: its entry is no identity"))
            elif not exit_op.outputs or exit_op.outputs[0].material_shape() \
                    != entry.inputs[0].material_shape():
                out.append((exit_op, f"loop {name!r}: the exit is not shaped "
                                     "as the source it feeds back"))
        inside = {op.guid: op.loop.name for op in marked}
        exits = {op.outputs[0].guid for op in marked
                 if op.loop.role == "exit" and op.outputs}
        prod = self.producers()
        for op in self.ops:
            for t in op.inputs:
                src = prod.get(t.guid)
                if src is None or src[0].guid not in inside:
                    continue
                if inside.get(op.guid) != inside[src[0].guid] \
                        and t.guid not in exits:
                    out.append((op, f"{op.name} reads {src[0].name}'s output "
                                    "from outside loop "
                                    f"{inside[src[0].guid]!r}"))
        return out

    def add_op(self, op: PCGOp) -> PCGOp:
        self.ops.append(op)
        self._producer_cache = None
        return op

    # -- structure ----------------------------------------------------------
    def producers(self) -> Dict[int, Tuple[PCGOp, int]]:
        """tensor guid -> (producing op, output index)."""
        if self._producer_cache is None:
            m: Dict[int, Tuple[PCGOp, int]] = {}
            for op in self.ops:
                for i, t in enumerate(op.outputs):
                    m[t.guid] = (op, i)
            self._producer_cache = m
        return self._producer_cache

    def in_edges(self, op: PCGOp) -> List[Edge]:
        prod = self.producers()
        es = []
        for j, t in enumerate(op.inputs):
            if t.guid in prod:
                src, i = prod[t.guid]
                es.append(Edge(src, op, i, j))
        return es

    def out_edges(self, op: PCGOp) -> List[Edge]:
        es = []
        out_guids = {t.guid: i for i, t in enumerate(op.outputs)}
        for other in self.ops:
            if other is op:
                continue
            for j, t in enumerate(other.inputs):
                if t.guid in out_guids:
                    es.append(Edge(op, other, out_guids[t.guid], j))
        return es

    def input_tensors(self) -> List[ParallelTensor]:
        prod = self.producers()
        seen: Set[int] = set()
        ins: List[ParallelTensor] = []
        for op in self.ops:
            for t in op.inputs:
                if t.guid not in prod and t.guid not in seen:
                    seen.add(t.guid)
                    ins.append(t)
        return ins

    def output_tensors(self) -> List[ParallelTensor]:
        """Tensors produced but never consumed."""
        consumed = {t.guid for op in self.ops for t in op.inputs}
        outs = []
        for op in self.ops:
            for t in op.outputs:
                if t.guid not in consumed:
                    outs.append(t)
        return outs

    def topo_order(self) -> List[PCGOp]:
        prod = self.producers()
        visited: Set[int] = set()
        order: List[PCGOp] = []

        def visit(op: PCGOp):
            if op.guid in visited:
                return
            visited.add(op.guid)
            for t in op.inputs:
                if t.guid in prod:
                    visit(prod[t.guid][0])
            order.append(op)

        for op in self.ops:
            visit(op)
        return order

    def check_correctness(self) -> bool:
        """reference: Graph::check_correctness — every op input either comes
        from another op or is a graph input; every tensor produced at most
        once; shapes valid; graph acyclic. Delegates to the static
        analyzer's structure pass (analysis/structure.py), which names the
        violation when one wants the details (the search only needs the
        boolean gate)."""
        from ..analysis.structure import graph_is_wellformed

        return graph_is_wellformed(self)

    def hash(self) -> int:
        """Structural hash (reference: Graph::hash used in dp_state_hash).

        MUST fold output and weight shape keys, not just inputs: rewrites
        that only change weight/output parallel degrees (attention
        head-partition, embedding channel-split) are otherwise
        hash-identical to the unrewritten graph — the best-first search
        deduplicates by this hash and would silently drop the whole
        attribute-/parameter-parallel candidate class."""
        h = 17
        for op in self.topo_order():
            key = (op.op_type, op.params)
            if op.loop is not None:
                key += (op.loop,)
            mv = op.machine_view.hash() if op.machine_view else 0
            h = hash((
                h, key, mv,
                tuple(t.shape_key() for t in op.inputs),
                tuple(t.shape_key() for t in op.outputs),
                tuple(w.shape_key() for w in op.weights),
            ))
        return h

    # -- dot export (reference: Graph::export_strategy_computation_graph,
    #    include/flexflow/utils/dot/) ---------------------------------------
    def export_dot(self) -> str:
        lines = ["digraph PCG {"]
        for op in self.ops:
            label = op.name
            if op.machine_view is not None:
                label += f"\\n{op.machine_view!r}"
            lines.append(f'  n{op.guid} [label="{label}"];')
        for op in self.ops:
            for e in self.in_edges(op):
                lines.append(f"  n{e.src.guid} -> n{e.dst.guid};")
        lines.append("}")
        return "\n".join(lines)

    def __len__(self):
        return len(self.ops)
