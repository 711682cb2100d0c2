"""PCG operator node.

TPU-native equivalent of the reference `Op` base (include/flexflow/operator.h:
51-277). The reference Op owns Legion launch plumbing (init/forward/backward
IndexLaunchers, OpMeta per device); here an Op is a pure IR node — params +
ParallelTensor inputs/outputs/weights + MachineView — and execution is
delegated to the registered forward fn under the PCG executor. Backward
derives from jax.grad, so there is no backward plumbing at all.

ParallelDimMappingRecord equivalent: sharding propagation input→output/weight
is implemented per-op in `propagate_sharding` handlers
(parallel/propagation.py), mirroring operator.h:22-49.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

from ..ff_types import OperatorType, PARALLEL_OP_TYPES
from .machine_view import MachineView
from .parallel_tensor import ParallelTensor

_op_guid = itertools.count(2000000)


class PCGOp:
    """A node in the parallel computation graph."""

    def __init__(
        self,
        op_type: OperatorType,
        params,
        inputs: List[ParallelTensor],
        name: str = "",
        layer_guid: int = -1,
    ):
        self.guid: int = next(_op_guid)
        self.op_type = op_type
        self.params = params
        self.name = name or f"{op_type.name.lower()}_{self.guid}"
        self.inputs: List[ParallelTensor] = list(inputs)
        self.outputs: List[ParallelTensor] = []
        self.weights: List[ParallelTensor] = []
        self.weight_names: List[str] = []
        self.machine_view: Optional[MachineView] = None
        self.layer_guid = layer_guid
        # initializer per weight name (resolved at executor init)
        self.initializers: Dict[str, object] = {}
        # the loop region the op belongs to (pcg/graph.py LoopMark), or None
        self.loop = None

    @property
    def is_parallel_op(self) -> bool:
        return self.op_type in PARALLEL_OP_TYPES

    def get_params_key(self):
        """Hashable identity for node dedup (reference: model.h:678-706
        get_or_create_node keyed on Params hash)."""
        return (self.op_type, self.params, tuple(t.get_shape() for t in self.inputs))

    def __repr__(self):
        return f"PCGOp({self.name})"
