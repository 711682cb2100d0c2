"""User-facing deferred Tensor and Layer IR.

TPU-native equivalents of the reference's graph-build IR: `Tensor`/`TensorBase`
(include/flexflow/tensor.h:36-94) and `Layer` (include/flexflow/layer.h:10-62).
API calls on FFModel create Layers holding shape-only Tensors; nothing is
materialized until compile(). Unlike the reference there is no Legion region
behind a Tensor — after compile, weight access (get_tensor/set_tensor,
reference: src/runtime/parallel_tensor.cc set_tensor/get_tensor) reads/writes
the jax.Array pytree held by the compiled model state.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..ff_types import DataType, OperatorType, ParameterSyncType

_guid = itertools.count(100)


class Tensor:
    """Shape-only tensor created during graph build (reference: tensor.h:36)."""

    def __init__(
        self,
        dims: Tuple[int, ...],
        dtype: DataType = DataType.DT_FLOAT,
        owner_layer: Optional["Layer"] = None,
        owner_idx: int = 0,
        create_gradients: bool = True,
        name: str = "",
    ):
        self.guid: int = next(_guid)
        self.dims: Tuple[int, ...] = tuple(int(d) for d in dims)
        self.data_type: DataType = dtype
        self.owner_layer = owner_layer
        self.owner_idx = owner_idx
        self.create_gradients = create_gradients
        self.sync_type = ParameterSyncType.NONE
        self.initializer = None
        self.name = name
        self._model = None  # set by FFModel for post-compile access

    @property
    def num_dims(self) -> int:
        return len(self.dims)

    def get_volume(self) -> int:
        return int(np.prod(self.dims)) if self.dims else 0

    # -- post-compile weight/value access (reference: flexflow_cffi.py:854) --
    def get_tensor(self, ffmodel=None):
        model = ffmodel or self._model
        assert model is not None, "tensor not attached to a compiled model"
        return model._get_tensor_value(self)

    def set_tensor(self, ffmodel, value):
        model = ffmodel or self._model
        model._set_tensor_value(self, np.asarray(value))

    # reference: flexflow_cffi.py Tensor.attach_numpy_array (zero-copy
    # Legion attach) / inline_map / get_array / inline_unmap. Here the
    # "mapped" view is a host numpy buffer; inline_unmap writes it back.
    def attach_numpy_array(self, ffmodel=None, ffconfig=None, array=None):
        model = ffmodel or self._model
        model._attach_array(self, array)

    def detach_numpy_array(self, ffmodel=None, ffconfig=None):
        pass  # nothing pinned host-side

    def inline_map(self, ffmodel=None, ffconfig=None):
        model = ffmodel or self._model
        try:
            self._inline_buf = np.array(model._get_tensor_value(self))
        except KeyError:
            # not yet bound (e.g. the label before any batch): fresh zeros
            self._inline_buf = np.zeros(self.dims, self.data_type.np_dtype)

    def get_array(self, ffmodel=None, ffconfig=None, data_type=None):
        assert getattr(self, "_inline_buf", None) is not None, (
            "call inline_map first"
        )
        return self._inline_buf

    def inline_unmap(self, ffmodel=None, ffconfig=None):
        model = ffmodel or self._model
        model._set_tensor_value(self, self._inline_buf)
        self._inline_buf = None

    # weight aliases (reference: flexflow_cffi.py Parameter.set_weights /
    # get_weights)
    def set_weights(self, ffmodel, value):
        self.set_tensor(ffmodel, value)

    def get_weights(self, ffmodel=None):
        return self.get_tensor(ffmodel)

    # numpy-style niceties used by frontends
    @property
    def shape(self):
        return self.dims

    def __repr__(self):
        return f"Tensor(guid={self.guid}, dims={self.dims}, {self.data_type.name})"


class Layer:
    """Deferred op record built by FFModel API calls (reference: layer.h:10).

    `params` is the op's hashable params dataclass (the reference uses a
    key-value property bag, layer.h:40-60 get/set_int_property)."""

    def __init__(
        self,
        op_type: OperatorType,
        params,
        inputs: List[Tensor],
        name: str = "",
    ):
        self.guid: int = next(_guid)
        self.op_type = op_type
        self.params = params
        self.inputs: List[Tensor] = list(inputs)
        self.outputs: List[Tensor] = []
        self.weights: List[Tensor] = []
        self.name = name or f"{op_type.name.lower()}_{self.guid}"
        # per-weight initializer overrides: weight name -> Initializer
        self.initializers: Dict[str, object] = {}
        # the loop region the layer belongs to (FFModel.loop), or None
        self.loop = None

    def get_output_tensor(self, idx: int = 0) -> Tensor:
        return self.outputs[idx]

    # reference: flexflow_cffi.py Op.get_input_tensor / get_weight_tensor /
    # get_bias_tensor (weights[0] is the kernel, weights[1] the bias)
    def get_input_tensor(self, idx: int = 0) -> Tensor:
        return self.inputs[idx]

    def get_weight_tensor(self, idx: int = 0) -> Tensor:
        return self.weights[idx]

    def get_bias_tensor(self) -> Tensor:
        assert len(self.weights) > 1, f"layer {self.name} has no bias weight"
        return self.weights[1]

    def __repr__(self):
        return f"Layer({self.name}, {self.op_type.name})"
