"""Element-wise unary, binary, and scalar operators.

TPU-native equivalent of reference src/ops/element_unary.cc (720 LoC),
element_binary.cc (812 LoC) and their CUDA kernels. On TPU each of these is a
single VPU-mapped jnp op that XLA fuses into neighbors, so the whole family
collapses into a dispatch table. Broadcast semantics follow the reference's
element_binary broadcast support.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ff_types import OperatorType
from .registry import register_op

# ---------------------------------------------------------------------------
# Unary (reference: element_unary.cc; OP list ffconst.h)
# ---------------------------------------------------------------------------

_UNARY_FNS = {
    OperatorType.OP_EXP: jnp.exp,
    OperatorType.OP_LOG: jnp.log,
    OperatorType.OP_RELU: jax.nn.relu,
    OperatorType.OP_SIGMOID: jax.nn.sigmoid,
    OperatorType.OP_TANH: jnp.tanh,
    OperatorType.OP_ELU: jax.nn.elu,
    OperatorType.OP_GELU: jax.nn.gelu,
    OperatorType.OP_SILU: jax.nn.silu,
    OperatorType.OP_RSQRT: lambda x: jax.lax.rsqrt(x),
    OperatorType.OP_SQRT: jnp.sqrt,
    OperatorType.OP_SIN: jnp.sin,
    OperatorType.OP_COS: jnp.cos,
    OperatorType.OP_IDENTITY: lambda x: x,
    OperatorType.OP_CEIL: jnp.ceil,
    OperatorType.OP_ROUND: jnp.round,
    OperatorType.OP_LOGICAL_NOT: jnp.logical_not,
    OperatorType.OP_LEAKYRELU: lambda x: jax.nn.leaky_relu(x, 0.01),
}


@dataclasses.dataclass(frozen=True)
class ElementUnaryParams:
    """reference: include/flexflow/ops/element_unary_params.h"""

    op_type: OperatorType
    inplace: bool = False
    scalar: float = 0.0


def _unary_infer(params, in_shapes, in_dtypes):
    return [in_shapes[0]], [in_dtypes[0]]


def _unary_forward(params: ElementUnaryParams, weights, inputs, ctx):
    (x,) = inputs
    t = params.op_type
    if t == OperatorType.OP_POW:
        return [jnp.power(x, params.scalar)]
    if t == OperatorType.OP_SCALAR_MULTIPLY:
        return [x * params.scalar]
    if t == OperatorType.OP_SCALAR_ADD:
        return [x + params.scalar]
    if t == OperatorType.OP_SCALAR_SUB:
        return [x - params.scalar]
    if t == OperatorType.OP_SCALAR_TRUE_DIV:
        return [x / params.scalar]
    if t == OperatorType.OP_SCALAR_FLOOR_DIV:
        return [jnp.floor_divide(x, params.scalar)]
    return [_UNARY_FNS[t](x)]


for _t in list(_UNARY_FNS) + [
    OperatorType.OP_POW,
    OperatorType.OP_SCALAR_MULTIPLY,
    OperatorType.OP_SCALAR_ADD,
    OperatorType.OP_SCALAR_SUB,
    OperatorType.OP_SCALAR_TRUE_DIV,
    OperatorType.OP_SCALAR_FLOOR_DIV,
]:
    register_op(_t, f"ElementUnary_{_t.name}", infer=_unary_infer,
                forward=_unary_forward, seq_pointwise=True)

# ---------------------------------------------------------------------------
# Binary (reference: element_binary.cc with broadcast support)
# ---------------------------------------------------------------------------

_BINARY_FNS = {
    OperatorType.OP_EW_ADD: jnp.add,
    OperatorType.OP_EW_SUB: jnp.subtract,
    OperatorType.OP_EW_MUL: jnp.multiply,
    OperatorType.OP_EW_DIV: jnp.divide,
    OperatorType.OP_EW_MAX: jnp.maximum,
    OperatorType.OP_EW_MIN: jnp.minimum,
    OperatorType.OP_EW_EQUAL: jnp.equal,
    OperatorType.OP_EW_GREATER: jnp.greater,
    OperatorType.OP_EW_LESS: jnp.less,
}


@dataclasses.dataclass(frozen=True)
class ElementBinaryParams:
    """reference: include/flexflow/ops/element_binary_params.h"""

    op_type: OperatorType
    inplace_a: bool = False


def _binary_infer(params, in_shapes, in_dtypes):
    a, b = in_shapes
    out = np.broadcast_shapes(tuple(a), tuple(b))
    dt = in_dtypes[0]
    if params.op_type in (
        OperatorType.OP_EW_EQUAL,
        OperatorType.OP_EW_GREATER,
        OperatorType.OP_EW_LESS,
    ):
        from ..ff_types import DataType

        dt = DataType.DT_BOOLEAN
    return [tuple(out)], [dt]


def _binary_forward(params: ElementBinaryParams, weights, inputs, ctx):
    a, b = inputs
    return [_BINARY_FNS[params.op_type](a, b)]


for _t in _BINARY_FNS:
    register_op(
        _t, f"ElementBinary_{_t.name}", infer=_binary_infer, forward=_binary_forward,
        num_inputs=2, seq_pointwise=True,
    )


# -- PReLU (learnable per-channel negative slope; ONNX frontend op) ---------
import dataclasses as _dc

from .registry import WeightSpec


@_dc.dataclass(frozen=True)
class PReluParams:
    pass


def _prelu_channels(shape):
    # channel dim: NCHW conv layout for 4-D (conv2d.py is NCHW), else last
    return shape[1] if len(shape) == 4 else shape[-1]


def _prelu_weights(params, in_shapes, in_dtypes):
    (s,) = in_shapes
    return [WeightSpec("alpha", (_prelu_channels(s),), in_dtypes[0], "constant:0.25")]


def _prelu_forward(params, weights, inputs, ctx):
    (x,) = inputs
    a = weights["alpha"].astype(x.dtype)
    if x.ndim == 4:  # broadcast per-channel over NCHW spatial dims
        a = a.reshape(1, -1, 1, 1)
    return [jnp.where(x >= 0, x, a * x)]


register_op(
    OperatorType.OP_PRELU,
    "PReLU",
    infer=lambda p, s, dt: ([s[0]], [dt[0]]),
    weights=_prelu_weights,
    forward=_prelu_forward,
)
