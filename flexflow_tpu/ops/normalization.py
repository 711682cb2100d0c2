"""BatchNorm and LayerNorm operators.

TPU-native equivalents of reference src/ops/batch_norm.cc (cuDNN BN with
running stats) and src/ops/layer_norm.cc (custom CUDA kernels, 446 LoC .cu).
Both are expressed in jnp; XLA fuses the mean/var reductions with the
normalize+scale epilogue, which is what the hand-written CUDA kernels do.

BatchNorm running stats: the reference mutates running_mean/var inside the
fwd task. In our functional design, running stats live in the model's
non-trainable state and the op returns updated stats through ctx.state_out.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ff_types import DataType, OperatorType
from .registry import WeightSpec, register_op


@dataclasses.dataclass(frozen=True)
class BatchNormParams:
    """reference: src/ops/batch_norm.cc ctor"""

    relu: bool = True
    momentum: float = 0.9
    eps: float = 1e-5


def _bn_infer(params, in_shapes, in_dtypes):
    return [in_shapes[0]], [in_dtypes[0]]


def _bn_weights(params, in_shapes, in_dtypes):
    c = in_shapes[0][1]  # NCHW
    return [
        WeightSpec("scale", (c,), in_dtypes[0], "one"),
        WeightSpec("bias", (c,), in_dtypes[0], "zero"),
    ]


def _bn_normalize(params, weights, x, mean, var):
    bshape = [1, -1] + [1] * (x.ndim - 2)
    xf = x.astype(jnp.float32)
    y = (xf - mean.reshape(bshape)) / jnp.sqrt(var.reshape(bshape) + params.eps)
    y = y * weights["scale"].astype(jnp.float32).reshape(bshape) + \
        weights["bias"].astype(jnp.float32).reshape(bshape)
    y = y.astype(x.dtype)
    if params.relu:
        y = jnp.maximum(y, 0)
    return y


def _bn_batch_stats(x):
    # Normalize over (N, H, W) per channel — NCHW axes (0, 2, 3)
    axes = (0, 2, 3) if x.ndim == 4 else tuple(i for i in range(x.ndim) if i != 1)
    xf = x.astype(jnp.float32)
    return jnp.mean(xf, axis=axes), jnp.var(xf, axis=axes)


def _bn_forward(params: BatchNormParams, weights, inputs, ctx):
    (x,) = inputs
    mean, var = _bn_batch_stats(x)
    return [_bn_normalize(params, weights, x, mean, var)]


def _bn_state(params, in_shapes, in_dtypes):
    c = in_shapes[0][1]  # NCHW
    return [
        WeightSpec("running_mean", (c,), DataType.DT_FLOAT, "zero"),
        WeightSpec("running_var", (c,), DataType.DT_FLOAT, "one"),
    ]


def _bn_forward_stateful(params: BatchNormParams, weights, state, inputs, ctx):
    """Training: batch stats normalize, running stats update with
    `momentum` (reference: cuDNN BN's exponentialAverageFactor,
    batch_norm.cu). Inference: the RUNNING stats normalize — the piece the
    stateless forward can't do."""
    (x,) = inputs
    if not state:  # stateless caller (cost measurement, decode) — batch stats
        return _bn_forward(params, weights, inputs, ctx), {}
    if ctx.training:
        mean, var = _bn_batch_stats(x)
        m = params.momentum
        new_state = {
            "running_mean": m * state["running_mean"] + (1 - m) * mean,
            "running_var": m * state["running_var"] + (1 - m) * var,
        }
        return [_bn_normalize(params, weights, x, mean, var)], new_state
    return [
        _bn_normalize(params, weights, x, state["running_mean"],
                      state["running_var"])
    ], state


register_op(
    OperatorType.OP_BATCHNORM,
    "BatchNorm",
    infer=_bn_infer,
    weights=_bn_weights,
    forward=_bn_forward,
    state_spec=_bn_state,
    forward_stateful=_bn_forward_stateful,
)


@dataclasses.dataclass(frozen=True)
class LayerNormParams:
    """reference: include/flexflow/ops/layer_norm_params.h"""

    axes: Tuple[int, ...] = (-1,)
    elementwise_affine: bool = True
    eps: float = 1e-5
    # RMS norm (Zhang & Sennrich 2019): no mean is taken off and there is
    # no bias, y = x / sqrt(mean(x^2) + eps) * scale
    rms: bool = False


def _ln_infer(params, in_shapes, in_dtypes):
    return [in_shapes[0]], [in_dtypes[0]]


def _ln_weights(params: LayerNormParams, in_shapes, in_dtypes):
    if not params.elementwise_affine:
        return []
    s = in_shapes[0]
    norm_shape = tuple(s[a % len(s)] for a in params.axes)
    scale = WeightSpec("scale", norm_shape, in_dtypes[0], "one")
    if params.rms:
        return [scale]
    return [scale, WeightSpec("bias", norm_shape, in_dtypes[0], "zero")]


def rms_normalize(x, scale, eps):
    """x / sqrt(mean(x^2) + eps) * scale over the last axis, in float32;
    the attention ops' q/k norms and the gated output norm share it."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _ln_forward(params: LayerNormParams, weights, inputs, ctx):
    (x,) = inputs
    axes = tuple(a % x.ndim for a in params.axes)
    xf = x.astype(jnp.float32)
    if params.rms:
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=axes, keepdims=True) + params.eps)
    else:
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.var(xf, axis=axes, keepdims=True)
        y = (xf - mean) / jnp.sqrt(var + params.eps)
    if params.elementwise_affine:
        bshape = [x.shape[a] if a in axes else 1 for a in range(x.ndim)]
        y = y * weights["scale"].astype(jnp.float32).reshape(bshape)
        if not params.rms:
            y = y + weights["bias"].astype(jnp.float32).reshape(bshape)
    return [y.astype(x.dtype)]


def _ln_seq_pointwise(params, op):
    """Safe on a single decoded token only while the normalized axes
    exclude the sequence axis (axis 1 of a rank>=3 tensor)."""
    nd = len(op.inputs[0].material_shape())
    return nd < 3 or all(a % nd != 1 for a in params.axes)


register_op(
    OperatorType.OP_LAYERNORM,
    "LayerNorm",
    infer=_ln_infer,
    weights=_ln_weights,
    forward=_ln_forward,
    seq_pointwise=_ln_seq_pointwise,
)
