"""Shared helpers for op forwards."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ff_types import ActiMode


def apply_activation(mode: ActiMode, x):
    """Fused activations (reference: ops use cudnnActivationForward; see
    linear_kernels.cu / conv_2d_kernels.cu). XLA fuses these into the matmul
    epilogue automatically."""
    if mode == ActiMode.AC_MODE_NONE:
        return x
    if mode == ActiMode.AC_MODE_RELU:
        return jax.nn.relu(x)
    if mode == ActiMode.AC_MODE_SIGMOID:
        return jax.nn.sigmoid(x)
    if mode == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if mode == ActiMode.AC_MODE_GELU:
        return jax.nn.gelu(x)
    if mode == ActiMode.AC_MODE_SILU:
        return jax.nn.silu(x)
    if mode == ActiMode.AC_MODE_RELU2:
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {mode}")
