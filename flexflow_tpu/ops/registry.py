"""Operator definition registry.

The reference implements each operator as a C++ class with Legion task
plumbing (src/ops/*.cc) plus CUDA kernels (src/ops/kernels/*.cu). On TPU the
per-device kernel IS the XLA program, so an operator definition reduces to:

  * a hashable Params dataclass        (reference: include/flexflow/ops/*_params.h)
  * shape inference                    (reference: each op's ctor computing output dims)
  * weight specs                       (reference: each op's weight allocation)
  * a pure forward function in jnp/lax (reference: src/ops/kernels/*.cu)

Backward never needs hand-writing: jax.grad differentiates the whole train
step (the reference writes a backward_task per op by hand).

`measure_operator_cost` parity lives in search/cost_model.py, which times or
analytically costs these same forward fns.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..ff_types import DataType, OperatorType


@dataclasses.dataclass
class WeightSpec:
    """Declares one weight tensor of an op."""

    name: str
    shape: Tuple[int, ...]
    dtype: DataType
    initializer: str = "glorot_uniform"  # default per reference (model.cc dense/conv)
    # Which logical op-dim each weight dim is tied to, for sharding propagation.
    # e.g. Linear kernel (in,out): out follows the op's channel-parallel degree.
    parallel_dim_tags: Tuple[str, ...] = ()


@dataclasses.dataclass
class OpDef:
    op_type: OperatorType
    name: str
    # (params, input_shapes: List[Tuple[int,...]], input_dtypes) -> (out_shapes, out_dtypes)
    infer: Callable
    # (params, input_shapes, input_dtypes) -> List[WeightSpec]
    weights: Callable
    # (params, weights: Dict[str, Array], inputs: List[Array], ctx: FwdCtx) -> List[Array]
    forward: Callable
    # Number of inputs the op consumes (-1 = variadic)
    num_inputs: int = 1
    # Incremental-decode support (executor.build_decode / serving KV cache):
    # seq_pointwise declares the forward treats the sequence dim as a
    # batch dim (dense/elementwise/...), so running it on the newest
    # token's slice is exact. Either a bool, or a callable
    # (params, op) -> bool for ops whose params decide it (softmax over
    # the seq axis is NOT pointwise; over features it is). Ops that MIX
    # positions instead provide forward_decode(params, weights, inputs,
    # ctx, state, t, valid=None) -> (outs, state') — attention appends
    # K/V there — and say what state that is: init_decode_state(params,
    # batch, max_len, dtype) makes it empty, slot axis leading, and
    # decode_section names the section of the decode caches it lives in
    # (parallel/decode.py SLOT_SECTIONS), under the op's name.
    seq_pointwise: object = False
    forward_decode: Optional[Callable] = None
    init_decode_state: Optional[Callable] = None
    decode_section: Optional[str] = None
    # The same op where every input but the first is static (cross-
    # attention over an encoder's output): init_decode_static(params,
    # weights, static_inputs, ctx) -> state, computed once a sequence;
    # forward_decode_static(params, weights, live_input, ctx, state) ->
    # outs reads it and appends nothing.
    init_decode_static: Optional[Callable] = None
    forward_decode_static: Optional[Callable] = None
    # Names of the integer counters the op's forward reports through
    # FwdCtx.count while a decode step is traced (parallel/decode.py keeps
    # them in the caches' "counters" section, one scalar a name for the
    # whole step; a name that ends in "_max" is a maximum, any other a sum).
    # Either the names, or a callable params -> names for an op whose
    # params decide what it counts (`counters_of`).
    decode_counters: object = ()
    # The same for what it counts only in a block of several positions (a
    # prefill): kept in the caches' "prefill_counters" section, which a
    # decode iteration does not fetch (`counters_of(params, "prefill")`).
    prefill_counters: object = ()
    # Cross-batch mutable buffers (reference: cuDNN BN running stats,
    # Cache op's CACHE_UPDATE_TASK). state_spec declares them like
    # weights; forward_stateful(params, weights, state, inputs, ctx) ->
    # (outs, new_state) consumes/produces them. The executor threads the
    # collection through the train step (functional update) and passes it
    # read-only to eval/forward.
    state_spec: Optional[Callable] = None
    forward_stateful: Optional[Callable] = None
    # Whether forward_decode takes a state stacked over a loop region's
    # steps (FFModel.loop): every leaf with the steps on its axis 1, after
    # the slot axis, and FwdCtx.loop_step the step whose slice the call
    # reads and writes in place. A stateful op without it cannot decode
    # inside a region.
    loop_state: bool = False

    def counters_of(self, params, which: str = "decode") -> Tuple[str, ...]:
        names = self.decode_counters if which == "decode" \
            else self.prefill_counters
        return tuple(names(params) if callable(names) else names)

    def is_seq_pointwise(self, params, op) -> bool:
        if callable(self.seq_pointwise):
            return bool(self.seq_pointwise(params, op))
        return bool(self.seq_pointwise)


_REGISTRY: Dict[OperatorType, OpDef] = {}


def register_op(
    op_type: OperatorType,
    name: str,
    *,
    infer: Callable,
    forward: Callable,
    weights: Optional[Callable] = None,
    num_inputs: int = 1,
    seq_pointwise: object = False,
    forward_decode: Optional[Callable] = None,
    init_decode_state: Optional[Callable] = None,
    decode_section: Optional[str] = None,
    init_decode_static: Optional[Callable] = None,
    forward_decode_static: Optional[Callable] = None,
    decode_counters: object = (),
    prefill_counters: object = (),
    state_spec: Optional[Callable] = None,
    forward_stateful: Optional[Callable] = None,
    loop_state: bool = False,
) -> OpDef:
    d = OpDef(
        op_type=op_type,
        name=name,
        infer=infer,
        weights=weights or (lambda p, s, dt: []),
        forward=forward,
        num_inputs=num_inputs,
        seq_pointwise=seq_pointwise,
        forward_decode=forward_decode,
        init_decode_state=init_decode_state,
        decode_section=decode_section,
        init_decode_static=init_decode_static,
        forward_decode_static=forward_decode_static,
        decode_counters=decode_counters if callable(decode_counters)
        else tuple(decode_counters),
        prefill_counters=prefill_counters if callable(prefill_counters)
        else tuple(prefill_counters),
        state_spec=state_spec,
        forward_stateful=forward_stateful,
        loop_state=loop_state,
    )
    _REGISTRY[op_type] = d
    return d


def get_op_def(op_type: OperatorType) -> OpDef:
    if op_type not in _REGISTRY:
        raise NotImplementedError(f"operator {op_type.name} not registered")
    return _REGISTRY[op_type]


def has_op_def(op_type: OperatorType) -> bool:
    return op_type in _REGISTRY


def all_op_types() -> List[OperatorType]:
    return list(_REGISTRY)


@dataclasses.dataclass
class FwdCtx:
    """Per-call context threaded through op forwards."""

    training: bool = True
    rng: Optional[object] = None  # jax PRNGKey for dropout etc.
    seq_length: int = -1  # FFIterationConfig.seq_length (reference: config.h:162)
    compute_dtype: Optional[object] = None  # bf16 autocast target
    # Differentiable auxiliary losses collected during the walk (MoE load
    # balancing — reference folds these into gate grads in hand-written
    # backwards, aggregate.cc; we add them to the scalar loss instead).
    aux_losses: Optional[list] = None
    # Devices in the executing mesh. Ops trace with GLOBAL shapes; kernels
    # that budget per-chip memory (attention dispatch) divide by this,
    # since batch/head axes shard across the mesh.
    n_devices: int = 1
    # The executing jax.sharding.Mesh, for ops that drop into shard_map
    # (pipeline block stack, ring attention).
    mesh: Optional[object] = None
    # The PCG op's name, for per-layer diagnostics (the attention
    # fallback warn-once/metric keys on it). "" when the caller has no
    # layer identity (raw op-def invocations in tests).
    op_name: str = ""
    # Integer counters of one traced decode step, by name (OpDef.
    # decode_counters); None wherever nobody collects them.
    counters: Optional[dict] = None
    # The step of a loop region a decode call runs (a traced int32), whose
    # slice of a stacked state it reads and writes (OpDef.loop_state); None
    # outside a region.
    loop_step: Optional[object] = None

    def add_aux_loss(self, value):
        if self.aux_losses is not None:
            self.aux_losses.append(value)

    def count(self, name: str, value):
        """Add `value` (a traced integer scalar) to the step's counter
        `name`; a name that ends in "_max" keeps the largest."""
        if self.counters is None:
            return
        if name not in self.counters:
            self.counters[name] = value
        elif name.endswith("_max"):
            import jax.numpy as jnp

            self.counters[name] = jnp.maximum(self.counters[name], value)
        else:
            self.counters[name] = self.counters[name] + value


def ensure_ops_loaded():
    """Import all op modules so their register_op calls run."""
    from . import (  # noqa: F401
        attention,
        batch_matmul,
        conv2d,
        dropout,
        elementwise,
        embedding,
        fused,
        linear,
        linear_attention,
        lstm,
        moe,
        normalization,
        pipeline,
        pool2d,
        reduce,
        softmax,
        state_space,
        tensor_ops,
    )
