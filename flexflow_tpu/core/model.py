"""FFModel: the user-facing model container and training driver.

TPU-native equivalent of the reference FFModel (include/flexflow/model.h:326,
src/runtime/model.cc:1160-3700) and its Python mirror
(python/flexflow/core/flexflow_cffi.py:883). API-call-for-API-call compatible:
each op method creates a deferred Layer; `compile()` lowers Layer graph → PCG,
applies/searches a parallelization strategy, and builds the jitted SPMD train
step; `fit()` runs the training loop (reference: flexflow_cffi.py:2058-2102
begin_trace → next_batch → forward → zero_gradients → backward → update →
end_trace — here one fused jitted step per iteration).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..config import FFConfig, FFIterationConfig
from ..ff_types import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType,
    PoolType,
    RegularizerMode,
    to_data_type,
)
from ..ops.attention import MultiHeadAttentionParams
from ..ops.batch_matmul import BatchMatmulParams
from ..ops.conv2d import Conv2DParams
from ..ops.dropout import DropoutParams
from ..ops.elementwise import ElementBinaryParams, ElementUnaryParams
from ..ops.embedding import EmbeddingParams
from ..ops.linear import LinearParams
from ..ops.moe import AggregateParams, AggregateSpecParams, CacheParams, GroupByParams
from ..ops.normalization import BatchNormParams, LayerNormParams
from ..ops.pool2d import Pool2DParams
from ..ops.reduce import ReduceParams, TopKParams
from ..ops.registry import get_op_def
from ..ops.softmax import SoftmaxParams
from ..ops.tensor_ops import (
    CastParams,
    ConcatParams,
    FlatParams,
    GatherParams,
    NoOpParams,
    PadParams,
    ReshapeParams,
    ReverseParams,
    SliceParams,
    SplitParams,
    TransposeParams,
)
from ..parallel import strategies
from ..parallel.executor import PCGExecutor, TrainState
from ..parallel.mesh import build_mesh
from ..pcg.lowering import layers_to_pcg
from .losses import to_loss_type
from .metrics import Metrics, PerfMetrics
from .optimizers import AdamOptimizer, Optimizer, SGDOptimizer
from .tensor import Layer, Tensor


_SHAPE_ONLY_OPS = (OperatorType.OP_RESHAPE, OperatorType.OP_FLAT,
                   OperatorType.OP_NOOP, OperatorType.OP_IDENTITY)


def _resolve_value_tail(op):
    """The op that produced an output's VALUES: unpack --fusion chains and
    skip shape-only steps."""
    steps = (
        [(s[0], s[1]) for s in op.params.chain]
        if op.op_type == OperatorType.OP_FUSED and op.params.chain
        else [(op.op_type, op.params)]
    )
    for op_type, params in reversed(steps):
        if op_type not in _SHAPE_ONLY_OPS:
            return op_type, params
    return steps[-1]


def _probability_like_tail(op_type, params) -> bool:
    """Does this value-producing tail op emit probabilities (in [0, 1])?"""
    if op_type in (OperatorType.OP_SOFTMAX, OperatorType.OP_SIGMOID):
        return True
    # fused activation inside the op (DLRM's final dense has
    # AC_MODE_SIGMOID, dlrm.cc create_mlp) keeps outputs in (0, 1)
    act = getattr(params, "activation", None)
    return act == ActiMode.AC_MODE_SIGMOID


def _fetch_global(v) -> np.ndarray:
    """Device value -> host numpy, multi-host safe: an array whose shards
    live on other processes can't be fetched directly (jax refuses), so
    allgather it first (runtime/distributed.py multi-host path — every
    process gets the full value, like the reference's CPU
    UPDATE_METRICS_TASK folding a future chain)."""
    if isinstance(v, jax.Array) and not v.is_fully_addressable:
        from jax.experimental import multihost_utils

        v = multihost_utils.process_allgather(v, tiled=True)
    return np.asarray(v)


class FFModel:
    """reference: model.h:326 FFModel / flexflow_cffi.py:883."""

    def __init__(self, ffconfig: Optional[FFConfig] = None):
        self.config = ffconfig or FFConfig()
        self.layers: List[Layer] = []
        self.input_tensors: List[Tensor] = []
        self.label_tensor: Optional[Tensor] = None
        self.optimizer: Optional[Optimizer] = None
        self.iter_config = FFIterationConfig()
        # compile products
        self.graph = None
        self.executor: Optional[PCGExecutor] = None
        # decode-objective second strategy (compile_decode): the same
        # layer graph re-searched under CostObjective.DECODE, carried
        # alongside the training strategy (Splitwise/DistServe
        # disaggregation within one model)
        self.decode_graph = None
        self.decode_executor: Optional[PCGExecutor] = None
        self.decode_searched_views: Dict[int, object] = {}
        self.decode_searched_cost: Optional[float] = None
        self.decode_trajectory = None
        self.state: Optional[TrainState] = None
        self.metrics_obj: Optional[Metrics] = None
        self.perf_metrics = PerfMetrics()
        self.loss_type: Optional[LossType] = None
        self.comp_mode = CompMode.COMP_MODE_TRAINING
        self._tensor_map: Dict[int, int] = {}
        self._pt_by_guid: Dict[int, object] = {}
        self._current_batch: Optional[Tuple] = None
        self._last_logits = None
        self._pending_grads = None
        self._dataloaders: List[object] = []
        # Tensor.guid -> scalar fill value OR baked np.ndarray contents
        self._constant_values: Dict[int, Union[float, np.ndarray]] = {}
        self._rng = jax.random.PRNGKey(self.config.seed)
        # the loop region layers are added to (FFModel.loop), or None
        self._loop = None

    # ------------------------------------------------------------------
    # Graph building (reference: FFModel::create_tensor, model.cc)
    # ------------------------------------------------------------------
    def create_tensor(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.DT_FLOAT,
        create_grad: bool = True,
        name: str = "",
    ) -> Tensor:
        t = Tensor(tuple(dims), _to_dt(dtype), create_gradients=create_grad, name=name)
        t._model = self
        self.input_tensors.append(t)
        return t

    def _add_layer(
        self,
        op_type: OperatorType,
        params,
        inputs: List[Tensor],
        name: str = "",
        initializers: Optional[Dict[str, object]] = None,
    ) -> Union[Tensor, List[Tensor]]:
        # deterministic per-model names so checkpoints/strategies match
        # across processes (guid-based names differ run to run)
        if not name:
            name = f"{op_type.name.lower()}_{len(self.layers)}"
        layer = Layer(op_type, params, inputs, name=name)
        if self._loop is not None:
            layer.loop = self._loop.mark
        if initializers:
            layer.initializers.update(
                {k: v for k, v in initializers.items() if v is not None}
            )
        opdef = get_op_def(op_type)
        in_shapes = [t.dims for t in inputs]
        in_dtypes = [t.data_type for t in inputs]
        out_shapes, out_dtypes = opdef.infer(params, in_shapes, in_dtypes)
        for i, (s, dt) in enumerate(zip(out_shapes, out_dtypes)):
            out = Tensor(s, dt, owner_layer=layer, owner_idx=i)
            out._model = self
            layer.outputs.append(out)
        # expose weight tensors for get/set_weights parity
        for spec in opdef.weights(params, in_shapes, in_dtypes):
            wt = Tensor(spec.shape, spec.dtype, owner_layer=layer, name=spec.name)
            wt._model = self
            layer.weights.append(wt)
        self.layers.append(layer)
        if len(layer.outputs) == 1:
            return layer.outputs[0]
        return layer.outputs

    # -- op API (reference: flexflow_cffi.py FFModel methods) ----------
    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        activation: ActiMode = ActiMode.AC_MODE_NONE,
        groups: int = 1,
        use_bias: bool = True,
        shared_op=None,
        kernel_initializer=None,
        bias_initializer=None,
        name: str = "",
    ) -> Tensor:
        p = Conv2DParams(
            out_channels=out_channels,
            kernel_h=kernel_h,
            kernel_w=kernel_w,
            stride_h=stride_h,
            stride_w=stride_w,
            padding_h=padding_h,
            padding_w=padding_w,
            groups=groups,
            use_bias=use_bias,
            activation=_to_acti(activation),
        )
        return self._add_layer(
            OperatorType.OP_CONV2D,
            p,
            [input],
            name,
            {"kernel": kernel_initializer, "bias": bias_initializer},
        )

    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: ActiMode = ActiMode.AC_MODE_NONE,
        use_bias: bool = True,
        datatype: DataType = DataType.DT_FLOAT,
        shared_op=None,
        kernel_initializer=None,
        bias_initializer=None,
        kernel_regularizer=None,
        name: str = "",
    ) -> Tensor:
        reg_type, reg_lambda = _to_regularizer(kernel_regularizer)
        p = LinearParams(
            out_channels=out_dim,
            use_bias=use_bias,
            activation=_to_acti(activation),
            data_type=_to_dt(datatype),
            kernel_reg_lambda=reg_lambda,
            kernel_reg_type=reg_type,
        )
        return self._add_layer(
            OperatorType.OP_LINEAR,
            p,
            [input],
            name,
            {"kernel": kernel_initializer, "bias": bias_initializer},
        )

    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
        dtype: DataType = DataType.DT_FLOAT,
        shared_op=None,
        kernel_initializer=None,
        name: str = "",
    ) -> Tensor:
        p = EmbeddingParams(
            num_entries=num_entries,
            out_channels=out_dim,
            aggr=aggr,
            data_type=_to_dt(dtype),
        )
        return self._add_layer(
            OperatorType.OP_EMBEDDING, p, [input], name, {"weight": kernel_initializer}
        )

    def pool2d(
        self,
        input: Tensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        pool_type: PoolType = PoolType.POOL_MAX,
        activation: ActiMode = ActiMode.AC_MODE_NONE,
        name: str = "",
    ) -> Tensor:
        p = Pool2DParams(
            kernel_h=kernel_h,
            kernel_w=kernel_w,
            stride_h=stride_h,
            stride_w=stride_w,
            padding_h=padding_h,
            padding_w=padding_w,
            pool_type=pool_type,
            activation=_to_acti(activation),
        )
        return self._add_layer(OperatorType.OP_POOL2D, p, [input], name)

    def batch_norm(self, input: Tensor, relu: bool = True, name: str = "") -> Tensor:
        return self._add_layer(
            OperatorType.OP_BATCHNORM, BatchNormParams(relu=relu), [input], name
        )

    def layer_norm(
        self,
        input: Tensor,
        axes: Sequence[int] = (-1,),
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: str = "",
    ) -> Tensor:
        p = LayerNormParams(
            axes=tuple(axes), elementwise_affine=elementwise_affine, eps=eps
        )
        return self._add_layer(OperatorType.OP_LAYERNORM, p, [input], name)

    def rms_norm(self, input: Tensor, eps: float = 1e-6,
                 name: str = "") -> Tensor:
        """RMS norm over the last axis with a learned scale: the layer
        norm op without the mean and the bias."""
        p = LayerNormParams(axes=(-1,), elementwise_affine=True, eps=eps,
                            rms=True)
        return self._add_layer(OperatorType.OP_LAYERNORM, p, [input], name)

    def batch_matmul(
        self,
        A: Tensor,
        B: Tensor,
        a_seq_length_dim: int = -1,
        b_seq_length_dim: int = -1,
        name: str = "",
    ) -> Tensor:
        p = BatchMatmulParams(a_seq_length_dim, b_seq_length_dim)
        return self._add_layer(OperatorType.OP_BATCHMATMUL, p, [A, B], name)

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = True,
        add_bias_kv: bool = False,
        add_zero_attn: bool = False,
        kernel_initializer=None,
        causal: bool = False,
        qk_norm: bool = False,
        qk_norm_eps: float = 1e-6,
        num_kv_heads: int = 0,
        rope=None,
        window: int = 0,
        head_gate: bool = False,
        name: str = "",
    ) -> Tensor:
        """`num_kv_heads` (0 = `num_heads`): grouped-query attention, query
        head i reading key-value head i // (num_heads / num_kv_heads); the
        decode cache holds the key-value heads alone. `rope` (a
        `RotaryParams`, or a dict of its fields: theta, dim, scaling,
        factor, ...): q and k rotated by position. `window` (causal ops):
        query i sees keys i - window < j <= i, and the decode cache is a
        ring of the window's positions. `head_gate`: head h's output times
        sigmoid(x wg)[h] (weight `wg`, embed x heads)."""
        from ..ops.attention import RotaryParams

        if isinstance(rope, dict):
            rope = RotaryParams(**rope)
        p = MultiHeadAttentionParams(
            embed_dim=embed_dim,
            num_heads=num_heads,
            kdim=kdim,
            vdim=vdim,
            dropout=dropout,
            bias=bias,
            add_bias_kv=add_bias_kv,
            add_zero_attn=add_zero_attn,
            causal=causal,
            qk_norm=qk_norm,
            qk_norm_eps=qk_norm_eps,
            num_kv_heads=0 if num_kv_heads == num_heads else num_kv_heads,
            rope=rope,
            window=window,
            head_gate=head_gate,
        )
        inits = (
            {k: kernel_initializer for k in ("wq", "wk", "wv", "wo", "wg")}
            if kernel_initializer
            else None
        )
        return self._add_layer(
            OperatorType.OP_MULTIHEAD_ATTENTION, p, [query, key, value], name, inits
        )

    def transformer_blocks(
        self,
        input: Tensor,
        hidden_size: int,
        num_heads: int,
        num_layers: int,
        name: str = "",
    ) -> Tensor:
        """`num_layers` benchmark encoder blocks (MHA + 2 dense, the
        reference's transformer.cc:33-45 block) as ONE stacked op whose
        layer dim shards over the pipe mesh axis — pipeline parallelism as
        a sharding (TPU addition; reference's OP_PIPELINE is enum-only).
        Stage count comes from config.pipeline_parallel_degree."""
        from ..ops.pipeline import BlockStackParams

        p = BlockStackParams(
            hidden=hidden_size,
            num_heads=num_heads,
            num_layers=num_layers,
            num_stages=max(1, self.config.pipeline_parallel_degree),
            num_microbatches=self.config.num_microbatches,
        )
        return self._add_layer(OperatorType.OP_BLOCK_STACK, p, [input], name)

    @contextlib.contextmanager
    def loop(self, steps: int, name: str = "loop"):
        """A loop region: the layers added inside run `steps` times over ONE
        copy of their weights, each step on what the last one gave. In it,
        `enter(x)` takes the tensor the first step reads and returns the one
        the body reads (every later step reads the exit there); `exit(y)`
        names the body's result, which after the region holds the last
        step's. Lowered as one loop body in training, forward, prefill and
        decode; an op that keeps decode state keeps one copy a step
        (docs/models.md).

            with model.loop(4, name="ut") as ut:
                h = ut.enter(x)
                h = block(h)
                y = ut.exit(h)
        """
        if self._loop is not None:
            raise ValueError(f"loop {name!r} inside loop "
                             f"{self._loop.mark.name!r}: regions do not nest")
        if int(steps) < 1:
            raise ValueError(f"loop {name!r}: steps must be >= 1, got {steps}")
        if any(layer.loop is not None and layer.loop.name == name
               for layer in self.layers):
            raise ValueError(f"a loop named {name!r} exists already")
        region = _LoopBuilder(self, int(steps), name)
        self._loop = region
        try:
            yield region
        finally:
            self._loop = None
        region.close()

    # elementwise binary
    def _binary(self, t: OperatorType, x: Tensor, y: Tensor, name: str) -> Tensor:
        return self._add_layer(t, ElementBinaryParams(op_type=t), [x, y], name)

    def add(self, x, y, inplace_a=False, name=""):
        return self._binary(OperatorType.OP_EW_ADD, x, y, name)

    def subtract(self, x, y, inplace_a=False, name=""):
        return self._binary(OperatorType.OP_EW_SUB, x, y, name)

    def multiply(self, x, y, inplace_a=False, name=""):
        return self._binary(OperatorType.OP_EW_MUL, x, y, name)

    def divide(self, x, y, inplace_a=False, name=""):
        return self._binary(OperatorType.OP_EW_DIV, x, y, name)

    def max(self, x, y, inplace_a=False, name=""):
        return self._binary(OperatorType.OP_EW_MAX, x, y, name)

    def min(self, x, y, inplace_a=False, name=""):
        return self._binary(OperatorType.OP_EW_MIN, x, y, name)

    # elementwise unary
    def _unary(self, t: OperatorType, x: Tensor, name: str, scalar=0.0, inplace=False):
        p = ElementUnaryParams(op_type=t, inplace=inplace, scalar=scalar)
        return self._add_layer(t, p, [x], name)

    def exp(self, x, name=""):
        return self._unary(OperatorType.OP_EXP, x, name)

    def log(self, x, name=""):
        return self._unary(OperatorType.OP_LOG, x, name)

    def relu(self, x, inplace=True, name=""):
        return self._unary(OperatorType.OP_RELU, x, name, inplace=inplace)

    def sigmoid(self, x, name=""):
        return self._unary(OperatorType.OP_SIGMOID, x, name)

    def tanh(self, x, name=""):
        return self._unary(OperatorType.OP_TANH, x, name)

    def elu(self, x, inplace=True, name=""):
        return self._unary(OperatorType.OP_ELU, x, name, inplace=inplace)

    def gelu(self, x, name=""):
        return self._unary(OperatorType.OP_GELU, x, name)

    def silu(self, x, name=""):
        return self._unary(OperatorType.OP_SILU, x, name)

    def identity(self, x, name=""):
        return self._unary(OperatorType.OP_IDENTITY, x, name)

    def rsqrt(self, x, name=""):
        return self._unary(OperatorType.OP_RSQRT, x, name)

    def sqrt(self, x, name=""):
        return self._unary(OperatorType.OP_SQRT, x, name)

    def sin(self, x, name=""):
        return self._unary(OperatorType.OP_SIN, x, name)

    def cos(self, x, name=""):
        return self._unary(OperatorType.OP_COS, x, name)

    def pow(self, x, exponent: float, name=""):
        return self._unary(OperatorType.OP_POW, x, name, scalar=exponent)

    def scalar_multiply(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OperatorType.OP_SCALAR_MULTIPLY, x, name, scalar=scalar)

    def scalar_add(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OperatorType.OP_SCALAR_ADD, x, name, scalar=scalar)

    def scalar_sub(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OperatorType.OP_SCALAR_SUB, x, name, scalar=scalar)

    def scalar_true_divide(self, x, scalar: float, inplace=True, name=""):
        return self._unary(OperatorType.OP_SCALAR_TRUE_DIV, x, name, scalar=scalar)

    # shape ops
    def concat(self, tensors: List[Tensor], axis: int, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_CONCAT, ConcatParams(axis=axis), list(tensors), name
        )

    def split(self, input: Tensor, sizes, axis: int, name="") -> List[Tensor]:
        if isinstance(sizes, int):
            assert input.dims[axis] % sizes == 0, (
                f"split: dim {input.dims[axis]} not divisible into {sizes} parts"
            )
            sizes = [input.dims[axis] // sizes] * sizes
        assert sum(sizes) == input.dims[axis], (
            f"split sizes {sizes} don't sum to dim {input.dims[axis]}"
        )
        out = self._add_layer(
            OperatorType.OP_SPLIT, SplitParams(tuple(sizes), axis), [input], name
        )
        return out if isinstance(out, list) else [out]

    def flat(self, input: Tensor, name="") -> Tensor:
        return self._add_layer(OperatorType.OP_FLAT, FlatParams(), [input], name)

    def softmax(self, input: Tensor, axis: int = -1, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_SOFTMAX, SoftmaxParams(dim=axis), [input], name
        )

    def reshape(self, input: Tensor, shape: Sequence[int], name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_RESHAPE, ReshapeParams(tuple(shape)), [input], name
        )

    def transpose(self, input: Tensor, perm: Sequence[int], name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_TRANSPOSE, TransposeParams(tuple(perm)), [input], name
        )

    def reverse(self, input: Tensor, axis: int, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_REVERSE, ReverseParams(axis=axis), [input], name
        )

    def cast(self, input: Tensor, dtype: DataType, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_CAST, CastParams(dtype=_to_dt(dtype)), [input], name
        )

    def squeeze(self, input: Tensor, axes=(), name="") -> Tensor:
        from ..ops.tensor_ops import SqueezeParams

        return self._add_layer(
            OperatorType.OP_SQUEEZE, SqueezeParams(tuple(axes)), [input], name
        )

    def unsqueeze(self, input: Tensor, axes, name="") -> Tensor:
        from ..ops.tensor_ops import UnsqueezeParams

        return self._add_layer(
            OperatorType.OP_UNSQUEEZE, UnsqueezeParams(tuple(axes)), [input], name
        )

    def where(self, cond: Tensor, x: Tensor, y: Tensor, name="") -> Tensor:
        from ..ops.tensor_ops import WhereParams

        return self._add_layer(
            OperatorType.OP_WHERE, WhereParams(), [cond, x, y], name
        )

    def resize(self, input: Tensor, out_shape, name="") -> Tensor:
        from ..ops.tensor_ops import ResizeParams

        return self._add_layer(
            OperatorType.OP_RESIZE, ResizeParams(tuple(out_shape)), [input], name
        )

    def prelu(self, input: Tensor, name="") -> Tensor:
        from ..ops.elementwise import PReluParams

        return self._add_layer(OperatorType.OP_PRELU, PReluParams(), [input], name)

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_DROPOUT, DropoutParams(rate=rate, seed=seed), [input], name
        )

    def gather(self, input: Tensor, index: Tensor, dim: int = 0, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_GATHER, GatherParams(dim=dim), [input, index], name
        )

    def reduce_sum(self, input: Tensor, axes, keepdims=False, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_REDUCE_SUM,
            ReduceParams(tuple(axes), keepdims),
            [input],
            name,
        )

    def reduce_mean(self, input: Tensor, axes, keepdims=False, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_REDUCE_MEAN,
            ReduceParams(tuple(axes), keepdims),
            [input],
            name,
        )

    def mean(self, input: Tensor, dims, keepdims=False, name="") -> Tensor:
        return self._add_layer(
            OperatorType.OP_MEAN, ReduceParams(tuple(dims), keepdims), [input], name
        )

    def top_k(self, input: Tensor, k: int, sorted: bool = True, name="") -> List[Tensor]:
        out = self._add_layer(
            OperatorType.OP_TOPK, TopKParams(k=k, sorted=sorted), [input], name
        )
        return out

    def lstm(self, input: Tensor, hidden_size: int, return_sequences: bool = True,
             name="") -> Tensor:
        """reference: nmt/ standalone LSTM (SURVEY §1 row 12), promoted to a
        first-class op here."""
        from ..ops.lstm import LSTMParams

        return self._add_layer(
            OperatorType.OP_LSTM,
            LSTMParams(hidden_size=hidden_size, return_sequences=return_sequences),
            [input],
            name,
        )

    def gated_delta_net(self, input: Tensor, num_heads: int, head_k_dim: int,
                        head_v_dim: int, conv_kernel: int = 4,
                        allow_neg_eigval: bool = True, norm_eps: float = 1e-6,
                        kernel_initializer=None, name="") -> Tensor:
        """Gated delta-rule linear attention over (batch, seq, embed): a
        recurrent state per head in place of keys and values
        (ops/linear_attention.py)."""
        from ..ops.linear_attention import GatedDeltaNetParams

        p = GatedDeltaNetParams(
            embed_dim=input.dims[-1], num_heads=num_heads,
            head_k_dim=head_k_dim, head_v_dim=head_v_dim,
            conv_kernel=conv_kernel, allow_neg_eigval=allow_neg_eigval,
            norm_eps=norm_eps)
        inits = (
            {k: kernel_initializer for k in
             ("wq", "wk", "wv", "wz", "wo", "wb", "wa", "conv")}
            if kernel_initializer else None
        )
        return self._add_layer(OperatorType.OP_GATED_DELTA_NET, p, [input],
                               name, inits)

    def mamba2(self, input: Tensor, num_heads: int, head_dim: int,
               state_size: int, n_groups: int = 1, conv_kernel: int = 4,
               chunk_size: int = 128, norm_eps: float = 1e-5,
               kernel_initializer=None, name="") -> Tensor:
        """Mamba-2 state-space mixer over (batch, seq, embed): `num_heads`
        heads of `head_dim` channels, each with a (head_dim x state_size)
        recurrent state in place of keys and values, B and C shared by
        `n_groups` groups of heads (ops/state_space.py)."""
        from ..ops.state_space import Mamba2Params

        p = Mamba2Params(
            embed_dim=input.dims[-1], num_heads=num_heads, head_dim=head_dim,
            state_size=state_size, n_groups=n_groups, conv_kernel=conv_kernel,
            chunk_size=chunk_size, norm_eps=norm_eps)
        inits = (
            {k: kernel_initializer for k in ("w_in", "w_out", "conv")}
            if kernel_initializer else None
        )
        return self._add_layer(OperatorType.OP_MAMBA2, p, [input], name,
                               inits)

    def expert_bank(self, input: Tensor, experts: int, top_k: int, width: int,
                    held=None, shared_width: int = 0, scale: float = 1.0,
                    norm_topk: bool = True, act="relu2",
                    router: str = "sigmoid",
                    kernel_initializer=None, name="") -> Tensor:
        """One expert layer as ONE op: a router over all `experts`
        (`router`: "sigmoid" scores or their "softmax"; top `top_k` a token,
        no capacity, no dropped token), the routed
        experts `held` = (first, one past the last) that live on this chip
        (None: all of them), and a shared expert
        of `shared_width` (0: none). What the experts elsewhere would add is
        left out: expert parallelism's share of the layer, without its
        exchange (ops/moe.py). `act` "silu_gated" makes the experts (and the
        shared one) gated: silu(x w_gate) * (x w_up) into w_down."""
        from ..ops.moe import ExpertBankParams

        lo, hi = held if held is not None else (0, experts)
        gated = act == "silu_gated"
        p = ExpertBankParams(
            experts=experts, held_from=lo, held_count=hi - lo, top_k=top_k,
            width=width, shared_width=shared_width, scale=scale,
            norm_topk=norm_topk,
            activation=_to_acti("silu" if gated else act), router=router,
            gated=gated)
        inits = (
            {k: kernel_initializer for k in
             ("router", "w_up", "w_down", "w_gate", "shared_up",
              "shared_down", "shared_gate")}
            if kernel_initializer else None
        )
        return self._add_layer(OperatorType.OP_EXPERT_BANK, p, [input], name,
                               inits)

    # MoE family (reference: moe.cc:20-44 FFModel::moe composite)
    def group_by(self, input: Tensor, assign: Tensor, n: int, alpha: float, name=""):
        return self._add_layer(
            OperatorType.OP_GROUP_BY, GroupByParams(n=n, alpha=alpha), [input, assign], name
        )

    def aggregate(self, tensors: List[Tensor], n: int, lambda_bal: float = 0.0, name=""):
        return self._add_layer(
            OperatorType.OP_AGGREGATE,
            AggregateParams(n=n, lambda_bal=lambda_bal),
            list(tensors),
            name,
        )

    def aggregate_spec(self, tensors: List[Tensor], n: int, lambda_bal: float = 0.0, name=""):
        return self._add_layer(
            OperatorType.OP_AGG_SPEC,
            AggregateSpecParams(n=n, lambda_bal=lambda_bal),
            list(tensors),
            name,
        )

    def cache(self, input: Tensor, num_batches: int = 1, name=""):
        """reference: FFModel::cache (src/ops/cache.cc) — cross-batch
        activation cache (MoE gating cache); see ops/moe.py CacheParams."""
        return self._add_layer(
            OperatorType.OP_CACHE,
            CacheParams(num_batches=num_batches),
            [input],
            name,
        )

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
    ) -> Tensor:
        """reference: src/ops/moe.cc:20-44 — gate -> top_k -> group_by ->
        per-expert dense -> aggregate."""
        gate_preds = self.dense(input, num_exp, ActiMode.AC_MODE_RELU)
        topk_out, topk_assign = self.top_k(gate_preds, num_select)
        exp_tensors = self.group_by(input, topk_assign, num_exp, alpha)
        if not isinstance(exp_tensors, list):
            exp_tensors = [exp_tensors]
        agg_inputs = [self.softmax(topk_out), topk_assign, topk_assign, gate_preds]
        for et in exp_tensors:
            agg_inputs.append(
                self.dense(et, expert_hidden_size, ActiMode.AC_MODE_RELU)
            )
        return self.aggregate(agg_inputs, num_exp, lambda_bal)

    # ------------------------------------------------------------------
    # compile (reference: model.cc:2803 FFModel::compile)
    # ------------------------------------------------------------------
    def set_optimizer(self, opt: Optimizer):
        self.optimizer = opt

    optimizer_setter = set_optimizer  # cffi property-style parity

    # pre-`set_optimizer` spellings (flexflow_c.cc
    # flexflow_model_set_sgd_optimizer / _set_adam_optimizer, used by
    # bootcamp_demo scripts)
    set_sgd_optimizer = set_optimizer
    set_adam_optimizer = set_optimizer

    def get_label_tensor(self):
        """Label tensor getter-method spelling (cffi exposes it as the
        `label_tensor` property, flexflow_cffi.py:2185). The label tensor is
        created by compile() — calling this earlier is an error, same as in
        the reference."""
        assert self.label_tensor is not None, (
            "label tensor exists after compile() — call compile() first"
        )
        return self.label_tensor

    def get_learning_rate(self) -> float:
        """Current learning rate, whatever the optimizer calls it
        (SGDOptimizer.lr, AdamOptimizer.alpha — optimizer.h:36-117)."""
        opt = self.optimizer
        return opt.alpha if hasattr(opt, "alpha") else opt.lr

    def set_learning_rate(self, lr: float) -> None:
        """Set the learning rate on the compiled optimizer and invalidate
        the jitted step (the rate is traced as a constant)."""
        opt = self.optimizer
        field = "alpha" if hasattr(opt, "alpha") else "lr"
        if getattr(opt, field) == lr:
            return
        setattr(opt, field, lr)
        if self.executor is not None:
            self.executor.invalidate_step_cache(train_only=True)

    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type=None,
        metrics: Sequence = (),
        comp_mode: CompMode = CompMode.COMP_MODE_TRAINING,
        calibration=None,
        artifact_store=None,
    ):
        if optimizer is not None:
            self.optimizer = optimizer
        if self.optimizer is None:
            self.optimizer = SGDOptimizer(lr=self.config.learning_rate)
        assert loss_type is not None, "compile() needs a loss_type"
        self.loss_type = to_loss_type(loss_type)
        self.comp_mode = comp_mode
        self.metrics_obj = Metrics(self.loss_type, metrics)
        # Persisted cost calibration (obs/calibration.py): an explicit
        # store/path — or the active telemetry session's store — resolves
        # to measured per-op (fwd, bwd) costs + cost-model globals BEFORE
        # the strategy search, so MCMC/DP price ops from measurement.
        # Rejected (stale/mismatched/empty) stores resolve to nothing and
        # the analytic roofline stands.
        from ..obs.calibration import resolve_calibration

        calib_table, calib_globals = resolve_calibration(calibration)
        if calib_table is not None and len(calib_table):
            self._profiled_op_costs = calib_table
        if calib_globals:
            self._calibration_globals = calib_globals
        # Every compile records what it did (phase timings + every search
        # decision) into a bounded in-memory trajectory; fit(telemetry=)
        # replays it into the event log and obs.explain_strategy joins it
        # with on-device measurements (obs/trajectory.py).
        self.search_trajectory = obs.SearchTrajectory()
        _t_phase = time.perf_counter()

        # 1. Layer graph -> PCG (reference: create_operators_from_layers)
        self.graph, self._tensor_map = layers_to_pcg(self.layers)
        if self.config.perform_fusion:
            # reference: apply_fusion (model.cc:2495, --fusion). Note:
            # per-layer weight get/set for non-head chain members is not
            # available on fused graphs (weights move under the fused op).
            from ..pcg.fusion import apply_fusion

            self.graph = apply_fusion(self.graph)
        self.search_trajectory.phase("lowering", _t_phase,
                                     ops=len(self.graph.ops))
        # 1.5 Artifact cache probe (runtime/artifact_store.py): a prior
        # compile of this exact (graph, topology, calibration) key already
        # paid for the Unity search — replay its winner instead of
        # re-searching. Store resolution: explicit arg > the store a
        # previous compile attached (recompile_for_topology reuses it) >
        # the process-ambient store (ReplicaSet wraps opaque model_fns in
        # store.ambient()). Corrupt/stale entries degrade to a fresh
        # search; the cause rides in strategy_provenance so
        # restore_elastic can count redundant searches.
        from ..runtime.artifact_store import get_ambient

        store = artifact_store or getattr(self, "artifact_store", None) \
            or get_ambient()
        self.artifact_store = store
        ndev = min(self.config.numWorkers, len(jax.devices()))
        search_enabled = (self.config.search_budget >= 0
                          and not self.config.only_data_parallel)
        self._artifact_key = None
        self._artifact_key_parts = None
        _cache_entry = None
        _research_cause = "no_store"
        if store is not None and search_enabled:
            _cache_entry, _research_cause = \
                self._probe_artifact_store(store, ndev)
        self._pt_by_guid = {}
        for op in self.graph.ops:
            for t in list(op.outputs) + list(op.weights):
                self._pt_by_guid[t.guid] = t
        for t in self.graph.input_tensors():
            self._pt_by_guid[t.guid] = t

        # 2. Parallelization strategy.
        #    - search_budget >= 0: Unity search (substitutions + DP view
        #      assignment, reference model.cc:2826 GRAPH_OPTIMIZE path).
        #    - else: manual degrees / pure data parallel (reference
        #      --only-data-parallel lowering).
        # Record user input order positionally BEFORE any search rewrite
        # (rewrites copy the graph with fresh tensor guids; graph input
        # order is stable under copy, so positions survive).
        pre_inputs = self.graph.input_tensors()
        pre_pos = {pt.guid: i for i, pt in enumerate(pre_inputs)}
        # one pass builds BOTH the positional map and the user-Tensor list
        # so attach_numpy_array / set_tensor slots stay element-wise
        # aligned with the executor's input order by construction
        _fit_pairs = [
            (t, pre_pos[self._tensor_map[t.guid]])
            for t in self.input_tensors
            if self._tensor_map.get(t.guid) in pre_pos
            and t.guid not in self._constant_values
        ]
        self._fit_input_tensors = [t for t, _ in _fit_pairs]
        self._input_positions = [i for _, i in _fit_pairs]
        self._constant_positions = {
            pre_pos[self._tensor_map[t.guid]]: self._constant_values[t.guid]
            for t in self.input_tensors
            if t.guid in self._constant_values
            and self._tensor_map.get(t.guid) in pre_pos
        }
        _t_phase = time.perf_counter()
        _pending_artifact_put = False
        if _cache_entry is not None:
            # artifact-cache hit: the stored winner replayed cleanly onto
            # the fresh lowering (degrees + views set, validators passed)
            # — rebuild the exact searched mesh and skip the search.
            views, mesh_axes, cost = _cache_entry
            self.searched_views = views
            self.searched_cost = cost
            if int(mesh_axes.get("pipe", 1)) > 1:
                self.searched_pipeline_degree = int(mesh_axes["pipe"])
            mesh = build_mesh(mesh_axes)
            self.strategy_provenance = {
                "source": "artifact_cache",
                "key": dict(self._artifact_key),
                "cost": cost,
            }
            self.search_trajectory.phase("strategy_cache_hit", _t_phase,
                                         devices=ndev,
                                         ops=len(self.graph.ops))
        elif search_enabled:
            with obs.mark("ff.compile.search", cat="compile", devices=ndev):
                mesh = self._run_strategy_search(ndev)
            self.strategy_provenance = {"source": "search",
                                        "cause": _research_cause}
            self.search_trajectory.phase("strategy_search", _t_phase,
                                         devices=ndev)
            # the artifact payload is written after the precision pass in
            # step 4 stamps compute/accum dtypes, so cache replays restore
            # the full typed strategy (_pending_artifact_put below)
            _pending_artifact_put = (
                store is not None and self._artifact_key is not None)
        else:
            tp = max(1, self.config.tensor_parallel_degree)
            sp = max(1, self.config.sequence_parallel_degree)
            ep = max(1, self.config.expert_parallel_degree)
            pp = max(1, self.config.pipeline_parallel_degree)
            dp = max(1, ndev // (tp * sp * ep * pp))
            # FSDP/ZeRO (config.fsdp_degree): the fsdp axis is carved out
            # of the data-parallel workers — weights shard over it, the
            # batch shards over ("data", "fsdp") jointly — so it must
            # divide the data degree (clamped down to the largest
            # power-of-two-ish divisor otherwise)
            fsdp = max(1, self.config.fsdp_degree)
            while fsdp > 1 and (fsdp > dp or dp % fsdp != 0):
                fsdp //= 2
            if fsdp != max(1, self.config.fsdp_degree):
                warnings.warn(
                    f"fsdp_degree {self.config.fsdp_degree} does not divide "
                    f"the data-parallel degree {dp}; clamped to {fsdp}"
                )
            axes = {"data": dp // fsdp if fsdp > 1 else dp, "model": tp,
                    "seq": sp, "expert": ep, "pipe": pp}
            if fsdp > 1:
                axes["fsdp"] = fsdp
            mesh = build_mesh(axes)
            strategies.apply_data_parallel(self.graph, dp, axis_idx=0)
            strategies.apply_tensor_parallel(self.graph, tp, axis_idx=1)
            strategies.apply_sequence_parallel(self.graph, sp, axis_idx=2)
            strategies.apply_expert_parallel(self.graph, ep, axis_idx=3)
            strategies.apply_pipeline_parallel(self.graph, pp, axis_idx=4)
            if fsdp > 1:
                strategies.apply_weight_sharding(self.graph, fsdp,
                                                 axis_idx=5)
            self.strategy_provenance = {"source": "manual"}
            self.search_trajectory.phase(
                "manual_lowering", _t_phase, devices=ndev,
                data=dp, model=tp, seq=sp, expert=ep, pipe=pp, fsdp=fsdp,
            )

        # 3. Label tensor matched to final op's sharding (model.cc:3054)
        logits_pt = self.graph.output_tensors()[-1]
        if self.loss_type in (
            LossType.LOSS_CATEGORICAL_CROSSENTROPY,
            LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        ):
            final_ops = [o for o in self.graph.ops
                         if any(t.guid == logits_pt.guid for t in o.outputs)]

            if final_ops:
                tail_type, tail_params = _resolve_value_tail(final_ops[0])
                if not _probability_like_tail(tail_type, tail_params):
                    warnings.warn(
                        "cross-entropy losses expect probability outputs "
                        "(the reference's loss kernels take them; "
                        "loss_functions.cc) but the model's final op is "
                        f"{tail_type.name} — raw logits get clipped to "
                        "[1e-12, 1] and gradients die. End the model with "
                        "model.softmax(...)."
                    )
        if self.label_tensor is None:
            label_dt = (
                DataType.DT_INT32
                if self.loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY
                else logits_pt.data_type
            )
            label_dims = (
                tuple(logits_pt.material_shape()[:-1]) + (1,)
                if label_dt == DataType.DT_INT32
                else logits_pt.material_shape()
            )
            self.label_tensor = Tensor(label_dims, label_dt, name="label")
            self.label_tensor._model = self

        # 4. Build executor + initialize weights (reference: optimizer->init,
        #    NCCL communicator setup — here: jit + shardings)
        plan_cost_model = self._build_cost_model()
        # Slice fault domains (runtime/fault_domains.py): on a multi-node
        # machine each node (slice) is a failure domain — recorded on the
        # model so the checkpoint sidecar, the health monitor and fit()'s
        # failure classification all share one map. None on single-node
        # machines (no slice boundary exists) or when the machine model
        # doesn't describe the actual mesh.
        _machine = plan_cost_model.machine
        if (_machine.num_nodes > 1
                and _machine.num_workers == int(mesh.devices.size)):
            from ..runtime.fault_domains import FaultDomainMap

            self.fault_domains = FaultDomainMap.from_machine(_machine)
        else:
            self.fault_domains = None
        compute_dtype = (
            jnp.bfloat16 if self.config.allow_mixed_precision else None
        )
        # bf16 grad storage rides mixed precision unless explicitly forced
        # off (config.bf16_grads; AMP-style half-width grads, f32 masters)
        use_bf16_grads = (
            self.config.allow_mixed_precision
            if self.config.bf16_grads is None else self.config.bf16_grads
        )
        grad_dtype = jnp.bfloat16 if use_bf16_grads else None
        # Precision as first-class PCG state (analysis/precision.py): stamp
        # compute_dtype/accum_dtype on the final graph's tensors from the
        # registry rules, then run the FFA7xx precision audit over the
        # winner — same warn-don't-block contract as the FFA5xx perf lint
        # above (fit(lint=...) re-checks and can hard-fail).
        from ..analysis.precision import (
            annotate_graph_precision,
            precision_diagnostics,
        )

        annotate_graph_precision(
            self.graph,
            compute_dtype=(DataType.DT_BF16
                           if self.config.allow_mixed_precision else None),
        )
        prec_rep = precision_diagnostics(
            self.graph, views=getattr(self, "searched_views", None),
            num_devices=ndev,
            drift_budget=self.config.precision_drift_budget,
            grad_dtype=(DataType.DT_BF16 if use_bf16_grads else None),
        )
        if prec_rep.errors:
            warnings.warn(
                "static precision analysis flagged the compiled strategy "
                "(fit(lint=...) re-checks; docs/analysis.md FFA7xx): "
                + "; ".join(d.format() for d in prec_rep.errors[:5])
            )
        self.search_trajectory.event(
            "precision_lint", errors=len(prec_rep.errors),
            warnings=len(prec_rep.warnings),
            codes=sorted({d.code for d in prec_rep}),
        )
        if _pending_artifact_put:
            self._artifact_store_put(store, mesh)
        # Map user input tensors (creation order) to their PCG tensors; only
        # those actually consumed by the graph become executor inputs.
        cur_inputs = self.graph.input_tensors()
        ordered_inputs = [cur_inputs[i] for i in self._input_positions]
        constants = {
            cur_inputs[i].guid: (cur_inputs[i], v)
            for i, v in self._constant_positions.items()
        }
        with obs.mark("ff.compile.lower", cat="compile",
                      ops=len(self.graph.ops)):
            _t_phase = time.perf_counter()
            self.executor = PCGExecutor(
                self.graph,
                mesh,
                self.optimizer,
                self.loss_type,
                self.metrics_obj,
                compute_dtype=compute_dtype,
                grad_dtype=grad_dtype,
                seed=self.config.seed,
                input_order=ordered_inputs,
                remat=self.config.remat,
                constants=constants,
                plan_cost_model=plan_cost_model,
                overlap_grad_sync=self.config.overlap_backward_update,
            )
            self.search_trajectory.phase("executor_build", _t_phase)
            _t_phase = time.perf_counter()
            self.state = self.executor.init_state()
            self.search_trajectory.phase("init_state", _t_phase)
        self.perf_metrics = PerfMetrics()

    def compile_decode(self, *, strategy_path: Optional[str] = None,
                       export_path: Optional[str] = None):
        """Run the Unity search a SECOND time over the same layer graph
        with the DECODE cost objective (ROADMAP item 3; the Splitwise/
        DistServe disaggregation insight): single-token decode is
        HBM-bandwidth-bound where training is MXU-bound, so the cheapest
        parallelization differs — the decode oracle prices each op off
        the bytes one token streams (weights/shard + KV-cache reads +
        1-token activations) and prices collectives latency-bound
        (search/cost_model.py CostObjective.DECODE).

        The model then carries TWO searched strategies: `graph`/
        `searched_views` (training/prefill, compute-bound) and
        `decode_graph`/`decode_searched_views`, with a separate
        `decode_trajectory` recording this search's decisions. The
        ContinuousBatcher lowers its batch decode executables from
        `decode_executor` while prefill keeps the training strategy
        (runtime/serving.py).

        strategy_path: import the decode strategy from a strategy_io
        JSON file instead of searching (ServingConfig.
        decode_strategy_path feeds this). export_path: export the
        searched strategy for later import. Returns the decode
        executor."""
        assert self.executor is not None, (
            "compile() the model before compile_decode() — the decode "
            "strategy is searched over the same layer graph and serves "
            "alongside the training one"
        )
        cfg = self.config
        ndev = min(cfg.numWorkers, len(jax.devices()))
        self.decode_trajectory = obs.SearchTrajectory()
        _t_phase = time.perf_counter()
        # fresh lowering: the training search REWROTE self.graph with its
        # own substitutions; the decode search must start from the same
        # unrewritten layer graph, not the training winner
        graph, _ = layers_to_pcg(self.layers)
        if cfg.perform_fusion:
            from ..pcg.fusion import apply_fusion

            graph = apply_fusion(graph)
        self.decode_trajectory.phase("decode_lowering", _t_phase,
                                     ops=len(graph.ops))
        cost_model = self._build_cost_model(objective="decode")
        _t_phase = time.perf_counter()
        if strategy_path:
            from ..runtime.strategy_io import (
                apply_imported_strategy,
                import_strategy,
            )

            strategy = import_strategy(strategy_path)
            apply_imported_strategy(graph, strategy, num_devices=ndev)
            views = {
                op.guid: op.machine_view for op in graph.ops
                if getattr(op, "machine_view", None) is not None
            }
            cost = None
            self.decode_trajectory.phase(
                "decode_strategy_import", _t_phase,
                records=len(strategy), devices=ndev,
            )
        else:
            from ..pcg.machine_view import MachineResource
            from ..search import (
                GraphSearchHelper,
                SearchHelper,
                generate_all_pcg_xfers,
            )

            machine = cost_model.machine
            sh = SearchHelper(cost_model, trajectory=self.decode_trajectory)
            degrees = []
            d = 2
            while d <= machine.num_workers:
                degrees.append(d)
                d *= 2
            budget = cfg.search_budget if cfg.search_budget > 0 else 10
            # parallelization xfers ONLY — no operator-substitution rules.
            # A substitution rewrites compute ops and rebuilds their
            # weights fresh from initializers, but the decode strategy
            # must serve the weights TRAINED under the training graph
            # (the batcher feeds both lowerings the same param store,
            # keyed by op name); a rewritten op could never find its
            # weights and would force the serving fallback every time.
            xfers = generate_all_pcg_xfers(degrees or [1], cfg)
            res = MachineResource(
                num_nodes=machine.num_nodes,
                all_procs_per_node=machine.workers_per_node,
                available_procs_per_node=machine.workers_per_node,
            )
            gsh = GraphSearchHelper(
                sh, xfers, alpha=cfg.search_alpha, budget=budget,
                trajectory=self.decode_trajectory,
            )
            with obs.mark("ff.compile.decode_search", cat="compile",
                          devices=ndev, budget=budget):
                graph, result = gsh.graph_optimize(graph, res)
            views = result.views
            cost = result.cost
            self.decode_trajectory.phase("decode_strategy_search", _t_phase,
                                         devices=ndev)
        self.decode_graph = graph
        self.decode_searched_views = views
        self.decode_searched_cost = cost
        # same vetting the training strategy gets: structural validators +
        # the static perf pass — run under the decode objective so FFA509
        # (over-sharded KV heads, latency-bound per-token collectives on
        # the critical path) fires here, at compile time
        from ..analysis.precision import (
            annotate_graph_precision,
            precision_diagnostics,
        )
        from ..search import run_strategy_validators

        # the decode graph's precision flow first (decode serves under the
        # same AMP dtype as training compute): the validators read the
        # accumulators it stamps, and a graph declared in bfloat16 has
        # none until then
        annotate_graph_precision(
            graph,
            compute_dtype=(DataType.DT_BF16
                           if cfg.allow_mixed_precision else None),
        )
        problems = run_strategy_validators(graph, views, ndev)
        if problems:
            warnings.warn(
                "decode-searched strategy failed structural validation "
                "(falling through to lowering, which demotes infeasible "
                "degrees to replicated): " + "; ".join(problems[:5])
            )
        from ..analysis.perf import perf_diagnostics

        perf_rep = perf_diagnostics(
            graph, views=views, cost_model=cost_model, num_devices=ndev,
            expert_degree=getattr(cfg, "expert_parallel_degree", 1),
            objective="decode",
        )
        if perf_rep.errors:
            warnings.warn(
                "static perf analysis flagged the decode-searched strategy "
                "(docs/analysis.md FFA5xx): "
                + "; ".join(d.format() for d in perf_rep.errors[:5])
            )
        self.decode_trajectory.event(
            "perf_lint", errors=len(perf_rep.errors),
            warnings=len(perf_rep.warnings),
            codes=sorted({d.code for d in perf_rep}),
        )
        # FFA7xx precision audit of the decode strategy, vetted like the
        # train path's
        prec_rep = precision_diagnostics(
            graph, views=views, num_devices=ndev,
            drift_budget=cfg.precision_drift_budget,
        )
        if prec_rep.errors:
            warnings.warn(
                "static precision analysis flagged the decode-searched "
                "strategy (docs/analysis.md FFA7xx): "
                + "; ".join(d.format() for d in prec_rep.errors[:5])
            )
        self.decode_trajectory.event(
            "precision_lint", errors=len(prec_rep.errors),
            warnings=len(prec_rep.warnings),
            codes=sorted({d.code for d in prec_rep}),
        )
        if export_path:
            from types import SimpleNamespace

            from ..runtime.strategy_io import export_strategy

            export_strategy(graph, SimpleNamespace(views=views, cost=cost),
                            export_path)
        # decode executor over the decode graph: params stay keyed by op
        # name, so a decode build whose op names survived the rewrite can
        # consume the TRAINING state's params directly; the batcher
        # checks compatibility before swapping it in (runtime/serving.py)
        cur_inputs = graph.input_tensors()
        ordered_inputs = [cur_inputs[i] for i in self._input_positions]
        constants = {
            cur_inputs[i].guid: (cur_inputs[i], v)
            for i, v in self._constant_positions.items()
        }
        axis_sizes = strategies.assign_mesh_axes(graph, ndev)
        mesh = build_mesh(axis_sizes)
        _t_phase = time.perf_counter()
        with obs.mark("ff.compile.lower", cat="compile", ops=len(graph.ops),
                      objective="decode"):
            self.decode_executor = PCGExecutor(
                graph,
                mesh,
                self.optimizer,
                self.loss_type,
                self.metrics_obj,
                compute_dtype=(
                    jnp.bfloat16 if cfg.allow_mixed_precision else None
                ),
                grad_dtype=None,  # decode never materializes gradients
                seed=cfg.seed,
                input_order=ordered_inputs,
                remat=False,
                constants=constants,
                plan_cost_model=cost_model,
            )
        self.decode_trajectory.phase("decode_executor_build", _t_phase)
        return self.decode_executor

    def _probe_artifact_store(self, store, ndev: int):
        """Look up + replay a stored strategy for the current lowering.

        Returns `((views, mesh_axes, cost), None)` on a usable hit, else
        `(None, cause)` where cause names why a search still runs
        ("cache_miss" / "cache_corrupt" — both feed
        ff_elastic_research_total). A replay that fails partway has
        already mutated tensor degrees, so the stale path re-lowers
        self.graph fresh before handing it to the search. Store failures
        of any kind degrade to a fresh search — a poisoned cache is
        never worse than no cache."""
        from ..runtime.artifact_store import (
            ArtifactCorruptionError,
            calibration_fingerprint,
            graph_fingerprint,
            make_key,
            replay_strategy,
            topology_digest,
        )
        from ..runtime.elastic import topology_fingerprint
        from ..runtime.strategy_io import StrategyImportError

        parts = {
            "graph": graph_fingerprint(self.graph),
            "topology": topology_digest(topology_fingerprint()),
            "calibration": calibration_fingerprint(
                getattr(self, "_profiled_op_costs", None),
                getattr(self, "_calibration_globals", None),
            ),
        }
        key = make_key(objective="train", num_devices=ndev, **parts)
        self._artifact_key_parts = parts
        self._artifact_key = key
        try:
            payload = store.get(key)
        except ArtifactCorruptionError:
            return None, "cache_corrupt"
        except Exception as e:
            warnings.warn(
                f"artifact store lookup failed ({e!r}); falling back to "
                "a fresh search"
            )
            return None, "cache_corrupt"
        if payload is None:
            return None, "cache_miss"
        try:
            # replay rebuilds the searched PCG around this lowering's
            # compute ops (search-inserted parallel ops reconstructed,
            # sharding state restored per dim) — the rebuilt graph
            # REPLACES the fresh lowering, exactly as a search would
            graph2, views, mesh_axes, cost = replay_strategy(
                self.graph, payload, num_devices=ndev)
            self.graph = graph2
            return (views, mesh_axes, cost), None
        except StrategyImportError as e:
            warnings.warn(
                f"artifact store entry could not be replayed ({e}); "
                "quarantining it and falling back to a fresh search"
            )
            try:
                store.note_stale(key, str(e))
            except Exception as qe:
                warnings.warn(
                    f"artifact store could not quarantine the stale "
                    f"entry ({qe!r}); the fresh search proceeds anyway"
                )
            # the failed replay mutated tensor degrees in place — the
            # search must start from an unmutated lowering
            self.graph, self._tensor_map = layers_to_pcg(self.layers)
            if self.config.perform_fusion:
                from ..pcg.fusion import apply_fusion

                self.graph = apply_fusion(self.graph)
            return None, "cache_miss"

    def _artifact_store_put(self, store, mesh) -> None:
        """Write the freshly searched winner through to the artifact
        store under the key _probe_artifact_store computed. Never fails
        the compile — the strategy is already in hand."""
        from ..runtime.artifact_store import strategy_payload

        try:
            mesh_axes = {
                str(name): int(size)
                for name, size in zip(mesh.axis_names, mesh.devices.shape)
            }
            store.put(self._artifact_key, strategy_payload(
                self.graph,
                getattr(self, "searched_views", None),
                cost=getattr(self, "searched_cost", None),
                mesh_axes=mesh_axes,
                provenance={"writer": "compile"},
            ))
        except Exception as e:
            warnings.warn(
                f"artifact store write failed ({e!r}); continuing "
                "without caching the strategy"
            )

    def _build_cost_model(self, objective: str = "train"):
        """The cost oracle for stage planning (and the search): the
        configured machine (file / search-dims / --machine-model-version)
        with the shipped calibration. `objective` selects what workload
        the oracle prices (search/cost_model.py CostObjective): "train"
        (default) or "decode" — the single-token HBM-roofline pricing
        compile_decode()'s second search runs under."""
        from ..search import CostModel, MachineModel, parse_machine_config
        from ..search.machine_model import chip_spec_for

        cfg = self.config
        override = getattr(self, "_machine_override", None)
        if override is not None:
            # recompile_for_topology re-targeted a machine description at
            # the live device count (elastic resume); it wins over the
            # stale file/config topology
            machine = override
        elif cfg.machine_model_file:
            machine = parse_machine_config(cfg.machine_model_file)
        else:
            nodes = (cfg.search_num_nodes if cfg.search_num_nodes > 0
                     else cfg.numNodes)
            workers = (cfg.search_num_workers if cfg.search_num_workers > 0
                       else cfg.workersPerNode)
            machine = MachineModel(num_nodes=nodes, workers_per_node=workers,
                                   chip=chip_spec_for(jax.devices()[0]))
        if cfg.machine_model_version >= 1 and not hasattr(machine, "topology"):
            from ..search.network import TopologyAwareMachineModel

            machine = TopologyAwareMachineModel(
                num_nodes=machine.num_nodes,
                workers_per_node=machine.workers_per_node,
                ici_bandwidth=machine.ici_bandwidth,
                dcn_bandwidth=machine.dcn_bandwidth,
                chip=machine.chip,
            )
        pen = cfg.search_survivability_penalty
        if pen < 0:
            # auto: bias toward slice-loss-survivable strategies only
            # where slices exist as failure domains (multi-node machine)
            pen = 0.25 if machine.num_nodes > 1 else 0.0
        cm = CostModel(
            machine, bf16=cfg.allow_mixed_precision,
            overlap_backward_update=cfg.search_overlap_backward_update,
            survivability_penalty=pen,
            objective=objective,
        )
        # In-situ measurements ride on the oracle through the shared
        # refresh seam (search/cost_model.py apply_calibration): per-op
        # timings from explain_strategy(...).apply(model) or a persisted
        # CalibrationStore override the analytic roofline for serial
        # views; the store's measured overlap efficiency and per-kind
        # collective bandwidths override the shipped calibration's. The
        # online re-search (runtime/tuner.py) rebuilds its oracle through
        # this same path, so drift-corrected searches are priced exactly
        # like compile-time ones. (--measured-search, if enabled above,
        # supersedes the per-op table with proper per-shard measurement.)
        from ..search import apply_calibration

        glb = getattr(self, "_calibration_globals", None) or {}
        return apply_calibration(
            cm,
            profiled=getattr(self, "_profiled_op_costs", None),
            overlap_efficiency=glb.get("overlap_efficiency"),
            collective_bandwidths=glb.get("collective_bytes_per_s"),
        )

    def _run_strategy_search(self, ndev: int):
        """Unity search over the lowered PCG (reference: compile's
        GRAPH_OPTIMIZE_TASK -> GraphSearchHelper::graph_optimize,
        substitution.cc:1898). Returns the execution mesh."""
        from ..pcg.machine_view import MachineResource
        from ..search import (
            CostModel,
            GraphSearchHelper,
            MachineModel,
            SearchHelper,
            generate_all_pcg_xfers,
            parse_machine_config,
        )

        cfg = self.config
        # (--machine-model-version 1 selects the EnhancedMachineModel
        # analog — per-link ICI hops, DCN hierarchy, congestion;
        # search/network.py)
        cost_model = self._build_cost_model()
        machine = cost_model.machine
        if cfg.measure_operator_costs:
            # --measured-search: per-op on-device timing feeds the search
            from ..search.measure import attach_measured_mode

            attach_measured_mode(
                cost_model,
                compute_dtype=(
                    jnp.bfloat16 if cfg.allow_mixed_precision else None
                ),
                cache_path=cfg.measured_cache_path or None,
            )
        sh = SearchHelper(cost_model, trajectory=self.search_trajectory)
        degrees = []
        d = 2
        while d <= machine.num_workers:
            degrees.append(d)
            d *= 2
        budget = cfg.search_budget if cfg.search_budget > 0 else 10
        xfers = generate_all_pcg_xfers(degrees or [1], cfg)
        # declarative rules: --substitution-json, or the shipped collection
        # (reference loads substitutions/graph_subst_3_v2.json by default;
        # ours is search/substitutions/graph_subst_tpu_v1.json — it adds
        # per-op partition sandwiches and column-parallel matmul, which
        # the programmatic xfers don't express)
        import os as _os

        from ..search.substitution_loader import (
            default_rules_path,
            load_rule_collection_from_path,
            rules_to_substitutions,
            zoo_rules_path,
        )

        if cfg.substitution_json_path:
            # explicit --substitution-json: a missing file must raise, not
            # silently fall back to the bundled defaults
            rules = load_rule_collection_from_path(cfg.substitution_json_path)
            xfers = xfers + rules_to_substitutions(rules)
        else:
            for rp in (default_rules_path(), zoo_rules_path()):
                if _os.path.exists(rp):
                    rules = load_rule_collection_from_path(rp)
                    xfers = xfers + rules_to_substitutions(rules)
        res = MachineResource(
            num_nodes=machine.num_nodes,
            all_procs_per_node=machine.workers_per_node,
            available_procs_per_node=machine.workers_per_node,
        )
        mem_budget = cfg.device_mem or machine.chip.hbm_capacity
        if cfg.perform_memory_search:
            # reference: --memory-search lambda loop (graph.cc:2060-2130)
            from ..search.memory_optimization import (
                graph_optimize_with_memory,
            )

            best_graph, result, _mem, _lam = graph_optimize_with_memory(
                self.graph, cost_model, res, xfers,
                device_mem_budget=mem_budget,
                alpha=cfg.search_alpha, budget=budget,
                train=self._is_training_compile(), optimizer=self.optimizer,
                grad_bytes_ratio=self._grad_bytes_ratio(),
                trajectory=self.search_trajectory,
            )
        else:
            gsh = GraphSearchHelper(
                sh,
                xfers,
                alpha=cfg.search_alpha,
                budget=budget,
                trajectory=self.search_trajectory,
            )
            best_graph, result = gsh.graph_optimize(self.graph, res)
        self.graph = best_graph
        self.searched_views = result.views
        self.searched_cost = result.cost
        # Pipeline as a SEARCHED dimension (beyond-parity: the reference's
        # OP_PIPELINE is enum-only, ffconst.h:158): when the best
        # unpipelined strategy's per-chip TRAINING memory (weights +
        # grads + optimizer slots + activations) exceeds the HBM budget,
        # weigh GPipe candidates (bubble fraction + cut-activation
        # transfers) against the best FITTING unpipelined strategy a
        # memory-pressured re-search finds, and adopt whichever is
        # cheaper. Runs before re-indexing/exports because it may replace
        # the strategy either way.
        pipe, alt = self._search_pipeline_degree(
            cost_model, result, ndev, mem_budget, res=res, xfers=xfers
        )
        if alt is not None:
            self.graph, result = alt
            self.searched_views = result.views
            self.searched_cost = result.cost
        self.search_trajectory.event(
            "pipeline_search", degree=pipe,
            replaced_by_researched=alt is not None, cost=result.cost,
        )
        # re-index pt lookup for the (possibly rewritten) graph
        self._pt_by_guid = {}
        for op in self.graph.ops:
            for t in list(op.outputs) + list(op.weights):
                self._pt_by_guid[t.guid] = t
        for t in self.graph.input_tensors():
            self._pt_by_guid[t.guid] = t
        # strategy-validator hook (search/__init__.py): structural vetting
        # of the final search result — machine views addressing only live
        # devices, degree products within the device count — so an insane
        # strategy is flagged here, not discovered as wrong numbers later
        from ..search import run_strategy_validators

        problems = run_strategy_validators(self.graph, self.searched_views,
                                           ndev)
        if problems:
            warnings.warn(
                "searched strategy failed structural validation "
                "(falling through to lowering, which demotes infeasible "
                "degrees to replicated): " + "; ".join(problems[:5])
            )
        # static perf audit of the WINNER (analysis/perf.py FFA5xx): the
        # search trusted a cost model that discounts overlappable
        # collectives — verify the discounts are schedulable and the
        # topology pricing holds before the strategy ever executes. The
        # cost model here is the SAME oracle the search scored with, so
        # an FFA501 finding is the search disagreeing with itself.
        from ..analysis.perf import perf_diagnostics

        perf_rep = perf_diagnostics(
            self.graph, views=self.searched_views, cost_model=cost_model,
            num_devices=ndev,
            expert_degree=getattr(cfg, "expert_parallel_degree", 1),
        )
        if perf_rep.errors:
            warnings.warn(
                "static perf analysis flagged the searched strategy "
                "(fit(lint=...) re-checks; docs/analysis.md FFA5xx): "
                + "; ".join(d.format() for d in perf_rep.errors[:5])
            )
        self.search_trajectory.event(
            "perf_lint", errors=len(perf_rep.errors),
            warnings=len(perf_rep.warnings),
            codes=sorted({d.code for d in perf_rep}),
        )
        if cfg.export_strategy_file:
            from ..runtime.strategy_io import export_strategy

            export_strategy(self.graph, result, cfg.export_strategy_file)
        if cfg.export_strategy_computation_graph_file:
            with open(cfg.export_strategy_computation_graph_file, "w") as f:
                f.write(self.graph.export_dot())
        axis_sizes = strategies.assign_mesh_axes(self.graph, ndev)
        if pipe > 1:
            # the pipeline candidate is a stage split + data parallelism
            # within each stage; it REPLACES the overflowing strategy's
            # axes (tensor degrees not matching the new axes demote to
            # replicated in lowering, as with any searched strategy)
            axis_sizes = {"data": max(1, ndev // pipe), "pipe": pipe}
            self.searched_pipeline_degree = pipe
        return build_mesh(axis_sizes)

    def _grad_bytes_ratio(self) -> float:
        """Gradient-buffer width relative to the master weight: 0.5 under
        the bf16-grad AMP recipe (executor grad_dtype), else 1.0 — the
        memory search charges `weights * (1 + this + optimizer slots)`."""
        cfg = self.config
        use_bf16 = (cfg.allow_mixed_precision if cfg.bf16_grads is None
                    else cfg.bf16_grads)
        return 0.5 if use_bf16 else 1.0

    def _is_training_compile(self) -> bool:
        """Inference compiles allocate no gradients or optimizer slots —
        charging them (2-4x weight bytes under Adam) would wrongly
        reject strategies that fit inference HBM comfortably."""
        return self.comp_mode == CompMode.COMP_MODE_TRAINING

    def recompile_for_topology(self, num_devices: Optional[int] = None) -> None:
        """Re-plan the compiled model for the CURRENT device topology
        (runtime/elastic.py): point the machine description at
        `num_devices` (default: every live device), then re-run compile()
        — which re-runs the strategy search / manual lowering for the new
        machine, rebuilds the mesh + executor and re-initializes state.
        Weights do NOT carry over; restore from a checkpoint afterwards
        (restore_elastic / fit(elastic=True))."""
        assert self.loss_type is not None, (
            "compile() the model once before recompile_for_topology"
        )
        from ..search import for_device_count, parse_machine_config

        n = num_devices if num_devices is not None else len(jax.devices())
        cfg = self.config
        # hypothetical-machine overrides would pin the search to the OLD
        # topology; the whole point here is planning for the live one
        cfg.search_num_nodes = -1
        cfg.search_num_workers = -1
        override = getattr(self, "_machine_override", None)
        if cfg.machine_model_file:
            # the file describes the machine we LOST; keep its per-chip and
            # link constants (the hardware kind didn't change) but re-point
            # the topology at the surviving device count
            base = parse_machine_config(cfg.machine_model_file)
            self._machine_override = for_device_count(n, like=base)
            cfg.machine_model_file = ""
        elif override is not None:
            # a previous elastic recompile already lifted the file into an
            # override; re-target it again for this topology change
            self._machine_override = for_device_count(n, like=override)
        else:
            from ..search import MachineModel

            m = for_device_count(n, like=MachineModel(
                num_nodes=cfg.numNodes, workers_per_node=cfg.workersPerNode,
            ))
            cfg.numNodes = m.num_nodes
            cfg.workersPerNode = m.workers_per_node
        self.compile(
            optimizer=self.optimizer,
            loss_type=self.loss_type,
            metrics=self.metrics_obj.measures if self.metrics_obj else (),
            comp_mode=self.comp_mode,
        )

    def _search_pipeline_degree(self, cost_model, result, ndev,
                                mem_budget, res=None, xfers=None):
        """Propose pipeline parallelism when the searched strategy cannot
        fit per-chip HBM under TRAINING memory accounting (weights +
        gradients + optimizer slots + activation residuals — reference:
        memory_optimization.h:45-100). Candidate cost for S stages over
        ndev devices (dp = ndev/S within each stage, M microbatches):

            T(S) ~ max_stage_time/dp * (M + S - 1)/M
                   + cut_bytes * 2 / ici_bw / dp

        i.e. the GPipe bubble fraction plus fwd+bwd boundary-activation
        transfers; per-chip memory ~ stage weights (replicated in the
        stage's dp group, carrying the grads+slots multiplier) + stage
        activation shards for ALL M microbatches (the scan-based GPipe
        backward stashes every microbatch's residuals).

        Returns (degree, alt): degree==1 when the unpipelined strategy
        already fits (a test pins that pipeline is NOT chosen then);
        alt!=None is a FITTING unpipelined (graph, result) found by a
        memory-pressured re-search that beats every pipeline candidate
        on cost — TP's per-layer collectives against GPipe's bubble is a
        cost question, not a memory one, so it is decided on cost."""
        from ..search.memory_optimization import (
            measure_memory,
            weight_bytes_multiplier,
        )
        from ..parallel.pipeline import balanced_linear_partition

        cfg = self.config
        if ndev < 2:
            return 1, None
        train = self._is_training_compile()
        gratio = self._grad_bytes_ratio()
        wmul = (weight_bytes_multiplier(
                    self.optimizer, gratio,
                    warn=any(op.weights for op in self.graph.ops))
                if train else 1.0)
        mem = measure_memory(
            self.graph, result.views, cost_model,
            train=train, optimizer=self.optimizer, grad_bytes_ratio=gratio,
        ).max_bytes
        if mem <= mem_budget:
            return 1, None
        from ..pcg.machine_view import MachineView

        machine = cost_model.machine
        ops = [o for o in self.graph.ops if not o.is_parallel_op]
        order = {o.guid: i for i, o in enumerate(self.graph.topo_order())}
        ops.sort(key=lambda o: order[o.guid])
        v1 = MachineView(start_device_id=0, dim=(1,), stride=(1,))
        costs = [cost_model.measure_operator_cost(o, v1).total_time
                 for o in ops]
        w_bytes = [
            sum(t.get_volume() * t.data_type.size for t in o.weights)
            for o in ops
        ]
        a_bytes = [
            sum(t.get_volume() * t.data_type.size for t in o.outputs)
            for o in ops
        ]
        best_s, best_t = 1, float("inf")
        S = 2
        while S <= ndev and len(ops) >= S:
            if ndev % S == 0:
                dp = ndev // S
                M = max(cfg.num_microbatches, S)
                bounds = balanced_linear_partition(costs, S)
                stage_t = [sum(costs[bounds[i]:bounds[i + 1]])
                           for i in range(S)]
                stage_w = [sum(w_bytes[bounds[i]:bounds[i + 1]])
                           for i in range(S)]
                stage_a = [sum(a_bytes[bounds[i]:bounds[i + 1]])
                           for i in range(S)]
                cut_bytes = sum(a_bytes[bounds[i + 1] - 1]
                                for i in range(S - 1))
                t = (max(stage_t) / dp * (M + S - 1) / M
                     + cut_bytes * 2 / machine.ici_bandwidth / dp)
                # stage weights replicate within the stage's dp group and
                # carry grads + optimizer slots (wmul); the scan-based
                # GPipe schedule (backward = reversed scan under
                # jax.grad) stashes ALL M microbatches' residuals — per
                # chip that is the stage's full batch-shard of
                # activations, not just the in-flight window
                m_per_chip = max(
                    w * wmul + a / dp
                    for w, a in zip(stage_w, stage_a)
                )
                if m_per_chip <= mem_budget and t < best_t:
                    best_s, best_t = S, t
            S *= 2
        if res is not None and xfers is not None \
                and not cfg.perform_memory_search:
            # The overflowing strategy was the COST winner; whether or
            # not any pipeline stage count fit, let the lambda loop look
            # for a fitting unpipelined strategy (e.g. parameter-parallel
            # sharding that divides the weight+grad+slot bytes). Adopt it
            # when it fits and beats the pipeline estimate on simulated
            # runtime (or when no pipeline fit at all). (Under
            # --memory-search that loop already ran and failed to fit,
            # so it is not repeated here.)
            from ..search.memory_optimization import (
                graph_optimize_with_memory,
            )

            budget = cfg.search_budget if cfg.search_budget > 0 else 10
            g2, r2, mem2, _lam = graph_optimize_with_memory(
                self.graph, cost_model, res, xfers,
                device_mem_budget=mem_budget,
                alpha=cfg.search_alpha, budget=budget,
                train=train, optimizer=self.optimizer,
                grad_bytes_ratio=gratio,
                trajectory=self.search_trajectory,
            )
            if mem2.max_bytes <= mem_budget and r2.cost < best_t:
                return 1, (g2, r2)
        if best_s == 1:
            warnings.warn(
                f"per-chip training memory "
                f"{mem / 2**20:.0f} MB exceeds the "
                f"{mem_budget / 2**20:.0f} MB budget and no pipeline "
                f"stage count or re-searched strategy fits; keeping the "
                f"fastest (overflowing) strategy"
            )
        return best_s, None

    # ------------------------------------------------------------------
    # training loop (reference: flexflow_cffi.py:2058 fit)
    # ------------------------------------------------------------------
    def _assert_same_global_batch(self, xs, y, bs: int) -> None:
        """Multi-host contract (runtime/distributed.py): every process
        feeds the SAME global batch. A diverging feed silently corrupts
        training — each process contributes its local shard of what it
        BELIEVES is the global array and no error ever surfaces — and an
        uneven batch count desyncs the collectives into a hang. Verify a
        cheap signature (dataset size, batch size, first-batch checksums)
        across processes before training and fail loudly on mismatch."""
        from jax.experimental import multihost_utils

        first = next(self._batches(list(xs) + [y], bs))
        sig = [float(bs), float(xs[0].shape[0])]
        for a in first:
            arr = np.asarray(a)
            head = arr.reshape(-1)[: 4096]
            sig += [
                float(np.sum(arr.astype(np.float64))),
                float(np.sum(np.abs(head.astype(np.float64)))),
            ]
        multihost_utils.assert_equal(
            np.asarray(sig, np.float32),
            fail_message=(
                "multi-host contract violated: every process must feed the "
                "SAME global batch and dataset (runtime/distributed.py) — "
                "rank data/batch signatures differ"
            ),
        )

    def _batches(self, arrays: List[np.ndarray], batch_size: int):
        n = arrays[0].shape[0]
        nb = n // batch_size
        for i in range(nb):
            yield [a[i * batch_size : (i + 1) * batch_size] for a in arrays]

    def fit(
        self,
        x: Union[np.ndarray, List[np.ndarray], None] = None,
        y: Optional[np.ndarray] = None,
        batch_size: Optional[int] = None,
        epochs: Optional[int] = None,
        verbose: bool = True,
        *,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every_n_steps: Optional[int] = None,
        keep_last_n: int = 3,
        resume: bool = True,
        skip_nonfinite_steps: bool = False,
        step_guard=None,
        max_consecutive_skips: int = 10,
        fault_injector=None,
        preemption_signal=None,
        elastic: bool = False,
        health_monitor=None,
        verify_strategy=None,
        canary=None,
        lint: Optional[str] = None,
        telemetry=None,
        tuner=None,
    ):
        if self.executor is None:
            from ..runtime.verify import NotCompiledError

            raise NotCompiledError("fit: call compile() first")
        if lint not in (None, "off", "warn", "error"):
            raise ValueError(
                'fit(lint=...) accepts "error", "warn", or "off" '
                f"(got {lint!r})"
            )
        # -- telemetry session (obs/): fit(telemetry=TelemetryConfig(...))
        # runs one session end to end — compile/search trajectory replay,
        # per-step events, metrics — and flushes events.jsonl /
        # metrics.prom / trace.json on exit. A session the caller already
        # opened (obs.session(...)) is fed without being finished here.
        tel = None
        _own_session = False
        if telemetry is not None:
            if not isinstance(telemetry, obs.TelemetryConfig):
                raise ValueError(
                    "fit(telemetry=...) takes an obs.TelemetryConfig "
                    f"(got {telemetry!r})"
                )
            tel = obs.start(telemetry)
            _own_session = True
        else:
            tel = obs.active()
        if tel is not None:
            tel.attach_model(self)
        try:
            return self._fit_impl(
                x, y, batch_size, epochs, verbose,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every_n_steps=checkpoint_every_n_steps,
                keep_last_n=keep_last_n, resume=resume,
                skip_nonfinite_steps=skip_nonfinite_steps,
                step_guard=step_guard,
                max_consecutive_skips=max_consecutive_skips,
                fault_injector=fault_injector,
                preemption_signal=preemption_signal,
                elastic=elastic, health_monitor=health_monitor,
                verify_strategy=verify_strategy, canary=canary,
                lint=lint, tel=tel, tuner=tuner,
            )
        except Exception as e:
            # OOM forensics (obs/step_profile.py): a step that dies with
            # RESOURCE_EXHAUSTED leaves the static memory prediction,
            # the live allocator stats and the top allocations behind —
            # the post-mortem answers "what ate the HBM" offline
            if tel is not None and "RESOURCE_EXHAUSTED" in str(e):
                from ..obs.step_profile import dump_oom_forensics

                try:
                    path = dump_oom_forensics(self, tel.config.dir,
                                              error=str(e))
                    obs.event("oom_forensics", cat="obs", path=path)
                except Exception as dump_err:  # fflint: disable=FFL002 — forensics must not mask the OOM
                    warnings.warn(f"oom forensics dump failed: {dump_err}")
            # flight recorder (obs/flight_recorder.py): typed failures
            # (non-finite grads, strategy divergence, KV exhaustion,
            # slice loss, ...) dump the recent event/metric tail plus
            # live-state providers; no-op for untyped exceptions or
            # without an armed recorder
            obs.record_failure(e, where="fit")
            raise
        finally:
            if _own_session:
                obs.finish()

    def _fit_impl(
        self, x, y, batch_size, epochs, verbose, *,
        checkpoint_dir, checkpoint_every_n_steps, keep_last_n, resume,
        skip_nonfinite_steps, step_guard, max_consecutive_skips,
        fault_injector, preemption_signal, elastic, health_monitor,
        verify_strategy, canary, lint, tel, tuner=None,
    ):
        if lint in ("warn", "error"):
            # static preflight (analysis/): shape/sharding inference,
            # collective consistency, and HBM-fit over the compiled PCG —
            # rejects a broken strategy before ANY device time is spent
            # (the differential verify_strategy preflight below still
            # needs 2 real steps)
            from ..analysis import StaticAnalysisError, analyze_model

            report = analyze_model(self)
            if not report.ok:
                if lint == "error":
                    raise StaticAnalysisError(report)
                warnings.warn("static analysis found problems "
                              "(fit(lint='warn')):\n" + report.summary())
            elif verbose and len(report):
                obs.progress(f"[analysis] {report!r}", name="analysis",
                             cat="compile")
        x, y = _unwrap_loaders(x, y)
        xs = x if isinstance(x, (list, tuple)) else [x]
        bs = batch_size or self.config.batch_size
        ep = epochs or self.config.epochs
        n = xs[0].shape[0]
        if n < bs:
            raise ValueError(
                f"dataset has {n} samples < batch_size {bs}; nothing to train on"
            )
        if n % bs != 0:
            obs.progress(
                f"[flexflow_tpu] warning: dropping {n % bs} tail samples "
                f"(dataset {n} % batch {bs})",
                name="tail_samples_dropped", dropped=n % bs,
            )
        if verify_strategy:
            # differential preflight (runtime/verify.py): K steps of the
            # searched strategy vs a serial single-device reference from
            # identical params/RNG; divergence raises
            # StrategyDivergenceError naming the first diverging op
            # BEFORE any real training budget is spent on a broken plan
            from ..runtime import verify as _vfy

            if verify_strategy not in (True, "preflight"):
                raise ValueError(
                    "fit(verify_strategy=...) accepts 'preflight' "
                    f"(got {verify_strategy!r})"
                )
            verdict = _vfy.verify_strategy(
                self, (xs, y), steps=2, batch_size=bs,
                raise_on_divergence=True,
            )
            obs.progress(
                "[verify] preflight: " + verdict.summary().split("\n")[0],
                verbose=verbose, name="verify_preflight", cat="runtime",
                ok=verdict.ok,
            )
        if (checkpoint_dir is not None or skip_nonfinite_steps
                or step_guard is not None or fault_injector is not None
                or preemption_signal is not None or elastic
                or health_monitor is not None or canary is not None
                or tuner is not None):
            # resilient stepwise loop (runtime/resilience.py): periodic
            # atomic checkpoints + mid-epoch resume, NaN/Inf step guard,
            # preemption handling, deterministic fault injection; with
            # elastic/health_monitor, the elastic runtime's topology-
            # change resume and hang watchdog ride along
            # (runtime/elastic.py)
            from ..runtime import resilience as _rz
            from ..runtime.elastic import shrunk_devices as _shrunk_devices

            failover_stack = contextlib.ExitStack()
            failovers = 0
            try:
                while True:
                    try:
                        return self._fit_resilient(
                            xs, y, bs, ep, verbose,
                            checkpoint_dir=checkpoint_dir,
                            checkpoint_every_n_steps=checkpoint_every_n_steps,
                            keep_last_n=keep_last_n, resume=resume,
                            skip_nonfinite_steps=skip_nonfinite_steps,
                            step_guard=step_guard,
                            max_consecutive_skips=max_consecutive_skips,
                            fault_injector=fault_injector,
                            preemption_signal=preemption_signal,
                            elastic=elastic,
                            health_monitor=health_monitor,
                            canary=canary,
                            tel=tel,
                            tuner=tuner,
                        )
                    except (_rz.SliceLossError, _rz.SliceDrained) as e:
                        # slice-granular failover: a SIMULATED whole-slice
                        # loss / drained preemption carries the surviving
                        # device count, so an elastic fit can shrink the
                        # visible device set in-process, re-search for the
                        # survivors and resume from the checkpoint the
                        # handler just flushed. Real (non-simulated)
                        # losses re-raise for the orchestrator, whose
                        # restart lands in restore_elastic.
                        surv = getattr(e, "surviving_devices", None)
                        if (not elastic or checkpoint_dir is None
                                or surv is None
                                or not getattr(e, "simulated", False)
                                or failovers >= 3):
                            raise
                        failovers += 1
                        # even a HANDLED slice loss leaves a forensics
                        # bundle: the post-incident review wants the
                        # pre-failover event tail, not just the recovery
                        obs.record_failure(e, where="slice_failover",
                                           surviving_devices=surv,
                                           attempt=failovers)
                        obs.event(
                            "slice_failover", cat="runtime", step=e.step,
                            kind=type(e).__name__,
                            surviving_devices=surv, attempt=failovers,
                        )
                        obs.count(
                            "ff_slice_failovers_total",
                            help="in-process shrink-onto-survivors "
                                 "failovers (fit elastic=True)",
                        )
                        obs.progress(
                            f"[elastic] {type(e).__name__} at step "
                            f"{e.step}: shrinking onto {surv} device(s), "
                            "re-searching and resuming from "
                            f"{e.checkpoint_path or 'last checkpoint'}",
                            verbose=verbose, name="slice_failover",
                            cat="runtime", step=e.step,
                            surviving_devices=surv,
                        )
                        if surv < len(jax.devices()):
                            failover_stack.enter_context(
                                _shrunk_devices(surv)
                            )
                        if preemption_signal is not None:
                            preemption_signal.clear()
                        # the drain checkpoint is the resume point
                        resume = True
                        # loop: re-entry sees mesh_is_live() False ->
                        # recompile_for_topology + checkpoint restore
            finally:
                failover_stack.close()
        # guard residue from a previous resilient fit would change the
        # step signature; drop it for the fast unguarded paths
        if self.executor.step_guard is not None:
            self.executor.set_step_guard(None)
        if getattr(self.state, "guard", None) is not None:
            self.state = dataclasses.replace(self.state, guard=None)
        step_fn = self.executor.build_train_step()
        in_pts = self.executor.input_pts
        if self.config.profiling:
            # reference: per-op event timing prints under --profiling
            # (kernels/linear_kernels.cu:94-117)
            from ..runtime.profiler import profile_ops

            first = next(self._batches(list(xs) + [y], bs))
            cast = [
                np.asarray(a, pt.data_type.np_dtype)
                for pt, a in zip(in_pts, first[:-1])
            ]
            profs = profile_ops(self, cast, backward=True)
            for op_name, p in sorted(profs.items(),
                                     key=lambda kv: -kv[1].total_s):
                obs.progress(
                    f"[profiling] {op_name}: {p.forward_s * 1e3:.3f} ms fwd"
                    f" + {p.backward_s * 1e3:.3f} ms bwd",
                    name="op_profile", cat="runtime", op=op_name,
                    forward_s=p.forward_s, backward_s=p.backward_s,
                )
        label_dt = self.label_tensor.data_type.jnp_dtype
        spd = max(1, self.config.iterations_per_dispatch)
        scan_fn = self.executor.build_train_scan() if spd > 1 else None
        self.perf_metrics = PerfMetrics()
        if jax.process_count() > 1:
            self._assert_same_global_batch(xs, y, bs)
        n_chips = max(1, self.executor.mesh.devices.size)
        tstep = 0
        start = time.time()
        num_samples = 0
        for epoch in range(ep):
            # per-epoch accumulator like the reference (PerfMetrics is reset
            # each epoch, model.cc reset_metrics)
            self.perf_metrics = PerfMetrics()
            # Keep partials on device during the epoch so host dispatch stays
            # ahead of the chip (no per-batch sync); fold once at epoch end.
            device_partials = []
            chunk: List[list] = []

            def flush(chunk):
                # fuse the chunk's steps into ONE dispatch (lax.scan driver
                # — the Legion trace-replay analog); partials come back
                # stacked on a steps axis
                nonlocal tstep
                with obs.mark("ff.fit.feed", cat="train", step=tstep) as feed:
                    bxs = [
                        self.executor.shard_batch_stack(
                            pt,
                            np.stack([np.asarray(b[i], pt.data_type.np_dtype)
                                      for b in chunk]),
                        )
                        for i, pt in enumerate(in_pts)
                    ]
                    bys = self.executor.put_replicated(
                        np.stack([b[-1] for b in chunk]).astype(label_dt)
                    )
                    # one key per step, split exactly like the stepwise
                    # path so dropout masks are identical whatever the
                    # dispatch grouping
                    subs = []
                    for _ in chunk:
                        self._rng, sub = jax.random.split(self._rng)
                        subs.append(sub)
                    keys = self.executor.put_replicated(jnp.stack(subs))
                with obs.mark("ff.train.step", cat="train", step_num=tstep,
                              steps=len(chunk)) as dispatch:
                    self.state, partials = scan_fn(self.state, bxs, bys, keys)
                device_partials.append(partials)
                if tel is not None:
                    tel.record_chunk(
                        first_step=tstep, steps=len(chunk),
                        dur_s=feed.dur + dispatch.dur, batch_size=bs,
                        n_chips=n_chips, t0=feed.t0,
                    )
                tstep += len(chunk)

            for batch in self._batches(list(xs) + [y], bs):
                if spd > 1:
                    chunk.append(batch)
                    if len(chunk) == spd:
                        flush(chunk)
                        chunk = []
                else:
                    with obs.mark("ff.fit.feed", cat="train",
                                  step=tstep) as feed:
                        bx = [
                            self.executor.shard_batch(pt, np.asarray(a, pt.data_type.np_dtype))
                            for pt, a in zip(in_pts, batch[:-1])
                        ]
                        by = self.executor.put_replicated(
                            np.asarray(batch[-1]).astype(label_dt)
                        )
                        self._rng, sub = jax.random.split(self._rng)
                        key = self.executor.put_replicated(sub)
                    # the dispatch (an enqueue); the device runs the step
                    # under the profiler's step marker of the same number
                    with obs.mark("ff.train.step", cat="train",
                                  step_num=tstep) as dispatch:
                        self.state, partials = step_fn(self.state, bx, by,
                                                       key)
                    device_partials.append(partials)
                    if tel is not None:
                        loss_val = None
                        dur_s = feed.dur + dispatch.dur
                        if tel.config.sync_per_step:
                            t_sync = time.perf_counter()
                            loss_val = float(
                                _fetch_global(partials["loss"]).ravel()[-1]
                            )
                            dur_s += time.perf_counter() - t_sync
                        tel.record_step(
                            step=tstep, dur_s=dur_s,
                            batch_size=bs, n_chips=n_chips, loss=loss_val,
                            t0=feed.t0,
                        )
                    tstep += 1
                num_samples += bs
            if chunk:  # tail chunk shorter than spd (own compiled shape)
                flush(chunk)
            # the epoch's one fetch of the partials: the host waits here
            # for the device to finish the steps dispatched above
            with obs.mark("ff.fit.fold", cat="train", epoch=epoch,
                          steps=len(device_partials)):
                folded = jax.tree_util.tree_map(
                    lambda *vs: sum(float(np.sum(_fetch_global(v)))
                                    for v in vs),
                    *device_partials,
                )
                last_loss = float(
                    _fetch_global(device_partials[-1]["loss"]).ravel()[-1]
                )
            folded.pop("loss", None)
            gnorm_sum = folded.pop("grad_norm", None)
            self.perf_metrics.update(folded)
            if tel is not None:
                tel.record_epoch(epoch=epoch, loss=last_loss,
                                 grad_norm_sum=gnorm_sum,
                                 steps=len(device_partials))
            obs.progress(
                f"epoch {epoch}: loss={last_loss:.4f} "
                + self.perf_metrics.report(),
                verbose=verbose, name="epoch", epoch=epoch, loss=last_loss,
            )
        with obs.mark("ff.fit.sync", cat="train", step=tstep):
            jax.block_until_ready(self.state.params)
        elapsed = time.time() - start
        # reference: transformer.cc:208-211 throughput print
        obs.progress(
            f"ELAPSED TIME = {elapsed:.4f}s, "
            f"THROUGHPUT = {num_samples / elapsed:.2f} samples/s",
            name="fit_done", elapsed_s=elapsed, samples=num_samples,
        )
        if tel is not None and getattr(tel.config, "step_profile", False):
            # in-situ step observatory (obs/step_profile.py): the step is
            # warm, the batch shapes are live — capture the measured
            # timeline + overlap/HBM reconciliation into this session
            from ..obs.step_profile import capture_into_session

            try:
                capture_into_session(self, tel, xs, y, bs)
            except Exception as e:  # fflint: disable=FFL002 — observability must not fail training
                warnings.warn(f"step-profile capture failed: {e}")
        return self.perf_metrics

    # ------------------------------------------------------------------
    # resilient training loop (runtime/resilience.py)
    # ------------------------------------------------------------------
    def _rng_key_data(self) -> list:
        """self._rng as a JSON-serializable list (checkpoint cursor)."""
        return np.asarray(jax.random.key_data(self._rng)).tolist()

    def _set_rng_from_key_data(self, data) -> None:
        arr = jnp.asarray(np.asarray(data, np.uint32))
        if jnp.issubdtype(self._rng.dtype, jax.dtypes.prng_key):
            arr = jax.random.wrap_key_data(arr)
        self._rng = arr

    def _save_resilient_ckpt(self, manager, step, epoch, batch_index,
                             done=False) -> str:
        """Checkpoint + the data-loader cursor: `batch_index` is the NEXT
        batch to run in `epoch`, and `rng` the key stream that batch will
        split from, so a resumed run replays the exact step sequence."""
        return manager.save(self, step, extra_meta={"train": {
            "epoch": epoch,
            "batch_index": batch_index,
            "rng": self._rng_key_data(),
            "done": done,
        }})

    def _canary_check(self, vfy, canary, prev_state, args, step_fn,
                      partials, fault_injector, manager, global_step,
                      epoch, bi, pnorm_fn, prev_pnorm, prev_loss):
        """SDC/determinism canary + per-step invariants
        (runtime/verify.py CanaryConfig). At the canary cadence the step
        is re-executed on the SAME cached inputs from the SAME pre-step
        state (args[0] still references it) and the two results compared;
        per-step invariants bound param-norm drift and loss deltas. Any
        violation reverts to the pre-step state, flushes it as a
        checkpoint (the state AFTER the step is untrusted) and raises —
        the same checkpoint-and-raise escalation the watchdog uses.
        Returns the updated (prev_pnorm, prev_loss) trackers."""
        def escalate(exc):
            obs.event("canary_violation", cat="runtime", step=global_step,
                      error=type(exc).__name__, detail=str(exc)[:500])
            obs.count("ff_canary_violations_total",
                      help="canary / invariant violations")
            self.state = prev_state
            if manager is not None:
                exc.checkpoint_path = self._save_resilient_ckpt(
                    manager, global_step, epoch, bi
                )
            raise exc

        if canary.every_n_steps > 0 \
                and global_step % canary.every_n_steps == 0:
            if fault_injector is not None:
                # SDC simulation: flip one bit in one weight of the FIRST
                # execution's result, as a faulty core would have
                # (target=None keeps disk-targeted plans for
                # CheckpointManager.save)
                plan = fault_injector.fire("bitflip", global_step,
                                           target=None)
                if plan is not None:
                    flipped, _name = vfy.bitflip_params(
                        self.state.params, op=plan.get("op"),
                        weight=plan.get("weight"),
                        bit=plan.get("bit", 6),
                        index=plan.get("index", 3),
                    )
                    self.state = dataclasses.replace(
                        self.state, params=flipped
                    )
            obs.count("ff_canary_checks_total",
                      help="canary step re-executions")
            state2, partials2 = step_fn(*args)
            bad = vfy.compare_step_results(
                {"params": self.state.params, "loss": partials["loss"]},
                {"params": state2.params, "loss": partials2["loss"]},
                mode=canary.mode, rtol=canary.rtol, atol=canary.atol,
            )
            if bad:
                escalate(vfy.CanaryMismatchError(
                    f"step {global_step}: canary re-execution disagrees "
                    f"({canary.mode} mode) — non-deterministic step or "
                    "silent data corruption: " + "; ".join(bad),
                    step=global_step, mismatches=bad,
                ))
        if pnorm_fn is not None:
            loss = float(_fetch_global(partials["loss"]).ravel()[-1])
            if not np.isfinite(loss) and self.executor.step_guard is None:
                escalate(vfy.InvariantViolationError(
                    f"step {global_step}: non-finite loss {loss} (enable "
                    "skip_nonfinite_steps for skip-and-rescale instead)",
                    step=global_step, invariant="finite_loss",
                ))
            if (canary.max_loss_delta is not None and prev_loss is not None
                    and abs(loss - prev_loss) > canary.max_loss_delta):
                escalate(vfy.InvariantViolationError(
                    f"step {global_step}: loss moved "
                    f"{abs(loss - prev_loss):.3g} in one step "
                    f"(bound {canary.max_loss_delta:g})",
                    step=global_step, invariant="loss_delta",
                ))
            pn = float(np.asarray(pnorm_fn(self.state.params)))
            if not np.isfinite(pn) or (
                prev_pnorm is not None and prev_pnorm > 0
                and pn > prev_pnorm * canary.max_param_norm_ratio
            ):
                escalate(vfy.InvariantViolationError(
                    f"step {global_step}: global param norm {pn:.3g} "
                    f"drifted past {canary.max_param_norm_ratio:g}x the "
                    f"previous step's ({prev_pnorm})",
                    step=global_step, invariant="param_norm_drift",
                ))
            return pn, loss
        return prev_pnorm, prev_loss

    def _fit_resilient(self, xs, y, bs, ep, verbose, *, checkpoint_dir,
                       checkpoint_every_n_steps, keep_last_n, resume,
                       skip_nonfinite_steps, step_guard,
                       max_consecutive_skips, fault_injector,
                       preemption_signal, elastic=False,
                       health_monitor=None, canary=None, tel=None,
                       tuner=None):
        from ..runtime import resilience as rz
        from ..runtime import verify as vfy

        if elastic and not self.executor.mesh_is_live():
            # a host (and its devices) disappeared since compile(): any
            # dispatch onto the stale mesh would wedge. Re-search the
            # strategy for the surviving machine and recompile; the
            # checkpoint restore below reshards the weights onto it.
            n = len(jax.devices())
            obs.progress(
                f"[elastic] device topology changed; re-searching "
                f"strategy for {n} device(s) and recompiling",
                verbose=verbose, name="elastic_recompile", cat="runtime",
                devices=n,
            )
            self.recompile_for_topology(n)
            if tel is not None:
                # the recompile minted a fresh trajectory/executor —
                # replay the re-search into the event log too
                tel._attached_models = [
                    m for m in tel._attached_models if m is not self
                ]
                tel.attach_model(self)

        guard_cfg = step_guard
        if guard_cfg is None and skip_nonfinite_steps:
            guard_cfg = rz.StepGuardConfig(
                max_consecutive_skips=max_consecutive_skips
            )
        self.executor.set_step_guard(guard_cfg)
        if guard_cfg is not None and getattr(self.state, "guard", None) is None:
            self.state = dataclasses.replace(
                self.state, guard=self.executor.init_guard_state()
            )
        elif guard_cfg is None and getattr(self.state, "guard", None) is not None:
            self.state = dataclasses.replace(self.state, guard=None)

        n = xs[0].shape[0]
        steps_per_epoch = n // bs
        manager = None
        if checkpoint_dir is not None:
            manager = rz.CheckpointManager(
                checkpoint_dir, keep_last_n=keep_last_n,
                fault_injector=fault_injector,
            )
        every = checkpoint_every_n_steps or steps_per_epoch
        preempt = preemption_signal or rz.PreemptionSignal()
        # drain-protocol state: how many steps ran inside a preemption
        # notice's grace window, whether the notice came from the fault
        # injector (simulated -> in-process failover may shrink devices
        # itself), and the last measured checkpoint-flush duration (feeds
        # the executor's drain-window estimate)
        drain_steps = 0
        drain_simulated = False
        drain_max_steps = None
        last_ckpt_dur_s = None
        mon = health_monitor
        if mon is not None:
            if getattr(mon, "fault_domains", None) is None:
                # share compile()'s fault-domain map so peer staleness
                # classifies per slice (host loss vs whole-slice loss)
                mon.fault_domains = getattr(self, "fault_domains", None)
            mon.start()

        # -- strategy tuner (runtime/tuner.py): fit(tuner=TunerConfig(...))
        # arms the self-healing re-search/hot-swap loop. It observes the
        # synced step durations below and acts between steps; when it
        # swaps (commit or rollback) the live executor changes and the
        # step function/input layout are rebuilt after the boundary hook.
        tuner_obj = None
        if tuner is not None:
            from ..runtime.tuner import StrategyTuner
            from ..runtime.tuner import TunerConfig as _TunerCfg

            if isinstance(tuner, StrategyTuner):
                tuner_obj = tuner
            else:
                tuner_obj = StrategyTuner(
                    self,
                    tuner if isinstance(tuner, _TunerCfg) else _TunerCfg(),
                    fault_injector=fault_injector,
                )
            # persisted quarantines (runtime/artifact_store.py): a
            # candidate rolled back by a previous process is never
            # re-proposed; committed winners write through for reuse
            tuner_obj.attach_artifact_store(
                getattr(self, "artifact_store", None))
            self._tuner = tuner_obj

        # the canary re-executes steps from the pre-step state, which
        # donation would have reclaimed on accelerators — use the
        # undonated step variant when it is armed
        step_fn = self.executor.build_train_step(donate=(canary is None))
        in_pts = self.executor.input_pts
        label_dt = self.label_tensor.data_type.jnp_dtype
        n_chips = max(1, self.executor.mesh.devices.size)
        if jax.process_count() > 1:
            self._assert_same_global_batch(xs, y, bs)
        pnorm_fn = None
        prev_pnorm = None
        prev_loss = None
        if canary is not None and canary.check_invariants:
            from ..parallel.executor import global_grad_norm

            pnorm_fn = jax.jit(global_grad_norm)

        start_epoch, start_batch, global_step = 0, 0, 0
        if manager is not None and resume:
            info = manager.restore_latest(self, elastic=elastic)
            if info is not None and elastic:
                from ..runtime.elastic import (
                    topology_fingerprint,
                    topology_matches,
                )

                saved_topo = (info.meta or {}).get("topology")
                live_topo = topology_fingerprint(self.executor.mesh)
                if not topology_matches(saved_topo, live_topo):
                    obs.progress(
                        f"[elastic] resumed step {info.step} across a "
                        f"topology change "
                        f"({(saved_topo or {}).get('num_devices', '?')} -> "
                        f"{live_topo['num_devices']} devices); strategy "
                        "re-searched and parameters resharded",
                        verbose=verbose, name="elastic_resume",
                        cat="runtime", step=info.step,
                        saved_devices=(saved_topo or {}).get("num_devices"),
                        live_devices=live_topo["num_devices"],
                    )
            if info is not None:
                tm = (info.meta or {}).get("train", {})
                start_epoch = int(tm.get("epoch", 0))
                start_batch = int(tm.get("batch_index", 0))
                if tm.get("rng") is not None:
                    self._set_rng_from_key_data(tm["rng"])
                global_step = info.step
                if start_batch >= steps_per_epoch:
                    start_epoch += 1
                    start_batch = 0
                obs.progress(
                    f"[resilience] resumed from step {info.step} "
                    f"(epoch {start_epoch}, batch {start_batch})",
                    verbose=verbose, name="checkpoint_resume",
                    cat="checkpoint", step=info.step, epoch=start_epoch,
                    batch=start_batch,
                )

        self.perf_metrics = PerfMetrics()
        start = time.time()
        num_samples = 0
        epoch, bi = start_epoch, start_batch
        try:
            for epoch in range(start_epoch, ep):
                self.perf_metrics = PerfMetrics()
                device_partials = []
                for bi, batch in enumerate(self._batches(list(xs) + [y], bs)):
                    if epoch == start_epoch and bi < start_batch:
                        continue
                    # -- preemption check BETWEEN steps (SIGTERM-style) --
                    if fault_injector is not None:
                        plan = fault_injector.fire("preempt", global_step)
                        if plan is not None:
                            preempt.trigger(
                                graceful=plan.get("graceful", True)
                            )
                    if fault_injector is not None:
                        plan = fault_injector.fire("preemption_notice",
                                                   global_step)
                        if plan is not None:
                            # deadline-bearing drain notice (simulated pod
                            # manager grace): arm the signal WITH its
                            # deadline; the drain protocol below uses the
                            # grace window instead of stopping immediately
                            preempt.trigger(
                                graceful=True,
                                deadline_s=plan.get("deadline_s", 30.0),
                                leaving_slice=plan.get("slice"),
                                surviving_devices=plan.get(
                                    "surviving_devices"
                                ),
                            )
                            drain_simulated = True
                            if plan.get("max_drain_steps") is not None:
                                drain_max_steps = int(
                                    plan["max_drain_steps"]
                                )
                    if preempt.triggered() and not preempt.draining:
                        raise rz.TrainingPreempted(
                            f"preempted before step {global_step}",
                            step=global_step, graceful=preempt.graceful,
                        )
                    if preempt.draining:
                        # -- drain protocol: the notice granted a grace
                        # deadline. Keep training while the remaining
                        # grace comfortably exceeds one more step + a
                        # checkpoint flush (executor drain window), then
                        # flush a final checkpoint and hand off to the
                        # slice failover (fit(elastic=True)) / the
                        # orchestrator BEFORE the deadline lands.
                        remaining = preempt.deadline_remaining()
                        window = self.executor.drain_window_s(
                            checkpoint_s=last_ckpt_dur_s
                        )
                        if drain_steps == 0:
                            obs.event(
                                "preemption_notice", cat="runtime",
                                step=global_step,
                                deadline_s=preempt.deadline_s,
                                leaving_slice=preempt.leaving_slice,
                                surviving_devices=preempt.surviving_devices,
                            )
                            obs.progress(
                                f"[resilience] preemption notice: "
                                f"{preempt.deadline_s:.1f}s grace"
                                + (f", slice {preempt.leaving_slice} "
                                   "leaving"
                                   if preempt.leaving_slice is not None
                                   else "")
                                + f"; draining (window {window:.2f}s)",
                                verbose=verbose, name="preemption_notice",
                                cat="runtime", step=global_step,
                            )
                        if remaining <= window or (
                            drain_max_steps is not None
                            and drain_steps >= drain_max_steps
                        ):
                            exc = rz.SliceDrained(
                                f"drained {drain_steps} step(s) under a "
                                f"{preempt.deadline_s:.1f}s preemption "
                                f"deadline before step {global_step}",
                                step=global_step,
                                deadline_s=preempt.deadline_s,
                                drained_steps=drain_steps,
                                leaving_slice=preempt.leaving_slice,
                                surviving_devices=preempt.surviving_devices,
                            )
                            exc.simulated = drain_simulated
                            if manager is not None:
                                exc.checkpoint_path = \
                                    self._save_resilient_ckpt(
                                        manager, global_step, epoch, bi
                                    )
                            left = preempt.deadline_remaining()
                            exc.met_deadline = (left is None or left >= 0.0)
                            self.search_trajectory.event(
                                "slice_drain", step=global_step,
                                deadline_s=preempt.deadline_s,
                                drained_steps=drain_steps,
                                met_deadline=exc.met_deadline,
                                leaving_slice=preempt.leaving_slice,
                            )
                            obs.event(
                                "slice_drain", cat="runtime",
                                step=global_step,
                                drained_steps=drain_steps,
                                met_deadline=exc.met_deadline,
                                checkpoint=exc.checkpoint_path,
                            )
                            raise exc
                        drain_steps += 1
                    if fault_injector is not None:
                        plan = fault_injector.fire("slice_loss", global_step)
                        if plan is not None:
                            # an entire fault domain vanished at once —
                            # the slice-granular analog of host_loss. The
                            # TrainingPreempted handler below flushes the
                            # final checkpoint; fit(elastic=True) then
                            # shrinks onto the survivors and resumes.
                            lost = plan.get("slice")
                            surv = plan.get("surviving_devices")
                            if surv is None and lost is not None and \
                                    getattr(self, "fault_domains", None):
                                surv = len(self.fault_domains
                                           .surviving_devices([lost]))
                            err = rz.SliceLossError(
                                f"slice {lost} lost before step "
                                f"{global_step}",
                                step=global_step,
                                graceful=plan.get("graceful", True),
                                lost_slice=lost,
                                surviving_devices=surv,
                            )
                            err.simulated = True
                            self.search_trajectory.event(
                                "slice_lost", step=global_step,
                                slice=lost, surviving_devices=surv,
                            )
                            obs.event("slice_lost", cat="runtime",
                                      step=global_step, slice=lost,
                                      surviving_devices=surv)
                            obs.count(
                                "ff_slice_losses_total",
                                help="whole-slice losses (real + injected)",
                            )
                            if lost is not None:
                                obs.gauge_set(
                                    "ff_slice_healthy", 0.0,
                                    help="1 while a fault domain's hosts "
                                         "all heartbeat, 0 once lost",
                                    slice=lost,
                                )
                            raise err
                    if fault_injector is not None:
                        plan = fault_injector.fire("host_loss", global_step)
                        if plan is not None:
                            # a host dropped out: flush-and-exit (the
                            # TrainingPreempted handler below writes the
                            # final checkpoint) so the orchestrator can
                            # restart elastically on the survivors
                            raise rz.HostLossError(
                                f"host lost before step {global_step}",
                                step=global_step,
                                graceful=plan.get("graceful", True),
                                surviving_devices=plan.get(
                                    "surviving_devices"
                                ),
                            )
                    if mon is not None:
                        if (fault_injector is not None
                                and fault_injector.fire("hung_step",
                                                        global_step)):
                            # simulated dead collective: blocks until the
                            # watchdog detects the stall and releases us
                            mon.simulate_hang()
                        if mon.hang_detected:
                            info = mon.hang_info
                            if info.get("kind") == "slice_loss":
                                # every host of a slice stopped
                                # heartbeating: whole-slice loss, not a
                                # straggler — flush-and-exit through the
                                # slice-granular error so recovery shrinks
                                # onto the survivors instead of waiting
                                # for the dead slice
                                lost = (info.get("lost_slices")
                                        or [None])[0]
                                err = rz.SliceLossError(
                                    "health watchdog: whole-slice loss "
                                    f"detected before step {global_step} "
                                    f"({info.get('classification', info)})",
                                    step=global_step, lost_slice=lost,
                                    surviving_devices=info.get(
                                        "surviving_devices"
                                    ),
                                )
                                self.search_trajectory.event(
                                    "slice_lost", step=global_step,
                                    slice=lost,
                                    surviving_devices=info.get(
                                        "surviving_devices"
                                    ),
                                )
                                raise err
                            raise rz.CollectiveTimeout(
                                "health watchdog: "
                                f"{info.get('kind', 'hang')} "
                                f"detected before step {global_step} "
                                f"({info})",
                                step=global_step, info=info,
                            )
                        mon.step_started(global_step)
                    t0 = time.perf_counter()
                    bx = [
                        self.executor.shard_batch(
                            pt, np.asarray(a, pt.data_type.np_dtype)
                        )
                        for pt, a in zip(in_pts, batch[:-1])
                    ]
                    by = self.executor.put_replicated(
                        np.asarray(batch[-1]).astype(label_dt)
                    )
                    self._rng, sub = jax.random.split(self._rng)
                    args = [self.state, bx, by,
                            self.executor.put_replicated(sub)]
                    if guard_cfg is not None:
                        poison = 1.0
                        if fault_injector is not None and \
                                fault_injector.fire("nan_grads", global_step):
                            poison = float("nan")
                        args.append(self.executor.put_replicated(
                            jnp.asarray(poison, jnp.float32)
                        ))
                    prev_state = self.state if canary is not None else None
                    self.state, partials = step_fn(*args)
                    if mon is not None:
                        # the watchdog can only observe completion if we
                        # wait for it — per-step sync is the price of
                        # hang detection (documented in docs/resilience.md)
                        jax.block_until_ready(partials["loss"])
                        mon.step_finished(global_step)
                    if (mon is not None or preempt.draining
                            or tuner_obj is not None):
                        # feed the executor's step-time EMA (drain-window
                        # estimate) and the tuner's drift watch — only
                        # from synced steps, where the wall time measures
                        # the step and not a dispatch
                        if mon is None:
                            jax.block_until_ready(partials["loss"])
                        _dur = time.perf_counter() - t0
                        self.executor.note_step_duration(_dur)
                        if tuner_obj is not None:
                            tuner_obj.observe_step(_dur)
                    if canary is not None:
                        prev_pnorm, prev_loss = self._canary_check(
                            vfy, canary, prev_state, args, step_fn,
                            partials, fault_injector, manager,
                            global_step, epoch, bi, pnorm_fn,
                            prev_pnorm, prev_loss,
                        )
                    if tel is not None:
                        loss_val = None
                        if tel.config.sync_per_step or mon is not None:
                            # the monitor already synced on the loss, so
                            # fetching it costs nothing extra
                            loss_val = float(
                                _fetch_global(partials["loss"]).ravel()[-1]
                            )
                        tel.record_step(
                            step=global_step,
                            dur_s=time.perf_counter() - t0,
                            batch_size=bs, n_chips=n_chips, loss=loss_val,
                            t0=t0,
                        )
                    device_partials.append(partials)
                    num_samples += bs
                    global_step += 1
                    if guard_cfg is not None:
                        # skip monitor: a run stuck on non-finite grads
                        # must fail loudly, not silently stop learning
                        skips = int(_fetch_global(
                            self.state.guard.consecutive_skips
                        ))
                        if tel is not None:
                            tel.metrics.gauge(
                                "ff_loss_scale",
                                "dynamic loss scale (step guard)",
                            ).set(float(_fetch_global(
                                self.state.guard.loss_scale
                            )))
                        if skips >= guard_cfg.max_consecutive_skips:
                            raise rz.NonFiniteGradientsError(
                                f"{skips} consecutive non-finite gradient "
                                f"steps (step {global_step}); loss_scale="
                                f"{float(_fetch_global(self.state.guard.loss_scale)):g}"
                            )
                    if tuner_obj is not None and not preempt.draining:
                        # step-boundary tuner hook: probe/trigger/collect
                        # the background search, execute a pending swap
                        # transactionally, police the guard window. A True
                        # return means the LIVE EXECUTOR changed (commit
                        # or rollback) — rebuild the step function and
                        # input layout for the new strategy. A swap during
                        # a preemption drain is suppressed: the grace
                        # window is for checkpointing, not re-planning.
                        if tuner_obj.on_step_boundary(
                            global_step, batch=(batch[:-1], batch[-1])
                        ):
                            step_fn = self.executor.build_train_step(
                                donate=(canary is None)
                            )
                            in_pts = self.executor.input_pts
                            n_chips = max(
                                1, self.executor.mesh.devices.size
                            )
                    if manager is not None and global_step % every == 0:
                        _ck0 = time.perf_counter()
                        self._save_resilient_ckpt(
                            manager, global_step, epoch, bi + 1
                        )
                        last_ckpt_dur_s = time.perf_counter() - _ck0
                if device_partials:
                    folded = jax.tree_util.tree_map(
                        lambda *vs: sum(
                            float(np.sum(_fetch_global(v))) for v in vs
                        ),
                        *device_partials,
                    )
                    last_loss = float(
                        _fetch_global(device_partials[-1]["loss"]).ravel()[-1]
                    )
                    folded.pop("loss", None)
                    skipped = folded.pop("skipped", 0.0)
                    gnorm_sum = folded.pop("grad_norm", None)
                    self.perf_metrics.update(folded)
                    if tel is not None:
                        tel.record_epoch(
                            epoch=epoch, loss=last_loss,
                            grad_norm_sum=gnorm_sum,
                            steps=len(device_partials), skipped=skipped,
                        )
                    extra = (f" skipped_steps={int(skipped)}"
                             if skipped else "")
                    obs.progress(
                        f"epoch {epoch}: loss={last_loss:.4f} "
                        + self.perf_metrics.report() + extra,
                        verbose=verbose, name="epoch", epoch=epoch,
                        loss=last_loss, skipped_steps=int(skipped),
                    )
        except rz.TrainingPreempted as e:
            if manager is not None and e.graceful \
                    and e.checkpoint_path is None:
                # SIGTERM grace period: flush a final checkpoint so the
                # resumed run continues exactly where this one stopped
                # (the drain protocol already wrote SliceDrained's —
                # don't save twice)
                e.checkpoint_path = self._save_resilient_ckpt(
                    manager, global_step, epoch, bi
                )
            raise
        except rz.CollectiveTimeout as e:
            # checkpoint-and-raise: flush the last good state, then exit
            # through the typed error so the orchestrator restarts
            # elastically instead of leaving a deadlocked psum spinning
            if manager is not None:
                e.checkpoint_path = self._save_resilient_ckpt(
                    manager, global_step, epoch, bi
                )
            raise
        jax.block_until_ready(self.state.params)
        if manager is not None:
            self._save_resilient_ckpt(manager, global_step, ep, 0, done=True)
        elapsed = time.time() - start
        if num_samples:
            obs.progress(
                f"ELAPSED TIME = {elapsed:.4f}s, "
                f"THROUGHPUT = {num_samples / elapsed:.2f} samples/s",
                name="fit_done", elapsed_s=elapsed, samples=num_samples,
            )
        if tel is not None and getattr(tel.config, "step_profile", False):
            # same in-situ capture epilogue as the plain loop: the
            # resilient route is the only one the tuner takes, and the
            # overlay it publishes is where the strategy-swap boundary
            # instants land (obs/step_profile.py publish_step_profile)
            from ..obs.step_profile import capture_into_session

            try:
                capture_into_session(self, tel, xs, y, bs)
            except Exception as e:  # fflint: disable=FFL002 — observability must not fail training
                warnings.warn(f"step-profile capture failed: {e}")
        return self.perf_metrics

    def eval(self, x=None, y=None, batch_size: Optional[int] = None):
        if self.executor is None:
            from ..runtime.verify import NotCompiledError

            raise NotCompiledError("eval: call compile() first")
        x, y = _unwrap_loaders(x, y)
        xs = x if isinstance(x, (list, tuple)) else [x]
        bs = batch_size or self.config.batch_size
        step_fn = self.executor.build_eval_step()
        in_pts = self.executor.input_pts
        pm = PerfMetrics()
        for batch in self._batches(list(xs) + [y], bs):
            bx = [
                self.executor.shard_batch(pt, np.asarray(a, pt.data_type.np_dtype))
                for pt, a in zip(in_pts, batch[:-1])
            ]
            by = jnp.asarray(batch[-1], self.label_tensor.data_type.jnp_dtype)
            _, partials = step_fn(self.state.params, bx, by,
                                  self.state.net_state)
            pm.update({k: float(v) for k, v in partials.items()})
        obs.progress(pm.report(), name="eval_done")
        return pm

    def predict(self, x, batch_size: Optional[int] = None):
        assert self.executor is not None
        xs = x if isinstance(x, (list, tuple)) else [x]
        fwd = self.executor.build_forward()
        bs = batch_size or self.config.batch_size
        outs = []
        n = xs[0].shape[0]
        for i in range(0, n, bs):
            chunk = [a[i : i + bs] for a in xs]
            pad = bs - chunk[0].shape[0]
            if pad > 0:  # pad the tail batch to the compiled batch size
                chunk = [
                    np.concatenate([c, np.repeat(c[-1:], pad, axis=0)], axis=0)
                    for c in chunk
                ]
            bx = [jnp.asarray(c) for c in chunk]
            out = np.asarray(fwd(self.state.params, bx,
                                 self.state.net_state))
            outs.append(out[: bs - pad] if pad > 0 else out)
        return np.concatenate(outs, axis=0) if outs else np.empty((0,))

    # -- stepwise API for cffi parity (reference: model.cc forward/backward/
    #    update/zero_gradients driven from flexflow_cffi.fit) -------------
    def set_iteration_batch(self, inputs: List[np.ndarray], label: np.ndarray):
        self._current_batch = (inputs, label)

    def _bound_inputs(self) -> List:
        inputs, _ = self._current_batch
        for i, a in enumerate(inputs):
            assert a is not None, (
                f"input tensor '{self._fit_input_tensors[i].name or i}' was "
                "never attached — call set_tensor/attach_numpy_array first"
            )
        return inputs

    def forward(self, seq_length: int = -1):
        assert self.executor is not None and self._current_batch is not None
        fwd = self.executor.build_forward(seq_length)
        bx = [jnp.asarray(a) for a in self._bound_inputs()]
        self._last_logits = fwd(self.state.params, bx, self.state.net_state)
        # The stepwise loop is synchronous like the reference's per-phase
        # Legion tasks. Blocking also keeps two sharded programs with
        # collectives from running concurrently, which can wedge the
        # CPU-mesh in-process all-reduce rendezvous.
        jax.block_until_ready(self._last_logits)
        return self._last_logits

    def zero_gradients(self):
        self._pending_grads = None

    def backward(self, seq_length: int = -1):
        assert self.executor is not None and self._current_batch is not None
        _, label = self._current_batch
        assert label is not None, (
            "label tensor was never attached — call set_tensor/"
            "attach_numpy_array on ffmodel.label_tensor first"
        )
        bx = [jnp.asarray(a) for a in self._bound_inputs()]
        by = jnp.asarray(label, self.label_tensor.data_type.jnp_dtype)
        # one jitted program (not eager per-op sharded execution, which
        # loses fusion and can wedge the CPU-mesh in-process collectives);
        # cached + invalidated on the executor like the other step traces
        grad_fn = self.executor.build_grad_step(seq_length)
        self._pending_grads, self._pending_net_state = grad_fn(
            self.state.params, bx, by, self.state.net_state
        )
        jax.block_until_ready(self._pending_grads)  # see forward()

    def update(self):
        assert self._pending_grads is not None, "call backward() first"
        new_params, new_opt = self.optimizer.update(
            self.state.params, self._pending_grads, self.state.opt_state
        )
        net_state = dict(self.state.net_state)
        net_state.update(getattr(self, "_pending_net_state", None) or {})
        self.state = TrainState(
            params=new_params, opt_state=new_opt, step=self.state.step + 1,
            net_state=net_state, guard=self.state.guard,
        )
        self._pending_grads = None
        self._pending_net_state = None

    def output_probability_like(self, output_index: int = -1) -> Optional[bool]:
        """Whether the model's output carries PROBABILITIES (tail op is
        softmax/sigmoid or a fused sigmoid activation) rather than raw
        logits. None when undetermined (not compiled / output untraced).
        Serving's beam scorer uses this instead of sniffing values."""
        if self.graph is None:
            return None
        outs = self.graph.output_tensors()
        if not outs:
            return None
        pt = outs[output_index]
        ops = [o for o in self.graph.ops
               if any(t.guid == pt.guid for t in o.outputs)]
        if not ops:
            return None
        return _probability_like_tail(*_resolve_value_tail(ops[0]))

    def get_perf_metrics(self) -> PerfMetrics:
        return self.perf_metrics

    def reset_metrics(self):
        """reference: flexflow_cffi.py:1968 reset_metrics."""
        self.perf_metrics = PerfMetrics()

    def compute_metrics(self):
        """Fold the current batch's metrics into perf_metrics
        (reference: flexflow_cffi.py:2004 compute_metrics)."""
        assert self._last_logits is not None and self._current_batch is not None
        _, label = self._current_batch
        from ..parallel.executor import truncate_labels

        by = jnp.asarray(label, self.label_tensor.data_type.jnp_dtype)
        by = truncate_labels(by, self._last_logits)
        partials = self.metrics_obj.compute(self._last_logits, by)
        self.perf_metrics.update(
            {k: float(v) for k, v in partials.items() if k != "loss"}
        )
        return self.perf_metrics

    def init_layers(self):
        """Re-initialize all weights (reference: flexflow_cffi.py:1975;
        there a Legion task per weight — here a fresh executor state)."""
        assert self.executor is not None, "call compile() first"
        self.state = self.executor.init_state()

    def prefetch(self):
        """No-op: XLA prefetches HBM transfers itself; kept for script
        compatibility (reference: flexflow_cffi.py:1982)."""

    def map_tensor(self, tensor, parallel_op=None):
        """No-op: tensors materialize with their NamedSharding at first use
        (reference: flexflow_cffi.py:937 maps Legion regions)."""

    def create_constant(self, dims, value, data_type=DataType.DT_FLOAT):
        """Constant input tensor: materialized by the executor, never part
        of fit()'s batch inputs (reference: flexflow_cffi.py:941)."""
        t = self.create_tensor(dims, data_type, create_grad=False)
        self._constant_values[t.guid] = float(value)
        return t

    def create_constant_tensor(self, array, data_type=None):
        """Constant tensor with arbitrary (non-trainable) contents — used by
        the torch frontend to bake traced masks/indices into the graph."""
        arr = np.asarray(array)
        dt = to_data_type(arr.dtype) if data_type is None else data_type
        t = self.create_tensor(arr.shape, dt, create_grad=False)
        self._constant_values[t.guid] = arr.astype(dt.np_dtype)
        return t

    def get_layers(self) -> Dict[int, Layer]:
        return dict(enumerate(self.layers))

    def get_layer_by_id(self, idx: int) -> Layer:
        return self.layers[idx]

    def get_layer_by_name(self, name: str) -> Layer:
        for layer in self.layers:
            if layer.name == name:
                return layer
        raise KeyError(f"no layer named {name!r}")

    def get_last_layer(self) -> Layer:
        return self.layers[-1]

    def print_layers(self, id: int = -1):
        """reference: flexflow_cffi.py print_layers."""
        for i, layer in enumerate(self.layers):
            if id in (-1, i):
                shapes = [tuple(t.dims) for t in layer.outputs]
                # user-facing inspection API: printing IS the contract
                print(  # fflint: disable=FFL201
                    f"layer {i}: {layer.name} ({layer.op_type.name}) "
                    f"-> {shapes}")

    # ------------------------------------------------------------------
    # weight access (reference: parallel_tensor.cc set_tensor/get_tensor)
    # ------------------------------------------------------------------
    def _find_weight_slot(self, t: Tensor):
        layer = t.owner_layer
        if layer is None or self.state is None:
            return None
        for i, wt in enumerate(layer.weights):
            if wt.guid == t.guid:
                # weight name from the lowered op
                for op in self.graph.ops:
                    if op.layer_guid == layer.guid:
                        return op.name, op.weight_names[i]
        return None

    def _get_tensor_value(self, t: Tensor):
        slot = self._find_weight_slot(t)
        if slot is not None:
            return np.asarray(self.state.params[slot[0]][slot[1]])
        if self._current_batch is not None:
            ins, lab = self._current_batch
            if (self.label_tensor is not None
                    and t.guid == self.label_tensor.guid and lab is not None):
                return np.asarray(lab)
            for i, ft in enumerate(self._fit_input_tensors):
                if ft.guid == t.guid and ins[i] is not None:
                    return np.asarray(ins[i])
        raise KeyError(f"tensor {t} is not a weight; activations are not retained")

    def _set_tensor_value(self, t: Tensor, value: np.ndarray):
        slot = self._find_weight_slot(t)
        if slot is None:
            # input or label tensor: bind the batch for the stepwise loop
            # (reference: mnist_mlp_attach.py input.set_tensor per batch)
            return self._attach_array(t, value)
        op_name, w_name = slot
        old = self.state.params[op_name][w_name]
        assert tuple(value.shape) == tuple(old.shape), (
            f"shape mismatch {value.shape} vs {old.shape}"
        )
        self.state.params[op_name][w_name] = jax.device_put(
            value.astype(old.dtype), old.sharding
        )

    def _attach_array(self, t: Tensor, arr) -> None:
        """Bind a numpy array to an input/label tensor for the stepwise
        forward/backward/update loop (reference: attach_numpy_array,
        flexflow_cffi.py — zero-copy Legion attach; here the array feeds
        the next jitted call)."""
        assert self.executor is not None, "attach needs compile() first"
        arr = np.asarray(arr)
        n = len(self.executor.input_pts)
        ins, lab = self._current_batch or ([None] * n, None)
        ins = list(ins)
        if self.label_tensor is not None and t.guid == self.label_tensor.guid:
            self._current_batch = (ins, arr)
            return
        for i, ft in enumerate(self._fit_input_tensors):
            if ft.guid == t.guid:
                ins[i] = arr
                self._current_batch = (ins, lab)
                return
        raise KeyError(
            f"tensor {t} is neither a weight, a graph input, nor the label"
        )

    def create_data_loader(self, batch_tensor: Tensor, full_array: np.ndarray):
        from .dataloader import SingleDataLoader

        dl = SingleDataLoader(self, batch_tensor, full_array)
        self._dataloaders.append(dl)
        return dl


class _LoopBuilder:
    """What `FFModel.loop` yields: marks the region's entry and exit."""

    def __init__(self, model: "FFModel", steps: int, name: str):
        from ..pcg.graph import LoopMark

        self.model, self.name = model, name
        self.mark = LoopMark(name, steps)
        self.entry: Optional[Layer] = None
        self.exit_layer: Optional[Layer] = None

    def _role(self, layer: Layer, role: str) -> None:
        layer.loop = dataclasses.replace(self.mark, role=role)

    def enter(self, x: Tensor) -> Tensor:
        if self.entry is not None:
            raise ValueError(f"loop {self.name!r} has an entry already")
        h = self.model.identity(x, name=f"{self.name}.entry")
        self.entry = h.owner_layer
        self._role(self.entry, "entry")
        return h

    def exit(self, y: Tensor) -> Tensor:
        layer = y.owner_layer
        if self.entry is None or layer is None or layer is self.entry \
                or layer.loop is None or layer.loop.name != self.name:
            raise ValueError(f"loop {self.name!r}: exit() takes a tensor "
                             "the body made after enter()")
        if y.owner_idx != 0 or y.dims != self.entry.inputs[0].dims:
            raise ValueError(f"loop {self.name!r}: the exit must be its "
                             "op's first output, shaped as the entry's input")
        self.exit_layer = layer
        self._role(layer, "exit")
        return y

    def close(self) -> None:
        if self.entry is None or self.exit_layer is None:
            raise ValueError(f"loop {self.name!r} needs enter() and exit()")


def _unwrap_loaders(x, y):
    """fit/eval accept SingleDataLoader objects for x/y like the reference
    (flexflow_cffi.py fit(x=dataloader_input, y=dataloader_label)); unwrap
    them to their backing arrays."""
    from .dataloader import SingleDataLoader

    def unwrap(v):
        if isinstance(v, SingleDataLoader):
            return v.full_array[: v.num_samples]
        return v

    if isinstance(x, (list, tuple)):
        x = [unwrap(v) for v in x]
    else:
        x = unwrap(x)
    return x, unwrap(y)


def _to_regularizer(reg):
    """Normalize a kernel_regularizer spec to (RegularizerMode, lambda).

    Accepts keras-style objects with `.type`/`._lambda` (frontends/keras/
    regularizers.py), ("l1"|"l2", lam) tuples, or a bare float (treated as L2
    like the reference's kernel_reg_lambda, linear.cc:41)."""
    if reg is None:
        return RegularizerMode.REG_MODE_NONE, 0.0
    if isinstance(reg, (int, float)):
        return RegularizerMode.REG_MODE_L2, float(reg)
    if isinstance(reg, tuple):
        kind, lam = reg
        mode = {
            "l1": RegularizerMode.REG_MODE_L1,
            "l2": RegularizerMode.REG_MODE_L2,
        }[str(kind).lower()]
        return mode, float(lam)
    return RegularizerMode(reg.type), float(reg._lambda)


def _to_dt(dt) -> DataType:
    if isinstance(dt, DataType):
        return dt
    from ..ff_types import to_data_type

    return to_data_type(dt)


def _to_acti(a) -> ActiMode:
    if isinstance(a, ActiMode):
        return a
    if a in (None, "none"):
        return ActiMode.AC_MODE_NONE
    return {
        "relu": ActiMode.AC_MODE_RELU,
        "sigmoid": ActiMode.AC_MODE_SIGMOID,
        "tanh": ActiMode.AC_MODE_TANH,
        "gelu": ActiMode.AC_MODE_GELU,
        "silu": ActiMode.AC_MODE_SILU,
        "relu2": ActiMode.AC_MODE_RELU2,
    }[a]
