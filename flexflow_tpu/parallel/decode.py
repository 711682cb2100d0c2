"""Incremental (KV-cache) decoding for arbitrary PCGs: the plan, the step
built from it (build_step, behind PCGExecutor.build_decode) and the tree
of caches the step steps, which this module alone takes apart (SLOT_SECTIONS
and the functions beside it: insert_row, take_rows, state_bytes,
declared_state_bytes). Which ops keep state between steps, and what, is
each op definition's own answer (ops/registry.py init_decode_state,
decode_section).

The reference's serving story is a Triton prototype that replays a full
forward per request (triton/README.md: "incomplete prototype"); it has no
incremental decode at all. This module gives the TPU build O(1)-per-token
decoding for ANY causal decoder or encoder-decoder PCG — including graphs
imported from HF (mt5), where attention is built from primitive ops
(batch_matmul / softmax / elementwise masks) rather than the fused MHA op.

How: classify every tensor by how the decode position flows through it.

  * live axis    — the axis indexed by decoder position; per step only the
    newest s0 positions are computed (s0 = 1, or prompt_len at prefill).
  * prefix axis  — an axis that ranges over ALL positions so far (the
    key/value axis of attention scores); reads come from a persistent
    cache of shape cap (= max_len) that each step appends to.
  * static       — everything not downstream of the decode input: the
    encoder subgraph, relative-position-bias chains, baked mask
    constants. Computed ONCE at init (with the static graph inputs) and
    sliced per step where a static axis aligns with a live/prefix axis.

Axis info propagates forward from the decode input through a per-op-type
rule table (pointwise ops pass it through; transpose/reshape remap it;
batch_matmul creates/consumes prefix axes). Ops the rules can't prove
exact raise NotImplementedError at build time — the same contract as the
strict seq-pointwise checker this generalizes.

Exactness: a softmax over a prefix axis gets an injected causality/
validity mask (cache position <= query position), which both enforces
causal attention and hides the cache's unwritten tail; for causal models
this reproduces the full forward bit-for-bit modulo float association
(asserted against the full forward in tests/test_serving_qa.py).

Causality of PRIMITIVE-op attention: the injected mask is only exact if
the graph's own attention IS causal, and for imported graphs that fact
lives in baked mask constants. build_plan PROVES it where it can — it
walks the live chain between the score matmul and each prefix softmax
looking for a baked constant aligned to the (query, key) plane whose
strict upper triangle is masked (additive <= -1e4, or all-False for a
boolean where-condition) — and otherwise REFUSES to build unless the
caller passes assume_causal=True. A bidirectional/prefix-LM import
therefore errors at build time instead of silently decoding causally;
the fused-MHA path already rejects non-causal self-attention via its
op params.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ff_types import AggrMode, OperatorType
from ..ops.registry import FwdCtx, get_op_def, has_op_def
from ..pcg.graph import LoopRegion

NEG_INF = -1e30


class DecodeExactnessError(NotImplementedError):
    """Incremental decode cannot prove a step exact for this graph.

    Subclasses NotImplementedError so existing callers keep working; the
    serving layer catches THIS type to fall back (e.g. the batcher keeps
    the training-strategy executables when a decode-searched graph's
    step can't be built) instead of swallowing unrelated bugs."""


# Decode-fallback bookkeeping, mirroring the attention fallback contract
# (ops/attention.py): every occurrence counts toward
# ff_decode_fallback_total{reason=...}; each distinct (site, reason)
# warns once per process. Build/trace-time exactness failures that have
# NO exact recovery still raise (DecodeExactnessError) — but counted, so
# an aborted batcher boot is visible in telemetry instead of silent.
_DECODE_FALLBACK_WARNED: set = set()


def reset_decode_fallback_warnings() -> None:
    """Forget which (site, reason) decode fallbacks already warned
    (tests; a fresh process starts empty)."""
    _DECODE_FALLBACK_WARNED.clear()


def decode_fallback(site: str, reason: str, detail: str) -> None:
    """Count + warn-once for a decode fast path falling back (or, for
    unrecoverable exactness failures, aborting visibly)."""
    from .. import obs

    obs.count("ff_decode_fallback_total",
              help="incremental-decode fast paths that fell back to a "
                   "dense/recovery path (or aborted on an unprovable "
                   "step)",
              reason=reason)
    key = (site, reason)
    if key in _DECODE_FALLBACK_WARNED:
        return
    _DECODE_FALLBACK_WARNED.add(key)
    warnings.warn(
        f"incremental decode on {site or 'a decode graph'} fell back "
        f"({reason}): {detail}"
    )

# pointwise in every axis (rank-preserving): the live/prefix axes pass
# straight through; execution on a slice is the plain forward
_POINTWISE = frozenset({
    OperatorType.OP_EW_ADD, OperatorType.OP_EW_SUB, OperatorType.OP_EW_MUL,
    OperatorType.OP_EW_DIV, OperatorType.OP_EW_MAX, OperatorType.OP_EW_MIN,
    OperatorType.OP_WHERE,
})


@dataclasses.dataclass(frozen=True)
class AxisInfo:
    """Where the decode position lives in a tensor. None = static/full."""

    live: Optional[int] = None
    prefix: Optional[int] = None

    @property
    def is_live(self) -> bool:
        return self.live is not None or self.prefix is not None


@dataclasses.dataclass
class DecodePlan:
    """Build-time product: everything the jitted step needs."""

    live_ops: List  # topo-ordered ops downstream of the decode input
    static_ops: List  # topo-ordered ops computable from static inputs
    info: Dict[int, AxisInfo]  # guid -> axis info (live tensors only)
    cached_guids: List[int]  # tensors consumed at full prefix length
    static_keyed: List  # live ops whose keys and values are static
    static_needed: List[int]  # static guids consumed by live ops
    live_len: int  # compiled decoder length L
    decode_pt: object  # the decode-driving input ParallelTensor
    requires_cap_le_live_len: bool  # static slicing present
    # loop regions (pcg/graph.py LoopRegion), each run as one body over its
    # steps; their ops stand in live_ops once
    loops: List = dataclasses.field(default_factory=list)

    def cached_pt(self, guid):
        """The tensor behind one of cached_guids."""
        return next(x for op in self.live_ops for x in op.outputs
                    if x.guid == guid)


def _is_unary_pointwise(op) -> bool:
    d = get_op_def(op.op_type)
    # rank-preserving single-input ops whose forward treats every axis as
    # a batch axis: elementwise unaries, cast, dropout(inference), linear
    # (contracts the LAST axis only), embedding lookup, identity
    return op.op_type in (
        OperatorType.OP_CAST, OperatorType.OP_DROPOUT, OperatorType.OP_NOOP,
        OperatorType.OP_IDENTITY,
    ) or (d.num_inputs == 1 and op.op_type.name.startswith(("OP_SCALAR_",))
          ) or op.op_type in (
        OperatorType.OP_EXP, OperatorType.OP_LOG, OperatorType.OP_RELU,
        OperatorType.OP_SIGMOID, OperatorType.OP_TANH, OperatorType.OP_ELU,
        OperatorType.OP_GELU, OperatorType.OP_SILU, OperatorType.OP_RSQRT,
        OperatorType.OP_SQRT, OperatorType.OP_SIN, OperatorType.OP_COS,
        OperatorType.OP_POW, OperatorType.OP_PRELU,
    )


def _bcast_axis(in_rank: int, out_rank: int, axis: int) -> int:
    """Right-aligned broadcast: input axis -> output axis position."""
    return axis + (out_rank - in_rank)


class _Propagator:
    """Forward axis-info propagation + build-time validation."""

    def __init__(self, live_len: int):
        self.live_len = live_len
        self.info: Dict[int, AxisInfo] = {}
        self.cached: set = set()
        self.saw_static_slicing = False
        # softmax ops over a prefix axis (primitive-op attention rows):
        # each needs a causality proof or an assume_causal opt-in
        self.prefix_softmaxes: List = []
        # ops whose keys and values are static (cross-attention): their
        # state is computed once, not appended to
        self.static_keyed: List = []

    def get(self, guid) -> AxisInfo:
        return self.info.get(guid, AxisInfo())

    def visit(self, op):
        t = op.op_type
        ins = [self.get(x.guid) for x in op.inputs]
        in_shapes = [tuple(x.material_shape()) for x in op.inputs]
        out_shapes = [tuple(x.material_shape()) for x in op.outputs]

        def fail(msg):
            raise DecodeExactnessError(
                f"{op.name} ({t.name}): incremental decode can't prove "
                f"exactness — {msg}"
            )

        def set_out(i, info):
            self.info[op.outputs[i].guid] = info

        if t == OperatorType.OP_MULTIHEAD_ATTENTION:
            q, k, v = ins
            if q.live != 1 or q.prefix is not None:
                fail("attention query must be (batch, seq, embed) with the "
                     "live axis at 1")
            if k.is_live or v.is_live:
                # self-attention via the op's own KV cache
                if not (k.live == 1 and v.live == 1 and k.prefix is None
                        and v.prefix is None):
                    fail("attention k/v must be live at axis 1")
                if not op.params.causal:
                    fail("needs causal=True (otherwise each position sees "
                         "the future and the cached prefix is stale)")
            else:
                if op.params.causal:
                    # the full forward would tril-mask cross scores; the
                    # decode kernel attends the full encoder unmasked
                    fail("causal cross-attention has no decode rule")
                # cross-attention: k/v static (encoder side) — full-length
                # K/V computed once, no causal mask (matches the full
                # forward)
                self.static_keyed.append(op)
            set_out(0, AxisInfo(live=1))
            return

        if t in (OperatorType.OP_GATED_DELTA_NET, OperatorType.OP_MAMBA2):
            (a,) = ins
            if a.live != 1 or a.prefix is not None:
                fail("the input must be (batch, seq, embed) with the live "
                     "axis at 1")
            # the op carries its own per-slot state from block to block
            # (the section of the caches its definition names)
            set_out(0, AxisInfo(live=1))
            return

        if _is_unary_pointwise(op) or (
            # a product over the LAST axis alone: every position its own
            # (the expert bank routes and computes token by token)
            t in (OperatorType.OP_LINEAR, OperatorType.OP_EXPERT_BANK)
        ) or (
            t == OperatorType.OP_EMBEDDING
            and op.params.aggr == AggrMode.AGGR_MODE_NONE
        ):
            a = ins[0]
            if t in (OperatorType.OP_LINEAR,
                     OperatorType.OP_EXPERT_BANK) and (
                a.live == len(in_shapes[0]) - 1
                or a.prefix == len(in_shapes[0]) - 1
            ):
                fail("linear contracts the live/prefix axis")
            if t == OperatorType.OP_EMBEDDING:
                # (.., L) ids -> (.., L, E): axes keep their positions
                set_out(0, AxisInfo(live=a.live, prefix=a.prefix))
                return
            set_out(0, a)
            return

        if t in (OperatorType.OP_LAYERNORM,):
            a = ins[0]
            nd = len(in_shapes[0])
            if any(ax % nd in (a.live, a.prefix) for ax in op.params.axes):
                fail("layernorm normalizes over the live/prefix axis")
            set_out(0, a)
            return

        if t in (OperatorType.OP_REDUCE_SUM, OperatorType.OP_REDUCE_MEAN,
                 OperatorType.OP_MEAN):
            a = ins[0]
            nd = len(in_shapes[0])
            axes = sorted(ax % nd for ax in op.params.axes)
            if any(ax in (a.live, a.prefix) for ax in axes):
                fail("reduce over the live/prefix axis")
            if getattr(op.params, "keepdims", True):
                set_out(0, a)
            else:
                def drop(axis):
                    if axis is None:
                        return None
                    return axis - sum(1 for ax in axes if ax < axis)
                set_out(0, AxisInfo(live=drop(a.live), prefix=drop(a.prefix)))
            return

        if t == OperatorType.OP_SOFTMAX:
            a = ins[0]
            nd = len(in_shapes[0])
            dim = op.params.dim % nd
            if dim == a.live:
                fail("softmax over the live axis")
            # softmax over the prefix axis is the attention row softmax;
            # the step injects the causality/validity mask there
            if dim == a.prefix:
                self.prefix_softmaxes.append(op)
            set_out(0, a)
            return

        if t == OperatorType.OP_TRANSPOSE:
            a = ins[0]
            perm = list(op.params.perm)

            def remap(axis):
                return None if axis is None else perm.index(axis)
            set_out(0, AxisInfo(live=remap(a.live), prefix=remap(a.prefix)))
            return

        if t in (OperatorType.OP_SQUEEZE, OperatorType.OP_UNSQUEEZE):
            a = ins[0]
            nd_in, nd_out = len(in_shapes[0]), len(out_shapes[0])
            if t == OperatorType.OP_UNSQUEEZE:
                added = sorted(ax % nd_out for ax in op.params.axes)

                def remap(axis):
                    if axis is None:
                        return None
                    for ad in added:
                        if ad <= axis:
                            axis += 1
                    return axis
            else:
                removed = sorted(ax % nd_in for ax in op.params.axes)
                if any(ax in (a.live, a.prefix) for ax in removed):
                    fail("squeeze removes the live/prefix axis")

                def remap(axis):
                    if axis is None:
                        return None
                    return axis - sum(1 for ax in removed if ax < axis)
            set_out(0, AxisInfo(live=remap(a.live), prefix=remap(a.prefix)))
            return

        if t in (OperatorType.OP_RESHAPE, OperatorType.OP_FLAT):
            a = ins[0]
            if a.prefix is not None:
                fail("reshape of a tensor with a prefix axis")
            if a.live is None:
                set_out(0, AxisInfo())
                return
            s_in, s_out = in_shapes[0], out_shapes[0]
            # the live axis must survive as a standalone axis: volumes
            # before/at it must match some output prefix
            pre = int(np.prod(s_in[:a.live], dtype=np.int64))
            out_live = None
            acc = 1
            for i, d in enumerate(s_out):
                if acc == pre and d == s_in[a.live]:
                    out_live = i
                    break
                acc *= d
            if out_live is None:
                fail(f"reshape {s_in}->{s_out} splits/merges the live axis")
            set_out(0, AxisInfo(live=out_live))
            return

        if t in _POINTWISE:
            out_rank = len(out_shapes[0])
            live = prefix = None
            for inf, s in zip(ins, in_shapes):
                if inf.live is not None:
                    al = _bcast_axis(len(s), out_rank, inf.live)
                    if live is not None and live != al:
                        fail("two live inputs broadcast to different axes")
                    live = al
                if inf.prefix is not None:
                    ap = _bcast_axis(len(s), out_rank, inf.prefix)
                    if prefix is not None and prefix != ap:
                        fail("two prefix inputs broadcast to different axes")
                    prefix = ap
            # static operands with a full-length axis aligned to live or
            # prefix get sliced per step — note that slicing happens
            for inf, s in zip(ins, in_shapes):
                if not inf.is_live:
                    for ax, d in enumerate(s):
                        pos = _bcast_axis(len(s), out_rank, ax)
                        if d > 1 and pos in (live, prefix):
                            if d != self.live_len:
                                fail(
                                    f"static operand axis {ax} (size {d}) "
                                    f"aligns with the decode axis but isn't "
                                    f"the compiled decoder length "
                                    f"{self.live_len}"
                                )
                            self.saw_static_slicing = True
            if live is None and prefix is None:
                fail("elementwise op classified live but no live input")
            set_out(0, AxisInfo(live=live, prefix=prefix))
            return

        if t == OperatorType.OP_CONCAT:
            axis = op.params.axis % len(out_shapes[0])
            lives = {inf.live for inf in ins}
            prefixes = {inf.prefix for inf in ins}
            if len(lives) != 1 or len(prefixes) != 1:
                fail("concat mixes live and static inputs")
            a = ins[0]
            if axis in (a.live, a.prefix):
                fail("concat along the live/prefix axis")
            set_out(0, a)
            return

        if t == OperatorType.OP_SPLIT:
            a = ins[0]
            axis = op.params.axis % len(in_shapes[0])
            if axis in (a.live, a.prefix):
                fail("split along the live/prefix axis")
            for i in range(len(op.outputs)):
                set_out(i, a)
            return

        if t == OperatorType.OP_BATCHMATMUL:
            a, b = ins
            ra, rb = len(in_shapes[0]), len(in_shapes[1])
            ro = len(out_shapes[0])
            M, K_a = ra - 2, ra - 1
            K_b, N = rb - 2, rb - 1

            # batch-dim liveness: both operands sliced at the same step —
            # behaves like an elementwise op over the batch dims
            a_batch_live = a.live is not None and a.live < M
            b_batch_live = b.live is not None and b.live < K_b

            if a.prefix is not None and a.prefix == K_a:
                # probs @ V: contract the prefix axis against a cached
                # full-length operand
                if b.is_live:
                    if b.live != K_b or b.prefix is not None:
                        fail("prefix contraction needs the rhs live on its "
                             "contraction axis")
                    self.cached.add(op.inputs[1].guid)
                elif in_shapes[1][K_b] != self.live_len:
                    fail("prefix contraction against a static rhs of the "
                         "wrong length")
                else:
                    self.saw_static_slicing = True
                if a.live is not None and a.live != M and not a_batch_live:
                    fail("unsupported live-axis position in lhs")
                set_out(0, AxisInfo(live=a.live if a.live != K_a else None))
                return
            if a.prefix is not None:
                fail("lhs prefix axis not on the contraction dim")

            if a.live == K_a or (b.is_live and b.live == K_b):
                fail("contraction over a live axis without a prefix lhs")

            out_live = None
            out_prefix = None
            if a_batch_live or b_batch_live:
                la = a.live if a_batch_live else None
                lb = b.live + (ro - rb) if b_batch_live else None
                if la is not None and lb is not None and la != lb:
                    fail("lhs/rhs live on different batch axes")
                out_live = la if la is not None else lb
            if a.live == M:
                if out_live is not None:
                    fail("live axis on both batch and M dims")
                out_live = ro - 2
            if b.is_live and b.live == N:
                # Q @ K^T: rhs is the transposed key matrix, consumed at
                # full prefix length -> the output's N axis is a prefix
                if b.prefix is not None:
                    fail("rhs has both live and prefix axes")
                self.cached.add(op.inputs[1].guid)
                out_prefix = ro - 1
            set_out(0, AxisInfo(live=out_live, prefix=out_prefix))
            return

        fail("op mixes sequence positions and has no decode rule")


def _is_causal_mask_constant(arr, live_ax: int, prefix_ax: int) -> bool:
    """True iff the baked constant masks every future position in the
    (query=live, key=prefix) plane: additive masks have strict-upper
    entries <= -1e4 for every leading index; boolean where-conditions
    (True = keep) have them all False. Entries on/below the diagonal are
    unconstrained — a combined bias+mask (T5-style) still proves causal."""
    v = np.asarray(arr)
    if v.ndim < 2:
        return False
    v = np.moveaxis(v, (live_ax, prefix_ax), (-2, -1))
    L = min(v.shape[-2], v.shape[-1])
    iu = np.triu_indices(n=v.shape[-2], k=1, m=v.shape[-1])
    if iu[0].size == 0:
        return L > 0  # 1x1 plane: nothing future-facing to mask
    upper = v[..., iu[0], iu[1]]
    if v.dtype == np.bool_:
        return not bool(upper.any())
    if not np.issubdtype(v.dtype, np.floating):
        return False
    return bool(np.all(upper <= -1e4))


def _static_chain_causal(guid: int, q_ax: int, k_ax: int, producer,
                         constants, live_len: int, depth: int = 0) -> bool:
    """Does the STATIC value `guid` carry a causal mask on its (q_ax, k_ax)
    plane? Baked constants are checked directly; computed statics (e.g.
    T5's position_bias = relative-bias-embedding + baked causal mask) are
    traced through mask-preserving ops: EW_ADD (adding anything finite to
    a -inf-masked entry keeps it masked), axis-remapping transpose/
    (un)squeeze, and cast/identity. Anything else ends the proof."""
    if depth > 32:
        return False
    if guid in constants:
        _, value = constants[guid]
        if not isinstance(value, np.ndarray):
            return False
        if (value.ndim <= max(q_ax, k_ax)
                or value.shape[q_ax] != live_len
                or value.shape[k_ax] != live_len):
            return False
        return _is_causal_mask_constant(value, q_ax, k_ax)
    p = producer.get(guid)
    if p is None:
        return False  # a graph input: value unknown at build time
    t = p.op_type
    out_rank = len(p.outputs[0].material_shape())
    if t == OperatorType.OP_CAST:
        # only float->float preserves additive-mask semantics (a -1e9 mask
        # cast to bool becomes all-True — the OPPOSITE of masked)
        import numpy as _np
        src_f = _np.issubdtype(p.inputs[0].data_type.np_dtype, _np.floating)
        dst_f = _np.issubdtype(p.outputs[0].data_type.np_dtype, _np.floating)
        if not (src_f and dst_f):
            return False
        return _static_chain_causal(p.inputs[0].guid, q_ax, k_ax, producer,
                                    constants, live_len, depth + 1)
    if getattr(p, "is_parallel_op", False) or t in (
        OperatorType.OP_NOOP, OperatorType.OP_IDENTITY,
        OperatorType.OP_DROPOUT,
    ):
        return _static_chain_causal(p.inputs[0].guid, q_ax, k_ax, producer,
                                    constants, live_len, depth + 1)
    if t in (OperatorType.OP_EW_ADD,):
        for x in p.inputs:
            s = tuple(x.material_shape())
            off = out_rank - len(s)
            qa, ka = q_ax - off, k_ax - off
            if (qa >= 0 and ka >= 0 and s[qa] == live_len
                    and s[ka] == live_len
                    and _static_chain_causal(x.guid, qa, ka, producer,
                                             constants, live_len, depth + 1)):
                return True
        return False
    if t == OperatorType.OP_TRANSPOSE:
        perm = list(p.params.perm)
        return _static_chain_causal(p.inputs[0].guid, perm[q_ax], perm[k_ax],
                                    producer, constants, live_len, depth + 1)
    if t == OperatorType.OP_UNSQUEEZE:
        added = sorted(ax % out_rank for ax in p.params.axes)
        if q_ax in added or k_ax in added:
            return False

        def back(axis):
            return axis - sum(1 for ad in added if ad < axis)
        return _static_chain_causal(p.inputs[0].guid, back(q_ax), back(k_ax),
                                    producer, constants, live_len, depth + 1)
    if t == OperatorType.OP_SQUEEZE:
        in_rank = len(p.inputs[0].material_shape())
        removed = sorted(ax % in_rank for ax in p.params.axes)

        def fwd(axis):
            for r in removed:
                if r <= axis:
                    axis += 1
            return axis
        return _static_chain_causal(p.inputs[0].guid, fwd(q_ax), fwd(k_ax),
                                    producer, constants, live_len, depth + 1)
    return False


def _prove_causal(softmax_op, prop: "_Propagator", live_ops, static_ops,
                  constants, live_len: int) -> bool:
    """Walk the live chain feeding a prefix softmax (back to the score
    matmul that created the prefix axis) and look for a static operand,
    aligned to the (live, prefix) plane, that provably masks the strict
    upper triangle (directly baked, or computed from a baked causal mask —
    _static_chain_causal). Finding one proves the graph's own attention is
    causal, so the injected decode mask reproduces the full forward."""
    producer = {}
    for op in list(live_ops) + list(static_ops):
        for t in op.outputs:
            producer[t.guid] = op

    seen = set()
    stack = [softmax_op.inputs[0].guid]
    while stack:
        guid = stack.pop()
        if guid in seen:
            continue
        seen.add(guid)
        p = producer.get(guid)
        if p is None:
            continue
        if getattr(p, "is_parallel_op", False):
            stack.append(p.inputs[0].guid)
            continue
        out_info = prop.get(p.outputs[0].guid)
        if out_info.prefix is None:
            continue  # left the attention-score region
        made_prefix = all(
            prop.get(x.guid).prefix is None for x in p.inputs
        )
        out_rank = len(p.outputs[0].material_shape())
        # Check this op's non-live operands for a provable mask — but ONLY
        # where the op APPLIES the operand in a mask-preserving way:
        #   * EW_ADD: adding a -inf-masked operand masks the output;
        #   * WHERE(cond, x, y): a tril boolean condition proves causal
        #     only if the else-branch y is itself provably <= -1e4.
        # An EW_SUB of a tril-negative constant would UNMASK the future,
        # and a WHERE with a finite else-branch doesn't mask at all — a
        # causal-looking constant on those ops must not count as proof.
        t = p.op_type
        if t == OperatorType.OP_EW_ADD:
            candidates = list(p.inputs)
        elif t == OperatorType.OP_WHERE and len(p.inputs) == 3:
            y = p.inputs[2]
            y_masked = False
            if y.guid in constants:
                _, yv = constants[y.guid]
                yarr = np.asarray(yv)
                y_masked = (np.issubdtype(yarr.dtype, np.floating)
                            and bool(np.all(yarr <= -1e4)))
            candidates = [p.inputs[0]] if y_masked else []
        else:
            candidates = []
        for x in candidates:
            if prop.get(x.guid).is_live:
                continue
            amap = _static_alignment(
                tuple(x.material_shape()), out_rank, out_info, live_len,
            )
            axes = dict((kind, ax) for ax, kind in amap)
            if "live" in axes and "prefix" in axes and _static_chain_causal(
                x.guid, axes["live"], axes["prefix"], producer, constants,
                live_len,
            ):
                return True
        if not made_prefix:
            for x in p.inputs:
                if prop.get(x.guid).is_live:
                    stack.append(x.guid)
    return False


def build_plan(topo, input_pts, constants, decode_input: Optional[int] = None,
               assume_causal: bool = False, loops=()):
    """Classify ops/tensors and validate decodability.

    decode_input: index into input_pts of the decode-driven input; default
    is the last input (enc-dec convention: (encoder_ids, decoder_ids)).
    assume_causal: skip the causality proof for primitive-op attention
    (graphs whose masks are computed rather than baked can't be verified
    at build time — the caller vouches that decoder self-attention is
    causal).
    loops: the graph's loop regions (Graph.loops). A region's body is
    proven once, on the axes of its source, and its exit must come out on
    the same axes, as every later step reads it there.
    """
    inputs = list(input_pts)
    if decode_input is None:
        decode_input = len(inputs) - 1
    decode_pt = inputs[decode_input]
    live_len = decode_pt.material_shape()[1]

    prop = _Propagator(live_len)
    prop.info[decode_pt.guid] = AxisInfo(live=1)

    live_ops, static_ops = [], []
    for op in topo:
        if op.is_parallel_op:
            # decode runs single-device; parallel ops are identity over an
            # unsharded value (degree bookkeeping only)
            src = op.inputs[0].guid
            if prop.get(src).is_live:
                prop.info[op.outputs[0].guid] = prop.get(src)
                live_ops.append(op)
            else:
                static_ops.append(op)
            continue
        if any(prop.get(x.guid).is_live for x in op.inputs):
            prop.visit(op)
            live_ops.append(op)
        else:
            static_ops.append(op)

    # static guids live ops actually read: outputs of static ops AND
    # static graph inputs consumed directly (e.g. an explicit attention
    # mask input added to live scores)
    static_out = {pt.guid for pt in inputs if pt.guid != decode_pt.guid}
    for op in static_ops:
        for x in op.outputs:
            static_out.add(x.guid)
    needed = []
    for op in live_ops:
        for x in op.inputs:
            if not prop.get(x.guid).is_live and x.guid in static_out:
                if x.guid not in needed:
                    needed.append(x.guid)

    if not assume_causal:
        for sm in prop.prefix_softmaxes:
            if not _prove_causal(sm, prop, live_ops, static_ops, constants,
                                 live_len):
                raise DecodeExactnessError(
                    f"{sm.name} ({sm.op_type.name}): primitive-op attention "
                    "whose causality can't be proven from baked mask "
                    "constants — the decode step would inject a causal "
                    "mask, which is wrong for bidirectional/prefix-LM "
                    "graphs. Pass assume_causal=True to vouch that "
                    "decoder self-attention is causal."
                )
    for reg in loops:
        where = f"loop {reg.name!r}"
        if not prop.get(reg.entry.outputs[0].guid).is_live:
            raise DecodeExactnessError(
                f"{where}: its source does not depend on the decode input")
        if prop.get(reg.exit.guid) != prop.get(reg.source.guid):
            raise DecodeExactnessError(
                f"{where}: the exit's positions lie on other axes "
                f"({prop.get(reg.exit.guid)}) than the source's "
                f"({prop.get(reg.source.guid)}) it feeds back")
        inside = {op.guid for op in reg.ops}
        if any(op.guid in inside for op in prop.static_keyed) or any(
                op.guid in inside for op in live_ops
                if any(x.guid in prop.cached for x in op.outputs)):
            raise DecodeExactnessError(
                f"{where}: attention from primitive ops or over a static "
                "side has no decode rule inside a loop region")
    return DecodePlan(
        live_ops=live_ops,
        static_ops=static_ops,
        info=prop.info,
        cached_guids=sorted(prop.cached),
        static_keyed=prop.static_keyed,
        static_needed=needed,
        live_len=live_len,
        decode_pt=decode_pt,
        requires_cap_le_live_len=prop.saw_static_slicing,
        loops=list(loops),
    )


def _slice_aligned(val, info_axis_map, t, s0, cap, out_rank=None,
                   site: str = ""):
    """Slice a static/full value per its alignment: live-aligned axes take
    [t:t+s0], prefix-aligned axes take [0:cap].

    When `t` is a (b,) vector of per-row positions (continuous batching:
    each decode slot is at its own position), live-aligned axes are sliced
    per row — a vmapped dynamic slice that materializes a leading batch
    axis. `out_rank` (the consuming op's output rank) is then required to
    re-align the result so broadcasting still lines the batch axis up with
    the live stream's axis 0.

    Alignment cases an exact recovery exists for fall back to it with the
    ff_decode_fallback_total{reason} counter + one warning (the
    batch-position live axis at s0=1 turns into a dense per-row gather);
    genuinely unprovable cases raise DecodeExactnessError — still
    counted, so an aborted batcher boot shows up in telemetry."""
    per_row_t = getattr(t, "ndim", 0) == 1
    live_axes = [axis for axis, kind in info_axis_map if kind == "live"]
    for axis, kind in info_axis_map:
        if kind == "prefix":
            val = jax.lax.slice_in_dim(val, 0, cap, axis=axis)
    if not live_axes:
        return val
    if not per_row_t:
        for axis in live_axes:
            val = jax.lax.dynamic_slice_in_dim(val, t, s0, axis=axis)
        return val
    if out_rank is None:
        decode_fallback(site, "no_out_rank",
                        "per-row decode positions need the consuming "
                        "op's output rank to realign a sliced static "
                        "operand — no exact recovery, aborting the build")
        raise DecodeExactnessError(
            "per-row decode positions need the consuming op's output rank "
            "to realign a sliced static operand"
        )
    b = t.shape[0]
    offset = out_rank - val.ndim  # right-aligned broadcast offset
    if any(axis + offset == 0 for axis in live_axes):
        # the live-aligned axis IS the output's batch axis (offset == 0,
        # axis == 0). For single-token steps (s0 == 1 — the only shape
        # per-row positions arrive in) row i of the output reads exactly
        # position t[i]: a dense per-row gather is exact, so recover
        # instead of aborting the batcher boot.
        if s0 == 1 and offset == 0:
            decode_fallback(
                site, "batch_live_gather",
                "a static operand's live-aligned axis coincides with the "
                "batch axis; recovered with a dense per-row gather "
                "(jnp.take over the position vector) instead of the "
                "sliced fast path",
            )
            val = jnp.take(val, t, axis=0)  # (b,) + val.shape[1:]
            rest = [axis for axis in live_axes if axis != 0]
            if rest:
                def slice_rest(v, tt):
                    for axis in rest:
                        v = jax.lax.dynamic_slice_in_dim(
                            v, tt, s0, axis=axis - 1)
                    return v
                val = jax.vmap(slice_rest, in_axes=(0, 0))(val, t)
            return val
        decode_fallback(
            site, "batch_live_block",
            "a static operand's live-aligned axis coincides with the "
            "batch axis and the step has s0 > 1 (a prefill block) — no "
            "exact per-row recovery, aborting the build",
        )
        raise DecodeExactnessError(
            "per-row decode positions: a static operand's live-aligned axis "
            "coincides with the batch axis"
        )
    if offset == 0:
        # the value's axis 0 occupies the batch position
        if val.shape[0] == b:
            def slice_row(v, tt):  # v: one row, axes shifted down by 1
                for axis in live_axes:
                    v = jax.lax.dynamic_slice_in_dim(v, tt, s0, axis=axis - 1)
                return v
            return jax.vmap(slice_row, in_axes=(0, 0))(val, t)
        if val.shape[0] != 1:
            decode_fallback(
                site, "batch_mismatch",
                f"static operand batch axis {val.shape[0]} matches "
                f"neither the decode batch {b} nor 1 — rows cannot be "
                "matched to slots, no exact recovery",
            )
            raise DecodeExactnessError(
                f"static operand batch axis {val.shape[0]} matches neither "
                f"the decode batch {b} nor 1"
            )

    def slice_full(tt):  # closes over val at its original rank
        v = val
        for axis in live_axes:
            v = jax.lax.dynamic_slice_in_dim(v, tt, s0, axis=axis)
        return v

    sliced = jax.vmap(slice_full)(t)  # (b,) + sliced val shape
    if offset == 0:  # drop the original size-1 batch axis
        return jnp.squeeze(sliced, axis=1)
    # no batch axis on the static value: the new leading axis is the
    # batch; pad interior size-1 axes so right-aligned broadcasting puts
    # it at the output's axis 0
    return jnp.reshape(sliced, (b,) + (1,) * (offset - 1) + sliced.shape[1:])


def _static_alignment(shape, out_rank, out_info: AxisInfo, live_len):
    """Which axes of a static operand need slicing against a live stream."""
    plan = []
    for ax, d in enumerate(shape):
        pos = _bcast_axis(len(shape), out_rank, ax)
        if d > 1 and d == live_len:
            if pos == out_info.live:
                plan.append((ax, "live"))
            elif pos == out_info.prefix:
                plan.append((ax, "prefix"))
    return plan


# -- the caches: the tree a step steps ------------------------------------------
# One tree, keyed by section, and this module alone knows its shape: the
# batcher and beam search (runtime/serving.py) and the sizing
# (runtime/kvcache.py) go through the functions below.
#
#   per-slot sections: every leaf has a leading slot (batch) axis, one row
#   a sequence in flight, and a step hands back its successor
#     "prefix"            guid -> a primitive-op attention's operand at
#                         full length (DecodePlan.cached_guids)
#     "mha", "recurrent"  op name -> what that op's definition keeps
#                         (ops/registry.py init_decode_state, decode_section)
#   shared sections: made once by init_caches and read by every step, never
#   stepped; a constant-derived leaf may have a leading axis of 1
#     "static"            guid -> an encoder-side value a live op reads
#     "mha_static"        op name -> a static-keyed attention's (k, v)
SLOT_SECTIONS = ("prefix", "mha", "recurrent")
# "counters": one integer scalar a name, what the ops of the LAST step
# counted (OpDef.decode_counters); a step replaces them, nothing else does.
# "prefill_counters" the same for what ops count only in a block of several
# positions (OpDef.prefill_counters), out of a decode iteration's fetch
SHARED_SECTIONS = ("static", "mha_static", "counters", "prefill_counters")
# what a slot pays for, by kind: keys and values, which grow with a
# sequence up to max_len (pages), and state of fixed size, which does not
STATE_KINDS = {"kv": ("prefix", "mha"), "fixed": ("recurrent",)}


def _stepped(caches):
    """The tree a step puts its successors into: the shared sections as
    they are, the per-slot ones as shallow copies."""
    new = {sec: caches[sec] for sec in SHARED_SECTIONS}
    new.update((sec, dict(caches[sec])) for sec in SLOT_SECTIONS)
    return new


def _write_rows(slotted, rows, slot):
    """The traced body of insert_row: every per-slot leaf of `slotted`
    with the one row of `rows` written at `slot` (a traced int32, so one
    program serves every slot)."""
    from ..runtime.verify import ServingConfigError
    from .executor import _count_trace

    _count_trace("insert")
    out = {}
    for sec in SLOT_SECTIONS:
        out[sec] = {}
        for key, batch in slotted[sec].items():
            olds, treedef = jax.tree_util.tree_flatten(batch)
            news = jax.tree_util.tree_leaves(rows[sec][key])
            shapes = next(
                ((tuple(o.shape), tuple(r.shape)) for o, r in zip(olds, news)
                 if r.shape[0] != 1 or o.shape[1:] != r.shape[1:]), None)
            if shapes is not None:
                raise ServingConfigError(
                    f"{sec} cache {key} has no per-slot leading axis "
                    f"(batch shape {shapes[0]} vs row {shapes[1]}) — this "
                    "graph folds batch with another axis and cannot be "
                    "continuously batched"
                )
            out[sec][key] = treedef.unflatten([
                jax.lax.dynamic_update_slice_in_dim(
                    o, r.astype(o.dtype), slot, axis=0)
                for o, r in zip(olds, news)])
    return out


# one program a cache tree's shapes (and a batch's placement): the batch is
# donated where the step donates its caches, the row never is (the batcher
# keeps prefilled rows to replay them)
_INSERT = {donate: jax.jit(_write_rows, donate_argnums=(0,) if donate else ())
           for donate in (False, True)}


def insert_row(caches, row_caches, slot: int, *, donate: bool = False):
    """A running batch's caches with slot `slot` replaced, in every
    per-slot leaf, by the one row of `row_caches` (a prefilled batch-1
    tree): whatever a previous occupant left there is gone. One compiled
    program writes every leaf; the shared sections pass around it as they
    are. CONSUMES `caches`: with `donate` (the step's rule,
    executor.donates_buffers) each leaf is written in place and the old
    ones are deleted, so the insert never holds a second generation of
    the caches; `row_caches` is only read."""
    out = {sec: caches[sec] for sec in SHARED_SECTIONS}
    out.update(_INSERT[donate](
        {sec: caches[sec] for sec in SLOT_SECTIONS},
        {sec: row_caches[sec] for sec in SLOT_SECTIONS}, np.int32(slot)))
    return out


def compiled_init(init):
    """`init` (build_step's init_caches) as one compiled program: a fresh
    tree from one dispatch, not one eager zero-fill a leaf. Every call
    still hands out buffers the caches alone own."""
    def init_caches(params=None, static_inputs=()):
        from .executor import _count_trace

        _count_trace("init_caches")
        return init(params, static_inputs)

    return jax.jit(init_caches)


def take_rows(caches, idx):
    """The caches with every per-slot leaf's rows taken by `idx` (beam
    search: each beam's caches follow the beam it grew from). The shared
    sections stay as they are: they are the same for every row, and a
    constant-derived entry has a leading axis of 1, where a gather would
    fill out-of-bounds rows with NaN."""
    out = jax.tree_util.tree_map(
        lambda c: jnp.take(c, idx, axis=0),
        {sec: caches[sec] for sec in SLOT_SECTIONS})
    out.update((sec, caches[sec]) for sec in SHARED_SECTIONS)
    return out


def state_bytes(caches) -> Dict[str, int]:
    """Bytes the caches hold of each kind of per-slot state
    (STATE_KINDS)."""
    return {kind: int(sum(leaf.nbytes for sec in sections for leaf in
                          jax.tree_util.tree_leaves(caches[sec])))
            for kind, sections in STATE_KINDS.items()}


def kv_bytes_by_kind(caches, max_len: int, looped=()) -> Dict[str, int]:
    """The keys and values the fused attention ops hold, split by what
    their leaves are: "window" for a ring shorter than `max_len` (a window
    layer keeps its last positions alone), "full" for the rest; and again,
    as "loop", those of the ops named in `looped` (the ops of loop regions,
    looped_ops), every step's."""
    out = {"window": 0, "full": 0}
    for leaf in jax.tree_util.tree_leaves(caches["mha"]):
        out["window" if leaf.shape[-2] < max_len else "full"] += leaf.nbytes
    if looped:
        out["loop"] = int(sum(
            leaf.nbytes for name, state in caches["mha"].items()
            if name in looped for leaf in jax.tree_util.tree_leaves(state)))
    return out


def looped_ops(topo) -> frozenset:
    """The names of the ops of `topo` inside a loop region."""
    return frozenset(op.name for op in topo if op.loop is not None)


def declared_state_bytes(topo, kind: str, max_len: int, dtype) -> int:
    """Bytes ONE slot of `max_len` positions holds of `kind` across the
    ops of `topo`, by what each op's definition says it keeps
    (init_decode_state, its shapes alone: nothing is allocated), an op in a
    loop region once a step. Counts every op of the graph, as the sizing
    formula does (docs/serving.md), not only those a decode plan finds
    live."""
    total = 0
    for op in topo:
        if not has_op_def(op.op_type):
            continue
        d = get_op_def(op.op_type)
        if d.decode_section not in STATE_KINDS[kind]:
            continue
        leaves = jax.tree_util.tree_leaves(jax.eval_shape(functools.partial(
            d.init_decode_state, op.params, 1, max_len, dtype)))
        # an op in a loop region keeps one copy a step
        steps = op.loop.steps if op.loop is not None else 1
        total += steps * sum(int(np.prod(leaf.shape, dtype=np.int64))
                             * leaf.dtype.itemsize for leaf in leaves)
    return total


# -- the step -------------------------------------------------------------------
def _check_plan(plan: DecodePlan, logits_pt, max_len: int) -> None:
    """Build-time refusals the plan alone cannot make: they need the
    decode batch, the cap or the graph's output."""
    # prefix caches patch ONLY axis 0 to the decode batch; a graph that
    # folds batch with heads on axis 0 (B*H, ...) would get a wrong-sized
    # cache when decoding at a different batch than compile (beam search
    # at num_beams) — reject at build like the other exactness checks
    compile_batch = plan.decode_pt.material_shape()[0]
    for g in plan.cached_guids:
        pt = plan.cached_pt(g)
        if plan.info[g].live != 0 and pt.material_shape()[0] != compile_batch:
            raise NotImplementedError(
                f"cached tensor guid {g} has axis-0 size "
                f"{pt.material_shape()[0]} != compiled batch "
                f"{compile_batch}: its batch dim is folded with "
                "another axis, so decoding at a different batch "
                "would mis-size the cache"
            )
    if plan.requires_cap_le_live_len and max_len > plan.live_len:
        raise NotImplementedError(
            f"max_len {max_len} > compiled decoder length "
            f"{plan.live_len}: the graph bakes full-length constants "
            "(masks/position tables) that can't be extended"
        )
    if not plan.info.get(logits_pt.guid, AxisInfo()).is_live:
        raise NotImplementedError(
            "the graph output does not depend on the decode input"
        )


def _constants_at(constants, batch: int):
    """Baked constants, with batch-uniform leading axes collapsed to 1:
    decode may run at a different batch than compile (beam search runs at
    num_beams), and constants like HF's extended attention masks carry
    the compiled batch size — when every row is identical (no per-sample
    padding was traced) a broadcastable row-1 constant is exact."""
    vals = {}
    for guid, (pt, value) in constants.items():
        shape = tuple(pt.material_shape())
        if isinstance(value, np.ndarray):
            arr = value
            if (arr.ndim >= 1 and arr.shape[0] not in (1, batch)
                    and np.array_equal(arr, np.broadcast_to(
                        arr[:1], arr.shape), equal_nan=True)):
                arr = arr[:1]
            vals[guid] = jnp.asarray(arr, pt.data_type.jnp_dtype)
        else:
            if len(shape) >= 1 and shape[0] not in (1, batch):
                shape = (1,) + shape[1:]
            vals[guid] = jnp.full(shape, value, pt.data_type.jnp_dtype)
    return vals


def _statics(plan: DecodePlan, vals, params, ctx):
    """The static side, once a sequence: every static op's outputs added
    to `vals` (the constants and the static graph inputs, by guid)."""
    for op in plan.static_ops:
        if op.is_parallel_op:
            vals[op.outputs[0].guid] = vals[op.inputs[0].guid]
            continue
        d = get_op_def(op.op_type)
        ins = [vals[x.guid] for x in op.inputs]
        w = (params or {}).get(op.name, {})
        if (op.op_type == OperatorType.OP_RESHAPE
                and tuple(ins[0].shape)
                != tuple(op.inputs[0].material_shape())):
            # traced reshape params bake the compiled batch size; decode
            # may run at a different batch (beam search) — recompute the
            # batch axis
            target = list(op.outputs[0].material_shape())
            target[0] = -1
            outs = [jnp.reshape(ins[0], target)]
        else:
            outs = d.forward(op.params, w, ins, ctx)
        for x, v in zip(op.outputs, outs):
            vals[x.guid] = v
    return vals


def _kept_statics(plan: DecodePlan, static_keyed):
    """The static values the step itself reads. Those whose ONLY live
    consumers are a static-keyed op's key/value slots are folded into
    that op's precomputed state — keeping the raw encoder hidden states
    in the caches as well would waste HBM per layer."""
    folded = {x.guid for op in static_keyed for x in op.inputs[1:]}
    keyed = {id(op) for op in static_keyed}
    other_uses = set()
    for op in plan.live_ops:
        if op.is_parallel_op or id(op) in keyed:
            continue
        for x in op.inputs:
            other_uses.add(x.guid)
    for op in static_keyed:
        other_uses.add(op.inputs[0].guid)
    return [g for g in plan.static_needed
            if g not in folded or g in other_uses]


def _prefix_softmax(x, dim: int, live_ax: int, t):
    """An attention row softmax over the prefix axis: inject the
    causality/validity mask (hides the cache's unwritten tail; for causal
    models this matches the graph's own mask)."""
    kv = jax.lax.broadcasted_iota(jnp.int32, x.shape, dim)
    if getattr(t, "ndim", 0) == 1:
        if x.shape[0] != t.shape[0]:
            raise NotImplementedError(
                f"per-row positions: attention scores fold batch with "
                f"another axis (axis 0 is {x.shape[0]}, batch "
                f"{t.shape[0]})"
            )
        t = t.reshape((t.shape[0],) + (1,) * (x.ndim - 1))
    qp = t + jax.lax.broadcasted_iota(jnp.int32, x.shape, live_ax)
    x = jnp.where(kv <= qp, x, NEG_INF)
    return jax.nn.softmax(x, axis=dim)


def _append(cache, v, t, ax: int, guid):
    """A prefix cache with the block `v` written at position `t` of its
    live axis `ax` (per row where `t` is a vector)."""
    if getattr(t, "ndim", 0) != 1:
        return jax.lax.dynamic_update_slice_in_dim(
            cache, v.astype(cache.dtype), t, axis=ax)
    if ax == 0 or cache.shape[0] != t.shape[0]:
        raise NotImplementedError(
            f"per-row positions: prefix cache guid {guid} has no "
            f"batch-leading axis (live axis {ax}, axis 0 {cache.shape[0]})"
        )
    return jax.vmap(
        lambda c, vv, tt: jax.lax.dynamic_update_slice_in_dim(
            c, vv, tt, axis=ax - 1)
    )(cache, v.astype(cache.dtype), t)


def _check_donated(caches, new_caches) -> None:
    """XLA aliases a donated leaf to the output of its own shape and
    type; one that comes back as another is copied every step after all,
    and says so."""
    for sec in SLOT_SECTIONS:
        for (path, old), new in zip(
                jax.tree_util.tree_leaves_with_path(caches[sec]),
                jax.tree_util.tree_leaves(new_caches[sec])):
            if (old.shape, old.dtype) != (new.shape, new.dtype):
                decode_fallback(
                    sec + jax.tree_util.keystr(path), "cache_not_donated",
                    f"{old.dtype}{list(old.shape)} comes back as "
                    f"{new.dtype}{list(new.shape)}")


# what a loop region's body counts each time it runs, in a decode step and
# in a prefill block
LOOP_PASSES = {"decode": "loop_passes", "prefill": "loop_prefill_passes"}


def build_step(topo, input_pts, constants, logits_pt, compute_dtype, *,
               batch: int, max_len: int, cache_dtype=None,
               decode_input: Optional[int] = None,
               assume_causal: bool = False, donate: bool = False,
               loops=()):
    """(init_caches, step) over the graph `topo`: the contract is
    PCGExecutor.build_decode's, which memoises this by its arguments.
    Decode is device-local: parallel ops are the identity and the weights
    are read where the training executor placed them.

    A loop region (`loops`, Graph.loops) runs as ONE fori_loop body over
    its steps in every step and prefill block. A stateful op inside it
    keeps one state a step, stacked on axis 1 after the slot axis (so
    insert_row and the rest take it as any per-slot leaf); the loop carries
    the stacked leaves, donated as the rest, and step u reads and writes
    its own slice in place (OpDef.loop_state, FwdCtx.loop_step)."""
    plan = build_plan(topo, input_pts, constants, decode_input,
                      assume_causal=assume_causal, loops=loops)
    _check_plan(plan, logits_pt, max_len)
    cdt = cache_dtype or compute_dtype or jnp.float32
    static_pts = [pt for pt in input_pts if pt.guid != plan.decode_pt.guid]
    ctx = FwdCtx(
        training=False, rng=None, seq_length=-1,
        compute_dtype=compute_dtype, aux_losses=None,
        n_devices=1, mesh=None,
    )
    info = plan.info
    cached_set = set(plan.cached_guids)

    # which live ops keep state is their definitions' answer; which of
    # them has static keys and values is the plan's
    static_keyed = plan.static_keyed
    static_keyed_set = {id(op) for op in static_keyed}
    stateful = [op for op in plan.live_ops
                if not op.is_parallel_op
                and get_op_def(op.op_type).decode_section is not None
                and id(op) not in static_keyed_set]
    stateful_set = {id(op) for op in stateful}
    loop_of = {op.guid: reg for reg in plan.loops for op in reg.ops}
    for op in stateful:
        if op.guid in loop_of and not get_op_def(op.op_type).loop_state:
            raise DecodeExactnessError(
                f"{op.name} ({op.op_type.name}): its decode state has no "
                "copy a step, so it cannot decode inside a loop region")
    needs_params = bool(static_keyed) or any(
        op.weights for op in plan.static_ops if not op.is_parallel_op
    )
    static_kept = _kept_statics(plan, static_keyed)
    counter_names = {sec: sorted({
        name for op in plan.live_ops if not op.is_parallel_op
        for name in get_op_def(op.op_type).counters_of(op.params, which)})
        for which, sec in (("decode", "counters"),
                           ("prefill", "prefill_counters"))}
    if plan.loops:
        counter_names["counters"].append(LOOP_PASSES["decode"])
        counter_names["prefill_counters"].append(LOOP_PASSES["prefill"])

    def init_caches(params=None, static_inputs=()):
        assert len(static_inputs) == len(static_pts), (
            f"need {len(static_pts)} static (non-decode) input arrays, "
            f"got {len(static_inputs)}"
        )
        assert params is not None or not needs_params, (
            "this graph has encoder-side ops: call "
            "init_caches(params, static_inputs)"
        )
        svals = _constants_at(constants, batch)
        for pt, arr in zip(static_pts, static_inputs):
            svals[pt.guid] = jnp.asarray(arr, pt.data_type.jnp_dtype)
        svals = _statics(plan, svals, params, ctx)
        # the step consumes the caches (donation): a static value that
        # IS the caller's array (an input or a weight a live op reads
        # as it came) or that lies under two guids is copied, so every
        # leaf is a buffer the caches alone own
        taken = {id(x) for x in jax.tree_util.tree_leaves(
            (params, list(static_inputs)))} if static_kept else set()
        caches = {sec: {} for sec in SHARED_SECTIONS + SLOT_SECTIONS}
        for g in static_kept:
            v = svals[g]
            caches["static"][g] = jnp.copy(v) if id(v) in taken else v
            taken.add(id(caches["static"][g]))
        for g in plan.cached_guids:
            pt = plan.cached_pt(g)
            shape = list(pt.material_shape())
            shape[info[g].live] = max_len
            if info[g].live != 0:
                shape[0] = batch  # decode batch, not compile batch
            caches["prefix"][g] = jnp.zeros(shape, pt.data_type.jnp_dtype)
        for op in stateful:
            d = get_op_def(op.op_type)
            state = d.init_decode_state(op.params, batch, max_len, cdt)
            reg = loop_of.get(op.guid)
            if reg is not None:  # one copy a step, on axis 1
                state = jax.tree_util.tree_map(
                    lambda a, n=reg.steps: jnp.broadcast_to(
                        a[:, None], a.shape[:1] + (n,) + a.shape[1:]), state)
            caches[d.decode_section][op.name] = state
        for sec, names in counter_names.items():
            for name in names:
                caches[sec][name] = jnp.zeros((), jnp.int32)
        for op in static_keyed:
            caches["mha_static"][op.name] = get_op_def(
                op.op_type).init_decode_static(
                    op.params, params.get(op.name, {}),
                    [svals[x.guid] for x in op.inputs[1:]], ctx)
        return caches

    # the last live op that mixes positions: an op with a decode rule of
    # its own, or a primitive-op attention's products and prefix softmax.
    # Every op after it treats the positions of a block alike.
    last_mixing = max(
        (i for i, op in enumerate(plan.live_ops)
         if not op.is_parallel_op and (
             id(op) in stateful_set | static_keyed_set
             or op.op_type in (OperatorType.OP_BATCHMATMUL,
                               OperatorType.OP_SOFTMAX)
             or any(x.guid in cached_set for x in op.outputs))),
        default=-1)
    # what the step runs: each live op, and each loop region (its live ops)
    # where its last live op stands; `cut` is the item after which a block
    # is cut to one row, never inside a region, whose later steps need the
    # whole block
    schedule, cut, region_ops = [], -1, {}
    for reg in plan.loops:
        region_ops[reg.name] = [op for op in plan.live_ops
                                if loop_of.get(op.guid) is reg]
    for i, op in enumerate(plan.live_ops):
        reg = loop_of.get(op.guid)
        if reg is None:
            schedule.append(op)
        elif op is region_ops[reg.name][-1]:
            schedule.append(reg)
        else:
            continue
        if last_mixing >= 0 and i >= last_mixing and cut < 0:
            cut = len(schedule) - 1

    def step(params, caches, t, batch_inputs, valid=None, row=None):
        from .executor import _count_trace

        (tok,) = batch_inputs
        tok = jnp.asarray(tok, plan.decode_pt.data_type.jnp_dtype)
        s0 = tok.shape[1]
        # one token a row is a decode step, a block of them a prefill
        _count_trace("decode_step" if s0 == 1 else "prefill")
        # t may be a scalar (all rows at the same position) or a (b,)
        # vector of per-row positions (continuous batching: each slot
        # of a running decode batch is mid-way through its own
        # sequence — runtime/serving.ContinuousBatcher)
        per_row_t = getattr(t, "ndim", 0) == 1
        if per_row_t and tok.shape[0] != t.shape[0]:
            raise NotImplementedError(
                f"per-row positions: {t.shape[0]} positions for "
                f"{tok.shape[0]} rows"
            )
        if valid is not None:
            valid = jnp.broadcast_to(
                jnp.asarray(valid, jnp.int32), (tok.shape[0],))
        consts = _constants_at(constants, batch)
        statics = dict(caches["static"])
        vals = {plan.decode_pt.guid: tok}
        new_caches = _stepped(caches)
        # what the ops count is of THIS trace's step
        sctx = dataclasses.replace(ctx, counters={}) \
            if any(counter_names.values()) else ctx

        def get_static(g):
            if g in statics:
                return statics[g]
            return consts[g]

        def aligned_input(vals, x, out_rank, out_info, site=""):
            """A live op's input value: live tensors yield their
            current slice; static/constant operands are sliced where
            their full-length axes align with the live/prefix axes."""
            g = x.guid
            if g in vals:
                return vals[g]
            full = get_static(g)
            # runtime shape, not the compiled ParallelTensor's — a
            # batch-collapsed constant differs on axis 0
            amap = _static_alignment(
                tuple(full.shape), out_rank, out_info, plan.live_len,
            )
            return _slice_aligned(full, amap, t, s0, max_len,
                                  out_rank=out_rank, site=site)

        def run_op(op, vals, sctx, held):
            """One op on the values of `vals`, its state read from and its
            successor written to `held` (per-slot sections)."""
            if op.is_parallel_op:
                vals[op.outputs[0].guid] = vals[op.inputs[0].guid]
                return
            d = get_op_def(op.op_type)
            w = params.get(op.name, {})
            ot = op.op_type
            out_info = info.get(op.outputs[0].guid, AxisInfo())

            if id(op) in stateful_set:
                ins = [vals[x.guid] for x in op.inputs]
                outs, held[d.decode_section][op.name] = \
                    d.forward_decode(
                        op.params, w, ins, sctx,
                        held[d.decode_section][op.name], t, valid=valid)
            elif id(op) in static_keyed_set:
                outs = d.forward_decode_static(
                    op.params, w, [vals[op.inputs[0].guid]], sctx,
                    caches["mha_static"][op.name],
                )
            elif ot == OperatorType.OP_BATCHMATMUL:
                a_pt, b_pt = op.inputs
                # lhs may itself be static (live operand on the rhs)
                a = (vals[a_pt.guid] if a_pt.guid in vals
                     else get_static(a_pt.guid))
                b_info = info.get(b_pt.guid, AxisInfo())
                if b_pt.guid in cached_set:
                    b = held["prefix"][b_pt.guid]
                elif b_info.is_live:
                    b = vals[b_pt.guid]
                else:
                    b_full = get_static(b_pt.guid)
                    a_info = info.get(a_pt.guid, AxisInfo())
                    rb = b_full.ndim
                    if a_info.prefix == len(a_pt.material_shape()) - 1:
                        # probs @ static V of compiled length: keep
                        # only the cap positions the cache covers
                        b_full = jax.lax.slice_in_dim(
                            b_full, 0, max_len, axis=rb - 2
                        )
                    b = b_full
                outs = [jnp.matmul(
                    a, b, preferred_element_type=jnp.float32
                ).astype(a.dtype)]
            elif ot == OperatorType.OP_SOFTMAX:
                x = vals[op.inputs[0].guid]
                dim = op.params.dim % x.ndim
                a_info = info[op.inputs[0].guid]
                if a_info.prefix is not None and dim == a_info.prefix:
                    assert a_info.live is not None, (
                        "prefix softmax without a live query axis"
                    )
                    outs = [_prefix_softmax(x, dim, a_info.live, t)]
                else:
                    outs = [jax.nn.softmax(x, axis=dim)]
            elif ot in (OperatorType.OP_RESHAPE, OperatorType.OP_FLAT):
                x = vals[op.inputs[0].guid]
                target = list(op.outputs[0].material_shape())
                if out_info.live is not None:
                    target[out_info.live] = s0
                if out_info.live != 0:
                    target[0] = -1  # batch may differ from compile
                outs = [jnp.reshape(x, target)]
            else:
                out_rank = len(op.outputs[0].material_shape())
                ins = [aligned_input(vals, x, out_rank, out_info, op.name)
                       for x in op.inputs]
                outs = d.forward(op.params, w, ins, sctx)

            for x, v in zip(op.outputs, outs):
                vals[x.guid] = v
                if x.guid in cached_set:
                    held["prefix"][x.guid] = _append(
                        held["prefix"][x.guid], v, t, info[x.guid].live,
                        x.guid)

        def run_loop(reg):
            """The region's live ops as ONE fori_loop body over its steps:
            the value fed back and the stacked states of its stateful ops
            are the carry, and what the body counts is summed over the
            steps (a name that ends in "_max" keeps the largest)."""
            body = [op for op in region_ops[reg.name] if op is not reg.entry]
            mine = [op for op in body if id(op) in stateful_set]
            which = "decode" if s0 == 1 else "prefill"
            names = sorted({LOOP_PASSES[which]} | {
                name for op in body if not op.is_parallel_op
                for name in get_op_def(op.op_type).counters_of(
                    op.params, which)})
            held = {sec: {} for sec in SLOT_SECTIONS}
            for op in mine:
                sec = get_op_def(op.op_type).decode_section
                held[sec][op.name] = new_caches[sec][op.name]

            def one_step(u, carry):
                x, held, counts = carry
                bctx = dataclasses.replace(sctx, counters={}, loop_step=u)
                bvals = dict(vals)
                bvals[reg.entry.outputs[0].guid] = x
                held = {sec: dict(v) for sec, v in held.items()}
                for op in body:
                    with jax.named_scope(op.name):
                        run_op(op, bvals, bctx, held)
                bctx.count(LOOP_PASSES[which], jnp.int32(1))
                got = {n: jnp.asarray(bctx.counters.get(n, 0), jnp.int32)
                       for n in names}
                counts = {n: jnp.maximum(c, got[n]) if n.endswith("_max")
                          else c + got[n] for n, c in counts.items()}
                return bvals[reg.exit.guid].astype(x.dtype), held, counts

            with jax.named_scope("ff.loop"):
                x, held, counts = jax.lax.fori_loop(
                    0, reg.steps, one_step,
                    (vals[reg.source.guid], held,
                     {n: jnp.zeros((), jnp.int32) for n in names}))
            vals[reg.exit.guid] = x
            for sec, states in held.items():
                new_caches[sec].update(states)
            for name, value in counts.items():
                sctx.count(name, value)

        # the same scopes as the train step's forward: ff.decode, then
        # one per PCG operator
        with jax.named_scope("ff.decode"):
            for i, item in enumerate(schedule):
                if isinstance(item, LoopRegion):
                    run_loop(item)
                else:
                    with jax.named_scope(item.name):
                        run_op(item, vals, sctx, new_caches)
                if i == cut and row is not None and s0 > 1:
                    # from here on one position a row: every live
                    # value is cut to it, and static operands are
                    # sliced at that position (aligned_input reads
                    # t and s0)
                    at = jnp.broadcast_to(
                        jnp.asarray(row, jnp.int32), (tok.shape[0],))
                    for g, v in list(vals.items()):
                        ax = info.get(g, AxisInfo()).live
                        if ax is None:
                            continue
                        if ax == 0:
                            raise NotImplementedError(
                                "one row of a block: a live tensor has "
                                "no batch axis before its live axis")
                        vals[g] = jax.vmap(
                            lambda r, n, _ax=ax:
                            jax.lax.dynamic_slice_in_dim(
                                r, n, 1, axis=_ax - 1)
                        )(v, at)
                    t, s0 = t + jnp.asarray(row, jnp.int32), 1
        for sec, names in counter_names.items():
            if names:
                new_caches[sec] = {
                    name: jnp.asarray(sctx.counters.get(name, 0), jnp.int32)
                    for name in names}
        if donate:
            _check_donated(caches, new_caches)
        return vals[logits_pt.guid], new_caches

    return init_caches, jax.jit(step, donate_argnums=(1,) if donate else ())
