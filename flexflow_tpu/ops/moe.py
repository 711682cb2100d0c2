"""Mixture-of-Experts operator family: Group_by, Aggregate, AggregateSpec,
Cache, and the expert bank.

TPU-native equivalents of reference src/ops/group_by.cc (534 LoC + CUDA),
aggregate.cc (569), aggregate_spec.cc (519), cache.cc (291). The reference
routes tokens to per-expert tensors with scatter CUDA kernels; the TPU-native
formulation is the dense dispatch/combine einsum (Mesh-TensorFlow / GShard
style): a one-hot dispatch mask [tokens, experts, capacity] turns routing into
two MXU matmuls, which is both jit-static and shardable over an expert mesh
axis (expert parallelism). What that costs: the mask is float32
[tokens x k, experts, capacity] and both einsums contract over it, so at 128
experts, top-6 and a 1,024-token prefill (capacity 48 at factor 1) it is 151
MB a layer and each einsum 2 x 6,144 x 128 x 48 x 2,688 = 203 GFLOP of
products that only move data, where the 128 experts' own work is 123; and a
token past an expert's capacity is dropped. A served model takes `expert_bank` below instead: ONE
op for a layer's router, the routed experts held here and its shared expert,
every held expert's matrices read once a block under the router's weights,
no capacity and no dropped token. (`models/zoo.py` `build_moe_transformer` still builds the
reference's composite from the ops above.)

Load balancing: the reference injects a lambda_bal term directly into the
gate gradients in aggregate's hand-written backward (aggregate.cc backward
task). Functionally we expose the same knob as an auxiliary load-balance loss
produced by group_by (ctx-free, differentiable), which jax.grad folds into
the gate weights — same gradient signal, no custom backward.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ff_types import ActiMode, DataType, OperatorType
from .common import apply_activation
from .registry import WeightSpec, register_op


def _capacity(batch_tokens: int, k: int, n: int, alpha: float) -> int:
    """reference: group_by.cc max_size = (int)ceil(alpha * k / n * batch)"""
    return max(1, int(math.ceil(alpha * k / n * batch_tokens)))


def _dispatch_mask(assign: jnp.ndarray, n: int, capacity: int):
    """Build the [b*k, n, capacity] one-hot dispatch mask from assignments.

    Tokens beyond an expert's capacity are dropped, matching the reference's
    fixed-size per-expert buffers (group_by.cc).
    """
    flat = assign.reshape(-1).astype(jnp.int32)  # [b*k]
    onehot = jax.nn.one_hot(flat, n, dtype=jnp.float32)  # [b*k, n]
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based rank within expert
    kept = (pos <= capacity).astype(jnp.float32) * onehot
    slot = jax.nn.one_hot((pos - 1.0).astype(jnp.int32), capacity, dtype=jnp.float32)
    return kept[..., None] * slot  # [b*k, n, capacity]


@dataclasses.dataclass(frozen=True)
class GroupByParams:
    """reference: include/flexflow/ops/groupby_params.h"""

    n: int  # number of experts
    alpha: float = 1.0  # capacity factor


def _gb_infer(params: GroupByParams, in_shapes, in_dtypes):
    inp, assign = in_shapes  # [b, d], [b, k]
    b, d = inp[0], inp[-1]
    k = assign[-1]
    cap = _capacity(b, k, params.n, params.alpha)
    return [(cap, d)] * params.n, [in_dtypes[0]] * params.n


def _gb_forward(params: GroupByParams, w, x, ctx):
    inp, assign = x  # [b, d], [b, k]
    b, d = inp.shape[0], inp.shape[-1]
    k = assign.shape[-1]
    cap = _capacity(b, k, params.n, params.alpha)
    mask = _dispatch_mask(assign, params.n, cap)  # [b*k, n, cap]
    rep = jnp.repeat(inp, k, axis=0)  # [b*k, d] token copies per slot
    packed = jnp.einsum("td,tnc->ncd", rep, mask.astype(inp.dtype))
    return [packed[e] for e in range(params.n)]


register_op(
    OperatorType.OP_GROUP_BY, "GroupBy", infer=_gb_infer, forward=_gb_forward,
    num_inputs=2,
)


@dataclasses.dataclass(frozen=True)
class AggregateParams:
    """reference: include/flexflow/ops/aggregate_params.h"""

    n: int
    lambda_bal: float = 0.0


def _agg_infer(params: AggregateParams, in_shapes, in_dtypes):
    # inputs: gate_preds [b,k], gate_assign [b,k], true_gate_assign [b,k],
    # full_gate_grads [b,n], exp_preds x n [cap, d]
    # (reference: aggregate.cc ctor — 4 + n inputs)
    d = in_shapes[4][-1]
    b = in_shapes[0][0]
    return [(b, d)], [in_dtypes[4]]


def _agg_forward(params: AggregateParams, w, x, ctx):
    gate_preds, gate_assign = x[0], x[1]
    exp_preds = x[4:]  # n tensors [cap, d]
    b, k = gate_preds.shape
    n = params.n
    cap = exp_preds[0].shape[0]
    stacked = jnp.stack(exp_preds, axis=0)  # [n, cap, d]
    mask = _dispatch_mask(gate_assign, n, cap)  # [b*k, n, cap]
    combine = mask * gate_preds.reshape(-1)[:, None, None].astype(jnp.float32)
    out_per_slot = jnp.einsum(
        "ncd,tnc->td", stacked.astype(jnp.float32), combine
    )  # [b*k, d]
    out = out_per_slot.reshape(b, k, -1).sum(axis=1)
    # Load-balance loss (reference: aggregate.cc backward folds lambda_bal
    # into gate grads). Switch-Transformer formulation: n * Σ_e f_e · P_e,
    # where f_e = dispatch fraction (stop-grad) and P_e = mean full-gate
    # probability (differentiable through x[3] = full gate activations).
    if params.lambda_bal > 0.0:
        full_gate = x[3].astype(jnp.float32)  # [b, n]
        probs = jax.nn.softmax(full_gate, axis=-1)
        p_mean = probs.mean(axis=0)  # [n]
        f = jax.lax.stop_gradient(mask.sum(axis=(0, 2)) / max(1, b * k))  # [n]
        ctx.add_aux_loss(params.lambda_bal * n * jnp.sum(f * p_mean))
    return [out.astype(exp_preds[0].dtype)]


register_op(
    OperatorType.OP_AGGREGATE, "Aggregate", infer=_agg_infer, forward=_agg_forward,
    num_inputs=-1,
)


@dataclasses.dataclass(frozen=True)
class AggregateSpecParams:
    """reference: include/flexflow/ops/aggregate_spec_params.h — speculative
    aggregation: same combine as Aggregate but each expert prediction is
    scored against replicated labels (model.cc:2875 replicates labels)."""

    n: int
    lambda_bal: float = 0.0


def _aggspec_infer(params: AggregateSpecParams, in_shapes, in_dtypes):
    # inputs: gate_preds [b,k], gate_assign [b,k], exp_preds x n [cap, d]
    d = in_shapes[2][-1]
    b = in_shapes[0][0]
    k = in_shapes[0][1]
    return [(b * k, d)], [in_dtypes[2]]


def _aggspec_forward(params: AggregateSpecParams, w, x, ctx):
    gate_preds, gate_assign = x[0], x[1]
    exp_preds = x[2:]
    b, k = gate_preds.shape
    n = params.n
    cap = exp_preds[0].shape[0]
    stacked = jnp.stack(exp_preds, axis=0)
    mask = _dispatch_mask(gate_assign, n, cap)
    out = jnp.einsum("ncd,tnc->td", stacked.astype(jnp.float32), mask)
    return [out.astype(exp_preds[0].dtype)]


register_op(
    OperatorType.OP_AGG_SPEC, "AggregateSpec", infer=_aggspec_infer,
    forward=_aggspec_forward, num_inputs=-1,
)


@dataclasses.dataclass(frozen=True)
class CacheParams:
    """reference: include/flexflow/ops/cache_params.h — caches an input
    tensor across batches (MoE gating cache: cache.cc keeps num_batches
    snapshots, CACHE_UPDATE_TASK writes the current batch, and a score
    function decides whether the cache is fresh enough to serve).

    Here the cache is a net_state buffer threaded through the train step:
    training passes the live input through AND writes it to the buffer
    (exponential blend over ~num_batches like the reference's rolling
    window); inference serves the CACHED value — the gating-cache
    behavior that lets MoE routing reuse recent statistics."""

    num_batches: int = 1


def _cache_state(params: CacheParams, in_shapes, in_dtypes):
    from .registry import WeightSpec

    # State buffers are DT_FLOAT regardless of the input dtype: the training
    # blend (1-alpha)*cached + alpha*x is float math, and a buffer typed to
    # an integer input would change dtype across the update, breaking the
    # lax.scan carry structure in build_train_scan. Values are cast on
    # write and cast back to the input dtype on serve.
    return [WeightSpec("cached", tuple(in_shapes[0]), DataType.DT_FLOAT,
                       "zero"),
            WeightSpec("filled", (1,), DataType.DT_FLOAT, "zero")]


def _cache_forward_stateful(params: CacheParams, weights, state, inputs, ctx):
    (x,) = inputs
    if not state:
        return [x], {}
    if ctx.training:
        # rolling blend over ~num_batches (reference keeps a window of
        # num_batches snapshots; the exponential average has the same
        # effective horizon without num_batches x memory)
        alpha = 1.0 / max(1, params.num_batches)
        filled = jnp.minimum(state["filled"] + 1.0, 1.0)
        xf = x.astype(state["cached"].dtype)
        cached = jnp.where(
            state["filled"] > 0,
            (1.0 - alpha) * state["cached"] + alpha * xf,
            xf,
        )
        cached = cached.astype(state["cached"].dtype)
        filled = filled.astype(state["filled"].dtype)
        return [x], {"cached": cached, "filled": filled}
    # inference: serve the cache when it has ever been written
    out = jnp.where(state["filled"] > 0, state["cached"].astype(x.dtype), x)
    return [out], state


register_op(
    OperatorType.OP_CACHE,
    "Cache",
    infer=lambda p, s, dt: ([s[0]], [dt[0]]),
    forward=lambda p, w, x, ctx: [x[0]],
    state_spec=_cache_state,
    forward_stateful=_cache_forward_stateful,
)


# ---------------------------------------------------------------------------
# The expert bank: router + the routed experts held here + the shared expert
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExpertBankParams:
    """One expert layer (DeepSeek-V3-style routing): `experts` routed
    experts of which this op HOLDS `held_count` from `held_from` on (expert
    parallelism's share of a layer: the rest live on other chips), `top_k`
    of all `experts` chosen a token by `router` scores ("sigmoid", or
    "softmax" over all the experts), two-matrix experts of `width` (`gated`:
    three matrices, act(x w_gate) * (x w_up) into w_down), one shared
    expert of `shared_width` of the same form (0: none)."""

    experts: int
    held_from: int
    held_count: int
    top_k: int
    width: int
    shared_width: int = 0
    scale: float = 1.0            # routed_scaling_factor
    norm_topk: bool = True        # weights divided by their sum
    activation: ActiMode = ActiMode.AC_MODE_RELU2
    router: str = "sigmoid"
    gated: bool = False

    def __post_init__(self):
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(f"router {self.router!r}: expected "
                             "sigmoid|softmax")
        if not (0 <= self.held_from
                and self.held_from + self.held_count <= self.experts
                and 0 < self.held_count and 0 < self.top_k <= self.experts):
            raise ValueError(f"expert bank holds [{self.held_from}, "
                             f"{self.held_from + self.held_count}) of "
                             f"{self.experts} experts, top {self.top_k}")


EXPERT_BANK_COUNTERS = ("moe_assignments_held", "moe_assignments_elsewhere",
                        "moe_experts_touched", "moe_expert_load_max")


def _bank_infer(params: ExpertBankParams, in_shapes, in_dtypes):
    return [tuple(in_shapes[0])], [in_dtypes[0]]


def _bank_weights(params: ExpertBankParams, in_shapes, in_dtypes):
    e, dt = in_shapes[0][-1], in_dtypes[0]
    n, f = params.held_count, params.width
    ws = [
        WeightSpec("router", (e, params.experts), dt),
        WeightSpec("b_corr", (params.experts,), dt, "zero"),
        WeightSpec("w_up", (n, e, f), dt),
        WeightSpec("w_down", (n, f, e), dt),
    ]
    if params.gated:
        ws.append(WeightSpec("w_gate", (n, e, f), dt))
    if params.shared_width:
        ws += [WeightSpec("shared_up", (e, params.shared_width), dt),
               WeightSpec("shared_down", (params.shared_width, e), dt)]
        if params.gated:
            ws.append(WeightSpec("shared_gate", (e, params.shared_width), dt))
    return ws


def route(params: ExpertBankParams, router, b_corr, x):
    """The router on tokens x (T, e), in float32: sigmoid scores over ALL
    experts (or their softmax, `params.router`), the `top_k` largest of
    score + b_corr chosen, the chosen scores (without b_corr) normalised
    and scaled. Returns (ids (T, k) int32, weights (T, k) float32)."""
    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) if params.router == "softmax" \
        else jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + b_corr.astype(f32), params.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if params.norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * params.scale


# bytes of float32 hidden activations (tokens x experts x width) one pass over
# a group of experts may hold; a block of tokens too long for all the held
# experts at once takes them a group at a time
_BANK_HIDDEN_BYTES = 256 << 20


def _bank_forward(params: ExpertBankParams, weights, inputs, ctx):
    """out = sum over a token's chosen experts THAT ARE HELD HERE of w_e
    e(x) + shared(x); what the experts elsewhere would add is left out, and
    no token is dropped: there is no capacity.

    Every held expert's first matrix meets every token of the block in one
    batched product, the router's weights (0 for an expert a token did not
    choose) scale the activations, and the second product contracts over
    experts x width at once, which sums the chosen experts' outputs. At a
    decode batch this reads each held expert once, which is the work's own
    bytes but for the few experts no token chose (61 of 64 at 64 tokens), and
    runs at 90% of the HBM roofline: 1.73 ms for 64 tokens at hidden 2,688,
    64 experts of 1,856, where `jax.lax.ragged_dot` over the assignments
    sorted by expert took 14.7 ms (its kernel walks 128 x 128 tiles of each
    touched matrix) and 21.8 ms against 9.2 for a 1,024-token block (TPU v5e,
    PR 33). The price is operations: tokens x held experts, not tokens
    x top_k; past a few thousand tokens a block a sorted grouped product
    with wide tiles (a Pallas kernel, ROADMAP B-M3) is what this wants."""
    (x,) = inputs
    cdt = ctx.compute_dtype
    if cdt is not None:
        x = x.astype(cdt)
    w = {n: (a.astype(x.dtype) if cdt is not None else a)
         for n, a in weights.items()}
    f32 = jnp.float32
    lead, e = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, e)
    T, n, f = xt.shape[0], params.held_count, params.width
    with jax.named_scope("ff.moe.route"):
        # the router reads the weights as stored, not rounded to the
        # compute type
        ids, gate = route(params, weights["router"], weights["b_corr"], xt)
    with jax.named_scope("ff.moe.dispatch"):
        local = ids - params.held_from                        # (T, k)
        held = (local >= 0) & (local < n)
        # an assignment elsewhere indexes past the last column: all zeros
        chosen = jax.nn.one_hot(jnp.where(held, local, n), n, dtype=f32)
        scale = jnp.sum(chosen * gate[..., None], axis=1)     # (T, n)
        load = jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)  # (n,)
        ctx.count("moe_assignments_held", jnp.sum(held, dtype=jnp.int32))
        ctx.count("moe_assignments_elsewhere",
                  jnp.sum(~held, dtype=jnp.int32))
        ctx.count("moe_experts_touched", jnp.sum(load > 0, dtype=jnp.int32))
        ctx.count("moe_expert_load_max", jnp.max(load))

    def group(up, down, scale, gate_w=None):
        """The experts `up` (g, e, f), `down` (g, f, e) on every token;
        gated ones act on `gate_w`'s product and scale `up`'s."""
        with jax.named_scope("ff.moe.experts"):
            h = jnp.einsum("te,gef->tgf", xt, up, preferred_element_type=f32)
            if gate_w is None:
                h = apply_activation(params.activation, h)
            else:
                h = h * apply_activation(params.activation, jnp.einsum(
                    "te,gef->tgf", xt, gate_w, preferred_element_type=f32))
        with jax.named_scope("ff.moe.combine"):
            h = (h * scale[:, :, None]).astype(x.dtype)
            return jnp.dot(h.reshape(T, -1), down.reshape(-1, e),
                           preferred_element_type=f32)

    g = n
    while g > 1 and (4 * T * g * f > _BANK_HIDDEN_BYTES or n % g):
        g -= 1
    gates = (w["w_gate"],) if params.gated else ()
    if g == n:
        out = group(w["w_up"], w["w_down"], scale, *gates)
    else:
        def body(acc, part):
            return acc + group(*part), None
        out, _ = jax.lax.scan(body, jnp.zeros((T, e), f32), (
            w["w_up"].reshape(n // g, g, e, f),
            w["w_down"].reshape(n // g, g, f, e),
            jnp.moveaxis(scale.reshape(T, n // g, g), 1, 0),
            *(a.reshape(n // g, g, e, f) for a in gates)))
    if params.shared_width:
        with jax.named_scope("ff.moe.shared"):
            s = jnp.dot(xt, w["shared_up"], preferred_element_type=f32)
            if params.gated:
                s = s * apply_activation(params.activation, jnp.dot(
                    xt, w["shared_gate"], preferred_element_type=f32))
            else:
                s = apply_activation(params.activation, s)
            out = out + jnp.dot(s.astype(x.dtype), w["shared_down"],
                                preferred_element_type=f32)
    return [out.astype(x.dtype).reshape(lead + (e,))]


register_op(
    OperatorType.OP_EXPERT_BANK, "ExpertBank", infer=_bank_infer,
    weights=_bank_weights, forward=_bank_forward, num_inputs=1,
    seq_pointwise=True, decode_counters=EXPERT_BANK_COUNTERS,
)
