"""Gated delta-rule linear attention (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", arXiv:2412.06464): a sequence mixer that keeps, per head, one
matrix of state in place of keys and values.

Per token t and head h, with the state S in R^(dv x dk), float32:

    [q~; k~; v~] = SiLU(causal depthwise conv_K([Wq x; Wk x; Wv x]))
    q = q~ / |q~| / sqrt(dk)        k = k~ / |k~|   (|a| = sqrt(a.a + 1e-6))
    beta  = sigmoid(Wb x)           (x 2 with allow_neg_eigval)
    alpha = exp(-exp(A_log) * softplus(Wa x + dt_bias))
    S_t = alpha_t S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t
    y   = Wo concat_h( RMSNorm_dv(o_t) * SiLU(Wz x) )

TWO FORMS OF ONE FUNCTION. `delta_rule_chunked` takes a block of tokens in
chunks of 64 (the paper's WY form: inside a chunk the rank-one updates are
folded into one triangular system, solved by matrix products; between chunks
the state is carried by a scan over chunks, never over tokens).
`delta_rule_step` is the recurrence for one token. Both take a per-row count
of valid tokens: a position at or beyond it leaves S and the convolution's
tail as they were (beta = 0, alpha = 1), because a serving prefill pads its
prompt to a bucket and, unlike keys and values, a state the padding has
touched is never overwritten. State and decay arithmetic are float32 at the
highest matmul precision whatever the compute type.

Per slot the op keeps S (heads x dv x dk float32) and the last K - 1 rows of
the convolution's input: the same bytes whatever the sequence's length.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ff_types import OperatorType
from .normalization import rms_normalize
from .registry import WeightSpec, register_op

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GatedDeltaNetParams:
    embed_dim: int
    num_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6

    @property
    def conv_channels(self) -> int:
        return self.num_heads * (2 * self.head_k_dim + self.head_v_dim)


def _infer(params: GatedDeltaNetParams, in_shapes, in_dtypes):
    (x,) = in_shapes
    return [(x[0], x[1], params.embed_dim)], [in_dtypes[0]]


def _weights(params: GatedDeltaNetParams, in_shapes, in_dtypes):
    e = in_shapes[0][-1]
    h, dk, dv = params.num_heads, params.head_k_dim, params.head_v_dim
    dt = in_dtypes[0]
    return [
        WeightSpec("wq", (e, h * dk), dt),
        WeightSpec("wk", (e, h * dk), dt),
        WeightSpec("wv", (e, h * dv), dt),
        WeightSpec("wz", (e, h * dv), dt),
        WeightSpec("wo", (h * dv, params.embed_dim), dt),
        WeightSpec("wb", (e, h), dt),
        WeightSpec("wa", (e, h), dt),
        WeightSpec("conv", (params.conv_kernel, params.conv_channels), dt),
        WeightSpec("A_log", (h,), dt, "zero"),
        WeightSpec("dt_bias", (h,), dt, "zero"),
        WeightSpec("norm", (dv,), dt, "one"),
    ]


def init_state(params: GatedDeltaNetParams, batch: int, dtype):
    """Fresh per-slot state: (S, conv_tail). S is float32 always; the tail
    holds the convolution's last K - 1 input rows in the compute type."""
    return (
        jnp.zeros((batch, params.num_heads, params.head_v_dim,
                   params.head_k_dim), jnp.float32),
        jnp.zeros((batch, params.conv_kernel - 1, params.conv_channels),
                  dtype),
    )


def state_bytes(params: GatedDeltaNetParams, itemsize: int) -> int:
    """Bytes one slot's state takes: S in float32 and the tail."""
    return (4 * params.num_heads * params.head_v_dim * params.head_k_dim
            + itemsize * (params.conv_kernel - 1) * params.conv_channels)


# -- the delta rule: two forms of one function ---------------------------------
def delta_rule_step(S, q, k, v, g, beta):
    """The recurrence for one token. S (b, h, dv, dk) float32; q, k (b, h,
    dk); v (b, h, dv); g = log alpha and beta (b, h). Returns (o, S')."""
    S = S * jnp.exp(g)[..., None, None]
    u = (v - jnp.einsum("bhvk,bhk->bhv", S, k, precision=_HI)) \
        * beta[..., None]
    S = S + u[..., :, None] * k[..., None, :]
    return jnp.einsum("bhvk,bhk->bhv", S, q, precision=_HI), S


def _unit_lower_inverse(m):
    """(I + m)^-1 for strictly lower-triangular m (.., c, c): m is
    nilpotent, so the inverse is the finite product
    (I - m)(I + m^2)(I + m^4)... up to the power c, all matrix products."""
    c = m.shape[-1]
    eye = jnp.eye(c, dtype=m.dtype)
    inv, power, reach = eye - m, m, 2
    while reach < c:
        power = jnp.matmul(power, power, precision=_HI)
        inv = jnp.matmul(inv, eye + power, precision=_HI)
        reach *= 2
    return inv


def delta_rule_chunked(S, q, k, v, g, beta, chunk: int = CHUNK):
    """A block of tokens in chunks. S (b, h, dv, dk) float32; q, k (b, s, h,
    dk); v (b, s, h, dv); g, beta (b, s, h), all float32; s a multiple of
    `chunk`. Returns (o (b, s, h, dv), S after the block).

    Inside a chunk, with G the running sum of g: the updates of its tokens
    to the state it started from solve (I + M) U = beta (v - exp(G) S0 k),
    M[i, j] = beta_i k_i.k_j exp(G_i - G_j) below the diagonal; the chunk's
    outputs and the state it hands on are matrix products with U."""
    b, s, h, dk = q.shape
    n = s // chunk

    def chunks(x):  # (b, s, h, ...) -> (n, b, h, chunk, ...)
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g), chunks(beta)           # (n, b, h, chunk)
    G = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = G[..., :, None] - G[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    M = jnp.einsum("nbhik,nbhjk->nbhij", kb, k, precision=_HI) * decay
    T = _unit_lower_inverse(jnp.where(jnp.tril(lower, -1), M, 0.0))
    U = jnp.matmul(T, v * beta[..., None], precision=_HI)
    W = jnp.matmul(T, kb * jnp.exp(G)[..., None], precision=_HI)
    qk = jnp.einsum("nbhik,nbhjk->nbhij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])

    def body(S, c):
        qk_c, q_c, k_c, U_c, W_c, last_c = c
        u = U_c - jnp.einsum("bhik,bhvk->bhiv", W_c, S, precision=_HI)
        o = jnp.einsum("bhik,bhvk->bhiv", q_c, S, precision=_HI) \
            + jnp.matmul(qk_c, u, precision=_HI)
        S = S * last_c[..., None, None] \
            + jnp.einsum("bhiv,bhik->bhvk", u, k_c, precision=_HI)
        return S, o

    S, o = jax.lax.scan(body, S, (qk, q_in, k_out, U, W, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)  # (b, n, chunk, h, dv)
    return o.reshape(b, s, h, -1), S


# -- the op --------------------------------------------------------------------
def _mix(params: GatedDeltaNetParams, weights, x, ctx, state, valid):
    """The op on a block x (b, s, e) from `state` = (S, conv_tail); `valid`
    (b,) counts each row's real tokens (None: all of them). Returns
    (y (b, s, e), state after the block's valid tokens)."""
    from .. import obs

    cdt = ctx.compute_dtype
    if cdt is not None:
        x = x.astype(cdt)
    w = {n: (a.astype(x.dtype) if cdt is not None else a)
         for n, a in weights.items()}
    b, s, _ = x.shape
    h, dk, dv = params.num_heads, params.head_k_dim, params.head_v_dim
    K = params.conv_kernel
    S, tail = state
    f32 = jnp.float32
    live = None if valid is None else \
        jnp.arange(s)[None, :] < valid[:, None]              # (b, s)

    def proj(name):
        return jnp.dot(x, w[name], preferred_element_type=f32)

    with jax.named_scope("ff.linear_attn.proj"):
        qkv = jnp.concatenate(
            [proj("wq"), proj("wk"), proj("wv")], axis=-1).astype(x.dtype)
        z = proj("wz").astype(x.dtype)
        beta = jax.nn.sigmoid(proj("wb"))
        if params.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(w["A_log"].astype(f32)) * jax.nn.softplus(
            proj("wa") + w["dt_bias"].astype(f32))           # log alpha
        if live is not None:
            beta = jnp.where(live[..., None], beta, 0.0)
            g = jnp.where(live[..., None], g, 0.0)
    with jax.named_scope("ff.linear_attn.conv"):
        # rows -K+1..-1 are the tail the last block left; the tail handed
        # on is the K - 1 rows that end at the last valid token
        u = jnp.concatenate([tail.astype(x.dtype), qkv], axis=1)
        conv = sum(u[:, j:j + s].astype(f32) * w["conv"][j].astype(f32)
                   for j in range(K))
        conv = jax.nn.silu(conv)
        if valid is None:
            tail = u[:, s:]
        else:
            tail = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
                r, n, K - 1, axis=0))(u, valid.astype(jnp.int32))
        q, k, v = jnp.split(conv, [h * dk, 2 * h * dk], axis=-1)
        q = q.reshape(b, s, h, dk)
        k = k.reshape(b, s, h, dk)
        v = v.reshape(b, s, h, dv)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * (dk ** -0.5)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    if s == 1:
        with jax.named_scope("ff.linear_attn.step"):
            o, S = delta_rule_step(S, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                   beta[:, 0])
            o = o[:, None]
    else:
        # a block shorter than a chunk is one chunk of its own length
        chunk = min(CHUNK, s)
        pad = -s % chunk
        if pad:
            # no whole number of chunks: padded with positions that touch
            # nothing, and counted
            obs.count("ff_linear_attn_fallback_total",
                      help="gated delta-rule blocks that took a slower "
                           "path than the whole-chunk form",
                      reason="ragged_chunk")
            q, k, v, g, beta = (
                jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                for a in (q, k, v, g, beta))
        with jax.named_scope("ff.linear_attn.scan"):
            o, S = delta_rule_chunked(S, q, k, v, g, beta, chunk)
            o = o[:, :s]
    with jax.named_scope("ff.linear_attn.gate"):
        o = rms_normalize(o, w["norm"], params.norm_eps) \
            * jax.nn.silu(z.reshape(b, s, h, dv).astype(f32))
        o = o.reshape(b, s, h * dv).astype(x.dtype)
    with jax.named_scope("ff.linear_attn.proj"):
        y = jnp.dot(o, w["wo"], preferred_element_type=f32).astype(x.dtype)
    return y, (S, tail.astype(state[1].dtype))


def _forward(params: GatedDeltaNetParams, weights, inputs, ctx):
    (x,) = inputs
    dtype = ctx.compute_dtype or x.dtype
    y, _ = _mix(params, weights, x, ctx,
                init_state(params, x.shape[0], dtype), None)
    return [y.astype(x.dtype)]


def _init_decode_state(params, batch, max_len, dtype):
    """The op's state as the decode caches hold it (section "recurrent"):
    of fixed size, so `max_len` is not used."""
    return init_state(params, batch, dtype)


def _forward_decode(params, weights, inputs, ctx, state, t, valid=None):
    """Incremental step (parallel/decode.py): the block's tokens from
    the slot's state. `t` is not used: the state carries the position."""
    (x,) = inputs
    y, state = _mix(params, weights, x, ctx, state, valid)
    return [y], state


register_op(
    OperatorType.OP_GATED_DELTA_NET,
    "GatedDeltaNet",
    infer=_infer,
    weights=_weights,
    forward=_forward,
    num_inputs=1,
    forward_decode=_forward_decode,
    init_decode_state=_init_decode_state,
    decode_section="recurrent",
)
