"""Mamba-2 mixer (Dao and Gu, "Transformers are SSMs", arXiv:2405.21060): a
state-space layer with a scalar decay a head, the second op (after
ops/linear_attention.py) that keeps a recurrent state per slot in place of
keys and values.

With H heads of P channels (d_in = H P), G groups of B and C (head h uses
group h // (H / G)), state size N and a K-tap convolution:

    [z; xBC; dt] = W_in u                    (d_in + (d_in + 2 G N) + H wide)
    xBC = SiLU(causal depthwise conv_K(xBC) + b_conv)  ->  x (H x P), B, C (G x N)
    dt  = softplus(dt + dt_bias)             A = -exp(A_log)          (a head)
    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T                S in R^(P x N)
    y_t = S_t C_t + D x_t
    out = W_out RMSNorm_g(y * SiLU(z))       (the norm over each group's d_in / G)

TWO FORMS OF ONE FUNCTION. `ssd_chunked` takes a block of tokens in chunks
(the paper's SSD form: inside a chunk the outputs are one masked product of
C B^T with the decay between positions; between chunks the state is carried
by a scan over chunks, never over tokens). `ssm_step` is the recurrence for
one token. Both take a per-row count of valid tokens: a position at or
beyond it has dt = 0, which leaves S as it was (decay 1, update 0), and the
convolution's tail ends at the last valid token: a serving prefill pads its
prompt to a bucket and a state the padding has touched is never
overwritten. State and decay arithmetic are float32 at the highest matmul
precision whatever the compute type.

Per slot the op keeps S (H x P x N float32) and the last K - 1 rows of the
convolution's input: the same bytes whatever the sequence's length.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ff_types import OperatorType
from .registry import WeightSpec, register_op

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Mamba2Params:
    embed_dim: int
    num_heads: int
    head_dim: int
    state_size: int
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.num_heads % self.n_groups:
            raise ValueError(f"n_groups {self.n_groups} does not divide "
                             f"num_heads {self.num_heads}")

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size

    @property
    def in_width(self) -> int:
        return self.inner + self.conv_channels + self.num_heads


def _infer(params: Mamba2Params, in_shapes, in_dtypes):
    (x,) = in_shapes
    return [(x[0], x[1], params.embed_dim)], [in_dtypes[0]]


def _weights(params: Mamba2Params, in_shapes, in_dtypes):
    e = in_shapes[0][-1]
    h = params.num_heads
    dt = in_dtypes[0]
    return [
        WeightSpec("w_in", (e, params.in_width), dt),
        WeightSpec("w_out", (params.inner, params.embed_dim), dt),
        WeightSpec("conv", (params.conv_kernel, params.conv_channels), dt),
        WeightSpec("conv_bias", (params.conv_channels,), dt, "zero"),
        WeightSpec("A_log", (h,), dt, "zero"),
        WeightSpec("dt_bias", (h,), dt, "zero"),
        WeightSpec("D", (h,), dt, "one"),
        WeightSpec("norm", (params.inner,), dt, "one"),
    ]


def init_state(params: Mamba2Params, batch: int, dtype):
    """Fresh per-slot state: (S, conv_tail). S is float32 always; the tail
    holds the convolution's last K - 1 input rows in the compute type."""
    return (
        jnp.zeros((batch, params.num_heads, params.head_dim,
                   params.state_size), jnp.float32),
        jnp.zeros((batch, params.conv_kernel - 1, params.conv_channels),
                  dtype),
    )


def state_bytes(params: Mamba2Params, itemsize: int) -> int:
    """Bytes one slot's state takes: S in float32 and the tail."""
    return (4 * params.num_heads * params.head_dim * params.state_size
            + itemsize * (params.conv_kernel - 1) * params.conv_channels)


# -- the state-space recurrence: two forms of one function ---------------------
def ssm_step(S, x, B, C, dt, A):
    """The recurrence for one token. S (b, h, p, n) float32; x (b, h, p); B,
    C (b, g, n); dt (b, h); A (h,). Returns (y (b, h, p) without the D
    term, S')."""
    b, h = dt.shape
    g = B.shape[1]
    Bh = jnp.repeat(B, h // g, axis=1)                       # (b, h, n)
    Ch = jnp.repeat(C, h // g, axis=1)
    S = S * jnp.exp(dt * A)[..., None, None] \
        + (dt[..., None] * x)[..., :, None] * Bh[..., None, :]
    return jnp.einsum("bhpn,bhn->bhp", S, Ch, precision=_HI), S


def ssd_chunked(S, x, B, C, dt, A, chunk: int):
    """A block of tokens in chunks. S (b, h, p, n) float32; x (b, s, h, p);
    B, C (b, s, g, n); dt (b, s, h), all float32; A (h,); s a multiple of
    `chunk`. Returns (y (b, s, h, p) without the D term, S after the block).

    Inside a chunk, with a = dt A and L its running sum: y_t = exp(L_t) S0
    C_t + sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s, one masked product
    a head; the chunk hands on exp(L_end) S0 + sum_s exp(L_end - L_s) dt_s
    x_s B_s^T."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    hg = h // g
    nc = s // chunk

    def chunks(t):  # (b, s, heads.., w) -> (nc, b, heads.., chunk, w)
        t = t.reshape((b, nc, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 2, -2), 1, 0)

    # heads by group (g, i), so that a group's B and C are never repeated;
    # positions and channels innermost, where the products want them
    x = chunks((x * dt[..., None]).reshape(b, s, g, hg, p))  # dt x
    B, C = chunks(B), chunks(C)                              # (nc, b, g, l, n)
    L = jnp.cumsum(chunks((dt * A).reshape(b, s, g, hg, 1))[..., 0], axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = L[..., :, None] - L[..., None, :]                 # (nc,b,g,i,l,s)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    cb = jnp.einsum("cbgln,cbgsn->cbgls", C, B, precision=_HI)
    y_in = jnp.einsum("cbgils,cbgisp->cbgilp", decay * cb[:, :, :, None], x,
                      precision=_HI)
    end = L[..., -1]                                         # (nc, b, g, i)
    handed = jnp.einsum("cbgisp,cbgsn->cbgipn",
                        x * jnp.exp(end[..., None] - L)[..., None], B,
                        precision=_HI)
    c_in = C[:, :, :, None] * jnp.exp(L)[..., None]          # (nc,b,g,i,l,n)

    def body(S, c):
        c_c, handed_c, end_c = c
        y = jnp.einsum("bgiln,bgipn->bgilp", c_c, S, precision=_HI)
        return S * jnp.exp(end_c)[..., None, None] + handed_c, y

    S, y_out = jax.lax.scan(body, S.reshape(b, g, hg, p, n),
                            (c_in, handed, end))
    y = jnp.moveaxis(y_in + y_out, 0, 1)                     # (b,nc,g,i,l,p)
    y = jnp.moveaxis(y, 4, 2)                                # (b,nc,l,g,i,p)
    return y.reshape(b, s, h, p), S.reshape(b, h, p, n)


# -- the op --------------------------------------------------------------------
def _mix(params: Mamba2Params, weights, u, ctx, state, valid):
    """The op on a block u (b, s, e) from `state` = (S, conv_tail); `valid`
    (b,) counts each row's real tokens (None: all of them). Returns
    (out (b, s, e), state after the block's valid tokens)."""
    from .. import obs

    cdt = ctx.compute_dtype
    if cdt is not None:
        u = u.astype(cdt)
    w = {n: (a.astype(u.dtype) if cdt is not None else a)
         for n, a in weights.items()}
    b, s, _ = u.shape
    h, p, n, g = (params.num_heads, params.head_dim, params.state_size,
                  params.n_groups)
    d_in, K = params.inner, params.conv_kernel
    S, tail = state
    f32 = jnp.float32
    with jax.named_scope("ff.ssm.proj"):
        zxd = jnp.dot(u, w["w_in"], preferred_element_type=f32)
        z = zxd[..., :d_in].astype(u.dtype)
        xbc = zxd[..., d_in:d_in + params.conv_channels].astype(u.dtype)
        dt = jax.nn.softplus(zxd[..., d_in + params.conv_channels:]
                             + w["dt_bias"].astype(f32))     # (b, s, h)
        if valid is not None:
            live = jnp.arange(s)[None, :] < valid[:, None]
            dt = jnp.where(live[..., None], dt, 0.0)
        A = -jnp.exp(w["A_log"].astype(f32))
    with jax.named_scope("ff.ssm.conv"):
        # rows -K+1..-1 are the tail the last block left; the tail handed
        # on is the K - 1 rows that end at the last valid token
        rows = jnp.concatenate([tail.astype(u.dtype), xbc], axis=1)
        conv = sum(rows[:, j:j + s].astype(f32) * w["conv"][j].astype(f32)
                   for j in range(K)) + w["conv_bias"].astype(f32)
        conv = jax.nn.silu(conv)
        if valid is None:
            tail = rows[:, s:]
        else:
            tail = jax.vmap(lambda r, m: jax.lax.dynamic_slice_in_dim(
                r, m, K - 1, axis=0))(rows, valid.astype(jnp.int32))
        x = conv[..., :d_in].reshape(b, s, h, p)
        B = conv[..., d_in:d_in + g * n].reshape(b, s, g, n)
        C = conv[..., d_in + g * n:].reshape(b, s, g, n)
    if s == 1:
        with jax.named_scope("ff.ssm.step"):
            y, S = ssm_step(S, x[:, 0], B[:, 0], C[:, 0], dt[:, 0], A)
            y = y[:, None]
    else:
        # a block shorter than a chunk is one chunk of its own length
        chunk = min(params.chunk_size, s)
        pad = -s % chunk
        xs, Bs, Cs, dts = x, B, C, dt
        if pad:
            # no whole number of chunks: padded with positions that touch
            # nothing (dt = 0), and counted
            obs.count("ff_ssm_fallback_total",
                      help="state-space blocks that took a slower path "
                           "than the whole-chunk form",
                      reason="ragged_chunk")
            xs, Bs, Cs, dts = (
                jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                for a in (x, B, C, dt))
        with jax.named_scope("ff.ssm.scan"):
            y, S = ssd_chunked(S, xs, Bs, Cs, dts, A, chunk)
            y = y[:, :s]
    with jax.named_scope("ff.ssm.gate"):
        y = y + w["D"].astype(f32)[:, None] * x
        y = y.reshape(b, s, g, d_in // g) \
            * jax.nn.silu(z.astype(f32)).reshape(b, s, g, d_in // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + params.norm_eps)
        y = (y.reshape(b, s, d_in) * w["norm"].astype(f32)).astype(u.dtype)
    with jax.named_scope("ff.ssm.proj"):
        out = jnp.dot(y, w["w_out"], preferred_element_type=f32) \
            .astype(u.dtype)
    return out, (S, tail.astype(state[1].dtype))


def _forward(params: Mamba2Params, weights, inputs, ctx):
    (u,) = inputs
    dtype = ctx.compute_dtype or u.dtype
    y, _ = _mix(params, weights, u, ctx,
                init_state(params, u.shape[0], dtype), None)
    return [y.astype(u.dtype)]


def _init_decode_state(params, batch, max_len, dtype):
    """The op's state as the decode caches hold it (section "recurrent"):
    of fixed size, so `max_len` is not used."""
    return init_state(params, batch, dtype)


def _forward_decode(params, weights, inputs, ctx, state, t, valid=None):
    """Incremental step (parallel/decode.py): the block's tokens from the
    slot's state. `t` is not used: the state carries the position."""
    (u,) = inputs
    y, state = _mix(params, weights, u, ctx, state, valid)
    return [y], state


register_op(
    OperatorType.OP_MAMBA2,
    "Mamba2",
    infer=_infer,
    weights=_weights,
    forward=_forward,
    num_inputs=1,
    forward_decode=_forward_decode,
    init_decode_state=_init_decode_state,
    decode_section="recurrent",
)
