"""MultiHeadAttention operator.

TPU-native equivalent of reference src/ops/attention.cc (926 LoC, cuDNN
`cudnnMultiHeadAttnForward` with packed qkv weights). Here attention is
expressed as einsum chains that XLA maps onto the MXU; a Pallas
flash-attention kernel (kernels/flash_attention.py) is used for long
sequences where the O(s^2) score tensor would blow HBM.

Head-dim parallelism: the reference partitions weights per-head
(attention.cc:214 — "attribute parallelism over heads"); our PCG carries that
as a degree on the heads dim, which lowers to sharding the (num_heads,...)
weight axes over the mesh's model axis.

Inputs are (batch, seq, embed) like the reference's (N, L, E).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from ..ff_types import DataType, OperatorType
from .registry import WeightSpec, register_op


# Dropout-fallback bookkeeping: the "dropout forces the dense path" warning
# used to fire on EVERY traced forward (once per layer per trace — dozens of
# identical lines per compile). Now each distinct (impl, layer, reason)
# warns once per process, and every occurrence is counted in the
# ff_attention_fallback_total{reason=...} metric instead (obs.count — a
# no-op without an active telemetry session).
_FALLBACK_WARNED: set = set()


class AttentionConfigError(ValueError):
    """An attention op was asked for a combination no path computes: a
    window under ring or ulysses sequence parallelism."""


def reset_attention_fallback_warnings() -> None:
    """Forget which (impl, layer, reason) fallbacks already warned
    (tests; a fresh process starts empty)."""
    _FALLBACK_WARNED.clear()


def _dropout_fallback(impl: str, op_name: str, reason: str) -> None:
    from .. import obs

    obs.count("ff_attention_fallback_total",
              help="attention ops that fell back to the dense path",
              reason=reason)
    key = (impl, op_name, reason)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    detail = {
        "kernel": f"FF_ATTENTION_IMPL={impl} does not thread the dropout "
                  "rng (only the fused flash kernels do)",
        "mesh": "the flash dropout kernel runs device-local; sharded "
                "meshes keep the dense path",
        "backend": "the fused Pallas kernel needs the TPU backend",
        "seq": "the sequence exceeds the fused kernel's VMEM tile",
        # sequence-parallel (ring/ulysses) fallbacks: the requested SP
        # impl cannot engage, so XLA all-gathers the full K/V instead
        "sp_mesh": f"FF_ATTENTION_IMPL={impl} needs a seq-sharded mesh "
                   "(sequence_parallel_degree > 1)",
        "sp_shape": "ring/ulysses need self-attention with batch, heads "
                    "and seq divisible by their mesh degrees",
        "sp_heads": "ulysses needs the per-device head count divisible "
                    "by the seq axis (heads scatter over it)",
        # paged flash-decode fallbacks (serving): the requested paged
        # kernel cannot prove exactness for this step, so the dense
        # per-row masked path runs instead
        "paged_block": "the paged flash-decode kernel attends ONE query "
                       "token per slot; multi-token blocks (prefill) "
                       "keep the dense masked path",
        "paged_untileable": "Mosaic cannot tile the paged flash-decode "
                            "kernel at this shape (heads*head_dim must "
                            "fill 128-lane registers and a page whole "
                            "sublane tiles: kernels/decode.py "
                            "decode_block_pages)",
    }[reason]
    kind = "dropout" if reason in ("kernel", "mesh", "backend", "seq") \
        else "paged decode" if reason.startswith("paged_") \
        else "sequence parallelism"
    knob = "FF_DECODE_IMPL" if reason.startswith("paged_") \
        else "FF_ATTENTION_IMPL"
    warnings.warn(
        f"attention {kind} on {op_name or 'a MHA op'} "
        f"({knob}={impl}) falls back to the dense path: "
        f"{detail}"
    )


# past this many bytes of float32 scores a block of decode queries takes the
# chunked scan (the same budget _forward streams from)
_DENSE_SCORE_BYTES = 256 * 1024 * 1024
# what a full layer's long prefill block counts of its causal walk's
# (query block, key chunk) tiles: those computed, and those skipped
PREFILL_TILE_COUNTERS = ("attn_prefill_tiles_computed",
                         "attn_prefill_tiles_skipped")
# the walk's tile: queries a block, cache positions a key chunk (at most)
_PREFILL_BLOCK, _KEY_CHUNK = 512, 256


@dataclasses.dataclass(frozen=True)
class RotaryParams:
    """A rotary position embedding on q and k: the first `dim` channels of
    a head (0 = all of them) rotated by position x inv_freq, channel i
    paired with i + dim/2 (`rotate_half`), the rest passed as they are.
    `scaling` "yarn" blends interpolated and extrapolated frequencies as
    `transformers` `_compute_yarn_parameters` does and multiplies cos and
    sin by `attention_factor` (0 = 0.1 ln(factor) + 1)."""

    theta: float = 10000.0
    dim: int = 0
    scaling: str = "default"      # "default" | "yarn"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0

    def __post_init__(self):
        if self.scaling not in ("default", "yarn"):
            raise ValueError(f"rotary scaling {self.scaling!r}: expected "
                             "default|yarn")
        if self.dim % 2:
            raise ValueError(f"rotary dim {self.dim} is odd")
        if self.scaling == "yarn" and (
                self.factor < 1.0
                or self.original_max_position_embeddings <= 0):
            raise ValueError("yarn needs factor >= 1 and "
                             "original_max_position_embeddings")


def rotary_table(rope: RotaryParams, head_dim: int):
    """(inv_freq (dim/2,) float32 numpy, the factor on cos and sin) of one
    op, computed once where the op is traced."""
    import numpy as np

    dim = rope.dim or head_dim
    if dim > head_dim:
        raise ValueError(f"rotary dim {dim} exceeds the head size {head_dim}")
    inv = rope.theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.scaling == "default":
        return inv.astype(np.float32), 1.0

    def correction(rotations):
        return dim * math.log(rope.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(rope.theta))

    low = max(math.floor(correction(rope.beta_fast)), 0)
    high = min(math.ceil(correction(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # as the source: no division by zero
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = inv / rope.factor * ramp + inv * (1.0 - ramp)
    return inv.astype(np.float32), (
        rope.attention_factor or 0.1 * math.log(rope.factor) + 1.0)


def apply_rotary(rope: RotaryParams, x, positions):
    """x (b, s, heads, d) rotated by `positions` ((s,) or (b, s), the true
    positions of its rows), in float32, back in x's type:
    out = x * cos + pair(x) * sin over whole heads, with constants that
    make the channels past the rotated ones pass (angle 0) and give the
    pairs their signs. pair(x) (channel i's partner, i +- rot/2) is a
    product with a 0/1 matrix, exact in x's own type: slices of a head
    joined again would ask the TPU compiler for unaligned lane offsets."""
    import numpy as np

    d = x.shape[-1]
    inv, factor = rotary_table(rope, d)
    half = inv.shape[0]
    rot = 2 * half
    inv_d, cos_f, sin_f = (np.zeros(d, np.float32) for _ in range(3))
    inv_d[:rot] = np.concatenate([inv, inv])
    cos_f[:rot], cos_f[rot:] = factor, 1.0
    sin_f[:half], sin_f[half:rot] = -factor, factor
    partner = np.zeros((d, d), np.float32)
    i = np.arange(half)
    partner[i + half, i] = partner[i, i + half] = 1.0
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_d)
    cos = (jnp.cos(ang) * cos_f)[..., None, :]         # over the heads
    sin = (jnp.sin(ang) * sin_f)[..., None, :]
    pair = jnp.einsum(
        "bshd,de->bshe", x, jnp.asarray(partner, x.dtype),
        precision=jax.lax.Precision.HIGHEST
        if x.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + pair * sin).astype(x.dtype)


def window_ring(window: int, max_len: int) -> int:
    """Positions a window layer's cache leaf keeps: the window rounded up
    to whole pages of 16, or `max_len` where that is no longer (such a leaf
    never wraps)."""
    return min(max_len, -(-window // 16) * 16)


@dataclasses.dataclass(frozen=True)
class MultiHeadAttentionParams:
    """reference: include/flexflow/ops/attention_params.h"""

    embed_dim: int
    num_heads: int
    kdim: int = 0  # 0 = embed_dim
    vdim: int = 0
    dropout: float = 0.0
    bias: bool = True
    add_bias_kv: bool = False
    add_zero_attn: bool = False
    causal: bool = False  # TPU addition: causal masking for decoder models
    # RMS norms on the projected q and k, each over its whole heads x head
    # size projection with a learned scale (the OLMo 2 block's QK-norm)
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    # grouped-query attention: key-value heads fewer than query heads, query
    # head i reading key-value head i // (num_heads / num_kv_heads). 0 means
    # num_heads (every query head its own keys and values).
    num_kv_heads: int = 0
    # rotary position embedding on q and the new keys (None: none)
    rope: Optional[RotaryParams] = None
    # window: query i sees keys j with i - window < j <= i (0: every key the
    # causal mask leaves); the decode cache of such an op is a ring
    # (init_decode_cache)
    window: int = 0
    # per-head output gate: head h's attention output times
    # sigmoid(x wg)[h], wg (embed, heads), before the output projection
    head_gate: bool = False

    def __post_init__(self):
        if self.num_kv_heads and self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_kv_heads {self.num_kv_heads} does not divide "
                f"num_heads {self.num_heads}")
        if self.window < 0 or (self.window and not self.causal):
            raise ValueError(f"window {self.window} needs a causal op")

    @property
    def marked(self):
        """An op with any of rope, window and head_gate marks its parts
        with scopes (ff.attn.*) and counts the positions its decode steps
        read; an op with none lowers as it always did."""
        return bool(self.rope is not None or self.window or self.head_gate)

    @property
    def kind(self):
        return "window" if self.window else "full"

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def group(self):
        """Query heads that share one key-value head."""
        return self.num_heads // self.kv_heads

    # reference semantics (attention.cc:86): kdim/vdim are PER-HEAD
    # projection sizes (qProjSize = kdim); 0 means embed_dim/num_heads.
    @property
    def qk_head_dim(self):
        return self.kdim or self.embed_dim // self.num_heads

    @property
    def v_head_dim(self):
        return self.vdim or self.embed_dim // self.num_heads

    @property
    def head_dim(self):
        return self.qk_head_dim


def _infer(params: MultiHeadAttentionParams, in_shapes, in_dtypes):
    q, k, v = in_shapes
    out = (q[0], q[1], params.embed_dim)
    return [out], [in_dtypes[0]]


def _weights(params: MultiHeadAttentionParams, in_shapes, in_dtypes):
    q, k, v = in_shapes
    h, hkv = params.num_heads, params.kv_heads
    dqk, dv = params.qk_head_dim, params.v_head_dim
    dt = in_dtypes[0]
    ws = [
        WeightSpec("wq", (q[-1], h, dqk), dt, "glorot_uniform", ("", "head", "")),
        WeightSpec("wk", (k[-1], hkv, dqk), dt, "glorot_uniform", ("", "head", "")),
        WeightSpec("wv", (v[-1], hkv, dv), dt, "glorot_uniform", ("", "head", "")),
        WeightSpec("wo", (h, dv, params.embed_dim), dt, "glorot_uniform", ("head", "", "")),
    ]
    if params.bias:
        ws.append(WeightSpec("bias_o", (params.embed_dim,), dt, "zero"))
    if params.qk_norm:
        ws.append(WeightSpec("q_norm", (h * dqk,), dt, "one"))
        ws.append(WeightSpec("k_norm", (hkv * dqk,), dt, "one"))
    if params.head_gate:
        ws.append(WeightSpec("wg", (q[-1], h), dt, "glorot_uniform",
                             ("", "head")))
    return ws


def _scope(params: MultiHeadAttentionParams, name: str):
    """`ff.attn.<name>` around a part of a marked op (params.marked)."""
    return jax.named_scope("ff.attn." + name) if params.marked \
        else contextlib.nullcontext()


def _rope(params, q, k, positions):
    """q and k rotated by the rows' true positions, or as they came."""
    if params.rope is None:
        return q, k
    with _scope(params, "rope"):
        return (apply_rotary(params.rope, q, positions),
                apply_rotary(params.rope, k, positions))


def _gate(params, weights, x_in, attn):
    """attn (b, s, h, dv) under the per-head gate sigmoid(x wg)."""
    if not params.head_gate:
        return attn
    with _scope(params, "gate"):
        g = jax.nn.sigmoid(jnp.einsum(
            "bse,eh->bsh", x_in, weights["wg"].astype(x_in.dtype),
            preferred_element_type=jnp.float32))
        return (attn.astype(jnp.float32) * g[..., None]).astype(attn.dtype)


def _band(q_pos, k_pos, window: int):
    """The causal mask, under a window a band: key at k_pos visible to the
    query at q_pos. Shapes broadcast; a key position below 0 is no key."""
    m = (k_pos <= q_pos) & (k_pos >= 0)
    return m & (k_pos > q_pos - window) if window else m


def _qk_norm(params: MultiHeadAttentionParams, weights, q, k, layout="bshd"):
    """The projected q and k through their RMS norms (float32 inside), or
    as they came where the op has none. `layout` says where the heads and
    the head size lie: "bshd" or "bhsd"."""
    if not params.qk_norm:
        return q, k
    h_ax = layout.index("h")

    def norm(x, scale):
        shape = [1, 1, 1, x.shape[3]]
        shape[h_ax] = x.shape[h_ax]
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=(h_ax, 3), keepdims=True)
        scale = scale.astype(jnp.float32).reshape(x.shape[h_ax], x.shape[3])
        return (xf * jax.lax.rsqrt(ms + params.qk_norm_eps)
                * scale.reshape(shape)).astype(x.dtype)

    return norm(q, weights["q_norm"]), norm(k, weights["k_norm"])


def _forward(params: MultiHeadAttentionParams, weights, inputs, ctx):
    q_in, k_in, v_in = inputs
    cdt = ctx.compute_dtype
    if cdt is not None:
        q_in, k_in, v_in = (t.astype(cdt) for t in (q_in, k_in, v_in))
    wq, wk, wv, wo = (
        weights["wq"], weights["wk"], weights["wv"], weights["wo"],
    )
    if cdt is not None:
        wq, wk, wv, wo = (w.astype(cdt) for w in (wq, wk, wv, wo))
    b, seq_len, _ = q_in.shape
    kv_len = k_in.shape[1]
    h = params.num_heads
    use_dropout = params.dropout > 0.0 and ctx.training and ctx.rng is not None
    seq_degree = data_degree = model_degree = expert_degree = 1
    if ctx.mesh is not None:
        seq_degree = ctx.mesh.shape.get("seq", 1)
        data_degree = ctx.mesh.shape.get("data", 1)
        model_degree = ctx.mesh.shape.get("model", 1)
        # under the expert merge (parallel/strategies.py assign_mesh_axes)
        # the batch rides the RENAMED data axis, so a nontrivial expert
        # axis must gate the device-local fast paths exactly like data
        expert_degree = ctx.mesh.shape.get("expert", 1)
    # Only the mesh axes that actually shard the score tensor's dims count
    # toward the per-chip footprint: data (batch), model (heads), seq
    # (query positions). The pipe axis doesn't divide this op's footprint.
    shard = ctx.n_devices
    if ctx.mesh is not None:
        shard = data_degree * model_degree * seq_degree
    score_bytes = 4 * b * h * seq_len * kv_len // max(1, shard)
    # FF_ATTENTION_IMPL ∈ {auto, dense, flash, chunked, ring, ulysses}
    # overrides the size-based dispatch (like picking a cuDNN MHA algo by
    # hand).
    impl = os.environ.get("FF_ATTENTION_IMPL", "auto")
    if impl not in ("auto", "dense", "flash", "chunked", "ring", "ulysses"):
        raise ValueError(
            f"FF_ATTENTION_IMPL={impl!r}: "
            "expected auto|dense|flash|chunked|ring|ulysses"
        )
    from ..kernels.attention import flash_supported, pallas_compiled

    on_tpu = pallas_compiled()

    # RNG-threaded flash dropout: the fused Pallas kernels regenerate a
    # counter-based keep-mask per VMEM tile (kernels/attention.py), so
    # dropout > 0 no longer forces the dense-materialized path wherever
    # the fused kernel is eligible. The other streaming kernels
    # (chunked/ring/ulysses) and sharded meshes still fall back to dense
    # — warn once per (impl, layer, reason), count every occurrence.
    flash_dropout_ok = (
        use_dropout
        and impl in ("auto", "flash")
        and on_tpu
        and flash_supported(seq_len, kv_len)
        and data_degree * model_degree * seq_degree * expert_degree == 1
    )
    if use_dropout and not flash_dropout_ok:
        if impl in ("chunked", "ring", "ulysses"):
            _dropout_fallback(impl, ctx.op_name, "kernel")
        elif impl == "flash" or (
                impl == "auto"
                and (on_tpu or score_bytes > _DENSE_SCORE_BYTES)):
            # without dropout this call would have streamed
            if not on_tpu:
                _dropout_fallback(impl, ctx.op_name, "backend")
            elif not flash_supported(seq_len, kv_len):
                _dropout_fallback(impl, ctx.op_name, "seq")
            else:
                _dropout_fallback(impl, ctx.op_name, "mesh")

    # Single-chip/unsharded fast path: project q/k/v straight into the
    # kernel's folded (b*h, s, d) layout — the head transpose rides the
    # projection einsum for free instead of costing a per-layer HBM
    # round-trip each way (fold + unfold, fwd and bwd).
    if (impl in ("auto", "flash")
            and on_tpu
            and params.group == 1
            and not params.marked
            and (not use_dropout or flash_dropout_ok)
            and flash_supported(seq_len, kv_len)
            and data_degree * model_degree * seq_degree * expert_degree
            == 1):
        from ..kernels.attention import dropout_seeds, flash_attention_folded

        dqk, dv = params.qk_head_dim, params.v_head_dim
        qf = jnp.einsum("bse,ehd->bhsd", q_in, wq,
                        preferred_element_type=jnp.float32)
        kf = jnp.einsum("bse,ehd->bhsd", k_in, wk,
                        preferred_element_type=jnp.float32)
        vf = jnp.einsum("bse,ehd->bhsd", v_in, wv,
                        preferred_element_type=jnp.float32)
        qf, kf = _qk_norm(params, weights, qf.astype(q_in.dtype),
                          kf.astype(q_in.dtype), "bhsd")
        qf = qf.reshape(b * h, seq_len, dqk)
        kf = kf.reshape(b * h, kv_len, dqk)
        vf = vf.astype(q_in.dtype).reshape(b * h, kv_len, dv)
        attn = flash_attention_folded(
            qf, kf, vf, params.causal,
            dropout=params.dropout if use_dropout else 0.0,
            seeds=dropout_seeds(ctx.rng) if use_dropout else None,
        )
        out = jnp.einsum(
            "bhsd,hde->bse", attn.reshape(b, h, seq_len, dv), wo,
            preferred_element_type=jnp.float32,
        ).astype(q_in.dtype)
        if params.bias:
            out = out + weights["bias_o"].astype(out.dtype)
        return [out]

    # (b, s, e) @ (e, h, d) -> (b, s, h, d). Three separate gemms: packing
    # q/k/v into one gemm against a concatenated weight (cuDNN-MHA style)
    # was tried and wins ~4.5% in isolation but loses ~6% inside the full
    # jitted train step (the per-step concat + slices cost XLA more in
    # layout/fusion than the bigger gemm saves).
    q = jnp.einsum("bse,ehd->bshd", q_in, wq, preferred_element_type=jnp.float32)
    k = jnp.einsum("bse,ehd->bshd", k_in, wk, preferred_element_type=jnp.float32)
    v = jnp.einsum("bse,ehd->bshd", v_in, wv, preferred_element_type=jnp.float32)
    q, k = _qk_norm(params, weights, q.astype(q_in.dtype),
                    k.astype(q_in.dtype))
    if params.rope is not None:
        if kv_len != seq_len:
            raise AttentionConfigError(
                "a rotary embedding needs self-attention: "
                f"{seq_len} queries, {kv_len} keys")
        q, k = _rope(params, q, k, jnp.arange(seq_len))
    window = params.window
    v = v.astype(q_in.dtype)
    if params.group > 1:
        # every kernel below indexes K and V by the query's head: a group's
        # queries get their shared head repeated
        k = jnp.repeat(k, params.group, axis=2)
        v = jnp.repeat(v, params.group, axis=2)

    # Dispatch: on TPU the fused Pallas kernel (fwd + bwd in VMEM,
    # kernels/attention.py) wins whenever its score tile fits — measured
    # 416 vs 313 samples/s against the XLA dense path on the bench config
    # (seq 512, hidden 1024 — the dense path moves 134 MB of f32 scores
    # per layer through HBM). The dense path remains for dropout (rng
    # threading), non-TPU backends, and as the general fallback; past a
    # per-chip score-byte budget the O(seq)-memory chunked/ring kernels
    # take over regardless. Shapes here are global; batch/head axes shard
    # over the mesh, so the per-chip footprint divides by n_devices.

    # pallas_call has no GSPMD partitioning rule: on a non-trivial mesh the
    # fused kernel must run under shard_map over the batch/head axes (each
    # program is independent per (batch, head)); when the seq axis shards
    # the queries, the ring/ulysses paths own the problem instead.
    mesh_nontrivial = (
        data_degree * model_degree * seq_degree * expert_degree > 1
    )
    flash_shardable = (
        seq_degree == 1
        and expert_degree == 1  # batch rides the expert axis when merged
        and b % data_degree == 0
        and h % model_degree == 0
    )
    # A seq-sharded mesh still wants streaming: the ring path intercepts
    # below (keeping K/V sharded), and its indivisible fallback lands on
    # chunked — never on a GSPMD-sharded pallas_call.
    prefer_flash = (
        impl == "auto"
        and on_tpu
        and flash_supported(seq_len, kv_len)
        and (not mesh_nontrivial or flash_shardable or seq_degree > 1)
    )
    use_streaming = (
        impl in ("flash", "chunked", "ring", "ulysses")
        or (impl == "auto"
            and (prefer_flash or score_bytes > _DENSE_SCORE_BYTES))
    ) and not use_dropout
    # Sequence/context parallelism: with the seq axis sharded, the dense
    # and flash paths would make XLA all-gather the full K/V on every chip;
    # ring attention keeps K/V resident and rotates shards over ICI
    # (kernels/attention.py). Chosen whenever streaming kicks in on a
    # seq-sharded mesh, or forced via FF_ATTENTION_IMPL=ring. shard_map
    # needs every sharded dim divisible (GSPMD tolerates uneven shards,
    # the explicit specs here don't) — otherwise fall back to streaming.
    sp_shardable = (
        seq_degree > 1
        and use_streaming
        and kv_len == seq_len
        and seq_len % seq_degree == 0
        and b % data_degree == 0
        and h % model_degree == 0
    )
    # Ulysses (all_to_all head scatter) additionally needs the local head
    # count to divide the seq axis; ring has no such constraint, so auto
    # keeps ring as the SP default and ulysses is opt-in.
    use_ulysses = (
        sp_shardable
        and impl == "ulysses"
        and (h // max(1, model_degree)) % seq_degree == 0
    )
    use_ring = sp_shardable and impl in ("auto", "ring")
    if impl in ("ring", "ulysses") and not (use_ring or use_ulysses) \
            and not use_dropout:
        # same dedup + ff_attention_fallback_total{reason} accounting as
        # the dropout fallbacks: warn once per (impl, layer, reason),
        # count every traced occurrence
        if seq_degree <= 1:
            reason = "sp_mesh"
        elif impl == "ulysses" and sp_shardable:
            reason = "sp_heads"
        else:
            reason = "sp_shape"
        _dropout_fallback(impl, ctx.op_name, reason)
    if window and (use_ring or use_ulysses or impl in ("ring", "ulysses")):
        raise AttentionConfigError(
            f"{ctx.op_name or 'attention'}: a window of {window} under "
            f"{'ulysses' if use_ulysses or impl == 'ulysses' else 'ring'} "
            "sequence parallelism is not computed (ROADMAP B-M4)")
    with _scope(params, params.kind):
        if use_ring or use_ulysses:
            import functools

            from jax.sharding import PartitionSpec as P

            from ..kernels.attention import ring_attention, ulysses_attention

            if use_ulysses:
                fn = functools.partial(
                    ulysses_attention, axis_name="seq", causal=params.causal
                )
            else:
                fn = functools.partial(
                    ring_attention, axis_name="seq", causal=params.causal
                )
            spec = P("data", "seq", "model", None)
            attn = jax.shard_map(
                fn,
                mesh=ctx.mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )(q, k, v)
        elif use_streaming:
            # Long sequences: O(seq) memory kernels instead of the s×s score
            # tensor — Pallas flash attention on TPU, chunked scan elsewhere
            # (kernels/attention.py; replaces cuDNN MHA's internal algorithm).
            import functools

            from ..kernels.attention import chunked_attention, local_attention

            if impl == "flash" and not flash_supported(seq_len, kv_len):
                warnings.warn(
                    "FF_ATTENTION_IMPL=flash ignored: "
                    f"{seq_len}x{kv_len} scores exceed the fused kernel's "
                    "VMEM tile — using chunked attention"
                )
            if impl == "chunked":
                attn = chunked_attention(q, k, v, causal=params.causal,
                                         window=window)
            elif mesh_nontrivial:
                # On a sharded mesh the Pallas kernel can only run on per-chip
                # shards: shard_map over batch (data) and heads (model) — each
                # (batch, head) program is independent, so no collectives. When
                # those dims don't divide the mesh, chunked attention (plain
                # jnp, GSPMD-partitionable) is the safe path.
                if flash_shardable:
                    from jax.sharding import PartitionSpec as P

                    spec = P("data", None, "model", None)
                    attn = jax.shard_map(
                        functools.partial(local_attention, causal=params.causal,
                                          window=window),
                        mesh=ctx.mesh,
                        in_specs=(spec, spec, spec),
                        out_specs=spec,
                    )(q, k, v)
                else:
                    if impl == "flash":
                        warnings.warn(
                            "FF_ATTENTION_IMPL=flash ignored: batch/heads don't "
                            "divide the data/model mesh axes (or the seq axis is "
                            "sharded) — using chunked attention"
                        )
                    attn = chunked_attention(q, k, v, causal=params.causal,
                                             window=window)
            else:
                attn = local_attention(q, k, v, causal=params.causal,
                                       window=window)
        else:
            scale = 1.0 / jnp.sqrt(jnp.asarray(params.head_dim, jnp.float32))
            scores = jnp.einsum(
                "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
            )
            scores = scores * scale
            if params.causal:
                s_len, t_len = scores.shape[-2], scores.shape[-1]
                mask = _band(jnp.arange(s_len)[:, None],
                             jnp.arange(t_len)[None, :], window) if window \
                    else jnp.tril(jnp.ones((s_len, t_len), bool))
                scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            if use_dropout:
                # same counter-based mask the flash kernels regenerate
                # blockwise in VMEM — the two paths draw IDENTICAL masks from
                # the same rng, so flash-with-dropout is testable against
                # dense-with-dropout (and switching paths between compiles
                # doesn't change the dropout stream)
                from ..kernels.attention import (
                    attention_dropout_mask,
                    dropout_seeds,
                )

                keep = attention_dropout_mask(
                    dropout_seeds(ctx.rng), params.dropout,
                    probs.shape[0] * probs.shape[1],
                    probs.shape[2], probs.shape[3],
                ).reshape(probs.shape)
                probs = jnp.where(
                    keep, probs * (1.0 / (1.0 - params.dropout)), 0
                ).astype(probs.dtype)
            attn = jnp.einsum(
                "bhst,bthd->bshd", probs, v, preferred_element_type=jnp.float32
            )
            attn = attn.astype(q.dtype)
    attn = _gate(params, weights, q_in, attn)
    out = jnp.einsum("bshd,hde->bse", attn, wo, preferred_element_type=jnp.float32)
    out = out.astype(q_in.dtype)
    if params.bias:
        out = out + weights["bias_o"].astype(out.dtype)
    return [out]


def _ring_positions(last, ring: int):
    """The position each slot of a ring holds once position `last` is
    written (scalar -> (ring,), per row (b,) -> (b, ring)): the largest
    p <= last with p % ring == slot; below 0, nothing yet."""
    last = jnp.asarray(last, jnp.int32)[..., None]
    return last - jnp.mod(last - jnp.arange(ring), ring)


def _ring_then_block(caches, news, t):
    """What a block at positions t.. of a window layer attends: each ring
    of `caches` rolled into the order of its positions, t - ring .. t - 1,
    then the block's own rows of `news`; and those keys' positions
    ((keys,), or (b, keys) under per-row t). A position below 0 is no key
    (_band)."""
    ring, s0 = caches[0].shape[1], news[0].shape[1]
    shift = -jnp.mod(t, ring)
    if getattr(t, "ndim", 0) == 1:
        roll = jax.vmap(lambda c, n: jnp.roll(c, n, axis=0))
        first = t[:, None] - ring
    else:
        roll = lambda c, n: jnp.roll(c, n, axis=1)  # noqa: E731
        first = t - ring
    k_att, v_att = (jnp.concatenate([roll(c, shift), n.astype(c.dtype)], 1)
                    for c, n in zip(caches, news))
    return k_att, v_att, first + jnp.arange(ring + s0)


def _ring_append(caches, news, t, valid):
    """The rings of a window layer after a block at positions t..: one
    token goes to slot t % ring; of a longer block the last min(valid,
    ring) REAL rows go each to its position's slot (`valid`: a padded
    block's count of real tokens, None = all of them), and the padded tail
    goes nowhere: in a ring it would lie over real keys."""
    ring, s0 = caches[0].shape[1], news[0].shape[1]
    per_row = getattr(t, "ndim", 0) == 1
    if s0 == 1:
        slot = jnp.mod(t, ring)
        if per_row:
            put = jax.vmap(
                lambda c, n, at: jax.lax.dynamic_update_slice(c, n, (at, 0)))
            return tuple(put(c, n, slot) for c, n in zip(caches, news))
        return tuple(jax.lax.dynamic_update_slice(c, n, (0, slot, 0))
                     for c, n in zip(caches, news))
    # row by row: each has its own first position and count of real rows
    rows = (caches[0].shape[0],)
    first = jnp.broadcast_to(jnp.asarray(t, jnp.int32), rows)[:, None]
    real = jnp.broadcast_to(
        jnp.asarray(s0 if valid is None else valid, jnp.int32), rows)
    holds = _ring_positions(first[:, 0] + real - 1, ring)  # after the block
    at = jnp.clip(holds - first, 0, s0 - 1)
    take = jax.vmap(lambda n, i: jnp.take(n, i, axis=0))
    return tuple(jnp.where((holds >= first)[..., None], take(n, at), c)
                 for c, n in zip(caches, news))


def _forward_decode(params, weights, inputs, ctx, cache, t, valid=None):
    """Incremental decode step with a KV cache (serving path,
    parallel/decode.py). Inputs are the NEW positions' slices
    (b, s0, e) starting at position t (s0 = 1 for token-by-token decode,
    s0 = prompt_len for one-shot prefill); cache holds (k, v) of shape
    (b, max_len, kv_heads*d) with positions < t valid (init_decode_cache
    says why the heads are folded; with grouped-query heads a row holds the
    key-value heads alone, and a query head reads its group's lanes).
    Appends the block's K/V and attends its
    queries against the prefix with intra-block causal masking —
    cache-width attention rows per token instead of the full O(L²)
    forward the reference's serving prototype would re-run (it has no KV
    cache; triton/README.md calls it an incomplete prototype).

    Requires self-attention (q_in is k_in is v_in upstream) — the decode
    builder rejects cross-attention graphs.

    `t` may be a scalar (every row at the same position — the generate
    APIs) or a (b,) vector of per-row positions (continuous batching,
    runtime/serving.py: each slot of a running decode batch is mid-way
    through its own sequence). The vector path appends each row's K/V at
    its own offset (a vmapped per-row update) and masks each row's
    attention against its own position. `valid` (a padded block's count
    of real tokens) lets a long block's causal walk leave the padded tail
    uncomputed (its rows come out as zeros, and nothing reads them); what
    a block writes beyond it lies behind the causal mask until a later
    token overwrites it, but a window layer's cache is a ring of
    window_ring(window, max_len) positions (init_decode_cache), in which
    the tail would lie over real keys, so its block writes real rows only
    (_ring_append). A decode step of such a layer appends at t % ring and
    reads min(t + 1, ring) positions; keys are rotated before they are
    stored, so the order they lie in does not matter. Where the ring is
    longer than the window (a window that is no whole number of pages, or
    a max_len that never wraps), entries the window has left are masked
    by the position each slot holds (_ring_positions), on the dense
    branch."""
    q_in, k_in, v_in = inputs
    cdt = ctx.compute_dtype
    if cdt is not None:
        q_in, k_in, v_in = (x.astype(cdt) for x in (q_in, k_in, v_in))
    wq, wk, wv, wo = (
        weights["wq"], weights["wk"], weights["wv"], weights["wo"],
    )
    if cdt is not None:
        wq, wk, wv, wo = (w.astype(cdt) for w in (wq, wk, wv, wo))
    q = jnp.einsum("bse,ehd->bshd", q_in, wq,
                   preferred_element_type=jnp.float32).astype(q_in.dtype)
    k_new = jnp.einsum("bse,ehd->bshd", k_in, wk,
                       preferred_element_type=jnp.float32).astype(q_in.dtype)
    v_new = jnp.einsum("bse,ehd->bshd", v_in, wv,
                       preferred_element_type=jnp.float32).astype(q_in.dtype)
    q, k_new = _qk_norm(params, weights, q, k_new)
    k_cache, v_cache = cache
    b, s0, h = q.shape[:3]
    group = params.group
    # inside a loop region the cache holds every step's keys and values,
    # (b, steps, max_len, kv_heads*d), and this call is step `u`'s: it
    # writes its slice in place and reads that slice alone
    u = ctx.loop_step
    max_len = k_cache.shape[-2]
    window = params.window
    if u is not None and window:
        raise NotImplementedError(
            f"{ctx.op_name}: a window layer's ring has no copy a step, so "
            "it cannot decode inside a loop region")
    per_row_t = getattr(t, "ndim", 0) == 1
    if params.marked:
        # the rows' true positions, (s0,) or per row (b, s0)
        q_pos = (t[:, None] if per_row_t else t) + jnp.arange(s0)
        # rotated before they are stored: the order keys lie in does not
        # matter
        q, k_new = _rope(params, q, k_new, q_pos)

    def by_query_head(cache):
        """The folded cache as (b, positions, h, d): each key-value head
        repeated for the query heads of its group."""
        c = cache.astype(q.dtype).reshape(b, cache.shape[1],
                                          params.kv_heads, -1)
        return c if group == 1 else jnp.repeat(c, group, axis=2)

    # the cache keeps a position's heads folded into one row (b, max_len,
    # kv_heads*d): the new rows fold the same way
    k_new = k_new.reshape(b, s0, -1).astype(k_cache.dtype)
    v_new = v_new.reshape(b, s0, -1).astype(v_cache.dtype)
    # what the queries attend, where that is not the cache after the
    # append: a window layer's block reads the ring as it was, in the order
    # of its positions, and then itself (the ring cannot hold a block
    # longer than itself, and need not)
    k_att = v_att = key_pos = None
    if window and s0 > 1:
        k_att, v_att, key_pos = _ring_then_block(
            (k_cache, v_cache), (k_new, v_new), t)
    if window:
        k_cache, v_cache = _ring_append(
            (k_cache, v_cache), (k_new, v_new), t, valid)
        if s0 == 1:
            key_pos = _ring_positions(t, max_len)
    elif per_row_t:
        row_update = jax.vmap(
            lambda c, n, tt: jax.lax.dynamic_update_slice(c, n, (tt, 0))
            if u is None else
            jax.lax.dynamic_update_slice(c, n[None], (u, tt, 0))
        )
        k_cache = row_update(k_cache, k_new, t)
        v_cache = row_update(v_cache, v_new, t)
    elif u is None:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k_new, (0, t, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v_new, (0, t, 0))
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new[:, None], (0, u, t, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new[:, None], (0, u, t, 0))
    if k_att is None:
        k_att, v_att = (c if u is None else jax.lax.dynamic_index_in_dim(
            c, u, axis=1, keepdims=False) for c in (k_cache, v_cache))
    if params.marked and s0 == 1:
        # positions whose keys this step reads, over the rows
        seen = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,)) + 1
        ctx.count(f"attn_{params.kind}_positions_read", jnp.sum(
            jnp.minimum(seen, window) if window else seen, dtype=jnp.int32))
    # FF_DECODE_IMPL ∈ {auto, dense, paged}: "paged" routes single-token
    # steps through the Pallas paged flash-decode kernel
    # (kernels/decode.py): the cache strips, as they lie, are the paged
    # pool (a reshape, no copy), one grid step is one slot with all its
    # heads, and only a slot's live pages are read. "auto" engages it
    # only where the compiled kernel runs (TPU backend); "dense" pins the
    # per-row masked reference path. A step the kernel cannot take (a
    # multi-token block under "paged"; a shape Mosaic cannot tile under
    # either) falls back dense with the shared
    # ff_attention_fallback_total{reason} counter + one warning.
    impl = os.environ.get("FF_DECODE_IMPL", "auto")
    if impl not in ("auto", "dense", "paged"):
        raise ValueError(
            f"FF_DECODE_IMPL={impl!r}: expected one of auto|dense|paged")
    from ..kernels.attention import pallas_compiled

    use_paged = interpret = False
    if impl == "paged":
        if s0 != 1:
            _dropout_fallback(impl, ctx.op_name, "paged_block")
        else:
            # asked for by hand: off the TPU that means the interpreter
            use_paged, interpret = True, not pallas_compiled()
    elif impl == "auto":  # interpret mode on CPU would lose to XLA dense
        use_paged = s0 == 1 and pallas_compiled()
    if use_paged and window and max_len > window:
        # a ring longer than its window (a window that is no whole number
        # of pages) holds entries the window has left: the kernel masks by
        # length alone, the dense branch below by position
        use_paged = False
    if use_paged:
        from ..kernels.decode import (
            decode_block_pages,
            decode_page_size,
            paged_flash_decode,
            paged_view_of_cache,
        )
        page_size = decode_page_size(max_len)
        # the interpreter takes any shape; Mosaic only what it can tile
        if not interpret and decode_block_pages(
                k_cache.shape[-1], v_cache.shape[-1], page_size,
                q.dtype) is None:
            _dropout_fallback(impl, ctx.op_name, "paged_untileable")
            use_paged = False
    with _scope(params, params.kind):
        if use_paged:
            # the whole cache as the pool, step u's pages by the table
            kp, vp, table = paged_view_of_cache(
                k_cache.astype(q.dtype), v_cache.astype(q.dtype), page_size,
                step=u)
            lengths = (t.astype(jnp.int32) if per_row_t
                       else jnp.full((b,), t, jnp.int32)) + 1
            if window:  # a full ring is read whole, in whatever order it lies
                lengths = jnp.minimum(lengths, max_len)
            attn = paged_flash_decode(
                q[:, 0], kp, vp, table, lengths, interpret=interpret,
            )[:, None]                     # (b, 1, h, dv)
        elif (s0 > 1 and not per_row_t
              and 4 * b * h * s0 * k_att.shape[1] > _DENSE_SCORE_BYTES):
            # a long prompt's block (serving's prefill from position t): the
            # dense branch below would hold (b, h, s0, max_len) float32 scores,
            # 2 GB at 30 heads and 4,096 x 4,096; kernels/attention.py walks
            # the cache 256 positions at a time under the same mask (cache
            # position <= t + row): a window layer's band, else blocks of
            # queries that meet only the chunks a real query of theirs sees
            from ..kernels.attention import _causal_scan, _chunk_scan

            if window:
                attn, _, _ = _chunk_scan(
                    q, by_query_head(k_att), by_query_head(v_att),
                    causal=True, chunk_size=min(_KEY_CHUNK, max_len),
                    q_offset=t, kv_offset=t - max_len, window=window)
            else:
                attn, computed, skipped = _causal_scan(
                    q, k_att, v_att, block=min(_PREFILL_BLOCK, s0),
                    chunk=min(_KEY_CHUNK, max_len), q_offset=t,
                    valid=None if valid is None else jnp.max(valid))
                ctx.count("attn_prefill_tiles_computed", computed)
                ctx.count("attn_prefill_tiles_skipped", skipped)
        else:
            k_all, v_all = k_att.astype(q.dtype), v_att.astype(q.dtype)
            scale = 1.0 / jnp.sqrt(jnp.asarray(params.qk_head_dim, jnp.float32))
            if s0 == 1:
                # one token a slot: every head's scores in one product over
                # the folded rows, as the kernel does it. Row h of `q_bd`
                # holds head h's values on head h's lanes, so no 4-D view of
                # the cache is made: on the TPU that view is a whole-cache
                # relayout (init_decode_cache), while heads times the MXU work
                # is nothing beside the cache's bytes.
                # (Grouped-query heads: row h holds head h's values on the
                # lanes of ITS GROUP's key-value head, so the folded row is
                # kv_heads*d wide and a group's rows share their lanes.)
                def own_lanes(per_head):
                    lanes = jnp.arange(params.kv_heads * per_head)[None, :] \
                        // per_head
                    rows = jnp.arange(h)[:, None]
                    return lanes == (rows if group == 1 else rows // group)
                q_bd = jnp.where(
                    own_lanes(params.qk_head_dim),
                    q.reshape(b, 1, -1) if group == 1
                    else jnp.tile(q[:, 0], (1, 1, params.kv_heads)),
                    0)                                         # (b, h, kv*d)
                scores = jnp.einsum(
                    "bhk,btk->bht", q_bd, k_all,
                    preferred_element_type=jnp.float32,
                )[:, :, None] * scale
            else:
                scores = jnp.einsum(
                    "bshd,bthd->bhst", q, by_query_head(k_all),
                    preferred_element_type=jnp.float32,
                ) * scale                  # (b, h, s0, max_len)
            pos = jnp.arange(max_len)               # cache positions
            if window:
                # by position: key_pos (keys,) or per row (b, keys)
                qp = q_pos[:, None, :, None] if per_row_t \
                    else q_pos[None, None, :, None]
                kp = key_pos[:, None, None, :] if key_pos.ndim == 2 \
                    else key_pos[None, None, None, :]
                scores = jnp.where(_band(qp, kp, window), scores,
                                   jnp.finfo(jnp.float32).min)
            elif per_row_t:
                q_pos = t[:, None] + jnp.arange(s0)[None, :]          # (b, s0)
                scores = jnp.where(
                    pos[None, None, None, :] <= q_pos[:, None, :, None],
                    scores, jnp.finfo(jnp.float32).min,
                )
            else:
                q_pos = t + jnp.arange(s0)          # this block's positions
                scores = jnp.where(
                    pos[None, None, None, :] <= q_pos[None, None, :, None],
                    scores, jnp.finfo(jnp.float32).min,
                )
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            if s0 == 1:
                attn = jnp.einsum(
                    "bht,btk->bhk", probs[:, :, 0], v_all,
                    preferred_element_type=jnp.float32,
                )                          # (b, h, kv*dv): row h's own lanes
                attn = jnp.where(own_lanes(params.v_head_dim), attn, 0)
                if group == 1:
                    attn = attn.sum(1)
                else:  # a group's rows share lanes: fold the lanes, not the rows
                    attn = attn.reshape(b, h, params.kv_heads, -1).sum(2)
                attn = attn.reshape(b, 1, h, -1).astype(q.dtype)
            else:
                attn = jnp.einsum(
                    "bhst,bthd->bshd", probs, by_query_head(v_all),
                    preferred_element_type=jnp.float32,
                ).astype(q.dtype)
    attn = _gate(params, weights, q_in, attn)
    out = jnp.einsum("bshd,hde->bse", attn, wo,
                     preferred_element_type=jnp.float32)
    out = out.astype(q_in.dtype)  # post-cast dtype, same as _forward
    if params.bias:
        out = out + weights["bias_o"].astype(out.dtype)
    return [out], (k_cache, v_cache)


def cross_decode_kv(params: MultiHeadAttentionParams, weights,
                    static_inputs, ctx):
    """Precompute the FULL encoder-side K/V for cross-attention decode
    (the op's init_decode_static): static_inputs = (k_in, v_in), the
    static encoder outputs (b, s_enc, e). Computed once per sequence —
    each decode step then attends its query slice against these without
    re-projecting (the O(1)/token contract for enc-dec serving)."""
    k_in, v_in = static_inputs
    cdt = ctx.compute_dtype
    if cdt is not None:
        k_in, v_in = k_in.astype(cdt), v_in.astype(cdt)
    wk, wv = weights["wk"], weights["wv"]
    if cdt is not None:
        wk, wv = wk.astype(cdt), wv.astype(cdt)
    k = jnp.einsum("bse,ehd->bshd", k_in, wk,
                   preferred_element_type=jnp.float32).astype(k_in.dtype)
    v = jnp.einsum("bse,ehd->bshd", v_in, wv,
                   preferred_element_type=jnp.float32).astype(k_in.dtype)
    return (k, v)


def _forward_decode_cross(params, weights, inputs, ctx, kv):
    """Cross-attention decode step (the op's forward_decode_static):
    project this block's queries, the one live input, and attend over the
    precomputed full encoder K/V (cross_decode_kv). No causal mask —
    every decoder position sees the whole encoder sequence, exactly like
    the training forward."""
    (q_in,) = inputs
    cdt = ctx.compute_dtype
    if cdt is not None:
        q_in = q_in.astype(cdt)
    wq, wo = weights["wq"], weights["wo"]
    if cdt is not None:
        wq, wo = wq.astype(cdt), wo.astype(cdt)
    q = jnp.einsum("bse,ehd->bshd", q_in, wq,
                   preferred_element_type=jnp.float32).astype(q_in.dtype)
    k, v = kv
    if params.group > 1:
        k = jnp.repeat(k, params.group, axis=2)
        v = jnp.repeat(v, params.group, axis=2)
    scale = 1.0 / jnp.sqrt(jnp.asarray(params.qk_head_dim, jnp.float32))
    scores = jnp.einsum(
        "bshd,bthd->bhst", q, k.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    attn = jnp.einsum(
        "bhst,bthd->bshd", probs, v.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    out = jnp.einsum("bshd,hde->bse", attn, wo,
                     preferred_element_type=jnp.float32)
    out = out.astype(q_in.dtype)
    if params.bias:
        out = out + weights["bias_o"].astype(out.dtype)
    return [out]


def init_decode_cache(params: MultiHeadAttentionParams, batch: int,
                      max_len: int, dtype):
    """Fresh (k, v) cache for one MHA op: (batch, max_len, kv_heads*d), one
    position's key-value heads folded into one row (kv_heads = heads but
    for grouped-query attention, where the cache is narrower by the group).
    The fold decides how the cache
    lies in the TPU's memory: XLA lays a 4-D (batch, max_len, heads, 64)
    array out with max_len innermost (a 64-wide minor axis would be padded
    to the 128 lanes), so every view of it by position, the paged kernel's
    pages and the per-token append alike, was a whole-cache transpose;
    with heads*d innermost a position is one contiguous row and a page a
    contiguous run of them."""
    h, dqk, dv = params.kv_heads, params.qk_head_dim, params.v_head_dim
    if params.window:
        # a window layer keeps a ring of its last positions, not max_len
        max_len = window_ring(params.window, max_len)
    return (
        jnp.zeros((batch, max_len, h * dqk), dtype),
        jnp.zeros((batch, max_len, h * dv), dtype),
    )


register_op(
    OperatorType.OP_MULTIHEAD_ATTENTION,
    "MultiHeadAttention",
    infer=_infer,
    weights=_weights,
    forward=_forward,
    num_inputs=3,
    forward_decode=_forward_decode,
    init_decode_state=init_decode_cache,
    decode_section="mha",
    # in a loop region the cache holds every step's keys and values
    loop_state=True,
    # a marked op counts the positions its decode steps read, by its kind
    decode_counters=lambda p: (
        (f"attn_{p.kind}_positions_read",) if p.marked else ()),
    # and a marked full one the tiles its long prefill blocks' causal walk
    # computes and skips (kernels/attention.py _causal_scan)
    prefill_counters=lambda p: (
        PREFILL_TILE_COUNTERS if p.marked and not p.window else ()),
    init_decode_static=cross_decode_kv,
    forward_decode_static=_forward_decode_cross,
)
