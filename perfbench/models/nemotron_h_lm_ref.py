"""Plain reference for `nemotron_h_lm.py`: jax.numpy, float32, matrix products
at `highest` precision, no kernel, no cache, no chunks, no sort, no batching.
Imports nothing of the program and takes nothing the program made: the
weights come from `init`, from the seed, and where the configuration stores
them in bfloat16 the stored values are cast up (an expert at a time), never
drawn again.

The model (`model_type` `nemotron_h`: every layer is ONE mixer behind its own
pre-norm; `hybrid_override_pattern` says which, `M` Mamba-2, `E` experts, `*`
attention), for hidden h and RMSNorm(a) = a / sqrt(mean(a^2) + eps) * scale:

  Model:  x0 = wte[ids];  x = x + mixer_i(RMSNorm_i(x));
          logits = RMSNorm_f(x_L) . head                       (head untied)

  M, Mamba-2 (arXiv:2405.21060; H heads of P, d_in = H P, G groups, state N,
  K taps), per token t and head, state S in R^(P x N):
    [z; xBC; dt] = W_in u                       (d_in + (d_in + 2 G N) + H)
    xBC = SiLU(causal depthwise conv_K(xBC) + b_conv) -> x (H x P), B, C (G x N)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T      (head h: group h // (H/G))
    y_t = S_t C_t + D x_t
    out = W_out RMSNorm_g(y * SiLU(z))    (the norm over each group's d_in / G)
  computed here TOKEN BY TOKEN (`lax.scan` over t), S_0 = 0.

  E, experts (DeepSeek-V3-style routing as `nemotron_h` runs it):
    s = sigmoid(W_r u) over ALL the published experts; the top k of s + b_corr
    chosen; w = s[chosen] / (sum + 1e-20) x routed_scaling_factor;
    e(u) = W_down_e relu(W_up_e u)^2;  shared(u) the same form, wider;
    out = sum over the chosen e THAT THIS CHIP HOLDS of w_e e(u) + shared(u)
  computed here with EVERY held expert applied to every token under a 0/w
  mask. What the experts held elsewhere would add is left out, as in the
  program: the configuration's file says which experts are here.

  *, attention (head size d, Hq query heads, Hkv key-value heads, no bias,
  no positional embedding: `assumed`): query head i reads key-value head
  i // (Hq / Hkv);  o = softmax(q k^T / sqrt(d) + causal) v;  y = Wo o

What the source's config.json does not state is listed under `assumed` in the
configuration's file, with the departures.

Everything is computed layer by layer through one small jitted function per
kind of layer. A `precision` other than "f32" is a control, not a reference:
every weight-matrix product and the attention products take their operands
rounded to float8_e4m3 ("fp8", the step below bfloat16) or to bfloat16
("bf16", the step below the float32 the rehearsal sizes state); the router,
the recurrence and the decay stay float32, as the configuration states them.
"""
import functools
import math

import jax
import jax.numpy as jnp

from .decoder_lm_ref import _mm
from .hybrid_lm_ref import Reference as _LayerwiseReference
from .hybrid_lm_ref import _embed, _head, _rms

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def sizes(cfg):
    g = cfg.get
    held = g("n_routed_experts")
    return {
        "layers": g("num_hidden_layers"),
        # one letter a layer: M Mamba-2, E experts, * attention
        "layer_types": tuple(g("hybrid_override_pattern")),
        "hidden": g("hidden_size"),
        "heads": g("num_attention_heads"),
        "kv_heads": g("num_key_value_heads"),
        "head_dim": g("head_dim"),
        "vocab": g("vocab_size"),
        "positions": g("max_position_embeddings"),
        "eps": g("layer_norm_epsilon"),
        "ssm_heads": g("mamba_num_heads"),
        "ssm_head_dim": g("mamba_head_dim"),
        "ssm_state": g("ssm_state_size"),
        "ssm_groups": g("n_groups"),
        "conv": g("conv_kernel"),
        "chunk": g("chunk_size"),
        # the router is as wide as the published layer; this chip holds
        # `held` of its experts, from `held_from` on
        "experts": g("published", {}).get("n_routed_experts", held),
        "held": held,
        "held_from": g("experts_held_from", 0),
        "top_k": g("num_experts_per_tok"),
        "expert_width": g("moe_intermediate_size"),
        "shared_width": g("moe_shared_expert_intermediate_size")
        * g("n_shared_experts"),
        "route_scale": g("routed_scaling_factor"),
        "norm_topk": g("norm_topk_prob"),
        "weights": jnp.dtype(cfg["dtype_policy"].get("weights", "float32")),
    }


def ssm_inner(z):
    return z["ssm_heads"] * z["ssm_head_dim"]


def ssm_conv_channels(z):
    return ssm_inner(z) + 2 * z["ssm_groups"] * z["ssm_state"]


def layer_shapes(z, kind):
    """short leaf name -> (shape, kind of initial values) of one layer."""
    h = z["hidden"]
    out = {"norm.scale": ((h,), "scale")}
    if kind == ATTENTION:
        nh, kv, d = z["heads"], z["kv_heads"], z["head_dim"]
        out.update({"mixer.wq": ((h, nh, d), "matrix"),
                    "mixer.wk": ((h, kv, d), "matrix"),
                    "mixer.wv": ((h, kv, d), "matrix"),
                    "mixer.wo": ((nh, d, h), "matrix")})
    elif kind == MAMBA:
        d_in, c, nh = ssm_inner(z), ssm_conv_channels(z), z["ssm_heads"]
        out.update({"mixer.w_in": ((h, d_in + c + nh), "matrix"),
                    "mixer.w_out": ((d_in, h), "matrix"),
                    "mixer.conv": ((z["conv"], c), "matrix"),
                    "mixer.conv_bias": ((c,), "vector"),
                    "mixer.A_log": ((nh,), "zero"),
                    "mixer.dt_bias": ((nh,), "dt_bias"),
                    "mixer.D": ((nh,), "one"),
                    "mixer.norm": ((d_in,), "scale")})
    else:
        n, f, fs = z["held"], z["expert_width"], z["shared_width"]
        out.update({"mixer.router": ((h, z["experts"]), "matrix"),
                    "mixer.b_corr": ((z["experts"],), "zero"),
                    "mixer.w_up": ((n, h, f), "matrix"),
                    "mixer.w_down": ((n, f, h), "matrix"),
                    "mixer.shared_up": ((h, fs), "matrix"),
                    "mixer.shared_down": ((fs, h), "matrix")})
    return out


def shapes(cfg):
    """canonical leaf name -> (shape, kind); kind picks the initial values."""
    z = sizes(cfg)
    out = {"wte": ((z["vocab"], z["hidden"]), "matrix"),
           "norm_f.scale": ((z["hidden"],), "scale"),
           "head": ((z["hidden"], z["vocab"]), "matrix")}
    for i, kind in enumerate(z["layer_types"]):
        for k, v in layer_shapes(z, kind).items():
            out[f"h{i}.{k}"] = v
    return out


DECAY_SPAN = (0.9, 0.999)  # the decay a head has where its dt input is 0


def init(cfg, seed):
    """All weights from the seed in ONE jitted call, on the device, in the
    type the configuration stores them in (`dtype_policy.weights`): matrices,
    the convolution's taps and its bias N(0, r), norm scales 1 + N(0, r),
    with r the file's `assumed.initializer_range` (0.02; the rehearsal sizes
    take 0.2, which gives a 32-wide model the gain 0.02 gives a 2,688-wide
    one). `b_corr` = 0, `D` = 1, `A_log` = 0 (A = -1) and `dt_bias` the
    inverse softplus of a rate spaced evenly in its logarithm over a layer's
    heads, so that where the dt input is 0 the heads' decay exp(dt A) spans
    DECAY_SPAN (dt from 0.001 to 0.105: the source's `time_step_min` ..
    `time_step_max`); the input term moves each token's about that."""
    spec = shapes(cfg)
    names = sorted(spec)
    dtype = sizes(cfg)["weights"]
    std = cfg["assumed"]["initializer_range"]
    lo, hi = (-math.log(a) for a in reversed(DECAY_SPAN))  # rates, low first

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            if kind == "zero":
                w = jnp.zeros(shape, jnp.float32)
            elif kind == "one":
                w = jnp.ones(shape, jnp.float32)
            elif kind == "dt_bias":
                rate = jnp.exp(jnp.linspace(math.log(lo), math.log(hi),
                                            shape[0]))
                w = jnp.log(jnp.expm1(rate))  # softplus(w) = rate
            else:
                w = std * jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)
                if kind == "scale":
                    w = w + 1.0
            out[name] = w.astype(dtype)
        return out

    # the seed may exceed 32 signed bits
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                   seed // (2 ** 31)))


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _attention(z, mm, lp, x):
    b, s, _ = x.shape
    group = z["heads"] // z["kv_heads"]
    q = mm("bse,ehd->bshd", x, lp["mixer.wq"])
    k = jnp.repeat(mm("bse,ehd->bshd", x, lp["mixer.wk"]), group, axis=2)
    v = jnp.repeat(mm("bse,ehd->bshd", x, lp["mixer.wv"]), group, axis=2)
    sc = mm("bshd,bthd->bhst", q, k) / math.sqrt(q.shape[-1])
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
    o = mm("bhst,bthd->bshd", jax.nn.softmax(sc, axis=-1), v)
    return mm("bshd,hde->bse", o, lp["mixer.wo"])


def _mamba(z, mm, lp, u):
    b, s, _ = u.shape
    nh, p, n, g = z["ssm_heads"], z["ssm_head_dim"], z["ssm_state"], \
        z["ssm_groups"]
    d_in, c, K = ssm_inner(z), ssm_conv_channels(z), z["conv"]
    zxd = mm("bse,ec->bsc", u, lp["mixer.w_in"])
    gate, xbc, dt = zxd[..., :d_in], zxd[..., d_in:d_in + c], \
        zxd[..., d_in + c:]
    xbc = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))  # causal: zeros before 0
    xbc = jax.nn.silu(sum(xbc[:, j:j + s] * lp["mixer.conv"][j]
                          for j in range(K)) + lp["mixer.conv_bias"])
    x = xbc[..., :d_in].reshape(b, s, nh, p)
    B = jnp.repeat(xbc[..., d_in:d_in + g * n].reshape(b, s, g, n),
                   nh // g, axis=2)                          # (b, s, nh, n)
    C = jnp.repeat(xbc[..., d_in + g * n:].reshape(b, s, g, n),
                   nh // g, axis=2)
    dt = jax.nn.softplus(dt + lp["mixer.dt_bias"])           # (b, s, nh)
    decay = jnp.exp(-jnp.exp(lp["mixer.A_log"]) * dt)
    hi = jax.lax.Precision.HIGHEST

    def token(S, c):  # S (b, nh, p, n)
        x_t, B_t, C_t, dt_t, a_t = c
        S = a_t[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=hi)

    _, y = jax.lax.scan(token, jnp.zeros((b, nh, p, n), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (x, B, C, dt, decay)),
                        unroll=8)  # eight tokens a loop turn, one by one
    y = jnp.moveaxis(y, 0, 1) + lp["mixer.D"][:, None] * x   # (b, s, nh, p)
    y = (y.reshape(b, s, d_in) * jax.nn.silu(gate)).reshape(b, s, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + z["eps"])
    return mm("bsc,ce->bse", y.reshape(b, s, d_in) * lp["mixer.norm"],
              lp["mixer.w_out"])


def _experts(z, mm, lp, u):
    b, s, h = u.shape
    t = u.reshape(b * s, h)
    # the router in float32 whatever the control rounds
    scores = jax.nn.sigmoid(jnp.einsum(
        "te,en->tn", t, lp["mixer.router"],
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + lp["mixer.b_corr"], z["top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * z["route_scale"]
    # (tokens, all experts): a chosen expert's weight, 0 for every other
    mask = jnp.sum(jax.nn.one_hot(chosen, z["experts"], dtype=jnp.float32)
                   * w[..., None], axis=1)
    mask = mask[:, z["held_from"]:z["held_from"] + z["held"]]

    def expert(out, e):  # every held expert on every token, one at a time
        up, down, m = e
        y = mm("tf,fe->te", _relu2(mm("te,ef->tf", t, up.astype(jnp.float32))),
               down.astype(jnp.float32))
        return out + m[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(t),
                          (lp["mixer.w_up"], lp["mixer.w_down"], mask.T))
    out = out + mm("tf,fe->te", _relu2(mm("te,ef->tf", t,
                                          lp["mixer.shared_up"])),
                   lp["mixer.shared_down"])
    return out.reshape(b, s, h)


_MIXERS = {MAMBA: _mamba, EXPERTS: _experts, ATTENTION: _attention}
# the stacked experts are cast up one at a time, inside the loop over them
_KEPT_AS_STORED = ("mixer.w_up", "mixer.w_down")


def _block(z, precision, kind, lp, x):
    """One layer on x (rows, s, h); lp holds the layer's leaves by short
    name, in the stored type: cast up here."""
    mm = functools.partial(_mm, precision=precision)
    lp = {k: v if k in _KEPT_AS_STORED else v.astype(jnp.float32)
          for k, v in lp.items()}
    return x + _MIXERS[kind](z, mm, lp, _rms(x, lp["norm.scale"], z["eps"]))


class Reference(_LayerwiseReference):
    """The pieces jitted once for one configuration and one precision; the
    walk over the layers, `logits` and `logits_at` are the hybrid family's
    (one jitted block a kind of layer, `z["layer_types"]` says which)."""

    def __init__(self, cfg, precision="f32"):
        z = self.z = sizes(cfg)
        self.embed = jax.jit(_embed)
        self.block = {kind: jax.jit(functools.partial(_block, z, precision,
                                                      kind))
                      for kind in set(z["layer_types"])}
        self.head = jax.jit(functools.partial(_head, z, precision))


# -- counts of operations and bytes, from shapes ---------------------------
def _kinds(z):
    return tuple(sum(1 for t in z["layer_types"] if t == kind)
                 for kind in (MAMBA, EXPERTS, ATTENTION))


def expert_params(z):
    """Parameters of ONE routed expert: its two matrices."""
    return 2 * z["hidden"] * z["expert_width"]


def counts(cfg):
    """Parameter counts: all of them as held here, and those a token's
    matrix products touch. THE COUNT IS OF THE WORK: of a layer's held
    experts a token's products touch the expected top_k x held / experts
    (3 of 64 at the published sizes; uniform routing), with the router and
    the shared expert whole. Not the embedding table, which is gathered, nor
    the convolution's taps and the vectors."""
    z = sizes(cfg)
    spec = shapes(cfg)
    size = {k: math.prod(s) for k, (s, _) in spec.items()}
    matrices = sum(n for k, n in size.items()
                   if spec[k][1] == "matrix" and k != "wte"
                   and not k.endswith((".conv", ".w_up", ".w_down")))
    routed = z["top_k"] * z["held"] / z["experts"]
    return {"params": sum(size.values()),
            "matmul_params": matrices
            + int(round(_kinds(z)[1] * routed * expert_params(z))),
            "fixed_matmul_params": matrices,
            "head_params": size["head"]}


def state_ops_per_token(cfg):
    """Operations one token costs a Mamba-2 layer outside its matrices: the
    state's decay, its rank-one update and its read (6 P N a head), and the
    K-tap convolution."""
    z = sizes(cfg)
    return 6 * z["ssm_heads"] * z["ssm_head_dim"] * z["ssm_state"] \
        + 2 * z["conv"] * ssm_conv_channels(z)


def forward_flops(cfg, positions, head_positions):
    """Floating-point operations the forward pass needs for tokens that sit
    at the given 0-based `positions` of their sequences: the matrices (of the
    routed experts the expected top_k x held / experts a token: `counts`),
    in the attention layers the causal scores and weighted values (a token
    at position t attends to t + 1 keys, every query head), in the Mamba-2
    layers the state's update and read, and the output head for
    `head_positions` of them. `serve_mfu` and `decode_step_mfu` read it."""
    z = sizes(cfg)
    c = counts(cfg)
    n_ssm, _, n_attn = _kinds(z)
    n = len(positions)
    body = 2 * (c["matmul_params"] - c["head_params"]) * n
    attn = 4 * z["heads"] * z["head_dim"] * n_attn \
        * sum(t + 1 for t in positions)
    state = n_ssm * state_ops_per_token(cfg) * n
    return body + attn + state + 2 * c["head_params"] * head_positions


def slot_state_bytes(cfg, bytes_per_value=2):
    """Bytes ONE slot holds of recurrent state over all Mamba-2 layers: the
    float32 state matrices and the convolution's last K - 1 input rows in
    the compute type."""
    z = sizes(cfg)
    return _kinds(z)[0] * (
        4 * z["ssm_heads"] * z["ssm_head_dim"] * z["ssm_state"]
        + bytes_per_value * (z["conv"] - 1) * ssm_conv_channels(z))


def experts_touched(z, tokens):
    """Distinct held experts of one layer that `tokens` tokens' choices
    touch, in expectation under uniform routing: held x (1 - (1 - top_k /
    experts)^tokens); 61.0 of 64 at 64 tokens of top 6 among 128."""
    return z["held"] * (1.0 - (1.0 - z["top_k"] / z["experts"]) ** tokens)


def decode_step_bytes(cfg, live_positions, bytes_per_value=2):
    """Bytes one batched decode step must move. THE COUNT IS OF THE WORK:
    every matrix outside the routed experts once in the compute type; of
    each expert layer the EXPECTED DISTINCT held experts the occupied slots'
    tokens touch (`experts_touched`), two matrices each; the keys and values
    of each occupied slot up to its position in the attention layers, at
    kv_heads x head size a position; and each occupied slot's recurrent
    state in the Mamba-2 layers read and written. `decode_step_hbm_share`
    reads it."""
    z = sizes(cfg)
    n_ssm, n_exp, n_attn = _kinds(z)
    n = len(live_positions)
    kv = 2 * n_attn * z["kv_heads"] * z["head_dim"] * sum(live_positions)
    routed = n_exp * experts_touched(z, n) * expert_params(z)
    return bytes_per_value * (counts(cfg)["fixed_matmul_params"] + routed
                              + kv) \
        + 2 * n * slot_state_bytes(cfg, bytes_per_value)
