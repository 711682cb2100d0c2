"""Plain reference for `laguna_lm.py`: jax.numpy, float32, matrix products at
`highest` precision, no kernel, no cache, no ring, no batching, the full
masks written out. Imports nothing of the program and takes nothing the
program made: the weights come from `init`, from the seed, and where the
configuration stores them in bfloat16 the stored values are cast up (an
expert at a time), never drawn again.

The model (`model_type` `laguna`), for hidden h, head size d, Hkv key-value
heads and RMSNorm(a) = a / sqrt(mean(a^2) + eps) * scale:

  Model:  x0 = wte[ids];  x = x + attn_i(RMSNorm(x));  x = x + ffn_i(RMSNorm(x));
          logits = RMSNorm_f(x_L) . head                       (head untied)

  attn, a layer with H query heads (`num_attention_heads_per_layer`: 48 in a
  full layer, 72 in a sliding one), query head i reading key-value head
  i // (H / Hkv), no bias, no q/k norm (`assumed`):
    q = rot(Wq u), k = rot(Wk u), v = Wv u
    rot: the first `rot` channels of a head rotated by position p, channel i
      paired with i + rot/2 (`rotate_half`): [a; b] -> [a cos - b sin;
      b cos + a sin] with angles p * inv_freq, the rest passed. A sliding
      layer: rot = d, inv_freq_i = theta^(-2i/rot), theta 10,000. A full
      layer: rot = d/2 (`partial_rotary_factor` 0.5), theta 500,000, YaRN as
      `transformers` `_compute_yarn_parameters` computes it over dim = rot:
      inv_extra = theta^(-2i/dim), inv_inter = inv_extra / factor,
      c(n) = dim ln(orig / (2 pi n)) / (2 ln theta), low = floor(c(beta_fast)),
      high = ceil(c(beta_slow)) clipped to [0, dim - 1], ramp_i = clip((i -
      low) / (high - low), 0, 1), inv_freq = inv_inter ramp + inv_extra (1 -
      ramp), and cos, sin multiplied by `attention_factor`.
    mask: key j visible to query i iff j <= i, and in a sliding layer
      i - window < j besides (`assumed`: the window's exact edge).
    o = softmax(q k^T / sqrt(d) + mask) v
    g = sigmoid(Wg u), Wg (h, H): head i's o times g_i     (`gating` per-head)
    y = Wo o
  computed here a block of 512 query rows at a time against every key the
  block's mask can leave (all up to its last row; in a sliding layer from
  window - 1 before its first), the mask written out over them, so that
  7,168 tokens fit.

  ffn, layer 0 (`mlp_only_layers`): W_down (SiLU(W_gate u) * W_up u).
  ffn, every other layer: s = softmax(W_r u) over ALL the published experts
    in float32; the top k of s chosen; w = s[chosen] / sum x
    `moe_routed_scaling_factor`; e(u) = W_down_e (SiLU(W_gate_e u) * W_up_e u);
    out = sum over the chosen e THAT THIS CHIP HOLDS of w_e e(u) + shared(u),
    shared of the same gated form, added ungated (`assumed`).
  computed here with EVERY held expert applied to every token under a 0/w
  mask. What the experts held elsewhere would add is left out, as in the
  program: the configuration's file says which experts are here.

What the source's config.json does not state is listed under `assumed` in the
configuration's file, with the departures.

Everything is computed layer by layer through one small jitted function per
kind of layer. A `precision` other than "f32" is a control, not a reference:
every weight-matrix product and the attention products take their operands
rounded to float8_e4m3 ("fp8", the step below bfloat16) or to bfloat16
("bf16", the step below the float32 the rehearsal sizes state); the rotary
angles and the router stay float32, as the configuration states them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .decoder_lm_ref import _mm
from .hybrid_lm_ref import _embed, _head, _rms

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def sizes(cfg):
    g = cfg.get
    held = g("num_experts")
    rope = g("rope_parameters")
    return {
        "layers": g("num_hidden_layers"),
        # one (attention kind, feed-forward kind, query heads) a layer
        "layer_types": tuple(zip(g("layer_types"), g("mlp_layer_types"),
                                 g("num_attention_heads_per_layer"))),
        "gating": tuple(g("gating_types")),
        "hidden": g("hidden_size"),
        "kv_heads": g("num_key_value_heads"),
        "head_dim": g("head_dim"),
        "vocab": g("vocab_size"),
        "positions": g("max_position_embeddings"),
        "eps": g("rms_norm_eps"),
        "window": g("sliding_window"),
        "rope": {FULL: rope[FULL], SLIDING: rope[SLIDING]},
        "ffn": g("intermediate_size"),
        # the router is as wide as the published layer; this chip holds
        # `held` of its experts, from `held_from` on
        "experts": g("published", {}).get("num_experts", held),
        "held": held,
        "held_from": g("experts_held_from", 0),
        "top_k": g("num_experts_per_tok"),
        "expert_width": g("moe_intermediate_size"),
        "shared_width": g("shared_expert_intermediate_size"),
        "route_scale": g("moe_routed_scaling_factor"),
        "norm_topk": g("norm_topk_prob"),
        "weights": jnp.dtype(cfg["dtype_policy"].get("weights", "float32")),
    }


def rope_of(z, attn_kind):
    """The rotary description of one kind of layer in the program's terms
    (`flexflow_tpu.ops.attention.RotaryParams`'s fields), from the file's
    `rope_parameters`."""
    r = z["rope"][attn_kind]
    out = {"theta": float(r["rope_theta"]),
           "dim": int(round(z["head_dim"] * r.get("partial_rotary_factor", 1))),
           "scaling": r["rope_type"]}
    if r["rope_type"] == "yarn":
        out.update(factor=float(r["factor"]),
                   original_max_position_embeddings=r[
                       "original_max_position_embeddings"],
                   beta_fast=float(r["beta_fast"]),
                   beta_slow=float(r["beta_slow"]),
                   attention_factor=float(r["attention_factor"]))
    return out


def inv_freq(rope):
    """(inv_freq (rot/2,) float32, the factor on cos and sin) of `rope`
    (rope_of's dict), by the formulas in this file's heading."""
    dim = rope["dim"]
    extra = rope["theta"] ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["scaling"] != "yarn":
        return extra.astype(np.float32), 1.0

    def c(n):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (2 * math.pi * n)) \
            / (2 * math.log(rope["theta"]))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / rope["factor"] * ramp + extra * (1.0 - ramp)
    return inv.astype(np.float32), rope["attention_factor"]


def layer_shapes(z, kind):
    """short leaf name -> (shape, kind of initial values) of one layer."""
    _, mlp, nh = kind
    h, kv, d = z["hidden"], z["kv_heads"], z["head_dim"]
    out = {"norm1.scale": ((h,), "scale"), "norm2.scale": ((h,), "scale"),
           "attn.wq": ((h, nh, d), "matrix"), "attn.wk": ((h, kv, d), "matrix"),
           "attn.wv": ((h, kv, d), "matrix"), "attn.wo": ((nh, d, h), "matrix"),
           "attn.wg": ((h, nh), "matrix")}
    if mlp == DENSE:
        f = z["ffn"]
        out.update({"gate.kernel": ((h, f), "matrix"),
                    "up.kernel": ((h, f), "matrix"),
                    "down.kernel": ((f, h), "matrix")})
    else:
        n, f, fs = z["held"], z["expert_width"], z["shared_width"]
        out.update({"moe.router": ((h, z["experts"]), "matrix"),
                    "moe.b_corr": ((z["experts"],), "zero"),
                    "moe.w_gate": ((n, h, f), "matrix"),
                    "moe.w_up": ((n, h, f), "matrix"),
                    "moe.w_down": ((n, f, h), "matrix"),
                    "moe.shared_gate": ((h, fs), "matrix"),
                    "moe.shared_up": ((h, fs), "matrix"),
                    "moe.shared_down": ((fs, h), "matrix")})
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


def init(cfg, seed):
    """All weights from the seed in ONE jitted call, on the device, in the
    type the configuration stores them in (`dtype_policy.weights`):
    matrices N(0, r), norm scales 1 + N(0, r), with r the file's
    `assumed.initializer_range` (0.02; the rehearsal sizes take 0.2, which
    gives a 32-wide model the gain 0.02 gives a 3,072-wide one). The router's
    choice-only bias `b_corr` is 0: this family has none."""
    spec = shapes(cfg)
    names = sorted(spec)
    dtype = sizes(cfg)["weights"]
    std = cfg["assumed"]["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            if kind == "zero":
                w = jnp.zeros(shape, jnp.float32)
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


def _rotate(rope, x):
    """x (b, s, heads, d) rotated by its rows' positions 0..s-1."""
    inv, factor = inv_freq(rope)
    rot = rope["dim"]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


ROWS = 512  # query rows a block of the attention holds against all keys


def _attention(z, mm, kind, lp, u):
    attn_kind, _, nh = kind
    b, s, _ = u.shape
    group = nh // z["kv_heads"]
    rope = rope_of(z, attn_kind)
    q = _rotate(rope, mm("bse,ehd->bshd", u, lp["attn.wq"]))
    k = _rotate(rope, mm("bse,ehd->bshd", u, lp["attn.wk"]))
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(mm("bse,ehd->bshd", u, lp["attn.wv"]), group, axis=2)
    window = z["window"] if attn_kind == SLIDING else 0
    outs = []
    for lo in range(0, s, ROWS):
        # this block's rows against every key its mask can leave: those up
        # to its last row, in a sliding layer from window - 1 before its
        # first; the mask written out over them
        hi = min(lo + ROWS, s)
        first = max(0, lo - window + 1) if window else 0
        i = jnp.arange(lo, hi)[:, None]
        j = jnp.arange(first, hi)[None, :]
        mask = j <= i
        if window:
            mask = mask & (j > i - window)
        sc = mm("bshd,bthd->bhst", q[:, lo:hi], k[:, first:hi]) \
            / math.sqrt(z["head_dim"])
        sc = jnp.where(mask[None, None], sc, -1e30)
        outs.append(mm("bhst,bthd->bshd", jax.nn.softmax(sc, axis=-1),
                       v[:, first:hi]))
    o = jnp.concatenate(outs, axis=1)
    gate = jax.nn.sigmoid(mm("bse,eh->bsh", u, lp["attn.wg"]))
    return mm("bshd,hde->bse", o * gate[..., None], lp["attn.wo"])


def _glu(mm, t, gate, up, down):
    return mm("tf,fe->te", jax.nn.silu(mm("te,ef->tf", t, gate))
              * mm("te,ef->tf", t, up), down)


def _dense(z, mm, lp, u):
    b, s, h = u.shape
    return _glu(mm, u.reshape(b * s, h), lp["gate.kernel"], lp["up.kernel"],
                lp["down.kernel"]).reshape(b, s, h)


def _experts(z, mm, lp, u):
    b, s, h = u.shape
    t = u.reshape(b * s, h)
    # the router in float32 whatever the control rounds
    scores = jax.nn.softmax(jnp.einsum(
        "te,en->tn", t, lp["moe.router"],
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, chosen = jax.lax.top_k(scores + lp["moe.b_corr"], z["top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * z["route_scale"]
    # (tokens, all experts): a chosen expert's weight, 0 for every other
    mask = jnp.sum(jax.nn.one_hot(chosen, z["experts"], dtype=jnp.float32)
                   * w[..., None], axis=1)
    mask = mask[:, z["held_from"]:z["held_from"] + z["held"]]

    def expert(out, e):  # every held expert on every token, one at a time
        gate, up, down, m = e
        return out + m[:, None] * _glu(
            mm, t, *(a.astype(jnp.float32) for a in (gate, up, down))), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(t), (
        lp["moe.w_gate"], lp["moe.w_up"], lp["moe.w_down"], mask.T))
    out = out + _glu(mm, t, lp["moe.shared_gate"], lp["moe.shared_up"],
                     lp["moe.shared_down"])
    return out.reshape(b, s, h)


# the stacked experts are cast up one at a time, inside the loop over them
_KEPT_AS_STORED = ("moe.w_gate", "moe.w_up", "moe.w_down")


def _block(z, precision, kind, lp, x):
    """One layer on x (rows, s, h); lp holds the layer's leaves by short
    name, in the stored type: cast up here."""
    mm = functools.partial(_mm, precision=precision)
    lp = {k: v if k in _KEPT_AS_STORED else v.astype(jnp.float32)
          for k, v in lp.items()}
    x = x + _attention(z, mm, kind, lp, _rms(x, lp["norm1.scale"], z["eps"]))
    ffn = _dense if kind[1] == DENSE else _experts
    return x + ffn(z, mm, lp, _rms(x, lp["norm2.scale"], z["eps"]))


class Reference:
    """The pieces jitted once for one configuration and one precision: one
    block a kind of layer (`z["layer_types"]` says which layer is which)."""

    HEAD = ("norm_f.scale", "head")

    def __init__(self, cfg, precision="f32"):
        z = self.z = sizes(cfg)
        self.embed = jax.jit(_embed)
        self.block = {kind: jax.jit(functools.partial(_block, z, precision,
                                                      kind))
                      for kind in set(z["layer_types"])}
        self.head = jax.jit(functools.partial(_head, z, precision))

    @staticmethod
    def layer(params, i):
        p = f"h{i}."
        return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}

    def hidden(self, params, ids):
        """Final hidden state of ids (rows, s)."""
        x = self.embed(params["wte"], ids)
        for i, kind in enumerate(self.z["layer_types"]):
            x = self.block[kind](self.layer(params, i), x)
        return x

    def logits(self, params, ids):
        """Full-forward logits (rows, s, vocab)."""
        return self.head({k: params[k] for k in self.HEAD},
                         self.hidden(params, ids))

    def logits_at(self, params, ids, positions, pad_to=1024, rows_to=256):
        """Reference logits (numpy, len(positions) x vocab) of ONE sequence
        `ids` at the given positions. The sequence is padded with token 0 to
        a multiple of `pad_to` (causal: what follows changes nothing before
        it) and the positions to a multiple of `rows_to`, so that few shapes
        compile; the head runs `rows_to` rows at a time and the rows are cut
        on the host."""
        n, k = len(ids), len(positions)
        buf = np.zeros((1, -(-n // pad_to) * pad_to), np.int32)
        buf[0, :n] = ids
        rows = np.zeros(-(-k // rows_to) * rows_to, np.int32)
        rows[:k] = positions
        x = self.hidden(params, jnp.asarray(buf))
        hp = {k_: params[k_] for k_ in self.HEAD}
        out = [np.asarray(self.head(hp, x[:, jnp.asarray(
            rows[lo:lo + rows_to])]))[0] for lo in range(0, len(rows),
                                                         rows_to)]
        return np.concatenate(out)[:k]


# -- counts of operations and bytes, from shapes ---------------------------
def _layers(z, attn_kind=None, mlp=None):
    return [k for k in z["layer_types"]
            if attn_kind in (None, k[0]) and mlp in (None, k[1])]


def expert_params(z):
    """Parameters of ONE routed expert: its three matrices."""
    return 3 * z["hidden"] * z["expert_width"]


def uncut(cfg):
    """The configuration with every reduced key at its published value:
    what `counts` of the whole model is taken of."""
    whole = dict(cfg, **cfg.get("published", {}))
    whole["published"] = {}
    return whole


def counts(cfg):
    """Parameter counts: all of them as held here (`params`; of the whole
    published model, `whole_params`), and those a token's matrix products
    touch. THE COUNT IS OF THE WORK: of a layer's held experts a token's
    products touch the expected top_k x held / experts (0.3125 of 8 at the
    published sizes; uniform routing), with the router and the shared expert
    whole. Not the embedding table, which is gathered, nor the vectors."""
    z = sizes(cfg)
    spec = shapes(cfg)
    size = {k: math.prod(s) for k, (s, _) in spec.items()}
    routed_names = (".w_gate", ".w_up", ".w_down")
    matrices = sum(n for k, n in size.items()
                   if spec[k][1] == "matrix" and k != "wte"
                   and not k.endswith(routed_names))
    routed = z["top_k"] * z["held"] / z["experts"]
    out = {"params": sum(size.values()),
           "matmul_params": matrices + int(round(
               len(_layers(z, mlp=SPARSE)) * routed * expert_params(z))),
           "fixed_matmul_params": matrices,
           "head_params": size["head"]}
    if cfg.get("published"):
        out["whole_params"] = counts(uncut(cfg))["params"]
    return out


def keys_seen(z, attn_kind, position):
    """Keys the token at 0-based `position` attends in a layer of the kind:
    position + 1, and in a sliding layer no more than the window."""
    seen = position + 1
    return min(seen, z["window"]) if attn_kind == SLIDING else seen


def forward_flops(cfg, positions, head_positions):
    """Floating-point operations the forward pass needs for tokens that sit
    at the given 0-based `positions` of their sequences: the matrices (of the
    routed experts the expected top_k x held / experts a token: `counts`),
    in every attention layer the scores and weighted values of each query
    head over the keys the token sees (`keys_seen`: a sliding layer's no more
    than the window), and the output head for `head_positions` of them.
    `serve_mfu` and `decode_step_mfu` read it."""
    z = sizes(cfg)
    c = counts(cfg)
    n = len(positions)
    body = 2 * (c["matmul_params"] - c["head_params"]) * n
    attn = 0
    for attn_kind in (FULL, SLIDING):
        heads = sum(k[2] for k in _layers(z, attn_kind))
        attn += 4 * heads * z["head_dim"] * sum(
            keys_seen(z, attn_kind, t) for t in positions)
    return body + attn + 2 * c["head_params"] * head_positions


def experts_touched(z, tokens):
    """Distinct held experts of one layer that `tokens` tokens' choices
    touch, in expectation under uniform routing: held x (1 - (1 - top_k /
    experts)^tokens); 5.76 of 8 at 32 tokens of top 10 among 256."""
    return z["held"] * (1.0 - (1.0 - z["top_k"] / z["experts"]) ** tokens)


def decode_step_bytes(cfg, live_positions, bytes_per_value=2):
    """Bytes one batched decode step must move. THE COUNT IS OF THE WORK:
    every matrix outside the routed experts once in the compute type; of
    each expert layer the EXPECTED DISTINCT held experts the occupied slots'
    tokens touch (`experts_touched`), three matrices each; and the keys and
    values of each occupied slot in every attention layer at kv_heads x head
    size a position: all `live` positions of a full layer, min(live, window)
    of a sliding one. `decode_step_hbm_share` reads it."""
    z = sizes(cfg)
    n = len(live_positions)
    row = 2 * z["kv_heads"] * z["head_dim"]
    kv = row * (len(_layers(z, FULL)) * sum(live_positions)
                + len(_layers(z, SLIDING))
                * sum(min(p, z["window"]) for p in live_positions))
    routed = len(_layers(z, mlp=SPARSE)) * experts_touched(z, n) \
        * expert_params(z)
    return bytes_per_value * (counts(cfg)["fixed_matmul_params"] + routed + kv)
