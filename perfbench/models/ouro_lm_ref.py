"""Plain reference for `ouro_lm.py`: jax.numpy, float32, matrix products at
`highest` precision, no kernel, no cache, no batching, no loop construct of
the program: the steps and the layers are Python loops over one small jitted
function a layer, written out. Imports nothing of the program and takes
nothing the program made: the weights come from `init`, from the seed, and
where the configuration stores them in bfloat16 the stored values are cast up
a layer at a time, never drawn again.

The model (`model_type` `ouro`, a looped language model, arXiv:2510.25741),
for hidden h, head size d, H heads over H key-value heads (plain multi-head)
and RMSNorm(a) = a / sqrt(mean(a^2) + eps) * scale:

  Model:  x = wte[ids]
          for u in 1..total_ut_steps (4):
              for i in 1..L (48):  x = block_i(x)
              x = RMSNorm_f(x)          (`assumed`: the final norm sits
                                         inside the loop, its output is the
                                         next step's input)
          logits = x . head             (head untied)
  Every step uses the same L layers' weights and the same positions; each
  step's attention reads its own keys and values, the ones that step made.

  block (`assumed`, the "sandwich" of the family's modelling code):
    x = x + RMSNorm_a2(attn(RMSNorm_a1(x)))
    x = x + RMSNorm_m2(mlp(RMSNorm_m1(x)))
    mlp(u) = W_down (SiLU(W_gate u) * W_up u), no biases
  attn: q = rot(Wq u), k = rot(Wk u), v = Wv u, no bias, no q/k norm;
    rot: all d channels of a head rotated by position p, channel i paired
    with i + d/2 (`rotate_half`, `assumed`): [a; b] -> [a cos - b sin;
    b cos + a sin], angles p * theta^(-2i/d), theta = rope_theta 1,000,000;
    mask: key j visible to query i iff j <= i; o = softmax(q k^T / sqrt(d)
    + mask) v; y = Wo o; computed a block of 512 query rows at a time
    against every key up to the block's last row, so that long sequences fit.

  The exit gate (`early_exit_threshold` 1: every token runs all the steps,
  so the gate changes no logit) is left out, as in the program.

Everything is computed layer by layer through one jitted function. A
`precision` other than "f32" is a control, not a reference: every
weight-matrix product and the attention products take their operands
rounded to float8_e4m3 ("fp8", the step below the bfloat16 the configuration
states) or to bfloat16 ("bf16", the step below the float32 the rehearsal
sizes state); the rotary angles stay float32.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .decoder_lm_ref import _mm
from .hybrid_lm_ref import _embed, _rms
from .laguna_lm_ref import _rotate


def sizes(cfg):
    g = cfg.get
    return {
        "layers": g("num_hidden_layers"),
        "steps": g("total_ut_steps"),
        "hidden": g("hidden_size"),
        "heads": g("num_attention_heads"),
        "kv_heads": g("num_key_value_heads"),
        "head_dim": g("head_dim"),
        "ffn": g("intermediate_size"),
        "vocab": g("vocab_size"),
        "positions": g("max_position_embeddings"),
        "eps": g("rms_norm_eps"),
        "theta": float(g("rope_theta")),
        "weights": jnp.dtype(cfg["dtype_policy"].get("weights", "float32")),
    }


def rope_of(z):
    """The rotary embedding in the program's terms (`RotaryParams`' fields)
    and in `laguna_lm_ref._rotate`'s: every channel of a head."""
    return {"theta": z["theta"], "dim": z["head_dim"], "scaling": "default"}


# one layer's leaves: the four norms of the sandwich, attention, gated MLP
NORMS = ("norm1", "norm2", "norm3", "norm4")


def layer_shapes(z):
    """short leaf name -> (shape, kind of initial values) of one layer."""
    h, nh, kv, d, f = (z["hidden"], z["heads"], z["kv_heads"], z["head_dim"],
                       z["ffn"])
    out = {f"{n}.scale": ((h,), "scale") for n in NORMS}
    out.update({"attn.wq": ((h, nh, d), "matrix"),
                "attn.wk": ((h, kv, d), "matrix"),
                "attn.wv": ((h, kv, d), "matrix"),
                "attn.wo": ((nh, d, h), "matrix"),
                "gate.kernel": ((h, f), "matrix"),
                "up.kernel": ((h, f), "matrix"),
                "down.kernel": ((f, h), "matrix")})
    return out


def shapes(cfg):
    """canonical leaf name -> (shape, kind); kind picks the initial values."""
    z = sizes(cfg)
    out = {"wte": ((z["vocab"], z["hidden"]), "matrix"),
           "norm_f.scale": ((z["hidden"],), "scale"),
           "head": ((z["hidden"], z["vocab"]), "matrix")}
    for i in range(z["layers"]):
        for k, v in layer_shapes(z).items():
            out[f"h{i}.{k}"] = v
    return out


def init(cfg, seed):
    """All weights from the seed in ONE jitted call, on the device, in the
    type the configuration stores them in (`dtype_policy.weights`):
    matrices N(0, r), norm scales 1 + N(0, r), with r the file's
    `assumed.initializer_range` (0.02; the rehearsal sizes take 0.2, which
    keeps a 64-wide model's signals well above the norms' epsilon)."""
    spec = shapes(cfg)
    names = sorted(spec)
    dtype = sizes(cfg)["weights"]
    std = cfg["assumed"]["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            if kind == "scale":
                w = w + 1.0
            out[name] = w.astype(dtype)
        return out

    # the seed may exceed 32 signed bits
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                   seed // (2 ** 31)))


ROWS = 512  # query rows a block of the attention holds against its keys


def _attention(z, mm, lp, u):
    s = u.shape[1]
    group = z["heads"] // z["kv_heads"]
    rope = rope_of(z)
    q = _rotate(rope, mm("bse,ehd->bshd", u, lp["attn.wq"]))
    k = jnp.repeat(_rotate(rope, mm("bse,ehd->bshd", u, lp["attn.wk"])),
                   group, axis=2)
    v = jnp.repeat(mm("bse,ehd->bshd", u, lp["attn.wv"]), group, axis=2)
    outs = []
    for lo in range(0, s, ROWS):
        hi = min(lo + ROWS, s)
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        sc = mm("bshd,bthd->bhst", q[:, lo:hi], k[:, :hi]) \
            / math.sqrt(z["head_dim"])
        sc = jnp.where(mask[None, None], sc, -1e30)
        outs.append(mm("bhst,bthd->bshd", jax.nn.softmax(sc, axis=-1),
                       v[:, :hi]))
    return mm("bshd,hde->bse", jnp.concatenate(outs, axis=1), lp["attn.wo"])


def _mlp(mm, lp, u):
    return mm("bsf,fe->bse", jax.nn.silu(mm("bse,ef->bsf", u,
                                            lp["gate.kernel"]))
              * mm("bse,ef->bsf", u, lp["up.kernel"]), lp["down.kernel"])


def _block(z, precision, lp, x):
    """One layer on x (rows, s, h); lp holds the layer's leaves by short
    name, in the stored type: cast up here."""
    mm = functools.partial(_mm, precision=precision)
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    eps = z["eps"]
    a = _attention(z, mm, lp, _rms(x, lp["norm1.scale"], eps))
    x = x + _rms(a, lp["norm2.scale"], eps)
    m = _mlp(mm, lp, _rms(x, lp["norm3.scale"], eps))
    return x + _rms(m, lp["norm4.scale"], eps)


def _norm_f(z, scale, x):
    return _rms(x, scale.astype(jnp.float32), z["eps"])


def _head(precision, head, x):
    return _mm("bsh,hv->bsv", x, head.astype(jnp.float32), precision)


class Reference:
    """The pieces jitted once for one configuration and one precision: the
    block, the final norm and the head."""

    def __init__(self, cfg, precision="f32"):
        z = self.z = sizes(cfg)
        self.embed = jax.jit(_embed)
        self.block = jax.jit(functools.partial(_block, z, precision))
        self.norm_f = jax.jit(functools.partial(_norm_f, z))
        self.head = jax.jit(functools.partial(_head, precision))

    @staticmethod
    def layer(params, i):
        p = f"h{i}."
        return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}

    def hidden(self, params, ids):
        """The last step's hidden state of ids (rows, s): every step through
        every layer, the final norm after each step."""
        x = self.embed(params["wte"], ids)
        layers = [self.layer(params, i) for i in range(self.z["layers"])]
        for _ in range(self.z["steps"]):
            for lp in layers:
                x = self.block(lp, x)
            x = self.norm_f(params["norm_f.scale"], x)
        return x

    def logits(self, params, ids):
        """Full-forward logits (rows, s, vocab)."""
        return self.head(params["head"], self.hidden(params, ids))

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
        out = [np.asarray(self.head(params["head"], x[:, jnp.asarray(
            rows[lo:lo + rows_to])]))[0] for lo in range(0, len(rows),
                                                         rows_to)]
        return np.concatenate(out)[:k]


# -- counts of operations and bytes, from shapes ---------------------------
def layer_matmul_params(z):
    """Parameters of ONE layer's matrices: attention's four, the MLP's three."""
    h, nh, kv, d, f = (z["hidden"], z["heads"], z["kv_heads"], z["head_dim"],
                       z["ffn"])
    return h * d * (2 * nh + 2 * kv) + 3 * h * f


def counts(cfg):
    """Parameter counts of the model as held (`params`: every layer once, the
    embedding, the final norm and the head), and those a token's matrix
    products touch in ONE pass of the layers (`layer_matmul_params`, all
    layers) and in the head."""
    z = sizes(cfg)
    size = {k: math.prod(s) for k, (s, _) in shapes(cfg).items()}
    return {"params": sum(size.values()),
            "layer_params": sum(math.prod(s) for s, _ in
                                layer_shapes(z).values()),
            "pass_matmul_params": z["layers"] * layer_matmul_params(z),
            "head_params": size["head"]}


def forward_flops(cfg, positions, head_positions):
    """Floating-point operations the forward pass needs for tokens that sit
    at the given 0-based `positions` of their sequences: EVERY STEP's pass
    of the layers' matrices, and every step's attention of each head over
    the t + 1 keys a token at t sees in each layer (scores and weighted
    values, 4 operations a channel and key), and the output head for
    `head_positions` of them. `serve_mfu` and `decode_step_mfu` read it."""
    z = sizes(cfg)
    c = counts(cfg)
    passes = z["steps"] * z["layers"]
    body = 2 * z["steps"] * c["pass_matmul_params"] * len(positions)
    attn = 4 * passes * z["heads"] * z["head_dim"] * sum(
        t + 1 for t in positions)
    return body + attn + 2 * c["head_params"] * head_positions


def decode_step_bytes(cfg, live_positions, bytes_per_value=2):
    """Bytes one batched decode step must move. THE COUNT IS OF THE WORK:
    every layer's matrices once A STEP (the loop reads them again each
    step), the head once, and every step's keys and values of every layer
    for the occupied slots' `live` positions, kv_heads x head size each.
    `decode_step_hbm_share` reads it."""
    z = sizes(cfg)
    c = counts(cfg)
    passes = z["steps"] * z["layers"]
    kv = passes * 2 * z["kv_heads"] * z["head_dim"] * sum(live_positions)
    return bytes_per_value * (z["steps"] * c["pass_matmul_params"]
                              + c["head_params"] + kv)


def loop_decode_bytes(cfg, live_positions, bytes_per_value=2):
    """The part of `decode_step_bytes` inside the loop region: the steps'
    passes of the layers' matrices and their keys and values, not the
    head. `loop_decode_roofline` reads it."""
    return decode_step_bytes(cfg, live_positions, bytes_per_value) \
        - bytes_per_value * counts(cfg)["head_params"]
