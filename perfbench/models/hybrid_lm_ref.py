"""Plain reference for `hybrid_lm.py`: jax.numpy, float32, matrix products at
`highest` precision, no kernel, no cache, no chunks, no batching. Imports
nothing of the program and takes nothing the program made: the weights come
from `init`, from the seed, and where the configuration stores them in
bfloat16 the stored values are cast up, never drawn again.

The model (a hybrid of gated delta-rule layers and full-attention layers in
the OLMo 2/3 block; `layer_types` says which layer is which), for hidden h,
H heads, and RMSNorm(a) = a / sqrt(mean(a^2) + eps) * scale:

  Full layer (head size d = h / H, no rotary embedding: `rope_theta` null):
    q = RMSNorm_q(Wq x), k = RMSNorm_k(Wk x)   over the whole h-wide projection
    v = Wv x;  per head  o = softmax(q k^T / sqrt(d) + causal) v;  y = Wo o

  Linear layer (gated delta net, arXiv:2412.06464; dk key and dv value
  channels a head), per token t and head, state S in R^(dv x dk):
    [q~; k~; v~] = SiLU(causal depthwise conv_K([Wq x; Wk x; Wv x]))
    q = q~ / |q~| / sqrt(dk),  k = k~ / |k~|     (|a| = sqrt(a.a + 1e-6))
    beta  = 2 sigmoid(Wb x)                      (2: linear_allow_neg_eigval)
    alpha = exp(-exp(A_log) * softplus(Wa x + dt_bias))
    S_t = alpha_t S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t
    y   = Wo concat_heads( RMSNorm_dv(o_t) * SiLU(Wz x) )
  computed here TOKEN BY TOKEN (`lax.scan` over t), S_0 = 0.

  Block:  h = x + RMSNorm(mixer(x));  out = h + RMSNorm(Wdown(SiLU(Wgate h) * Wup h))
  Model:  x0 = wte[ids];  logits = RMSNorm_f(x_L) . head   (head untied)

What the source's config.json does not state (the norm placement, the q/k
norms, the output gate, the decay's parametrisation) is the family's
published convention and is listed under `assumed` in the configuration's
file, with the departures.

Everything is computed layer by layer through one small jitted function per
kind of layer. A `precision` other than "f32" is a control, not a reference:
every weight-matrix product and the attention products take their operands
rounded to float8_e4m3 ("fp8", the step below bfloat16) or to bfloat16
("bf16", the step below the float32 the rehearsal sizes state); the
recurrence itself stays float32, as the configuration states its state.
"""
import functools
import math

import jax
import jax.numpy as jnp

from .decoder_lm_ref import _mm

LINEAR, FULL = "linear_attention", "full_attention"


def sizes(cfg):
    g = cfg.get
    return {
        "layers": g("num_hidden_layers"),
        "layer_types": tuple(g("layer_types")),
        "hidden": g("hidden_size"),
        "heads": g("num_attention_heads"),
        "ffn": g("intermediate_size"),
        "vocab": g("vocab_size"),
        "positions": g("max_position_embeddings"),
        "eps": g("rms_norm_eps"),
        "lin_heads": g("linear_num_value_heads"),
        "lin_dk": g("linear_key_head_dim"),
        "lin_dv": g("linear_value_head_dim"),
        "conv": g("linear_conv_kernel_dim"),
        "neg_eigval": g("linear_allow_neg_eigval"),
        "weights": jnp.dtype(cfg["dtype_policy"].get("weights", "float32")),
    }


def layer_shapes(z, kind):
    """short leaf name -> (shape, kind of initial values) of one layer."""
    h, f = z["hidden"], z["ffn"]
    out = {"norm1.scale": ((h,), "scale"), "norm2.scale": ((h,), "scale"),
           "gate.kernel": ((h, f), "matrix"), "up.kernel": ((h, f), "matrix"),
           "down.kernel": ((f, h), "matrix")}
    if kind == FULL:
        nh, d = z["heads"], h // z["heads"]
        out.update({"mixer.wq": ((h, nh, d), "matrix"),
                    "mixer.wk": ((h, nh, d), "matrix"),
                    "mixer.wv": ((h, nh, d), "matrix"),
                    "mixer.wo": ((nh, d, h), "matrix"),
                    "mixer.q_norm": ((h,), "scale"),
                    "mixer.k_norm": ((h,), "scale")})
    else:
        nh, dk, dv = z["lin_heads"], z["lin_dk"], z["lin_dv"]
        out.update({"mixer.wq": ((h, nh * dk), "matrix"),
                    "mixer.wk": ((h, nh * dk), "matrix"),
                    "mixer.wv": ((h, nh * dv), "matrix"),
                    "mixer.wz": ((h, nh * dv), "matrix"),
                    "mixer.wo": ((nh * dv, h), "matrix"),
                    "mixer.wb": ((h, nh), "matrix"),
                    "mixer.wa": ((h, nh), "matrix"),
                    "mixer.conv": ((z["conv"], nh * (2 * dk + dv)), "conv"),
                    "mixer.A_log": ((nh,), "A_log"),
                    "mixer.dt_bias": ((nh,), "dt_bias"),
                    "mixer.norm": ((dv,), "scale")})
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


ALPHA_SPAN = (0.9, 0.999)  # the decay a head has where Wa x = 0


def init(cfg, seed):
    """All weights from the seed in ONE jitted call, on the device, in the
    type the configuration stores them in (`dtype_policy.weights`): matrices
    and the convolution's taps N(0, r), norm scales 1 + N(0, r), with r the
    file's `assumed.initializer_range` (0.02; the rehearsal sizes take 0.2,
    which gives a 32-wide model the gain 0.02 gives a 3,840-wide one: at
    0.02 every signal there lies under the norms' epsilon).
    `A_log` = 0 and `dt_bias` the inverse softplus of a rate spaced
    evenly in its logarithm over a layer's heads, so that at Wa x = 0 the
    heads' decay alpha = exp(-softplus(dt_bias)) spans ALPHA_SPAN; the input
    term moves each token's about that."""
    spec = shapes(cfg)
    names = sorted(spec)
    dtype = sizes(cfg)["weights"]
    std = cfg["assumed"]["initializer_range"]
    lo, hi = (-math.log(a) for a in reversed(ALPHA_SPAN))  # rates, low first

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            if kind == "A_log":
                w = jnp.zeros(shape, jnp.float32)
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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _full_mixer(z, mm, lp, x):
    b, s, h = x.shape
    nh = z["heads"]
    q = _rms(mm("bse,ehd->bshd", x, lp["mixer.wq"]).reshape(b, s, h),
             lp["mixer.q_norm"], z["eps"]).reshape(b, s, nh, -1)
    k = _rms(mm("bse,ehd->bshd", x, lp["mixer.wk"]).reshape(b, s, h),
             lp["mixer.k_norm"], z["eps"]).reshape(b, s, nh, -1)
    v = mm("bse,ehd->bshd", x, lp["mixer.wv"])
    sc = mm("bshd,bthd->bhst", q, k) / math.sqrt(q.shape[-1])
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
    o = mm("bhst,bthd->bshd", jax.nn.softmax(sc, axis=-1), v)
    return mm("bshd,hde->bse", o, lp["mixer.wo"])


def _linear_mixer(z, mm, lp, x):
    b, s, _ = x.shape
    nh, dk, dv, K = z["lin_heads"], z["lin_dk"], z["lin_dv"], z["conv"]
    u = jnp.concatenate([mm("bse,ec->bsc", x, lp["mixer." + w])
                         for w in ("wq", "wk", "wv")], axis=-1)
    u = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))  # causal: zeros before 0
    conv = jax.nn.silu(sum(u[:, j:j + s] * lp["mixer.conv"][j]
                           for j in range(K)))
    q, k, v = jnp.split(conv, [nh * dk, 2 * nh * dk], axis=-1)
    q = _l2(q.reshape(b, s, nh, dk)) / math.sqrt(dk)
    k = _l2(k.reshape(b, s, nh, dk))
    v = v.reshape(b, s, nh, dv)
    beta = jax.nn.sigmoid(mm("bse,eh->bsh", x, lp["mixer.wb"]))
    if z["neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(lp["mixer.A_log"]) * jax.nn.softplus(
        mm("bse,eh->bsh", x, lp["mixer.wa"]) + lp["mixer.dt_bias"]))
    hi = jax.lax.Precision.HIGHEST

    def token(S, c):  # S (b, nh, dv, dk)
        q_t, k_t, v_t, a_t, b_t = c
        S = a_t[..., None, None] * S
        S = S + jnp.einsum(
            "bhv,bhk->bhvk",
            b_t[..., None] * (v_t - jnp.einsum("bhvk,bhk->bhv", S, k_t,
                                               precision=hi)), k_t)
        return S, jnp.einsum("bhvk,bhk->bhv", S, q_t, precision=hi)

    _, o = jax.lax.scan(token, jnp.zeros((b, nh, dv, dk), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (q, k, v, alpha, beta)),
                        unroll=8)  # eight tokens a loop turn, one by one
    o = jnp.moveaxis(o, 0, 1)                                # (b, s, nh, dv)
    gate = jax.nn.silu(mm("bse,ec->bsc", x, lp["mixer.wz"]))
    o = _rms(o, lp["mixer.norm"], z["eps"]).reshape(b, s, nh * dv) * gate
    return mm("bsc,ce->bse", o, lp["mixer.wo"])


def _block(z, precision, kind, lp, x):
    """One layer on x (rows, s, h); lp holds the layer's leaves by short
    name, in the stored type: cast up here."""
    mm = functools.partial(_mm, precision=precision)
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    mixer = _full_mixer if kind == FULL else _linear_mixer
    x = x + _rms(mixer(z, mm, lp, x), lp["norm1.scale"], z["eps"])
    m = jax.nn.silu(mm("bsh,hf->bsf", x, lp["gate.kernel"])) \
        * mm("bsh,hf->bsf", x, lp["up.kernel"])
    return x + _rms(mm("bsf,fh->bsh", m, lp["down.kernel"]),
                    lp["norm2.scale"], z["eps"])


def _embed(wte, ids):
    return wte[ids].astype(jnp.float32)


def _head(z, precision, hp, x):
    x = _rms(x, hp["norm_f.scale"].astype(jnp.float32), z["eps"])
    return _mm("bsh,hv->bsv", x, hp["head"].astype(jnp.float32), precision)


class Reference:
    """The pieces jitted once for one configuration and one precision."""

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

    def logits_at(self, params, ids, positions, pad_to=512, rows_to=128):
        """Reference logits (numpy, len(positions) x vocab) of ONE sequence
        `ids` at the given positions. The sequence is padded with token 0 to
        a multiple of `pad_to` (causal: what follows changes nothing before
        it) and the positions to a multiple of `rows_to`, so that few shapes
        compile; the rows are cut on the host."""
        import numpy as np

        n, k = len(ids), len(positions)
        buf = np.zeros((1, -(-n // pad_to) * pad_to), np.int32)
        buf[0, :n] = ids
        rows = np.zeros(-(-k // rows_to) * rows_to, np.int32)
        rows[:k] = positions
        x = self.hidden(params, jnp.asarray(buf))
        logits = self.head({k_: params[k_] for k_ in self.HEAD},
                           x[:, jnp.asarray(rows)])
        return np.asarray(logits)[0, :k]


# -- counts of operations and bytes, from shapes ---------------------------
def counts(cfg):
    """Parameter counts: all of them, and those a token's matrix products
    touch (every weight matrix of the blocks and the head; not the embedding
    table, which is gathered, nor the convolution's taps and the vectors)."""
    spec = shapes(cfg)
    size = {k: math.prod(s) for k, (s, _) in spec.items()}
    matmul = sum(n for k, n in size.items()
                 if spec[k][1] == "matrix" and k != "wte")
    return {"params": sum(size.values()), "matmul_params": matmul,
            "head_params": size["head"]}


def _kinds(z):
    return (sum(1 for t in z["layer_types"] if t == FULL),
            sum(1 for t in z["layer_types"] if t == LINEAR))


def state_ops_per_token(cfg):
    """Operations one token costs a linear layer outside its matrices: the
    state's decay-and-correct, its rank-one update and its read (6 dv dk a
    head), and the K-tap convolution."""
    z = sizes(cfg)
    return 6 * z["lin_heads"] * z["lin_dv"] * z["lin_dk"] \
        + 2 * z["conv"] * z["lin_heads"] * (2 * z["lin_dk"] + z["lin_dv"])


def forward_flops(cfg, positions, head_positions):
    """Floating-point operations the forward pass needs for tokens that sit
    at the given 0-based `positions` of their sequences: the matrices, in
    the full layers the causal scores and weighted values (a token at
    position t attends to t + 1 keys), in the linear layers the state's
    update and read, and the output head for `head_positions` of them."""
    z = sizes(cfg)
    c = counts(cfg)
    n_full, n_lin = _kinds(z)
    n = len(positions)
    body = 2 * (c["matmul_params"] - c["head_params"]) * n
    attn = 4 * z["hidden"] * n_full * sum(t + 1 for t in positions)
    state = n_lin * state_ops_per_token(cfg) * n
    return body + attn + state + 2 * c["head_params"] * head_positions


def slot_state_bytes(cfg, bytes_per_value=2):
    """Bytes ONE slot holds of recurrent state over all linear layers: the
    float32 state matrices and the convolution's last K - 1 input rows in
    the compute type."""
    z = sizes(cfg)
    nh, dk, dv = z["lin_heads"], z["lin_dk"], z["lin_dv"]
    return _kinds(z)[1] * (4 * nh * dv * dk + bytes_per_value
                           * (z["conv"] - 1) * nh * (2 * dk + dv))


def decode_step_bytes(cfg, live_positions, bytes_per_value=2):
    """Bytes one batched decode step must move: every matrix once in the
    compute type, the keys and values of each occupied slot up to its
    position in the full layers, and each occupied slot's recurrent state
    in the linear layers read and written."""
    z = sizes(cfg)
    kv = 2 * _kinds(z)[0] * z["hidden"] * sum(live_positions)
    return bytes_per_value * (counts(cfg)["matmul_params"] + kv) \
        + 2 * len(live_positions) * slot_state_bytes(cfg, bytes_per_value)
