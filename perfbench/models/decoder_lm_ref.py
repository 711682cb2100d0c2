"""Plain reference for `decoder_lm.py`: jax.numpy, float32, matrix products at
`highest` precision, no kernel, no cache, no batching. Imports nothing of the
program and takes nothing the program made: the weights come from `init`,
from the seed.

The equations (GPT-2 / OPT, pre-layernorm):
    x0      = wte[ids] + wpe[arange(s) + position_offset]
    a       = LN1(x);  q,k,v = a.wq, a.wk, a.wv  (per head, no q/k/v bias)
    x      += softmax(causal(q k^T / sqrt(d))) v . wo + bias_o
    x      += act(LN2(x) W1 + b1) W2 + b2       act = gelu(tanh) | relu
    logits  = LN_f(x) . head                     (head untied, no bias)
    loss    = mean over tokens of -log softmax(logits)[label]
Departures from the published models are those the configuration's file lists.

Everything is computed layer by layer through one small jitted function per
piece, so that a 24-layer model at full width fits beside nothing else: the
forward keeps each layer's input, the backward re-runs one layer at a time
under `jax.vjp`.

A `precision` other than "f32" is a control, not a reference: every matrix
product takes its operands rounded, to float8_e4m3 with one scale per tensor
("fp8", the step below the bfloat16 the configurations state) or to bfloat16
("bf16", the step below the float32 the rehearsal sizes state).
"""
import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest float8_e4m3fn


def sizes(cfg):
    """The published keys under one spelling, whichever family wrote them."""
    g = cfg.get
    hidden = g("n_embd") or g("hidden_size")
    return {
        "layers": g("n_layer") or g("num_hidden_layers"),
        "hidden": hidden,
        "heads": g("n_head") or g("num_attention_heads"),
        "ffn": g("n_inner") or g("ffn_dim") or 4 * hidden,
        "vocab": g("vocab_size"),
        "positions": g("n_positions") or g("max_position_embeddings"),
        "pos_offset": cfg["assumed"]["position_offset"],
        "activation": g("activation_function"),
        "eps": g("layer_norm_epsilon") or cfg["assumed"]["layer_norm_epsilon"],
    }


def shapes(cfg):
    """canonical leaf name -> (shape, kind); kind picks the initial values."""
    z = sizes(cfg)
    h, nh, f, v = z["hidden"], z["heads"], z["ffn"], z["vocab"]
    d = h // nh
    out = {"wte": ((v, h), "matrix"),
           "wpe": ((z["positions"] + z["pos_offset"], h), "matrix"),
           "ln_f.scale": ((h,), "scale"), "ln_f.bias": ((h,), "bias"),
           "head": ((h, v), "matrix")}
    for i in range(z["layers"]):
        p = f"h{i}."
        for ln in ("ln1", "ln2"):
            out[p + ln + ".scale"] = ((h,), "scale")
            out[p + ln + ".bias"] = ((h,), "bias")
        for w in ("wq", "wk", "wv"):
            out[p + "attn." + w] = ((h, nh, d), "matrix")
        out[p + "attn.wo"] = ((nh, d, h), "matrix")
        out[p + "attn.bias_o"] = ((h,), "bias")
        out[p + "fc1.kernel"] = ((h, f), "matrix")
        out[p + "fc1.bias"] = ((f,), "bias")
        out[p + "fc2.kernel"] = ((f, h), "matrix")
        out[p + "fc2.bias"] = ((h,), "bias")
    return out


def init(cfg, seed):
    """All weights from the seed in ONE jitted call, on the device, float32:
    matrices N(0, 0.02) (both models' published initializer range), layernorm
    scales 1 + N(0, 0.02), biases N(0, 0.02) so that no leaf is idle."""
    spec = shapes(cfg)
    names = sorted(spec)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            w = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
            out[name] = w + 1.0 if kind == "scale" else w
        return out

    # the seed may exceed 32 signed bits
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                   seed // (2 ** 31)))


def _quant(a):
    """a rounded to float8_e4m3 under one scale for the tensor; the gradient
    passes straight through, so the backward products see rounded operands
    and unrounded cotangents."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a + jax.lax.stop_gradient(q - a)


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _quant(a), _quant(b)
    elif precision == "bf16":
        a, b = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (a, b))
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _ln(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _act(name, x):
    if name == "relu":
        return jnp.maximum(x, 0.0)
    # gelu_new: the tanh approximation
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(z, precision, lp, x):
    """One layer on x (rows, s, h); lp holds the layer's leaves by short name."""
    mm = functools.partial(_mm, precision=precision)
    a = _ln(x, lp["ln1.scale"], lp["ln1.bias"], z["eps"])
    q = mm("bse,ehd->bhsd", a, lp["attn.wq"])
    k = mm("bse,ehd->bhsd", a, lp["attn.wk"])
    v = mm("bse,ehd->bhsd", a, lp["attn.wv"])
    s = mm("bhsd,bhtd->bhst", q, k) / math.sqrt(q.shape[-1])
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bhst,bhtd->bhsd", p, v)
    x = x + mm("bhsd,hde->bse", o, lp["attn.wo"]) + lp["attn.bias_o"]
    m = _ln(x, lp["ln2.scale"], lp["ln2.bias"], z["eps"])
    m = _act(z["activation"], mm("bsh,hf->bsf", m, lp["fc1.kernel"])
             + lp["fc1.bias"])
    return x + mm("bsf,fh->bsh", m, lp["fc2.kernel"]) + lp["fc2.bias"]


def _embed(z, ep, ids):
    pos = jnp.arange(ids.shape[-1]) + z["pos_offset"]
    return ep["wte"][ids] + ep["wpe"][pos]


def _head(z, precision, hp, x):
    x = _ln(x, hp["ln_f.scale"], hp["ln_f.bias"], z["eps"])
    return _mm("bsh,hv->bsv", x, hp["head"], precision)


def _nll_sum(z, precision, hp, x, labels):
    logp = jax.nn.log_softmax(_head(z, precision, hp, x), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


class Reference:
    """The pieces jitted once for one configuration and one precision."""

    EMBED = ("wte", "wpe")
    HEAD = ("ln_f.scale", "ln_f.bias", "head")

    def __init__(self, cfg, precision="f32"):
        z = self.z = sizes(cfg)
        self.layers = z["layers"]
        self.embed = jax.jit(functools.partial(_embed, z))
        self.block = jax.jit(functools.partial(_block, z, precision))
        self.head = jax.jit(functools.partial(_head, z, precision))
        nll = functools.partial(_nll_sum, z, precision)
        self.nll_grad = jax.jit(jax.value_and_grad(nll, argnums=(0, 1)))

        def block_vjp(lp, x, dy):
            _, vjp = jax.vjp(functools.partial(_block, z, precision), lp, x)
            return vjp(dy)

        self.block_vjp = jax.jit(block_vjp)

        def embed_vjp(ep, ids, dx):
            _, vjp = jax.vjp(lambda e: _embed(z, e, ids), ep)
            return vjp(dx)[0]

        self.embed_vjp = jax.jit(embed_vjp)

    @staticmethod
    def layer(params, i):
        p = f"h{i}."
        return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}

    def hidden(self, params, ids, keep=False):
        """Final hidden state of ids (rows, s); with keep, every layer's
        input too."""
        x = self.embed({k: params[k] for k in self.EMBED}, ids)
        kept = []
        for i in range(self.layers):
            if keep:
                kept.append(x)
            x = self.block(self.layer(params, i), x)
        return (x, kept) if keep else x

    def logits(self, params, ids):
        """Full-forward logits (rows, s, vocab)."""
        return self.head({k: params[k] for k in self.HEAD},
                         self.hidden(params, ids))

    def logits_at(self, params, ids, positions, pad_to=256):
        """Reference logits (numpy, len(positions) x vocab) of ONE sequence
        `ids` at the given positions. The sequence is padded with token 0 to
        a multiple of `pad_to` (causal: what follows changes nothing before
        it) and the positions to a multiple too, so that few shapes compile."""
        import numpy as np

        n, k = len(ids), len(positions)
        buf = np.zeros((1, min(-(-n // pad_to) * pad_to, self.z["positions"])),
                       np.int32)
        buf[0, :n] = ids
        rows = np.zeros(-(-k // pad_to) * pad_to, np.int32)
        rows[:k] = positions
        x = self.hidden(params, jnp.asarray(buf))
        logits = self.head({k_: params[k_] for k_ in self.HEAD},
                           x[:, jnp.asarray(rows)])
        return np.asarray(logits[0, :k])

    def loss_and_grads(self, params, ids, labels, rows_per_block=1):
        """Mean token loss over all rows and its gradient, computed in
        blocks of rows so that full width fits."""
        n = ids.shape[0] * ids.shape[1]
        hp = {k: params[k] for k in self.HEAD}
        ep = {k: params[k] for k in self.EMBED}
        groups = {}  # leaf-name prefix -> summed gradient of that group
        total = 0.0

        def add(prefix, g):
            groups[prefix] = _axpy(groups[prefix], g, 1.0 / n) \
                if prefix in groups else _axpy(g, g, 1.0 / n - 1.0)

        for r in range(0, ids.shape[0], rows_per_block):
            rid = ids[r:r + rows_per_block]
            x, kept = self.hidden(params, rid, keep=True)
            nll, (ghp, dx) = self.nll_grad(hp, x, labels[r:r + rows_per_block])
            total = total + nll
            add("", ghp)
            for i in reversed(range(self.layers)):
                glp, dx = self.block_vjp(self.layer(params, i), kept[i], dx)
                add(f"h{i}.", glp)
            add("embed:", self.embed_vjp(ep, rid, dx))
        grads = {p.replace("embed:", "") + k: v
                 for p, g in groups.items() for k, v in g.items()}
        return total / n, grads


@jax.jit
def _axpy(acc, g, a):
    return jax.tree_util.tree_map(lambda x, y: x + a * y, acc, g)


@jax.jit
def norms(tree):
    """Norm of every leaf."""
    return {k: jnp.linalg.norm(v) for k, v in tree.items()}


@jax.jit
def diff_norms(a, b):
    """Norm of every leaf's difference."""
    return {k: jnp.linalg.norm(a[k] - b[k]) for k in a}


@functools.partial(jax.jit, static_argnums=(3,))
def adam(params, grads, state, hyper):
    """Adam as the configurations' optimizer states it: bias-corrected step
    size, epsilon outside the square root, no weight decay."""
    alpha, b1, b2, eps = hyper
    b1t, b2t = state["b1t"] * b1, state["b2t"] * b2
    step = alpha * jnp.sqrt(1.0 - b2t) / (1.0 - b1t)
    m = {k: b1 * state["m"][k] + (1 - b1) * g for k, g in grads.items()}
    v = {k: b2 * state["v"][k] + (1 - b2) * g * g for k, g in grads.items()}
    new = {k: params[k] - step * m[k] / (jnp.sqrt(v[k]) + eps)
           for k in params}
    return new, {"m": m, "v": v, "b1t": b1t, "b2t": b2t}


def adam_init(params):
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"m": zeros, "v": dict(zeros), "b1t": jnp.float32(1.0),
            "b2t": jnp.float32(1.0)}


# -- counts of operations and bytes, from shapes ---------------------------
def counts(cfg):
    """Parameter counts: all of them, and those a token's matrix products
    touch (every weight matrix of the blocks and the head; not the embedding
    tables, which are gathered, nor the vectors)."""
    spec = shapes(cfg)
    size = {k: math.prod(s) for k, (s, _) in spec.items()}
    matmul = sum(n for k, n in size.items()
                 if len(spec[k][0]) >= 2 and k not in ("wte", "wpe"))
    return {"params": sum(size.values()), "matmul_params": matmul,
            "head_params": size["head"]}


def forward_flops(cfg, positions, head_positions):
    """Floating-point operations the forward pass needs for tokens that sit
    at the given 0-based `positions` of their sequences (a token at position
    t attends to t + 1 keys: causal work only, not the masked half), with
    the output head computed for `head_positions` of them."""
    z = sizes(cfg)
    c = counts(cfg)
    n = len(positions)
    body = 2 * (c["matmul_params"] - c["head_params"]) * n
    # scores and weighted values: 2 * 2 * keys * hidden per token and layer
    attn = 4 * z["hidden"] * z["layers"] * sum(t + 1 for t in positions)
    return body + attn + 2 * c["head_params"] * head_positions


def train_flops_per_token(cfg, seq):
    """Forward + backward (twice the forward), no recomputation, every
    position of a sequence of `seq` tokens through the head."""
    return 3 * forward_flops(cfg, range(seq), seq) / seq


def decode_step_bytes(cfg, live_positions, bytes_per_value=2):
    """Bytes one batched decode step must read: every matrix once in the
    compute type, and the keys and values of each occupied slot up to its
    position."""
    z = sizes(cfg)
    kv = 2 * z["layers"] * z["hidden"] * sum(live_positions)
    return bytes_per_value * (counts(cfg)["matmul_params"] + kv)
