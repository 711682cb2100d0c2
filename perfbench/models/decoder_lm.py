"""Decoder-only language model (GPT-2 / OPT family) built through FFModel's
public builder calls, parameterised by a configuration file.

Block, as the program's ops compute it: token embedding + learned absolute
position embedding (a constant id tensor through `embedding`, offset as the
configuration says), then per layer  x += MHA(LN(x));  x += W2 act(W1 LN(x)),
a final layer norm, an untied output head and a softmax (the program's
cross-entropy takes probabilities).

`names(cfg)` is the map from this benchmark's canonical weight names (the
ones `decoder_lm_ref.py` uses) to the program's (op name, weight name), so
weights made by the benchmark from the seed can be handed to the program.
"""
import numpy as np

from .decoder_lm_ref import sizes


def build(model, cfg, batch, seq):
    """Add the graph to `model`; returns the input id tensor."""
    from flexflow_tpu import ActiMode, AggrMode, DataType

    z = sizes(cfg)
    act = {"gelu_new": ActiMode.AC_MODE_GELU,
           "relu": ActiMode.AC_MODE_RELU}[z["activation"]]
    ids = model.create_tensor((batch, seq), DataType.DT_INT32, name="ids")
    pos = model.create_constant_tensor(
        np.broadcast_to(np.arange(seq, dtype=np.int32) + z["pos_offset"],
                        (batch, seq)))
    # every weight is replaced by the benchmark's own from the seed: the
    # program's initializers only have to be cheap
    zero = "zeros"
    x = model.add(
        model.embedding(ids, z["vocab"], z["hidden"],
                        AggrMode.AGGR_MODE_NONE, kernel_initializer=zero,
                        name="wte"),
        model.embedding(pos, z["positions"] + z["pos_offset"], z["hidden"],
                        AggrMode.AGGR_MODE_NONE, kernel_initializer=zero,
                        name="wpe"),
        name="embed_add")
    for i in range(z["layers"]):
        a = model.layer_norm(x, eps=z["eps"], name=f"h{i}.ln1")
        a = model.multihead_attention(a, a, a, z["hidden"], z["heads"],
                                      causal=True, kernel_initializer=zero,
                                      name=f"h{i}.attn")
        x = model.add(x, a, name=f"h{i}.res1")
        m = model.layer_norm(x, eps=z["eps"], name=f"h{i}.ln2")
        m = model.dense(m, z["ffn"], act, kernel_initializer=zero,
                        name=f"h{i}.fc1")
        m = model.dense(m, z["hidden"], kernel_initializer=zero,
                        name=f"h{i}.fc2")
        x = model.add(x, m, name=f"h{i}.res2")
    x = model.layer_norm(x, eps=z["eps"], name="ln_f")
    x = model.dense(x, z["vocab"], use_bias=False, kernel_initializer=zero,
                    name="head")
    model.softmax(x, name="probs")
    return ids


def names(cfg):
    """canonical leaf name -> (program op name, program weight name)."""
    out = {"wte": ("wte", "weight"), "wpe": ("wpe", "weight"),
           "ln_f.scale": ("ln_f", "scale"), "ln_f.bias": ("ln_f", "bias"),
           "head": ("head", "kernel")}
    for i in range(sizes(cfg)["layers"]):
        p = f"h{i}."
        for ln in ("ln1", "ln2"):
            out[p + ln + ".scale"] = (p + ln, "scale")
            out[p + ln + ".bias"] = (p + ln, "bias")
        for w in ("wq", "wk", "wv", "wo", "bias_o"):
            out[p + "attn." + w] = (p + "attn", w)
        for fc in ("fc1", "fc2"):
            out[p + fc + ".kernel"] = (p + fc, "kernel")
            out[p + fc + ".bias"] = (p + fc, "bias")
    return out
