"""Hybrid language model (gated delta-rule layers among full-attention
layers, in the OLMo 2/3 block) built through FFModel's public builder calls,
parameterised by a configuration file.

Block, as the program's ops compute it: token embedding (no position
embedding: the recurrent layers carry position), then per layer
x += RMSNorm(mixer(x));  x += RMSNorm(Wdown(SiLU(Wgate x) * Wup x)),  where
the mixer is `gated_delta_net` or causal `multihead_attention` with RMS
norms on q and k, as `layer_types` says; a final RMS norm, an untied output
head and a softmax (the program's cross-entropy takes probabilities).

The graph's tensors are declared in the type the configuration stores its
weights in (`dtype_policy.weights`), so the program holds its weights in
that type: 3.27B parameters are 6.5 GB in bfloat16 and would be 13.1 GB as
float32 masters, which leaves a 16 GB chip no cache.

`names(cfg)` is the map from this benchmark's canonical weight names (the
ones `hybrid_lm_ref.py` uses) to the program's (op name, weight name).
"""
from .hybrid_lm_ref import FULL, layer_shapes, sizes


def build(model, cfg, batch, seq):
    """Add the graph to `model`; returns the input id tensor."""
    from flexflow_tpu import ActiMode, AggrMode, DataType

    z = sizes(cfg)
    dt = {"bfloat16": DataType.DT_BF16,
          "float32": DataType.DT_FLOAT}[z["weights"].name]
    ids = model.create_tensor((batch, seq), DataType.DT_INT32, name="ids")
    # every weight is replaced by the benchmark's own from the seed: the
    # program's initializers only have to be cheap
    zero = "zeros"
    x = model.embedding(ids, z["vocab"], z["hidden"], AggrMode.AGGR_MODE_NONE,
                        dtype=dt, kernel_initializer=zero, name="wte")

    def dense(t, width, name, act=ActiMode.AC_MODE_NONE):
        return model.dense(t, width, act, use_bias=False, datatype=dt,
                           kernel_initializer=zero, name=name)

    for i, kind in enumerate(z["layer_types"]):
        if kind == FULL:
            a = model.multihead_attention(
                x, x, x, z["hidden"], z["heads"], causal=True, bias=False,
                qk_norm=True, qk_norm_eps=z["eps"], kernel_initializer=zero,
                name=f"h{i}.mixer")
        else:
            a = model.gated_delta_net(
                x, z["lin_heads"], z["lin_dk"], z["lin_dv"],
                conv_kernel=z["conv"], allow_neg_eigval=z["neg_eigval"],
                norm_eps=z["eps"], kernel_initializer=zero,
                name=f"h{i}.mixer")
        x = model.add(x, model.rms_norm(a, eps=z["eps"], name=f"h{i}.norm1"),
                      name=f"h{i}.res1")
        m = model.multiply(dense(x, z["ffn"], f"h{i}.gate",
                                 ActiMode.AC_MODE_SILU),
                           dense(x, z["ffn"], f"h{i}.up"), name=f"h{i}.glu")
        m = dense(m, z["hidden"], f"h{i}.down")
        x = model.add(x, model.rms_norm(m, eps=z["eps"], name=f"h{i}.norm2"),
                      name=f"h{i}.res2")
    x = model.rms_norm(x, eps=z["eps"], name="norm_f")
    x = dense(x, z["vocab"], "head")
    model.softmax(x, name="probs")
    return ids


def names(cfg):
    """canonical leaf name -> (program op name, program weight name)."""
    z = sizes(cfg)
    out = {"wte": ("wte", "weight"), "norm_f.scale": ("norm_f", "scale"),
           "head": ("head", "kernel")}
    for i, kind in enumerate(z["layer_types"]):
        for leaf in layer_shapes(z, kind):
            op, weight = leaf.split(".")
            out[f"h{i}.{leaf}"] = (f"h{i}.{op}", weight)
    return out
