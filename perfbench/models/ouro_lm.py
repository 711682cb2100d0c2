"""Ouro looped language model built through FFModel's public builder calls,
parameterised by a configuration file: the token embedding, then ONE loop
region (`FFModel.loop`) of `total_ut_steps` steps whose body is every layer
and the final RMS norm, then an untied output head and a softmax (the
program's cross-entropy takes probabilities).

Block, as the program's ops compute it (`ouro_lm_ref.py` has the equations):
x += RMSNorm(attn(RMSNorm(x))); x += RMSNorm(mlp(RMSNorm(x))), where attn is
causal `multihead_attention` with a rotary embedding over every channel of a
head, and mlp is dense x dense -> multiply -> dense. The layers are added
once and run once a step over the same weights; each step's attention keeps
keys and values of its own (the decode caches gain a step axis).

The graph's tensors are declared in the type the configuration stores its
weights in (`dtype_policy.weights`), so the program holds its weights in
that type: 2.67B parameters are 5.34 GB in bfloat16, held once.

`names(cfg)` is the map from this benchmark's canonical weight names (the
ones `ouro_lm_ref.py` uses) to the program's (op name, weight name).
"""
from .ouro_lm_ref import layer_shapes, rope_of, sizes


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

    def norm(t, name):
        return model.rms_norm(t, eps=z["eps"], name=name)

    with model.loop(z["steps"], name="ut") as ut:
        h = ut.enter(x)
        for i in range(z["layers"]):
            a = model.multihead_attention(
                *[norm(h, f"h{i}.norm1")] * 3, z["hidden"], z["heads"],
                kdim=z["head_dim"], vdim=z["head_dim"], causal=True,
                bias=False, num_kv_heads=z["kv_heads"], rope=rope_of(z),
                kernel_initializer=zero, name=f"h{i}.attn")
            h = model.add(h, norm(a, f"h{i}.norm2"), name=f"h{i}.res1")
            m = norm(h, f"h{i}.norm3")
            m = model.multiply(dense(m, z["ffn"], f"h{i}.gate",
                                     ActiMode.AC_MODE_SILU),
                               dense(m, z["ffn"], f"h{i}.up"),
                               name=f"h{i}.glu")
            m = dense(m, z["hidden"], f"h{i}.down")
            h = model.add(h, norm(m, f"h{i}.norm4"), name=f"h{i}.res2")
        x = ut.exit(norm(h, "norm_f"))
    model.softmax(dense(x, z["vocab"], "head"), name="probs")
    return ids


def names(cfg):
    """canonical leaf name -> (program op name, program weight name)."""
    z = sizes(cfg)
    out = {"wte": ("wte", "weight"), "norm_f.scale": ("norm_f", "scale"),
           "head": ("head", "kernel")}
    for i in range(z["layers"]):
        for leaf in layer_shapes(z):
            op, weight = leaf.split(".")
            out[f"h{i}.{leaf}"] = (f"h{i}.{op}", weight)
    return out
