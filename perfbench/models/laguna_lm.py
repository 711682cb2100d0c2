"""Laguna language model (full and sliding-window attention layers with head
counts of their own, rotary embeddings, a per-head output gate; a dense gated
MLP in layer 0 and a softmax-routed gated expert bank in every other) built
through FFModel's public builder calls, parameterised by a configuration
file.

Block, as the program's ops compute it: token embedding (no position
embedding: the rotary embedding in every attention layer carries position),
then per layer x += attn(RMSNorm(x)); x += ffn(RMSNorm(x)), where attn is
causal `multihead_attention` with `num_kv_heads`, `rope`, `head_gate` and, in
a sliding layer, `window` (its decode cache is then a ring of the window's
positions), and ffn is dense x dense -> multiply -> dense in layer 0 and
`expert_bank` (router "softmax", act "silu_gated") elsewhere; a final RMS
norm, an untied output head and a softmax (the program's cross-entropy takes
probabilities).

The expert layers are told which experts this chip holds (`num_experts` of
the `published` count, from `experts_held_from`): the router keeps its
published width, and what the experts elsewhere would add is left out.

The graph's tensors are declared in the type the configuration stores its
weights in (`dtype_policy.weights`), so the program holds its weights in
that type: 2.50B parameters are 5.01 GB in bfloat16.

`names(cfg)` is the map from this benchmark's canonical weight names (the
ones `laguna_lm_ref.py` uses) to the program's (op name, weight name).
"""
from .laguna_lm_ref import DENSE, SLIDING, layer_shapes, rope_of, sizes


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

    for i, (attn_kind, mlp, heads) in enumerate(z["layer_types"]):
        a = model.rms_norm(x, eps=z["eps"], name=f"h{i}.norm1")
        a = model.multihead_attention(
            a, a, a, z["hidden"], heads, kdim=z["head_dim"],
            vdim=z["head_dim"], causal=True, bias=False,
            num_kv_heads=z["kv_heads"], rope=rope_of(z, attn_kind),
            window=z["window"] if attn_kind == SLIDING else 0,
            head_gate=z["gating"][i] == "per_head",
            kernel_initializer=zero, name=f"h{i}.attn")
        x = model.add(x, a, name=f"h{i}.res1")
        m = model.rms_norm(x, eps=z["eps"], name=f"h{i}.norm2")
        if mlp == DENSE:
            m = model.multiply(dense(m, z["ffn"], f"h{i}.gate",
                                     ActiMode.AC_MODE_SILU),
                               dense(m, z["ffn"], f"h{i}.up"),
                               name=f"h{i}.glu")
            m = dense(m, z["hidden"], f"h{i}.down")
        else:
            m = model.expert_bank(
                m, z["experts"], z["top_k"], z["expert_width"],
                held=(z["held_from"], z["held_from"] + z["held"]),
                shared_width=z["shared_width"], scale=z["route_scale"],
                norm_topk=z["norm_topk"], act="silu_gated", router="softmax",
                kernel_initializer=zero, name=f"h{i}.moe")
        x = model.add(x, m, name=f"h{i}.res2")
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
