"""Nemotron-H language model (Mamba-2, expert and grouped-query attention
layers, each ONE mixer behind its own pre-norm) built through FFModel's
public builder calls, parameterised by a configuration file.

Block, as the program's ops compute it: token embedding (no position
embedding: the Mamba-2 layers carry position), then per layer
x += mixer(RMSNorm(x)), where the mixer is `mamba2`, `expert_bank` or causal
`multihead_attention` with `num_kv_heads`, as `hybrid_override_pattern`
says; a final RMS norm, an untied output head and a softmax (the program's
cross-entropy takes probabilities).

The expert layers are told which experts this chip holds (`n_routed_experts`
of the `published` count, from `experts_held_from`): the router keeps its
published width, and what the experts elsewhere would add is left out.

The graph's tensors are declared in the type the configuration stores its
weights in (`dtype_policy.weights`), so the program holds its weights in
that type: 4.94B parameters are 9.87 GB in bfloat16.

`names(cfg)` is the map from this benchmark's canonical weight names (the
ones `nemotron_h_lm_ref.py` uses) to the program's (op name, weight name).
"""
from .nemotron_h_lm_ref import ATTENTION, MAMBA, layer_shapes, sizes


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
    for i, kind in enumerate(z["layer_types"]):
        a = model.rms_norm(x, eps=z["eps"], name=f"h{i}.norm")
        if kind == ATTENTION:
            a = model.multihead_attention(
                a, a, a, z["hidden"], z["heads"], kdim=z["head_dim"],
                vdim=z["head_dim"], causal=True, bias=False,
                num_kv_heads=z["kv_heads"], kernel_initializer=zero,
                name=f"h{i}.mixer")
        elif kind == MAMBA:
            a = model.mamba2(
                a, z["ssm_heads"], z["ssm_head_dim"], z["ssm_state"],
                n_groups=z["ssm_groups"], conv_kernel=z["conv"],
                chunk_size=z["chunk"], norm_eps=z["eps"],
                kernel_initializer=zero, name=f"h{i}.mixer")
        else:
            a = model.expert_bank(
                a, z["experts"], z["top_k"], z["expert_width"],
                held=(z["held_from"], z["held_from"] + z["held"]),
                shared_width=z["shared_width"], scale=z["route_scale"],
                norm_topk=z["norm_topk"], act="relu2",
                kernel_initializer=zero, name=f"h{i}.mixer")
        x = model.add(x, a, name=f"h{i}.res")
    x = model.rms_norm(x, eps=z["eps"], name="norm_f")
    x = model.dense(x, z["vocab"], ActiMode.AC_MODE_NONE, use_bias=False,
                    datatype=dt, kernel_initializer=zero, name="head")
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
