"""The whole training step's share of the chips' peak: operations the forward
and backward passes need per token (the family's count, causal attention's
needed half, no recomputation) x tokens per second of the window, over
chips x peak bf16 FLOP/s."""
from perfbench.harness import spec


def read(facts):
    cell = facts["cell"]
    _, ref = spec.family(cell.config)
    per_token = ref.train_flops_per_token(cell.config, cell.mix["seq"])
    rate = facts["tokens"] / facts["window_s"]
    return 100.0 * per_token * rate / (cell.chips * facts["peaks"]["flops_bf16"])
