"""Model FLOPs of every prompt and output token processed in the window, over
window x peak: small by nature under decode, but it bounds a claim once a
kernel has gone from the path."""
from perfbench.harness import spec
from perfbench.harness.window import decode_tokens


def read(facts):
    cell = facts["cell"]
    _, ref = spec.family(cell.config)
    flops = 0
    for r in facts["requests"]:
        p, had = r["prompt_tokens"], r["produced_at_open"]
        if had == 0 and r["produced_at_close"] > 0:
            # admitted in the window: the prompt through prefill, the head
            # at its last position
            flops += ref.forward_flops(cell.config, range(p), 1)
        n = decode_tokens(r)
        if n:  # output token j + 1 comes of feeding token j at p + j - 1
            first = p + max(had, 1) - 1
            flops += ref.forward_flops(cell.config, range(first, first + n), n)
    return 100.0 * flops / (facts["window_s"] * cell.chips
                            * facts["peaks"]["flops_bf16"])
