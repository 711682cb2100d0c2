"""The whole batched decode step's share of the chip's peak in the traced
slice: operations the forward pass needs for the tokens one of the slice's
iterations produced, on average (the family's count: every occupied slot one
token at its own position, the head with it), over peak bf16 FLOP/s, against
the device time of a decode step (the program the slice executed once per
decode iteration, by the batcher's own count). It stands beside the
paged-decode kernel's roofline and moves the same end-to-end metric: with
the kernel gone from the path, this still bounds a claim."""
import statistics

from perfbench.harness import spec


def decode_step(facts):
    """(device seconds of one decode step, the slots' positions at each of
    the slice's iterations), or None."""
    traced = facts["traced"]
    if not traced or not any(traced["positions"]):
        return None
    step = facts["trace"].program(executions=traced["iterations"])
    if step is None:
        return None
    return step[0] / step[1], [ps for ps in traced["positions"] if ps]


def read(facts):
    cell, found = facts["cell"], decode_step(facts)
    if found is None:
        return None
    seconds, positions = found
    _, ref = spec.family(cell.config)
    flops = statistics.mean(ref.forward_flops(cell.config, ps, len(ps))
                            for ps in positions)
    return 100.0 * flops / (seconds * cell.chips
                            * facts["peaks"]["flops_bf16"])
