"""The gated delta-rule state update's share of its roofline in the traced
slice's decode steps: the least time the chip could take for one linear
layer's step over the occupied slots, over the device time of the
operations under the scope `ff.linear_attn.step`, a call being one linear
layer in one decode step (the step's program by the batcher's count of
iterations, as `decode_step_mfu` finds it). The count is of the work,
whatever kernel or fusion does it: every occupied slot's state, heads x dv
x dk float32, read once and written once, and 6 dv dk operations a head
(decay-and-correct, rank-one update, read). Bytes bound it: 0.5 operations
a byte. Silent where no operation of the slice lies under that scope."""
import statistics

from perfbench.harness import program_spans, spec

SCOPE = "ff.linear_attn.step"


def step_need(z, slots):
    """(operations, bytes) of one linear layer's decode step over `slots`
    occupied slots."""
    cells = z["lin_heads"] * z["lin_dv"] * z["lin_dk"]
    return 6 * cells * slots, 2 * 4 * cells * slots


def linear_layers(z):
    return sum(1 for t in z["layer_types"] if t == "linear_attention")


def read(facts):
    cell, traced = facts["cell"], facts["traced"]
    spans = program_spans.of(facts)
    if spans is None or not traced or not any(traced["positions"]):
        return None
    seconds = spans.scope_seconds(SCOPE)
    step = facts["trace"].program(executions=traced["iterations"])
    if not seconds or step is None:
        return None
    _, ref = spec.family(cell.config)
    z = ref.sizes(cell.config)
    slots = statistics.mean(len(ps) for ps in traced["positions"] if ps)
    flops, moved = step_need(z, slots)
    peaks = facts["peaks"]
    least = max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * step[1] * linear_layers(z) * least / seconds
