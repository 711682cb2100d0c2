"""The Mamba-2 state update's share of its roofline in the traced slice's
decode steps: the least time the chip could take for one Mamba-2 layer's step
over the occupied slots, over the device time of the operations under the
scope `ff.ssm.step`, a call being one Mamba-2 layer in one decode step (the
step's program by the batcher's count of iterations, as `decode_step_mfu`
finds it). The count is of the work, whatever kernel or fusion does it: every
occupied slot's state, heads x head size x state size float32, read once and
written once, and 6 operations a cell (decay, rank-one update, read), as
`linear_attn_decode_roofline` counts its state. Bytes bound it: 0.75
operations a byte. Silent where no operation of the slice lies under that
scope."""
import statistics

from perfbench.harness import program_spans, spec

SCOPE = "ff.ssm.step"


def step_need(z, slots):
    """(operations, bytes) of one Mamba-2 layer's decode step over `slots`
    occupied slots."""
    cells = z["ssm_heads"] * z["ssm_head_dim"] * z["ssm_state"]
    return 6 * cells * slots, 2 * 4 * cells * slots


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
    layers = sum(1 for t in z["layer_types"] if t == "M")
    return 100.0 * step[1] * layers * least / seconds
