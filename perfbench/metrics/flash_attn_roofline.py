"""The flash-attention kernels' share of their roofline in the traced slice:
the least time the chip could take for the forward and backward kernels of
every layer and step (the larger of operations over peak FLOP/s and bytes
over peak bytes/s; at these shapes operations bound it), over the summed
device time of the kernels' calls. The kernels are known by what they work
on, `bf16[batch x heads, sequence, head size]` among a Mosaic call's operands
and results. Silent when no Mosaic call ran, or none on that layout."""
from perfbench.harness import spec


def flash_need(batch, heads, seq, head_dim, bytes_per_value=2):
    """(operations, bytes) of one layer's causal forward + backward.
    Operations: the needed (unmasked) half of 2 matrix products forward
    (q k^T, p v) and 5 backward (scores again, dv, dp, dq, dk), 2 per
    multiply-add. Bytes: q, k, v in and o out forward; q, k, v, o, do in and
    dq, dk, dv out backward."""
    flops = 7 * batch * heads * seq * seq * head_dim
    moved = 12 * batch * heads * seq * head_dim * bytes_per_value
    return flops, moved


def read(facts):
    trace, cell = facts["trace"], facts["cell"]
    _, ref = spec.family(cell.config)
    z = ref.sizes(cell.config)
    batch, seq, head_dim = cell.mix["batch"], cell.mix["seq"], \
        z["hidden"] // z["heads"]
    kernel = trace.kernel("bf16[%d,%d,%d]" % (batch * z["heads"], seq,
                                              head_dim))
    if kernel is None:
        return None
    seconds, _, programs = kernel
    flops, moved = flash_need(batch, z["heads"], seq, head_dim)
    peaks = facts["peaks"]
    least = max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])
    _, steps = trace.program(names=programs)
    return 100.0 * z["layers"] * steps * least / seconds
