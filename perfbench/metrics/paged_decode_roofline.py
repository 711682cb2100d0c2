"""The paged-decode kernel's share of its roofline in the traced slice: the
least time the chip could take to read the live keys and values of one layer
(bytes bound it: a decode query does 2 operations per value read), over the
mean device time of the kernel's calls. The kernel is known by the paged
cache it reads, `bf16[heads, pages, page size, head size]` among a Mosaic
call's operands; the live positions are those of the slice's own iterations.
Silent when no Mosaic call ran, or none on that layout: the kernel is then
absent from the served path, or no longer the one this count is of."""
import statistics

from perfbench.harness import spec


def paged_need(live_positions, hidden, bytes_per_value=2):
    """(operations, bytes) of one layer's decode attention over the live
    positions of all occupied slots: keys and values read once, q k^T and
    p v at 2 operations per value."""
    values = 2 * live_positions * hidden
    return 2 * values, values * bytes_per_value


def paged_layout(sizes, serving):
    pages = serving["slots"] * serving["max_len"] // serving["page_size"]
    return "bf16[%d,%d,%d,%d]" % (sizes["heads"], pages, serving["page_size"],
                                  sizes["hidden"] // sizes["heads"])


def read(facts):
    cell, traced = facts["cell"], facts["traced"]
    _, ref = spec.family(cell.config)
    z = ref.sizes(cell.config)
    kernel = facts["trace"].kernel(paged_layout(z, facts["serving"]))
    if kernel is None or not traced or not traced["positions"]:
        return None
    seconds, calls, _ = kernel
    live = statistics.mean(sum(p + 1 for p in ps)
                           for ps in traced["positions"])
    flops, moved = paged_need(live, z["hidden"])
    peaks = facts["peaks"]
    least = max(flops / peaks["flops_bf16"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
