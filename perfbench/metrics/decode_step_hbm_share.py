"""Bytes one batched decode step must read (every matrix once in the compute
type + the live keys and values of the occupied slots, the family's count,
at the positions of the traced slice's own iterations) over peak HBM
bandwidth, against the device time of a decode step in that slice."""
import statistics

from perfbench.harness import spec


def read(facts):
    cell = facts["cell"]
    found = spec.module("metrics", "decode_step_mfu.py").decode_step(facts)
    if found is None:
        return None
    seconds, positions = found
    _, ref = spec.family(cell.config)
    need = statistics.mean(
        ref.decode_step_bytes(cell.config, [p + 1 for p in ps])
        for ps in positions)
    return 100.0 * need / facts["peaks"]["hbm_bytes_per_s"] / seconds
