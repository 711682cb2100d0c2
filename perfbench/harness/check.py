"""What decides `correct`: each number compared stands beside its limit, and
the run is correct when every number is within its own. Limits come from the
cell's file (workloads/<cell>.json "limits"), set from readings on the chip
as PERF.md records; a number the file gives no limit is refused, not passed.
"""
import math
import statistics
import sys


class Checks:
    def __init__(self, limits):
        self.limits = limits
        self.rows = {}  # name -> {"value", "limit"}

    def add(self, name, value):
        if name not in self.limits:
            raise KeyError(f"no limit for the compared number {name!r} in "
                           "the cell's file")
        self.rows[name] = {"value": float(value),
                           "limit": float(self.limits[name])}

    @property
    def correct(self):
        return bool(self.rows) and all(
            math.isfinite(r["value"]) and r["value"] <= r["limit"]
            for r in self.rows.values())

    def report(self):
        out = sys.stderr
        for name, r in self.rows.items():
            ok = math.isfinite(r["value"]) and r["value"] <= r["limit"]
            print(f"check {name} value {r['value']:.6g} limit "
                  f"{r['limit']:.6g} {'ok' if ok else 'FAILED'}", file=out)
        print(f"correct {self.correct}", file=out, flush=True)


def worst_norm_gap(got, want, keep=None):
    """Worst leaf of |got - want| / max(want, median of want): the gap between
    two NORMS of one leaf, measured against the reference's norm of that leaf
    or of the median leaf, whichever is larger (some leaves are all but zero).
    `keep` names the leaves that count."""
    names = [k for k in want if keep is None or k in keep]
    floor = statistics.median(want[k] for k in want)
    worst, where = 0.0, None
    for k in names:
        gap = abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
        if math.isnan(gap):  # a NaN is the worst there is
            return gap, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def train_numbers(program, reference):
    """The numbers a training cell compares, from two readings of the first
    steps (`loss`: list per step; `grad`: leaf -> norm of the first gradient
    as the optimizer got it; `moved`: leaf -> norm of the parameters' change
    over the steps).

    Leaves whose reference gradient is under a thousandth of the median
    leaf's are nought to rounding and move under Adam by round-off alone:
    they stay out of `update_norm_gap` (by this rule, not by name).
    """
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], out["grad_norm_gap_leaf"] = worst_norm_gap(
        program["grad"], reference["grad"])
    floor = 1e-3 * statistics.median(reference["grad"].values())
    live = {k for k, v in reference["grad"].items() if v >= floor}
    out["update_norm_gap"], out["update_norm_gap_leaf"] = worst_norm_gap(
        program["moved"], reference["moved"], keep=live)
    return out
