"""The one general traffic generator. A mix is a data file under
perfbench/traffic/; nothing here knows a mix by name.

Sizes and gaps between arrivals are the distribution's own quantiles (no
sampling noise), so every seed gets the SAME set of prompt lengths, output
lengths and gaps; the seed draws their order, the token ids and the weights.
Runs of different seeds therefore do the same work in another order, with
the long and the short spread evenly through it (`spread_out`), so that the
part that falls into a window weighs much the same under every seed and
what differs between runs is the system's doing.
"""
import math

import numpy as np

_POOL = 200_000  # draws behind the quantiles of a distribution


def _draws(dist, rng):
    kind = dist["dist"]
    if kind == "lognormal":
        return rng.lognormal(math.log(dist["median"]), dist["sigma"], _POOL)
    if kind == "exponential":
        return rng.exponential(dist["mean"], _POOL)
    raise ValueError(f"unknown distribution {kind!r}: the mix that needs "
                     "another brings it")


def quantiles(dist, n):
    """The n mid-quantiles of `dist`, clipped to its min/max where given."""
    pool = np.sort(_draws(dist, np.random.RandomState(0)))
    q = pool[((np.arange(n) + 0.5) / n * _POOL).astype(int)]
    if "min" in dist or "max" in dist:
        q = np.clip(q, dist.get("min", -np.inf), dist.get("max", np.inf))
    return q


def rng_of(seed, stream):
    # --seed may exceed 32 bits
    return np.random.RandomState(
        [seed % (2 ** 32), seed // (2 ** 32), stream])


def train_batches(mix, vocab, seed):
    """ids (steps, batch, seq) and labels (steps, batch, seq, 1): the first
    `check_steps` batches feed the steps the reference follows, the other
    `steps_per_fit` feed every timed fit() call. Labels are the ids shifted
    by one; every row differs."""
    steps = mix["check_steps"] + mix["steps_per_fit"]
    ids = rng_of(seed, 1).randint(
        0, vocab, (steps, mix["batch"], mix["seq"] + 1)).astype(np.int32)
    return ids[..., :-1], ids[..., 1:, None]


def spread_out(rng, sizes, strata=8):
    """`sizes` in an order drawn from `rng` in which every run of `strata`
    consecutive ones (from the first on) holds one size out of each
    `strata`-th of the sorted sizes: the same sizes under every seed, and
    any stretch of the schedule, such as what a window admits, holds much
    the same work. (In a simulation of the batcher a plain shuffle spreads
    a saturated cell's tokens per second twice as widely between seeds:
    PERF.md, Findings.)"""
    sizes, n = np.sort(sizes), len(sizes)
    parts = [list(rng.permutation(sizes[k * n // strata:(k + 1) * n // strata]))
             for k in range(strata)]
    out = []
    while len(out) < n:
        group = [part.pop() for part in parts if part]
        out += list(rng.permutation(group))
    return np.array(out)


def serve_schedule(mix, vocab, seed, seconds):
    """Open-loop schedule for a window of `seconds`: a list of (due_s, prompt
    ids, output tokens), due times rising, 0 where the window is to open.

    The mix's `preroll` puts the start of the offered load `seconds` before
    the window, with `backlog` requests due at that start, so that the window
    opens on a server that has been under this load for a while and not on
    an empty one. From there the stream arrives at `arrival.rate_per_s`
    until the window closes."""
    arrival = mix["arrival"]
    pre = mix.get("preroll") or {"seconds": 0.0, "backlog": 0}
    span = pre["seconds"] + seconds
    stream = max(1, int(round(arrival["rate_per_s"] * span)))
    n = pre["backlog"] + stream
    gaps = quantiles(dict(arrival["gap"], mean=1.0 / arrival["rate_per_s"]),
                     stream)
    gaps = gaps * (span / gaps.sum())  # the last one closes the window
    plen = np.rint(quantiles(mix["prompt_len"], n)).astype(int)
    olen = np.rint(quantiles(mix["output_len"], n)).astype(int)
    order = rng_of(seed, 4)
    gaps = order.permutation(gaps)
    plen, olen = (spread_out(order, a) for a in (plen, olen))
    due = np.concatenate([np.zeros(pre["backlog"]),
                          np.cumsum(gaps) - gaps[0]]) - pre["seconds"]
    rng = rng_of(seed, 2)
    return [(float(due[i]), rng.randint(0, vocab, plen[i]).astype(np.int32),
             int(olen[i])) for i in range(n)]
