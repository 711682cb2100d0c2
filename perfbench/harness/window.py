"""Window and percentile arithmetic: from stamps to end-to-end metrics."""
import math


def percentile(values, p):
    """Nearest-rank percentile: the smallest value with at least p% of the
    sample at or below it."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[max(0, math.ceil(p / 100.0 * len(vals)) - 1)]


def train_metrics(tokens, t_open, t_close, chips):
    """All tokens of all steps finished in the window over all its time."""
    return {"train_tokens_per_s_per_chip":
            tokens / (t_close - t_open) / chips}


def produced(r):
    """Output tokens request `r` produced inside the window: what it had at
    the close less what it had at the open (read from its slot while it was
    running, from its answer once finished)."""
    return r["produced_at_close"] - r["produced_at_open"]


def serve_metrics(requests, t_open, t_close):
    """`requests`: one dict per request offered up to the close. The rate is
    over ALL output tokens produced inside the window and all its time:
    those of requests that were already running when it opened and of those
    still running when it closed count with what they produced inside it. By
    whole requests it would move by a whole request whenever one ends near
    the close."""
    return {"serve_out_tokens_per_s":
            sum(produced(r) for r in requests) / (t_close - t_open)}


def decode_tokens(r):
    """Of the tokens `r` produced in the window, those a decode iteration
    gave it: a request's first token comes from its prefill."""
    return max(0, r["produced_at_close"] - max(r["produced_at_open"], 1))
