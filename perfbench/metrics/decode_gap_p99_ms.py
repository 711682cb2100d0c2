"""The 99th percentile of the gap between two consecutive output tokens of
one request, pooled over all requests: the per-token stamps the program
keeps on each request (`GenerationRequest.token_t`), both stamps inside the
window. An admission stalls every slot, so it shows here where the pooled
mean hides it. Says how many gaps it pooled; None under 1,000."""
import sys

from perfbench.harness.window import percentile

MIN_GAPS = 1000


def read(facts):
    lo, hi = facts["t_open"], facts["t_close"]
    gaps, stamped = [], False
    for r in facts["requests"]:
        stamps = getattr(r["row"]["req"], "token_t", None)
        if stamps is None:
            continue
        stamped = True
        gaps += [b - a for a, b in zip(stamps, stamps[1:])
                 if lo <= a and b <= hi]
    if not stamped:
        return None
    print(f"decode_gap_p99_ms: {len(gaps)} gaps pooled", file=sys.stderr)
    return 1e3 * percentile(gaps, 99) if len(gaps) >= MIN_GAPS else None
