"""How late the generator offered against its schedule, over the requests
due inside the window: a starved generator is not a fast server."""
from perfbench.harness.window import percentile


def read(facts):
    late = [r["offered"] - r["due"] for r in facts["requests"]
            if r["offered"] is not None and r["due"] >= facts["t_open"]]
    return 1e3 * percentile(late, 95) if late else None
