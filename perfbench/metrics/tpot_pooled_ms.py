"""Time per output token of the requests that finished inside the window,
pooled: the sum of their (finished - first token) over the sum of their
(output tokens - 1), from the requests' own stamps. A stall inside any
request shows in it in proportion; it is no tail, which some tens of
requests to a window could not carry."""


def read(facts):
    done = [r for r in facts["requests"]
            if r["ok"] and r["out_tokens"] > 1
            and facts["t_open"] <= r["finished"] <= facts["t_close"]]
    if not done:
        return None
    return 1e3 * sum(r["finished"] - r["first_token"] for r in done) \
        / sum(r["out_tokens"] - 1 for r in done)
