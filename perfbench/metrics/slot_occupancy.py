"""Tokens the decode iterations of the window produced over iterations x
slots: each iteration gives every occupied slot one token (a request's first
token comes from its prefill)."""
from perfbench.harness.window import decode_tokens


def read(facts):
    iterations = facts["stats"].get("iterations", 0)
    if not iterations:
        return None
    tokens = sum(decode_tokens(r) for r in facts["requests"])
    return 100.0 * tokens / (iterations * facts["serving"]["slots"])
