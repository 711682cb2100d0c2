"""Host milliseconds blocked on the device in one computed prefill: the
seconds inside `ff.serve.prefill.wait` (`prefill_wait_s`, the serve loop's
always-on counter: the wait for the prefill's first id) over the prefills
computed in the window, `prefills` less `prefill_skips` (a prompt the memo
replays runs no prefill). None from a program that does not count it, or
with no prefill computed."""


def read(facts):
    stats = facts["stats"]
    computed = stats.get("prefills", 0) - stats.get("prefill_skips", 0)
    if "prefill_wait_s" not in stats or computed <= 0:
        return None
    return 1e3 * stats["prefill_wait_s"] / computed
