"""Host milliseconds one admission holds the serve loop while not blocked on
the device: `admit_s` less `prefill_wait_s` (the host waiting for the
prefill's first id, `ff.serve.prefill.wait`) over `admitted`, the serve
loop's always-on counters, whole window. The page reserve, the batch-1
cache's dispatch, the step's dispatch, the fetch and the insert's eager
updates are in it. With `prefill_wait_ms` x computed prefills / `admitted`
it adds up to `admission_stall_ms`. None from a program that does not count
the prefill's wait."""


def read(facts):
    stats = facts["stats"]
    if "prefill_wait_s" not in stats or not stats.get("admitted"):
        return None
    return 1e3 * (stats["admit_s"] - stats["prefill_wait_s"]) \
        / stats["admitted"]
