"""Host seconds one admission holds the serve loop, every slot waiting:
from the request polled off the queue to its slot filled (page reserve,
bucketed prefill, cache insert), `admit_s` over `admitted` of the serve
loop's always-on counters, whole window."""


def read(facts):
    stats = facts["stats"]
    if "admit_s" not in stats or not stats.get("admitted"):
        return None
    return 1e3 * stats["admit_s"] / stats["admitted"]
