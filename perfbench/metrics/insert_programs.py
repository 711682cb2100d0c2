"""Programs one admission's cache insert dispatches: `insert_programs` (the
per-slot leaves `parallel/decode.py` `insert_row` writes, one eager update
each) over `admitted`, the serve loop's always-on counters, whole window.
None from a program that does not count them."""


def read(facts):
    stats = facts["stats"]
    if "insert_programs" not in stats or not stats.get("admitted"):
        return None
    return stats["insert_programs"] / stats["admitted"]
