"""Host seconds of one decode iteration that are not the wait for the
device: building the step's inputs, the dispatch, the copy of the slots x
vocabulary logits, and the per-slot argmax and bookkeeping, from the serve
loop's always-on counters (`ContinuousBatcher.stats`: `decode_prepare_s` +
`decode_dispatch_s` + `decode_fetch_s` + `decode_sample_s`, the seconds
inside the `ff.serve.decode.*` spans) over the whole window's iterations."""
PARTS = ("decode_prepare_s", "decode_dispatch_s", "decode_fetch_s",
         "decode_sample_s")


def read(facts):
    stats = facts["stats"]
    if not stats.get("iterations") or any(k not in stats for k in PARTS):
        return None
    return 1e3 * sum(stats[k] for k in PARTS) / stats["iterations"]
