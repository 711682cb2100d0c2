"""Share of the prefilled positions that were padding: 1 - prompt tokens
prefilled over the power-of-two buckets they were padded to
(`prefill_tokens`, `prefill_bucket_tokens` of the serve loop's always-on
counters, whole window)."""


def read(facts):
    stats = facts["stats"]
    if not stats.get("prefill_bucket_tokens"):
        return None
    return 100.0 * (1.0 - stats["prefill_tokens"]
                    / stats["prefill_bucket_tokens"])
