"""Replica scheduler: share of prompt tokens served from the radix cache,
window deltas of the engines' own counters."""


def read(rec):
    c0, c1 = rec["counters"]
    prompt = c1["prefill_tokens"] - c0["prefill_tokens"]
    if prompt <= 0:
        return None
    return 100.0 * (c1["cached_tokens"] - c0["cached_tokens"]) / prompt
