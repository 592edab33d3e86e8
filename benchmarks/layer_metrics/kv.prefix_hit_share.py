"""Share of the window's prompt tokens served from a prefix match (HBM
pool or host tier) instead of computed: delta sum
dynamo_engine_prefill_matched_tokens / (matched + delta sum
dynamo_engine_prefill_tokens). 0 where the cell's `why` says the prefix
cache is bypassed."""

MATCHED = "dynamo_engine_prefill_matched_tokens"
COMPUTED = "dynamo_engine_prefill_tokens"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(n not in h for n in (MATCHED, COMPUTED) for h in (a, b)):
        return None
    matched = b[MATCHED]["sum"] - a[MATCHED]["sum"]
    total = matched + b[COMPUTED]["sum"] - a[COMPUTED]["sum"]
    if total <= 0:
        return None
    return matched / total * 100.0
