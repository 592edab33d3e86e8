"""Share of the consumed decode rounds that were dispatched behind at
least one prefill program: delta count of
dynamo_engine_round_prefill_tokens_ahead (observed only by such rounds)
over delta count of dynamo_engine_step_gap_seconds (every round)."""

BEHIND = "dynamo_engine_round_prefill_tokens_ahead"
ALL = "dynamo_engine_step_gap_seconds"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(n not in h for n in (ALL, BEHIND) for h in (a, b)):
        return None
    n_all = b[ALL]["count"] - a[ALL]["count"]
    if n_all <= 0:
        return None
    return (b[BEHIND]["count"] - a[BEHIND]["count"]) / n_all * 100.0
