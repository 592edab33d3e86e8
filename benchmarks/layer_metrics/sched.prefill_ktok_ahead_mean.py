"""How much prefill a round that waits, waits behind: delta sum / delta
count of dynamo_engine_round_prefill_tokens_ahead (the padded prompt
positions of the prefill programs dispatched since the round before,
observed only by rounds that had some), in thousands of tokens."""

NAME = "dynamo_engine_round_prefill_tokens_ahead"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) / 1e3
