"""Mean time per output token from inside the engine: delta sum / delta
count of dynamo_request_tpot_seconds, one observation a finished request
with more than one token, (last emit - first token fetched) / (tokens -
1): the engine's side of what the generator's tpot is computed from, less
the way from the engine's emit to the client's chunk."""

NAME = "dynamo_request_tpot_seconds"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) * 1e3
