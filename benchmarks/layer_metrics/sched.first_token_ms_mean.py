"""Mean time from a request's first lane (where its queue wait ends) to
its first token fetched on the host: prefill device time plus the wait
behind the programs already in flight. Delta sum / delta count of
dynamo_request_first_token_seconds over the window
(engine._note_first_token). Engine TTFT = queue wait + this."""

NAME = "dynamo_request_first_token_seconds"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) * 1e3
