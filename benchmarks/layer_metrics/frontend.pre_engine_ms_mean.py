"""Mean time a request spent before the engine took it: HTTP handler
entry -> engine intake (parse, templating, tokenizing, routing, transport),
delta sum / delta count of dynamo_request_frontend_seconds over the
window (engine.generate, from the frontend's received_unix stamp)."""

NAME = "dynamo_request_frontend_seconds"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) * 1e3
