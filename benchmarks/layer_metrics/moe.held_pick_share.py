"""Of the (token, pick) pairs the router made for live lanes in decode
rounds, the share that landed on an expert held HERE: delta sum
dynamo_moe_tokens_routed (which, in a program that holds a share, counts
the picks on held experts only) / delta sum dynamo_moe_picks_routed (all
the pairs) over the window. How much of the deployment's expert work this
chip did: ~50 % for one of two chips on random weights. A program that
holds every expert has no dynamo_moe_picks_routed: nothing to read."""

HELD, ROUTED = "dynamo_moe_tokens_routed", "dynamo_moe_picks_routed"


def read(sources):
    d = {}
    for name in (HELD, ROUTED):
        a = sources["before"]["histograms"].get(name)
        b = sources["after"]["histograms"].get(name)
        if a is None or b is None or b["count"] <= a["count"]:
            return None
        d[name] = b["sum"] - a["sum"]
    if d[ROUTED] <= 0:
        return None
    return d[HELD] / d[ROUTED] * 100.0
