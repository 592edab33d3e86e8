"""How uneven the routing of decode steps was: the most tokens one expert
received in one step of one layer (dynamo_moe_expert_load_max, mean over
the window's rounds of each round's maximum) over the mean load of a
TOUCHED expert (tokens routed / experts touched over the same rounds).
1 = every touched expert saw the same number of tokens. A program
without the counters: nothing to read."""

LOAD_MAX = "dynamo_moe_expert_load_max"
ROUTED = "dynamo_moe_tokens_routed"
TOUCHED = "dynamo_moe_experts_touched"


def read(sources):
    d = {}
    for name in (LOAD_MAX, ROUTED, TOUCHED):
        a = sources["before"]["histograms"].get(name)
        b = sources["after"]["histograms"].get(name)
        if a is None or b is None or b["count"] <= a["count"]:
            return None
        d[name] = (b["sum"] - a["sum"], b["count"] - a["count"])
    if d[TOUCHED][0] <= 0:
        return None
    mean_load = d[ROUTED][0] / d[TOUCHED][0]
    return d[LOAD_MAX][0] / d[LOAD_MAX][1] / mean_load
