"""Share of the HELD routed experts (expert layers x the experts this chip
holds of each: 11 x 32 of the published 256) whose weights one decode step
read, mean over the window, in the coding-turn cell: delta sum
dynamo_moe_experts_touched / (rounds consumed x flush_every x expert
layers x num_experts, the key that counts the experts held). ~10 live
lanes x 10 picks land ~12 picks a layer on this chip's eighth, so a step
touches about a third of what it holds and the expert bytes follow
routing. The counter counts held experts only; a program without it, or a
configuration without ``mlp_only_layers``: nothing to read."""

TOUCHED = "dynamo_moe_experts_touched"


def read(sources):
    cfg = sources["config"]
    a = sources["before"]["histograms"].get(TOUCHED)
    b = sources["after"]["histograms"].get(TOUCHED)
    if (a is None or b is None or b["count"] <= a["count"]
            or "mlp_only_layers" not in cfg or "num_experts" not in cfg):
        return None
    steps = (b["count"] - a["count"]) * sources["engine_up"]["flush_every"]
    layers = cfg["num_hidden_layers"] - len(cfg["mlp_only_layers"])
    return ((b["sum"] - a["sum"])
            / (steps * layers * cfg["num_experts"]) * 100.0)
