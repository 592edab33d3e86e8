"""Share of the HELD routed experts (expert layers x the experts this chip
holds of each: 10 x 64 of the published 512, one routing group) whose
weights one decode step read, mean over the window, in the reasoning
cell: delta sum dynamo_moe_experts_touched / (rounds consumed x
flush_every x expert layers x num_local_experts). A lane lands one pick
in eight here, so 40 live lanes touch about half of the 64. The counter
counts held experts only; a program without it: nothing to read."""

TOUCHED = "dynamo_moe_experts_touched"


def read(sources):
    cfg = sources["config"]
    a = sources["before"]["histograms"].get(TOUCHED)
    b = sources["after"]["histograms"].get(TOUCHED)
    if (a is None or b is None or b["count"] <= a["count"]
            or "num_local_experts" not in cfg):
        return None
    steps = (b["count"] - a["count"]) * sources["engine_up"]["flush_every"]
    layers = cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)
    return ((b["sum"] - a["sum"])
            / (steps * layers * cfg["num_local_experts"]) * 100.0)
