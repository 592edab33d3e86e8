"""Share of the routed experts (expert layers x experts a layer) whose
weights one decode step read, mean over the window: delta sum
dynamo_moe_experts_touched / (rounds consumed x flush_every x expert
layers x n_routed_experts). What the decode step's expert streaming
costs follows this, not the batch width. A program without the counter:
nothing to read."""

TOUCHED = "dynamo_moe_experts_touched"


def read(sources):
    cfg = sources["config"]
    a = sources["before"]["histograms"].get(TOUCHED)
    b = sources["after"]["histograms"].get(TOUCHED)
    if (a is None or b is None or b["count"] <= a["count"]
            or "n_routed_experts" not in cfg):
        return None
    layers = cfg["num_hidden_layers"] - min(
        cfg.get("first_k_dense_replace", 0), cfg["num_hidden_layers"])
    steps = (b["count"] - a["count"]) * sources["engine_up"]["flush_every"]
    return ((b["sum"] - a["sum"])
            / (steps * layers * cfg["n_routed_experts"]) * 100.0)
