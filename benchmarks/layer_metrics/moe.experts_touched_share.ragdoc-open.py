"""Share of the HELD routed experts (layers x the experts this chip holds
of each: 10 x 36 of the published 72) whose weights one decode step
read, mean over the window, in the retrieved-passages cell: delta sum
dynamo_moe_experts_touched / (rounds consumed x flush_every x layers x
num_local_experts). A lane's 10 picks land on ~5 held experts, so a
dozen live lanes already touch most of the 36. The counter counts held
experts only; a program without it: nothing to read."""

TOUCHED = "dynamo_moe_experts_touched"


def read(sources):
    cfg = sources["config"]
    a = sources["before"]["histograms"].get(TOUCHED)
    b = sources["after"]["histograms"].get(TOUCHED)
    if (a is None or b is None or b["count"] <= a["count"]
            or "num_local_experts" not in cfg):
        return None
    steps = (b["count"] - a["count"]) * sources["engine_up"]["flush_every"]
    return ((b["sum"] - a["sum"])
            / (steps * cfg["num_hidden_layers"] * cfg["num_local_experts"])
            * 100.0)
