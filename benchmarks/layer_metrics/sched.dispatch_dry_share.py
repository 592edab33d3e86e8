"""Share of the model-program dispatches (fused rounds, prefills, spec
verifies) that found the device dry: delta sum / delta count of
dynamo_engine_dispatch_found_dry, which observes 1 when the newest program
dispatched before had already finished (or none had been), else 0. The
count is exact, idle arrivals included; how long the device stood dry is
bounded from above by sched.starved_share."""

NAME = "dynamo_engine_dispatch_found_dry"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) * 100.0
