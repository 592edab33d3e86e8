"""Mean time between two decode steps as the engine's host loop sees it:
delta sum / delta count of dynamo_engine_step_gap_seconds over the window.
One observation a consumed fused round (engine._process_round): its consume
time less the later of its dispatch and the previous round's consume, over
the round's steps. While a round is always in flight the gaps x steps add
up to the wall, so this is what a live stream waits a token, prefill
programs dispatched between rounds included."""

NAME = "dynamo_engine_step_gap_seconds"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) * 1e3
