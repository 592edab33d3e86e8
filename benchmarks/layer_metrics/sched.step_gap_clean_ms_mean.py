"""The same gap (sched.step_gap_ms_mean) over the rounds that had NO
prefill program dispatched since the round before them: delta sum / delta
count of dynamo_engine_step_gap_clean_seconds. The host's view of
step.decode_ms; what it reads above the device's step is host time the
round in flight did not hide."""

NAME = "dynamo_engine_step_gap_clean_seconds"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) * 1e3
