"""Share of the decode steps run for lanes that were live at dispatch and
still delivered nothing (steps past a request's end, in the round that
ends it and in the rounds already in flight): 1 - delta sum
dynamo_engine_round_tokens / delta sum dynamo_engine_round_live_lane_steps
over the window."""

TOKENS = "dynamo_engine_round_tokens"
LIVE = "dynamo_engine_round_live_lane_steps"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if TOKENS not in b or LIVE not in b or TOKENS not in a or LIVE not in a:
        return None
    live = b[LIVE]["sum"] - a[LIVE]["sum"]
    if live <= 0:
        return None
    return (1.0 - (b[TOKENS]["sum"] - a[TOKENS]["sum"]) / live) * 100.0
