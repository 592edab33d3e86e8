"""Share of the lane-steps the fused decode rounds ran that delivered a
token to a stream: delta sum dynamo_engine_round_tokens / (rounds consumed
x flush_every x the configuration's decode slots). The rest ran for empty
lanes or past a request's end."""

TOKENS = "dynamo_engine_round_tokens"


def read(sources):
    a = sources["before"]["histograms"].get(TOKENS)
    b = sources["after"]["histograms"].get(TOKENS)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    lane_steps = ((b["count"] - a["count"])
                  * sources["engine_up"]["flush_every"]
                  * sources["config"]["engine"]["max_decode_slots"])
    return (b["sum"] - a["sum"]) / lane_steps * 100.0
