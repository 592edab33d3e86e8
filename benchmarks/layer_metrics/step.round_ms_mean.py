"""Mean wall time of a fused decode round, dispatch to result fetched (it
ends in a device sync): delta sum / delta count of
dynamo_engine_round_seconds over the window."""


def read(sources):
    return sources["delta_hist_mean_ms"]("dynamo_engine_round_seconds")
