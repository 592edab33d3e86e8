"""Mean wait of a request between intake and the start of its prefill:
delta sum / delta count of dynamo_request_queue_seconds over the window
(engine._note_queue_wait). The buckets are too coarse for a percentile."""


def read(sources):
    return sources["delta_hist_mean_ms"]("dynamo_request_queue_seconds")
