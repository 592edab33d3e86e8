"""90th percentile of time to first token (due time -> first streamed chunk
with text) in the open-loop chat-decode mix, from the generator's clock in
the traced run. Recorded, not judged: an open loop's TTFT tail lands on
one of a few trajectories of its schedule (PERF.md sections 2 and 6)."""


def read(sources):
    return sources["gen"].get("ttft_ms_p90")
