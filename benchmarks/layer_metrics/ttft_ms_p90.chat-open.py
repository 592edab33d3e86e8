"""90th percentile of time to first token (due time -> first streamed chunk
with text) in the open-loop chat mix, from the generator's clock in the
traced run. It was the cell's judged tail until PR 30; recorded as a
per-layer metric since, not judged: one schedule lands on one of a few
trajectories, and a set of six runs spreads by anything from 0.4 % to 16 %
of it, wider than half of any bound the contract allows (PERF.md sections
2 and 6)."""


def read(sources):
    return sources["gen"].get("ttft_ms_p90")
