"""Median time to first token in the open-loop chat mix, from the generator's
clock in the traced run. Recorded as a per-layer metric, not judged: its
spread from run to run is wider than any bound the contract allows
(PERF.md section 2)."""


def read(sources):
    return sources["gen"].get("ttft_ms_p50")
