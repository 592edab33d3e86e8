"""Device time of one decode step: mean device duration of the fused
decode round's XLA module in the trace, over its steps (flush_every)."""

MODULE = "jit_engine_round_seal"


def read(sources):
    trace = sources.get("trace")
    if not trace or MODULE not in trace.get("modules", {}):
        return None
    m = trace["modules"][MODULE]
    return m["seconds"] / m["count"] / sources["engine_up"]["flush_every"] * 1e3
