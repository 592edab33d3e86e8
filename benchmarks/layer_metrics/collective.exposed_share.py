"""Share of the traced span a chip spent in collective ops while no other
op ran on it (worst chip)."""


def read(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("window_s") or trace.get("chips", 0) < 2:
        return None
    worst = max(c["collective_exposed_s"] for c in trace["per_chip"].values())
    return worst / trace["window_s"] * 100.0
