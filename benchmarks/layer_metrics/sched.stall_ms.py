"""The window's milliseconds inside STALLED passes of the host loop: a pass
whose wall outside the `fetch` segment reached 0.1 s (the top edge of the
host buckets; telemetry/prof.py, RoundProf.end_round). Delta of the prof
plane's stalls.total_s: 0 in a clean window."""


def read(sources):
    a = sources["before"]["prof"].get("stalls")
    b = sources["after"]["prof"].get("stalls")
    if a is None or b is None:
        return None
    return (b["total_s"] - a["total_s"]) * 1e3
