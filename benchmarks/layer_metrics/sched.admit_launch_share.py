"""Share of admission's host time spent LAUNCHING the prefill program (the
jnp.asarray uploads and the call itself): delta of the prof plane's
`admit_launch` segment over delta of the four admit segments
(telemetry/prof.py). 0.0 in a window with no admission time."""

PARTS = ("admit", "admit_pack", "admit_launch", "admit_first")


def read(sources):
    sa = sources["before"]["prof"].get("segments") or {}
    sb = sources["after"]["prof"].get("segments") or {}
    if any(p not in sa or p not in sb for p in PARTS):
        return None
    whole = sum(sb[p] - sa[p] for p in PARTS)
    if whole <= 0:
        return 0.0
    return (sb["admit_launch"] - sa["admit_launch"]) / whole * 100.0
