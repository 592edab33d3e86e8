"""The late rounds' excess over a recent clean round's wall that is the
HOST's and not the schedule's: delta of the prof plane's late.excess_s for
the causes `gc` (the collector's seconds since the consume before) and
`host` (the segments other than `fetch` ran at least half of what was
left), in milliseconds. `behind_prefill` and `other` (the device's own)
stay out."""


def read(sources):
    a = sources["before"]["prof"].get("late")
    b = sources["after"]["prof"].get("late")
    if a is None or b is None:
        return None
    return sum(b["excess_s"][c] - a["excess_s"][c]
               for c in ("gc", "host")) * 1e3
