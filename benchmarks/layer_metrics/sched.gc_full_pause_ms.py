"""The window's milliseconds inside FULL collections (generation 2) of the
interpreter's cyclic garbage collector: delta of the prof plane's
gc.pause_s[2] (telemetry/prof.py). The young generations' collections are
microseconds each; a full one walks everything the process holds."""


def read(sources):
    a = sources["before"]["prof"].get("gc")
    b = sources["after"]["prof"].get("gc")
    if a is None or b is None:
        return None
    return (b["pause_s"][2] - a["pause_s"][2]) * 1e3
