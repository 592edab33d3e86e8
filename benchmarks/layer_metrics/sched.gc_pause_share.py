"""Share of the window inside collections of the interpreter's cyclic
garbage collector, every generation and every thread (a collection holds
the GIL, so the engine thread stands still too): delta of the sum of the
prof plane's gc.pause_s (telemetry/prof.py, the module's gc.callbacks
hook) over the wall time between the two snapshots."""


def read(sources):
    a = sources["before"]["prof"].get("gc")
    b = sources["after"]["prof"].get("gc")
    wall = sources["after"]["t_wall"] - sources["before"]["t_wall"]
    if a is None or b is None or wall <= 0:
        return None
    return (sum(b["pause_s"]) - sum(a["pause_s"])) / wall * 100.0
