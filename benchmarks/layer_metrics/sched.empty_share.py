"""Share of the window the engine thread stood EMPTY: delta of the prof
plane's idle seconds (telemetry/prof.py, RoundProf.idle_enter/idle_exit
around the loop's doorbell wait, plus the passes end_round(record=False)
drops) over the wall time between the two snapshots. Want of work, not
want of the host: what device.idle_share cannot tell apart."""


def read(sources):
    a = sources["before"]["prof"].get("idle")
    b = sources["after"]["prof"].get("idle")
    wall = sources["after"]["t_wall"] - sources["before"]["t_wall"]
    if a is None or b is None or wall <= 0:
        return None
    return (b["total_s"] - a["total_s"]) / wall * 100.0
