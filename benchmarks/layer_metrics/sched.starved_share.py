"""Share of the window in which requests were live but the engine had no
fetch in flight, so the device had nothing queued: delta of the prof
plane's starved total (telemetry/prof.py, RoundProf.mark_starved/mark_fed)
over the wall time between the two snapshots. The host's own estimate of
device.idle_share; its split by host segment is in the snapshots."""


def read(sources):
    a = sources["before"]["prof"].get("starved")
    b = sources["after"]["prof"].get("starved")
    wall = sources["after"]["t_wall"] - sources["before"]["t_wall"]
    if a is None or b is None or wall <= 0:
        return None
    return (b["total_s"] - a["total_s"]) / wall * 100.0
