"""How late the load generator ran: p90 over the window's requests of
(actual - due) send time, from the generator's own clock."""


def read(sources):
    return sources["gen"].get("late_ms_p90")
