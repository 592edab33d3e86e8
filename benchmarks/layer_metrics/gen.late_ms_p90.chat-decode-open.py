"""How late the load generator ran in the open-loop chat-decode mix: p90
over the window's requests of (actual - due) send time, from the
generator's own clock. The mix offers nine times the chat-open mix's
request rate (some 720 requests a window), which is where a generator
that falls behind its schedule would stretch the inter-token times the
cell judges."""


def read(sources):
    return sources["gen"].get("late_ms_p90")
