"""Host time per engine round outside the wait for the device: delta of
the prof plane's segment totals (telemetry/prof.py, the sums behind
dynamo_host_round_seconds{segment}) over delta rounds, leaving out the
`fetch` segment, which blocks on the round's result and is therefore
device time."""


def read(sources):
    a, b = sources["before"]["prof"], sources["after"]["prof"]
    rounds = b["rounds"] - a["rounds"]
    if rounds <= 0:
        return None
    host = sum(b["segments"][s] - a["segments"][s]
               for s in b["segments"] if s != "fetch")
    return host / rounds * 1e3
