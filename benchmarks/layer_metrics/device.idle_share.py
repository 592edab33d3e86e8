"""Share of the traced span in which no op ran on the device (the chip
that was busy least, on four): 1 - union of op intervals / span."""


def read(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return (1.0 - trace["busy_s_min"] / trace["window_s"]) * 100.0
