"""Rows the window layers' decode read from the lanes' window buffers,
over the rows the window admits (``min(n, window)`` a live lane a layer a
step): delta sum ``dynamo_attn_window_rows_read`` / delta sum
``dynamo_attn_window_rows_bound`` (the host's mirrors,
``models/ssm_moe.py: decode_mirror``). 1.0 is the bound; what keeps it
above: the kernel's work list fetches a lane's buffered rows in WHOLE
chunks (a lane of 100 rows reads a 512-row chunk), and the current round's
rows are read from the ring beside them. A program without the counters:
nothing to read."""

READ = "dynamo_attn_window_rows_read"
BOUND = "dynamo_attn_window_rows_bound"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(k not in h for h in (a, b) for k in (READ, BOUND)):
        return None
    bound = b[BOUND]["sum"] - a[BOUND]["sum"]
    if bound <= 0:
        return None
    return (b[READ]["sum"] - a[READ]["sum"]) / bound
