"""The window layers' share of the query-head rows decode attention
scored: delta sum ``dynamo_decode_attn_q_rows_window`` over that + delta
sum ``dynamo_decode_attn_q_rows_full`` (live lanes x steps x the query
heads of every layer of the kind; the host's mirrors,
``models/ssm_moe.py: decode_mirror``). With 72 heads on nine layers
against 48 on three it is 648 / 792 = 81.8 % whatever the traffic: the
head counts' own number, recorded so that a reader can set each kind's
share of the score rows beside its share of the rows read
(attn.full_rows_read_share) and of the device's time (the two kernels'
rooflines). A program without the counters: nothing to read."""

FULL = "dynamo_decode_attn_q_rows_full"
WINDOW = "dynamo_decode_attn_q_rows_window"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(k not in h for h in (a, b) for k in (FULL, WINDOW)):
        return None
    window = b[WINDOW]["sum"] - a[WINDOW]["sum"]
    total = window + b[FULL]["sum"] - a[FULL]["sum"]
    return window / total * 100.0 if total > 0 else None
