"""Of the K/V rows a decode round's attention read from the region, the
share that were the FULL layers' (rows of the context's length, whole
chunks of the live lanes' own): delta sum ``dynamo_decode_attn_rows_read``
(a layer) x the full layers, over that + delta sum
``dynamo_attn_window_rows_read`` (all window layers; the host's mirrors,
``models/ssm_moe.py: decode_mirror``). Three of twelve layers read 70-95 %
of the rows at contexts of 2k-17k: what a uniform stack's twelve would
read is four times the numerator. A configuration without
``full_attention`` layers, or a program without the counters: nothing to
read."""

FULL = "dynamo_decode_attn_rows_read"
WINDOW = "dynamo_attn_window_rows_read"


def read(sources):
    kinds = sources["config"].get("layer_types") or ()
    n_full = sum(t == "full_attention" for t in kinds)
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if not n_full or any(k not in h for h in (a, b) for k in (FULL, WINDOW)):
        return None
    full = (b[FULL]["sum"] - a[FULL]["sum"]) * n_full
    total = full + b[WINDOW]["sum"] - a[WINDOW]["sum"]
    return full / total * 100.0 if total > 0 else None
