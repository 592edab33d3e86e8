"""Share of the region rows the latent decode attention read that were
some live lane's own context: delta sum ``dynamo_decode_attn_rows_live``
/ delta sum ``dynamo_decode_attn_rows_read`` (the host's mirror of
``ops/latent_decode.py``'s trip count: every lane reads up to the LONGEST
live lane's rows in whole 256-row chunks, dead lanes too). With contexts
of 2k-14k side by side most of what is read is masked; a kernel that
stops at each lane's own length would read this share of it. A program
without the counters: nothing to read."""

READ = "dynamo_decode_attn_rows_read"
LIVE = "dynamo_decode_attn_rows_live"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(k not in h for h in (a, b) for k in (READ, LIVE)):
        return None
    rows = b[READ]["sum"] - a[READ]["sum"]
    if rows <= 0:
        return None
    return (b[LIVE]["sum"] - a[LIVE]["sum"]) / rows * 100.0
