"""Share of the rows a dense read would take that the sparse attention
layers' decode read took: delta sum ``dynamo_sparse_attn_rows_read`` /
delta sum ``dynamo_sparse_attn_rows_live`` (the host's mirrors of
``ops/sparse_attention.py: decode_rows``). Read: the selected blocks' rows
and the ring of a lane past the switch to the selection, its own rows for
a lane below it, and the compressed keys the selection scored (every
lane's whole compressed region, in their own rows); live: the live lanes'
contexts. Under 100 % as long as the lanes stand well past ``topk x
block_size`` rows. A program without the counters: nothing to read."""

READ = "dynamo_sparse_attn_rows_read"
LIVE = "dynamo_sparse_attn_rows_live"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(k not in h for h in (a, b) for k in (READ, LIVE)):
        return None
    live = b[LIVE]["sum"] - a[LIVE]["sum"]
    if live <= 0:
        return None
    return (b[READ]["sum"] - a[READ]["sum"]) / live * 100.0
