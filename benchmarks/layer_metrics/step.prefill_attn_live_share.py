"""Share of the (query, key) pairs the prefill attention scored that the
mask admits for a real prompt row: delta sum
dynamo_engine_prefill_attn_live_pairs / delta sum
dynamo_engine_prefill_attn_scored_pairs over the window (observed at the
three prefill dispatch sites of engine.py, from what the dispatch knows
on the host: bucket, lanes, each lane's q_start and seq_len, the block).
The rest is block granularity: rows past a prompt's end inside its last
live block, and the upper triangle of the diagonal blocks. A program
that has no such counters (before PR 29) reads nothing."""

LIVE = "dynamo_engine_prefill_attn_live_pairs"
SCORED = "dynamo_engine_prefill_attn_scored_pairs"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if LIVE not in b or SCORED not in b or LIVE not in a or SCORED not in a:
        return None
    scored = b[SCORED]["sum"] - a[SCORED]["sum"]
    if scored <= 0:
        return None
    return (b[LIVE]["sum"] - a[LIVE]["sum"]) / scored * 100.0
