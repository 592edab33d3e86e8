"""Share of the token positions the prefill programs ran that held no
prompt token (bucket padding and dummy lanes of a batched group):
1 - delta sum dynamo_engine_prefill_tokens / delta sum
dynamo_engine_prefill_padded_tokens over the window (observed at the three
prefill dispatch sites of engine.py)."""

REAL = "dynamo_engine_prefill_tokens"
PADDED = "dynamo_engine_prefill_padded_tokens"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if REAL not in b or PADDED not in b or REAL not in a or PADDED not in a:
        return None
    padded = b[PADDED]["sum"] - a[PADDED]["sum"]
    if padded <= 0:
        return None
    return (1.0 - (b[REAL]["sum"] - a[REAL]["sum"]) / padded) * 100.0
