"""What masking costs the sparse layers' prefill: the (query, key) pairs
they computed a score for (the whole causal context in whole blocks,
masked to the selection afterwards) over the pairs the selection admits:
delta sum ``dynamo_sparse_prefill_pairs_scored`` / delta sum
``dynamo_sparse_prefill_pairs_selected``. 1 x is a prefill that gathers
the chosen blocks a query block. A program without the counters: nothing
to read."""

SCORED = "dynamo_sparse_prefill_pairs_scored"
SELECTED = "dynamo_sparse_prefill_pairs_selected"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(k not in h for h in (a, b) for k in (SCORED, SELECTED)):
        return None
    selected = b[SELECTED]["sum"] - a[SELECTED]["sum"]
    if selected <= 0:
        return None
    return (b[SCORED]["sum"] - a[SCORED]["sum"]) / selected
