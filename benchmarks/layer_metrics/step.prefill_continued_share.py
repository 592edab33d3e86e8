"""Share of the window's computed prompt positions that ran in prefill
chunks CONTINUING a context already in the region (``q_start > 0``: for
a latent model, absorbed attention over the region's rows): delta sum
``dynamo_prefill_continued_tokens`` / delta sum
``dynamo_engine_prefill_tokens``. 0 in every cell whose prompts fit one
chunk; the long-document cell is the first in which chunked prefill runs.
A program without the counter: nothing to read."""

CONTINUED = "dynamo_prefill_continued_tokens"
TOKENS = "dynamo_engine_prefill_tokens"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(k not in h for h in (a, b) for k in (CONTINUED, TOKENS)):
        return None
    tokens = b[TOKENS]["sum"] - a[TOKENS]["sum"]
    if tokens <= 0:
        return None
    return (b[CONTINUED]["sum"] - a[CONTINUED]["sum"]) / tokens * 100.0
