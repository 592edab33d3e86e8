"""Share of the time between tokens that is not a clean decode step: with
S the delta sum and N the delta count of dynamo_engine_step_gap_seconds
and c the mean clean gap (delta mean of
dynamo_engine_step_gap_clean_seconds), (S - N x c) / S. What the rounds
that stood behind a prefill program cost over what they would have cost
clean, as a share of all the gaps: the host's view of the prefill
modules' share of the device where lanes are always live."""

ALL = "dynamo_engine_step_gap_seconds"
CLEAN = "dynamo_engine_step_gap_clean_seconds"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(n not in h for n in (ALL, CLEAN) for h in (a, b)):
        return None
    n_all = b[ALL]["count"] - a[ALL]["count"]
    n_clean = b[CLEAN]["count"] - a[CLEAN]["count"]
    s_all = b[ALL]["sum"] - a[ALL]["sum"]
    if n_all <= 0 or n_clean <= 0 or s_all <= 0:
        return None
    clean = (b[CLEAN]["sum"] - a[CLEAN]["sum"]) / n_clean
    return (s_all - n_all * clean) / s_all * 100.0
