"""Share of the window's prompt rows x layers that the prefill programs
never ran: delta sum ``dynamo_prefill_layer_rows_not_climbed`` / delta sum
``dynamo_prefill_layer_rows`` (the host's mirror, ``models/ssm_moe.py:
prefill_mirror``). A chunk's rows stop after the K/V projection of the
layer whose rows the cross layers read; only its last real row climbs the
layers above (15 of 32 here: 46.9 % less one row a chunk). 0, or nothing to
read, says the skip is off."""

RAN = "dynamo_prefill_layer_rows"
SKIPPED = "dynamo_prefill_layer_rows_not_climbed"


def read(sources):
    a, b = sources["before"]["histograms"], sources["after"]["histograms"]
    if any(k not in h for h in (a, b) for k in (RAN, SKIPPED)):
        return None
    ran = b[RAN]["sum"] - a[RAN]["sum"]
    if ran <= 0:
        return None
    return (b[SKIPPED]["sum"] - a[SKIPPED]["sum"]) / ran * 100.0
