"""Of the (token, pick) rows the expert layers of the window's prefill
programs sorted, the share their gathers into sorted order ran: delta sum
dynamo_moe_prefill_rows_moved (the row blocks that hold a pick computed
here, routed and on an expert held here, x their height, counted by the
program and read when it has finished) / delta sum
dynamo_moe_prefill_rows_sorted (positions x picks x expert layers of the
same programs) over the window. 100 % is the straight-line gather of every
row; what is left above the live share is block tails. Only programs whose
expert layers move rows in the looped form are counted (models/moe.py:
move_block): a program without the counters, or a window without such a
program, has nothing to read."""

MOVED = "dynamo_moe_prefill_rows_moved"
SORTED = "dynamo_moe_prefill_rows_sorted"


def read(sources):
    d = {}
    for name in (MOVED, SORTED):
        a = sources["before"]["histograms"].get(name)
        b = sources["after"]["histograms"].get(name)
        if a is None or b is None or b["count"] <= a["count"]:
            return None
        d[name] = b["sum"] - a["sum"]
    if d[SORTED] <= 0:
        return None
    return d[MOVED] / d[SORTED] * 100.0
