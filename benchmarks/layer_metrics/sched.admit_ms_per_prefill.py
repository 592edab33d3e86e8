"""Host milliseconds of admission a prefill program dispatched: delta of the
four admit segments of the prof plane (telemetry/prof.py: `admit` the
waiting scan, lane and prefix match; `admit_pack` the dispatch's host
assembly; `admit_launch` the uploads and the program call; `admit_first`
first-token sampling, the admission patch, the seals) over delta of the
prefill dispatches (dispatch_counts prefill + prefill_batch + sp_prefill).
0.0 in a window that dispatched none."""

PARTS = ("admit", "admit_pack", "admit_launch", "admit_first")
DISPATCHES = ("prefill", "prefill_batch", "sp_prefill")


def read(sources):
    a, b = sources["before"], sources["after"]
    sa = a["prof"].get("segments") or {}
    sb = b["prof"].get("segments") or {}
    if any(p not in sa or p not in sb for p in PARTS):
        return None
    n = sum(b["dispatch_counts"].get(k, 0) - a["dispatch_counts"].get(k, 0)
            for k in DISPATCHES)
    if n <= 0:
        return 0.0
    return sum(sb[p] - sa[p] for p in PARTS) / n * 1e3
