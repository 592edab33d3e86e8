"""Request log -> end-to-end metrics. Pure arithmetic, no JAX, no I/O.

A request's record (times in seconds relative to the window's start):
  due     when it was due to be sent (open loop) or was sent (closed)
  sent    when the generator really sent it
  chunks  arrival time of every streamed chunk that carried text
  tokens  usage.completion_tokens (None if the stream never said)
  asked   max_tokens asked for (ignore_eos: the stream must deliver it)
  ok      HTTP 200, finish_reason "length", usage tokens == asked, no
          in-band error
Requests with 0 <= due < seconds are "of the window": they make
``attempted``/``failed`` and the latency metrics. Pre-roll requests
(due < 0) only add the tokens that reach the client inside the window.

``python3 benchmarks/stats.py --selftest`` checks this file on a
synthetic log.
"""
from __future__ import annotations

import math
import sys

# The engine emits a request's first token alone (the prefill's sample)
# and the rest a fused round at a time, one HTTP chunk per emission
# (frontend/service.py writes one chunk per engine output). usage gives
# the request's total; a chunk's own count is not on the wire. So: the
# first chunk is credited FIRST_CHUNK_TOKENS, and the remaining tokens
# are spread evenly over the remaining chunks. Checked in the CPU
# rehearsal: n tokens arrive as 1 + ceil((n - 1) / flush_every) chunks.
FIRST_CHUNK_TOKENS = 1


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def of_window(rec: dict, seconds: float) -> bool:
    return 0.0 <= rec["due"] < seconds


def ttft_s(rec: dict) -> float:
    return rec["chunks"][0] - rec["due"]


def tpot_s(rec: dict) -> float | None:
    """(last chunk - first chunk) / (tokens after the first chunk)."""
    rest = rec["tokens"] - FIRST_CHUNK_TOKENS
    if rest <= 0 or len(rec["chunks"]) < 2:
        return None
    return (rec["chunks"][-1] - rec["chunks"][0]) / rest


def tokens_inside(rec: dict, seconds: float) -> float:
    """Completion tokens of one succeeded request that reached the client
    inside [0, seconds]."""
    chunks, n = rec["chunks"], rec["tokens"]
    if not chunks:
        return 0.0
    if len(chunks) == 1:
        return float(n) if 0.0 <= chunks[0] <= seconds else 0.0
    per_later = (n - FIRST_CHUNK_TOKENS) / (len(chunks) - 1)
    got = FIRST_CHUNK_TOKENS if 0.0 <= chunks[0] <= seconds else 0.0
    got += per_later * sum(1 for t in chunks[1:] if 0.0 <= t <= seconds)
    return got


def reduce_log(log: list[dict], seconds: float) -> dict:
    """Every number the harness reports from the generator's own clock."""
    mine = [r for r in log if of_window(r, seconds)]
    good = [r for r in mine if r["ok"]]
    out = {
        "attempted": len(mine),
        "failed": len(mine) - len(good),
        "tok_s": sum(tokens_inside(r, seconds)
                     for r in log if r["ok"]) / seconds,
        "late_s": [r["sent"] - r["due"] for r in mine],
    }
    ttfts = [ttft_s(r) for r in good]
    tpots = [t for t in (tpot_s(r) for r in good) if t is not None]
    if ttfts:
        out["ttft_ms_p50"] = percentile(ttfts, 0.5) * 1e3
        out["ttft_ms_p90"] = percentile(ttfts, 0.9) * 1e3
    if tpots:
        out["tpot_ms_p50"] = percentile(tpots, 0.5) * 1e3
        out["tpot_ms_p90"] = percentile(tpots, 0.9) * 1e3
    if out["late_s"]:
        out["late_ms_p90"] = percentile(out["late_s"], 0.9) * 1e3
    return out


def selftest() -> int:
    def near(a, b):
        return abs(a - b) < 1e-9

    bad = []

    def expect(name, cond):
        if not cond:
            bad.append(name)

    expect("p50 odd", near(percentile([3, 1, 2], 0.5), 2))
    expect("p90 interpolates", near(percentile(list(range(11)), 0.9), 9))
    expect("p90 of two", near(percentile([0, 10], 0.9), 9))
    # due 1.0, sent late at 1.2, first chunk at 1.5: TTFT counts from due
    a = {"due": 1.0, "sent": 1.2, "chunks": [1.5, 1.6, 1.7, 1.8],
         "tokens": 13, "asked": 13, "ok": True}
    expect("ttft from due", near(ttft_s(a), 0.5))
    # 13 tokens: 1 in the first chunk, 12 over 0.3 s
    expect("tpot", near(tpot_s(a), 0.3 / 12))
    expect("one-token request has no tpot",
           tpot_s({"chunks": [1.0], "tokens": 1}) is None)
    # window of 1.65 s: first chunk (1 token) + one later chunk (4 tokens)
    expect("tokens inside", near(tokens_inside(a, 1.65), 1 + 4))
    expect("all inside", near(tokens_inside(a, 10), 13))
    pre = {"due": -1.0, "sent": -1.0, "chunks": [-0.5, 0.5], "tokens": 9,
           "asked": 9, "ok": True}       # pre-roll: 8 tokens land inside
    failed = {"due": 2.0, "sent": 2.0, "chunks": [2.5], "tokens": None,
              "asked": 9, "ok": False}
    after = {"due": 11.0, "sent": 11.0, "chunks": [11.5], "tokens": 1,
             "asked": 1, "ok": True}     # due after the window: not counted
    r = reduce_log([a, pre, failed, after], 10.0)
    expect("attempted", r["attempted"] == 2 and r["failed"] == 1)
    expect("tok_s", near(r["tok_s"], (13 + 8) / 10.0))
    expect("ttft p50", near(r["ttft_ms_p50"], 500.0))
    expect("late p90", near(r["late_ms_p90"], 0.9 * 200.0))
    for name in bad:
        print(f"stats selftest FAILED: {name}")
    print("stats selftest:", "FAILED" if bad else "ok")
    return 1 if bad else 0


if __name__ == "__main__":
    if sys.argv[1:] == ["--selftest"]:
        sys.exit(selftest())
    sys.exit("usage: stats.py --selftest")
