#!/usr/bin/env python
"""Sweep an open-loop cell's offered rate on the chip and say where its
backlog stops being bounded (PERF.md section 4: the knee; the cell then
offers 0.8 of it).

For each rate: writes ``benchmarks/cells/<cell>.json`` IN THE CHIP
MACHINE'S COPY (never committed from there), runs ``benchmarks/run.py``
once, and reads the request log it leaves. One JSON line per rate with
TTFT percentiles of the window's first and second half (a backlog that
grows through the window shows as a second half far above the first),
the tokens completed per second against the tokens the schedule asked
for, the mean number of streams decoding, the end-to-end metrics, and
``bounded``: the one rule by which a cell's knee is read (``bounded``
below; PERF.md section 4). No JAX here.

  chiprun --timeout 3000 -- python3 tools/knee_sweep.py \
      --workload mla-moe-joyai-d5.chat-decode --rates 4 6 8 10
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import stats  # noqa: E402


LEVEL = 1.5      # second-half TTFT over first-half TTFT, p50 and p90
KEEPS_UP = 0.88  # tokens completed in the window over tokens it asked for


def bounded(rec: dict) -> bool:
    """Whether the backlog stayed bounded through the window: no request
    failed, the TTFT of the requests due in the second half stands within
    ``LEVEL`` x the first half's at the median AND at p90 (a rate 5 % over
    capacity adds 1.25 s of queue by the second half, 2x and more of any
    cell's TTFT; halves of one trajectory differ by up to 0.6-1.4x), and
    the window completed ``KEEPS_UP`` of the tokens its requests asked
    for (0.90-0.97 with level TTFT, because the requests due in its last
    seconds finish after it: completion alone reads a knee one step low).
    A cell's knee is the highest swept rate that reads bounded, twice."""
    try:
        level = all(rec[f"ttft_ms_{p}_second_half"]
                    <= LEVEL * rec[f"ttft_ms_{p}_first_half"]
                    for p in ("p50", "p90"))
        return (rec["failed"] == 0 and level
                and rec["tok_s"] >= KEEPS_UP * rec["offered_tok_s"])
    except KeyError:   # a half with no finished request
        return False


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=3100000001)
    args = ap.parse_args()
    cell_file = os.path.join(REPO, "benchmarks", "cells",
                             args.workload + ".json")
    with open(cell_file) as f:
        kept = f.read()
    try:
        for i, rate in enumerate(args.rates):
            with open(cell_file, "w") as f:
                json.dump({"rate_rps": rate}, f)
            r = subprocess.run(
                [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
                 "--workload", args.workload, "--seed", str(args.seed + i),
                 "--seconds", str(args.seconds), "--trace", "0"],
                cwd=REPO, capture_output=True, text=True)
            rec = {"rate_rps": rate, "rc": r.returncode}
            if r.returncode != 0:
                rec["stderr"] = r.stderr[-1500:]
                print(json.dumps(rec), flush=True)
                continue
            line = json.loads(r.stdout.strip().splitlines()[-1])
            with open(os.path.join(REPO, "chiprun_out", "bench",
                                   args.workload, "requests.json")) as f:
                log = json.load(f)
            mine = [q for q in log if stats.of_window(q, args.seconds)
                    and q["ok"]]
            half = args.seconds / 2
            for tag, part in (("first_half", [q for q in mine
                                              if q["due"] < half]),
                              ("second_half", [q for q in mine
                                               if q["due"] >= half])):
                t = [stats.ttft_s(q) * 1e3 for q in part]
                if t:
                    rec[f"ttft_ms_p50_{tag}"] = stats.percentile(t, 0.5)
                    rec[f"ttft_ms_p90_{tag}"] = stats.percentile(t, 0.9)
            gen = stats.reduce_log(log, args.seconds)
            asked = sum(q["asked"] for q in log
                        if stats.of_window(q, args.seconds))
            # streams between their first and last chunk, averaged over
            # the window (pre-roll's included): the decode lanes live
            decoding = sum(
                max(0.0, min(q["chunks"][-1], args.seconds)
                    - max(q["chunks"][0], 0.0))
                for q in log if q["ok"] and q["chunks"]) / args.seconds
            rec.update(
                correct=line["correct"], attempted=line["attempted"],
                failed=line["failed"], offered_tok_s=asked / args.seconds,
                decoding_streams_mean=round(decoding, 2),
                tok_s=gen.get("tok_s"), tpot_ms_p50=gen.get("tpot_ms_p50"),
                tpot_ms_p90=gen.get("tpot_ms_p90"),
                ttft_ms_p90=gen.get("ttft_ms_p90"),
                late_ms_p90=gen.get("late_ms_p90"),
                last_finish_s=max((q["chunks"][-1] for q in log
                                   if q["chunks"]), default=None),
                setup_s=line["metrics"]["setup_s"]["value"],
                memory_peak_bytes=line["device"]["memory_peak_bytes"])
            rec["bounded"] = bounded(rec)
            print(json.dumps(rec), flush=True)
    finally:
        with open(cell_file, "w") as f:
            f.write(kept)
    return 0


if __name__ == "__main__":
    sys.exit(main())
