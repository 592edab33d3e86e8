#!/usr/bin/env python3
"""Label a device trace's idle gaps with the host segment that covers them,
or set the host's gaps between decode rounds beside the device's own.

  JAX_PLATFORMS=cpu python3 tools/trace_gaps.py <trace_dir> [--json OUT]
  JAX_PLATFORMS=cpu python3 tools/trace_gaps.py <trace_dir> --rounds

``<trace_dir>`` is what ``jax.profiler.start_trace`` was given. While a
profiler session is on, the engine's host loop wraps every attribution
segment (telemetry/prof.py) in a ``TraceAnnotation("host/<segment>")``, so
the ``/host:CPU`` plane carries the segments on the same clock as the
``/device:TPU:n`` planes. For each chip: the idle gaps are the complement
of the union of its ``XLA Ops`` intervals inside the traced window (the
same arithmetic as ``benchmarks/trace_reduce.py``, whose pure interval
functions are imported), and each gap goes to the segment whose
annotations overlap it most, or to ``unattributed`` when none does. The
empty engine's doorbell wait is ``host/idle`` on the same plane (PR 56),
so a gap for want of work reads ``idle``; and a pause of the whole
interpreter is ``pause/<kind>`` (``pause/gc``: one collection of the cyclic
collector, its generation as a stat): a gap more than half under one reads
``<segment>+<kind>``.

The window is the one ``trace_reduce`` uses, first op on ANY chip to the
last. The chips' traces do not start and stop together, so on several
chips the time before a chip's own first op and after its last reads as
idle there (``device.idle_share`` includes it). It is reported apart as
``edge_s`` and not attributed: no op was traced, which is not the same as
no op ran.

``--rounds``: while a session is on the engine also leaves two
``engine/round`` marks a fused round (telemetry/prof.py ``mark_round``): at
its dispatch (ordinal, prefill programs and padded tokens dispatched since
the round before) and at its consume (ordinal, the wall the host booked to
it: ``dynamo_engine_step_gap_seconds`` x steps; ``late=<cause>`` when the
round took twice a clean round's wall, RoundProf.judge_round). The first chip's
``jit_engine_round_seal`` modules run in dispatch order, so one offset
pairs them with the ordinals: the one at which every round starts after
its dispatch mark and ends before its consume mark. For each paired round:
the device's own time from the end of the round before to its end, split
into the modules that ran in between, the round itself and idle, beside
the host's wall for the same ordinal. The calibration of the host's gaps
against the device, taken by hand from a kept trace.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import warnings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, find_xplane, module_base, overlap,
    total, union)
from dynamo_tpu.telemetry.prof import (  # noqa: E402
    ANNOTATION_PREFIX, PAUSE_PREFIX, ROUND_ANNOTATION)

UNATTRIBUTED = "unattributed"
ROUND_MODULE = "jit_engine_round_seal"


def read_planes(path: str):
    """({chip: [(start, end)] of its ops}, {segment: [(start, end)]},
    {pause kind: [(start, end)]})."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    segments: dict[str, list] = {}
    pauses: dict[str, list] = {}
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                span = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                if device:
                    ops.setdefault(plane.name, []).append(span)
                elif ev.name.startswith(ANNOTATION_PREFIX):
                    segments.setdefault(
                        ev.name[len(ANNOTATION_PREFIX):], []).append(span)
                elif ev.name.startswith(PAUSE_PREFIX):
                    pauses.setdefault(
                        ev.name[len(PAUSE_PREFIX):], []).append(span)
    return ops, segments, pauses


def label_gaps(ops: dict[str, list], segments: dict[str, list],
               pauses: dict[str, list] | None = None) -> dict:
    """Per chip: idle seconds by the segment covering most of each gap
    (``idle``: the engine was empty), ``+<kind>`` behind it where a pause
    of that kind covers more than half the gap."""
    every = [s for spans in ops.values() for s in spans]
    if not every:
        return {"window_s": 0.0, "chips": {}}
    w0 = min(s for s, _ in every)
    w1 = max(e for _, e in every)
    covers = {seg: union(spans) for seg, spans in segments.items()}
    paused = {kind: union(spans) for kind, spans in (pauses or {}).items()}
    chips = {}
    for chip, spans in sorted(ops.items()):
        busy = union(spans)
        edge = (busy[0][0] - w0) + (w1 - busy[-1][1])
        bounds = [x for s, e in busy for x in (s, e)][1:-1]
        gaps: list[tuple[int, str]] = []      # (ns, segment)
        for g0, g1 in zip(bounds[0::2], bounds[1::2]):
            best, best_ns = UNATTRIBUTED, 0
            for seg, cover in covers.items():
                ns = overlap([(g0, g1)], cover)
                if ns > best_ns:
                    best, best_ns = seg, ns
            for kind, cover in paused.items():
                if 2 * overlap([(g0, g1)], cover) > g1 - g0:
                    best += "+" + kind
            gaps.append((g1 - g0, best))
        by_seg: dict[str, int] = {}
        for ns, seg in gaps:
            by_seg[seg] = by_seg.get(seg, 0) + ns
        idle = sum(by_seg.values())
        named = idle - by_seg.get(UNATTRIBUTED, 0)
        chips[chip] = {
            "busy_s": total(busy) / 1e9,
            "edge_s": edge / 1e9,
            "idle_s": idle / 1e9,
            "attributed_share": named / idle if idle else 1.0,
            "idle_by_segment_s": {
                k: v / 1e9 for k, v in sorted(
                    by_seg.items(), key=lambda kv: -kv[1])},
            "longest_gaps": [[seg, ns / 1e9]
                             for ns, seg in sorted(gaps, reverse=True)[:5]],
        }
    return {"window_s": (w1 - w0) / 1e9, "chips": chips,
            "annotations": {seg: len(spans)
                            for seg, spans in sorted(segments.items())},
            "pauses": {kind: [len(spans), total(union(spans)) / 1e9]
                       for kind, spans in sorted((pauses or {}).items())}}


def read_rounds(path: str):
    """(the first chip's modules [(base name, start, end)], the dispatch
    marks {ordinal: (t, programs ahead, padded tokens ahead)}, the consume
    marks {ordinal: (t, wall_ns, steps, late cause or None)})."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips = sorted(p.name for p in data.planes if DEVICE_PLANE.match(p.name))
    modules, dispatched, consumed = [], {}, {}
    for plane in data.planes:
        if chips and plane.name == chips[0]:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules += [(module_base(ev.name), int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns))
                                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name != ROUND_ANNOTATION:
                        continue
                    # nanobind's stats iterator warns of its own missing
                    # __module__; raised as an error it aborts the process
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        st = dict(ev.stats)
                    if "dispatched" in st:
                        dispatched[int(st["dispatched"])] = (
                            int(ev.start_ns), int(st["programs_ahead"]),
                            int(st["padded_tokens_ahead"]))
                    elif "consumed" in st:
                        consumed[int(st["consumed"])] = (
                            int(ev.start_ns), int(st["wall_us"]) * 1000,
                            int(st["steps"]), st.get("late"))
    return sorted(modules, key=lambda m: m[1]), dispatched, consumed


def pair_rounds(rounds: list, dispatched: dict, consumed: dict):
    """The index of the device round that ran ordinal ``first`` (the lowest
    marked one), and how many marks contradict it: a round starts after
    its dispatch mark and ends before its consume mark. Of the offsets
    that contradict the fewest marks, the LAST: with two rounds always in
    flight an offset one too low contradicts nothing either (every round
    starts after the NEXT one was dispatched), while one too high has a
    round end after the consume of the round before it."""
    if not rounds or not dispatched:
        return None, 0
    first = min(dispatched)
    best = None
    for k in range(first - max(dispatched), len(rounds)):
        pairs = bad = 0
        for o, (t_disp, _, _) in dispatched.items():
            i = k + o - first
            if not 0 <= i < len(rounds):
                continue
            pairs += 1
            bad += rounds[i][1] < t_disp or (
                o in consumed and rounds[i][2] > consumed[o][0])
        if pairs >= len(dispatched) // 2 and (
                best is None or bad <= best[0]):
            best = (bad, k)
    return (best[1], best[0]) if best else (None, 0)


def rounds_report(modules: list, dispatched: dict, consumed: dict) -> dict:
    """Per paired round and in the mean: device time between the ends of
    consecutive fused rounds by what ran in it, beside the host's wall."""
    rounds = [m for m in modules if m[0] == ROUND_MODULE]
    k, contradicted = pair_rounds(rounds, dispatched, consumed)
    rows = []
    for o in sorted(dispatched) if k is not None else ():
        i = k + o - min(dispatched)
        if not 1 <= i < len(rounds) or o not in consumed:
            continue
        (_, r0, r1), prev_end = rounds[i], rounds[i - 1][2]
        between: dict[str, int] = {}
        for name, s, e in modules:
            if prev_end <= s < r0:
                between[name] = between.get(name, 0) + (e - s)
        _, programs, padded = dispatched[o]
        _, wall_ns, steps, *late = consumed[o]
        rows.append({
            "ordinal": o, "programs_ahead": programs,
            "padded_tokens_ahead": padded, "steps": steps,
            "late": late[0] if late else None,
            "device_s": (r1 - prev_end) / 1e9, "round_s": (r1 - r0) / 1e9,
            "between_s": {n: ns / 1e9 for n, ns in sorted(between.items())},
            "idle_s": (r0 - prev_end - sum(between.values())) / 1e9,
            "host_s": wall_ns / 1e9,
        })

    def mean(sel, key):
        picked = [key(r) for r in rows if sel(r)]
        return sum(picked) / len(picked) if picked else None

    groups = {}
    for label, sel in (("all", lambda r: True),
                       ("clean", lambda r: not r["programs_ahead"]),
                       ("behind_prefill", lambda r: r["programs_ahead"])):
        names = sorted({n for r in rows if sel(r) for n in r["between_s"]})
        groups[label] = {
            "rounds": sum(1 for r in rows if sel(r)),
            "device_s": mean(sel, lambda r: r["device_s"]),
            "host_s": mean(sel, lambda r: r["host_s"]),
            "abs_diff_s": mean(
                sel, lambda r: abs(r["host_s"] - r["device_s"])),
            "round_s": mean(sel, lambda r: r["round_s"]),
            "idle_s": mean(sel, lambda r: r["idle_s"]),
            "between_s": {n: mean(sel, lambda r: r["between_s"].get(n, 0.0))
                          for n in names},
            "device_step_s": mean(
                sel, lambda r: r["device_s"] / r["steps"]),
            "host_step_s": mean(sel, lambda r: r["host_s"] / r["steps"]),
        }
    return {"device_rounds": len(rounds), "dispatch_marks": len(dispatched),
            "consume_marks": len(consumed), "offset": k,
            "marks_contradicted": contradicted, "means": groups,
            "rounds": rows}


def print_rounds(result: dict) -> None:
    print(f"{result['device_rounds']} fused rounds on the first chip, "
          f"{result['dispatch_marks']} dispatch and "
          f"{result['consume_marks']} consume marks; offset "
          f"{result['offset']}, {result['marks_contradicted']} marks "
          f"contradict it; {len(result['rounds'])} rounds paired")
    for label, g in result["means"].items():
        if not g["rounds"]:
            continue
        between = ", ".join(f"{n} {s * 1e3:.3f}"
                            for n, s in g["between_s"].items() if s)
        print(f"{label:<15} {g['rounds']:>4} rounds: device "
              f"{g['device_s'] * 1e3:.3f} ms between round ends (round "
              f"{g['round_s'] * 1e3:.3f}, idle {g['idle_s'] * 1e3:.3f}"
              f"{', ' + between if between else ''}); host "
              f"{g['host_s'] * 1e3:.3f} ms, mean |host - device| "
              f"{g['abs_diff_s'] * 1e3:.3f} ms; a step: device "
              f"{g['device_step_s'] * 1e3:.3f}, host "
              f"{g['host_step_s'] * 1e3:.3f} ms")
    for r in result["rounds"]:
        if r["late"]:
            print(f"late: round {r['ordinal']} ({r['late']}): host "
                  f"{r['host_s'] * 1e3:.3f} ms, device "
                  f"{r['device_s'] * 1e3:.3f} (round "
                  f"{r['round_s'] * 1e3:.3f}, idle {r['idle_s'] * 1e3:.3f})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--json", metavar="OUT", help="also write the table here")
    ap.add_argument("--rounds", action="store_true",
                    help="the host's gaps between decode rounds beside the "
                         "device's, round by round")
    args = ap.parse_args()
    path = find_xplane(args.trace_dir)
    result = (rounds_report(*read_rounds(path)) if args.rounds
              else label_gaps(*read_planes(path)))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    if args.rounds:
        print_rounds(result)
        return 0
    print(f"window {result['window_s']:.6f} s; host annotations: "
          f"{sum(result.get('annotations', {}).values())}; pauses: "
          + (", ".join(f"{n} {kind} {s * 1e3:.3f} ms" for kind, (n, s)
                       in result.get("pauses", {}).items()) or "none"))
    for chip, c in result["chips"].items():
        print(f"{chip}: busy {c['busy_s']:.6f} s, untraced edges "
              f"{c['edge_s']:.6f} s, idle {c['idle_s']:.6f} s, "
              f"{c['attributed_share'] * 100:.1f} % of it under a named "
              "segment")
        for seg, s in c["idle_by_segment_s"].items():
            print(f"    {seg:<18} {s:.6f} s")
        print("    longest gaps: " + ", ".join(
            f"{seg} {s * 1e3:.3f} ms" for seg, s in c["longest_gaps"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
