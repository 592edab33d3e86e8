#!/usr/bin/env python3
"""Label a device trace's idle gaps with the host segment that covers them.

  JAX_PLATFORMS=cpu python3 tools/trace_gaps.py <trace_dir> [--json OUT]

``<trace_dir>`` is what ``jax.profiler.start_trace`` was given. While a
profiler session is on, the engine's host loop wraps every attribution
segment (telemetry/prof.py) in a ``TraceAnnotation("host/<segment>")``, so
the ``/host:CPU`` plane carries the segments on the same clock as the
``/device:TPU:n`` planes. For each chip: the idle gaps are the complement
of the union of its ``XLA Ops`` intervals inside the traced window (the
same arithmetic as ``benchmarks/trace_reduce.py``, whose pure interval
functions are imported), and each gap goes to the segment whose
annotations overlap it most, or to ``unattributed`` when none does.

The window is the one ``trace_reduce`` uses, first op on ANY chip to the
last. The chips' traces do not start and stop together, so on several
chips the time before a chip's own first op and after its last reads as
idle there (``device.idle_share`` includes it). It is reported apart as
``edge_s`` and not attributed: no op was traced, which is not the same as
no op ran.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, OPS_LINE, find_xplane, overlap, total, union)
from dynamo_tpu.telemetry.prof import ANNOTATION_PREFIX  # noqa: E402

UNATTRIBUTED = "unattributed"


def read_planes(path: str):
    """({chip: [(start, end)] of its ops}, {segment: [(start, end)]})."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    segments: dict[str, list] = {}
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
    return ops, segments


def label_gaps(ops: dict[str, list], segments: dict[str, list]) -> dict:
    """Per chip: idle seconds by the segment covering most of each gap."""
    every = [s for spans in ops.values() for s in spans]
    if not every:
        return {"window_s": 0.0, "chips": {}}
    w0 = min(s for s, _ in every)
    w1 = max(e for _, e in every)
    covers = {seg: union(spans) for seg, spans in segments.items()}
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
                            for seg, spans in sorted(segments.items())}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--json", metavar="OUT", help="also write the table here")
    args = ap.parse_args()
    result = label_gaps(*read_planes(find_xplane(args.trace_dir)))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    print(f"window {result['window_s']:.6f} s; host annotations: "
          f"{sum(result.get('annotations', {}).values())}")
    for chip, c in result["chips"].items():
        print(f"{chip}: busy {c['busy_s']:.6f} s, untraced edges "
              f"{c['edge_s']:.6f} s, idle {c['idle_s']:.6f} s, "
              f"{c['attributed_share'] * 100:.1f} % of it under a named "
              "segment")
        for seg, s in c["idle_by_segment_s"].items():
            print(f"    {seg:<14} {s:.6f} s")
        print("    longest gaps: " + ", ".join(
            f"{seg} {s * 1e3:.3f} ms" for seg, s in c["longest_gaps"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
