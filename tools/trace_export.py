#!/usr/bin/env python3
"""Export a request's observability data as a Perfetto/Chrome trace.

Merges up to four sources into one Trace Event Format JSON
(telemetry/timeline.py) loadable at https://ui.perfetto.dev or
chrome://tracing:

  - a /debug/trace/{request_id} span tree (frontend + worker spans,
    disagg kv chunks, spec draft/verify children)
  - a /debug/flight dump (recent engine dispatches, as instants)
  - kv_transfer stream events captured in a debug JSON payload
  - host-round segment records (``RoundProf.recent()`` rows, as the
    /debug/outliers dossier carries them)

Usage:
    python tools/trace_export.py http://HOST:PORT/debug/trace/REQ_ID \
        [--flight http://HOST:PORT/debug/flight] [-o trace.json]
    python tools/trace_export.py trace_debug.json -o trace.json
    curl -s .../debug/trace/ID | python tools/trace_export.py - -o out.json
    python tools/trace_export.py --base http://HOST:PORT --request REQ_ID
    python tools/trace_export.py --base http://HOST:PORT --outlier 0

A file/stdin source may be either a raw trace dict ({"trace_id", "spans"})
or a pre-merged bundle {"trace": ..., "flight": [...], "stream": [...],
"rounds": [[end_s, wall_s, [seg_s, ...]], ...]}.

Forensics modes (--base): ``--request <id>`` fetches the SLO-breach
dossier at /debug/outliers/<id> — already a pre-merged bundle with the
request's clipped host rounds and flight/stream events — falling back to
/debug/trace/<id> when no dossier was captured; ``--outlier <n>`` picks
the n-th most recent entry from the /debug/outliers index (0 = newest).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Optional

# tools/ runs standalone (no package install): make the repo importable
if __package__ in (None, ""):
    import os

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

from dynamo_tpu.telemetry.timeline import to_chrome_trace  # noqa: E402


def load(source: str) -> dict[str, Any]:
    if source == "-":
        return json.load(sys.stdin)
    if source.startswith(("http://", "https://")):
        from urllib.request import urlopen

        with urlopen(source, timeout=10) as resp:  # noqa: S310 — operator URL
            return json.load(resp)
    with open(source) as f:
        return json.load(f)


def build(
    doc: dict[str, Any],
    flight: Optional[list[dict[str, Any]]] = None,
) -> dict[str, Any]:
    """One source document (+ optional flight events) -> Chrome trace."""
    if "trace" in doc or "stream" in doc or "rounds" in doc:
        # pre-merged bundle
        trace = doc.get("trace") or {}
        spans = list(trace.get("spans") or [])
        label = str(trace.get("trace_id", ""))
        stream = list(doc.get("stream") or [])
        rounds = [
            (float(r[0]), float(r[1]), tuple(float(x) for x in r[2]))
            for r in doc.get("rounds") or []
        ]
        fl = list(doc.get("flight") or []) + list(flight or [])
    else:
        spans = list(doc.get("spans") or [])
        label = str(doc.get("trace_id", ""))
        stream, rounds, fl = [], [], list(flight or [])
    return to_chrome_trace(
        spans=spans, round_records=rounds, flight_events=fl,
        stream_events=stream, label=label,
    )


def resolve_forensics(
    base: str, request: Optional[str], outlier: Optional[int]
) -> dict[str, Any]:
    """Fetch a dossier bundle from a frontend/system-server ``base``
    URL: by request id (dossier first, raw trace fallback) or by index
    into the outlier ring (0 = newest)."""
    base = base.rstrip("/")
    if outlier is not None:
        index = load(f"{base}/debug/outliers")
        entries = index.get("outliers") or []
        if outlier >= len(entries):
            return {"error": f"outlier index {outlier} out of range "
                             f"({len(entries)} retained)"}
        request = entries[outlier]["request_id"]
    try:
        return load(f"{base}/debug/outliers/{request}")
    except Exception:  # noqa: BLE001 — 404s fall through to the raw trace
        return load(f"{base}/debug/trace/{request}")


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("source", nargs="?", default=None,
                    help="/debug/trace URL, JSON file, or - for stdin")
    ap.add_argument("--base", default=None,
                    help="frontend/system-server base URL for the "
                         "forensics modes (--request / --outlier)")
    ap.add_argument("--request", default=None,
                    help="with --base: export this request id's dossier "
                         "(/debug/outliers/<id>), falling back to its "
                         "raw /debug/trace")
    ap.add_argument("--outlier", type=int, default=None,
                    help="with --base: export the n-th most recent "
                         "outlier dossier (0 = newest)")
    ap.add_argument("--flight", default=None,
                    help="optional /debug/flight URL or JSON file to "
                         "merge as instant events")
    ap.add_argument("-o", "--output", default="trace.json",
                    help="output path (default trace.json); - for stdout")
    args = ap.parse_args(argv)

    if args.base is not None:
        if args.request is None and args.outlier is None:
            ap.error("--base needs --request or --outlier")
        doc = resolve_forensics(args.base, args.request, args.outlier)
    elif args.source is None:
        ap.error("a source (or --base with --request/--outlier) is "
                 "required")
        return 2  # unreachable; ap.error raises
    else:
        doc = load(args.source)
    if "error" in doc:
        print(f"error: {doc['error']}", file=sys.stderr)
        return 1
    flight = None
    if args.flight:
        fdoc = load(args.flight)
        if isinstance(fdoc, dict):
            # worker system server: {"events": [...]};
            # frontend: {"engines": {name: {"events": [...]}}}
            flight = list(fdoc.get("events") or [])
            for eng in (fdoc.get("engines") or {}).values():
                flight.extend(eng.get("events") or [])
        else:
            flight = fdoc
    chrome = build(doc, flight=flight)
    out = json.dumps(chrome)
    if args.output == "-":
        print(out)
    else:
        with open(args.output, "w") as f:
            f.write(out)
        n = len(chrome["traceEvents"])
        print(f"wrote {args.output} ({n} events) — open at "
              f"https://ui.perfetto.dev", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
