"""Performance-attribution plane: where the engine's host milliseconds go.

This module says WHERE inside `TpuEngine._round` the host's time is
spent: it attributes every host-side slice of the serving round to a named segment with a flat current-segment switch model:
``enter(seg)`` charges the elapsed time since the previous switch to the
previous segment, so the per-round segment sums equal the measured round
wall EXACTLY (self-coverage ~1.0 by construction) and the cost per
switch is one ``time.monotonic()`` call plus a float add — cheap enough
to stay always-on.

Per-round records accumulate in a bounded per-engine ring
(:class:`RoundProf`) and fold into the process-global :data:`PROF`
registry at the engine's metrics-publish cadence (~10 Hz), which renders
``dynamo_host_round_seconds{segment=...}`` histograms, a coverage-ratio
gauge, and the SLO burn-rate gauges on all three scrape surfaces (same
pattern as the RESILIENCE / KV_TRANSFER plane registries). ``/debug/prof``
serves the live top-segment summary.

Two things ride the same switches. STARVED time: before every dispatch
of a model program the engine asks whether the newest program it
dispatched has already finished (``poll(dry)``); when it has, what each
segment ran since the poll before is charged to ``starved[segment]`` --
which host segments ran while the device had nothing queued. And, while a
``jax.profiler`` session is on, each segment is a
``TraceAnnotation("host/<segment>")`` on the profiler's own clock, so a
device trace's idle gaps can be labelled by the host segment that covers
them, and each fused round leaves an ``engine/round`` mark at its dispatch
and at its consume (``mark_round``) that names it by ordinal
(tools/trace_gaps.py).

The loop's clock closes (PR 56). Beside the recorded passes the engine
thread books its EMPTY time (``idle_enter`` / ``idle_exit`` around the
doorbell wait, and the passes ``end_round(record=False)`` drops), so
recorded wall + idle is the thread's life (``loop_coverage``). One
module-level ``gc.callbacks`` hook times the interpreter's cyclic
collector by generation, by thread and by the segment the engine thread
stood in when the collection ended: an overlay on that segment, not a
segment of its own, so the segment sums still equal the wall. A pass whose
wall outside ``fetch`` reaches ``STALL_S`` is a stall, and a consumed
fused round that took twice the last clean rounds' mean names where its excess
went (``judge_round``).
"""
from __future__ import annotations

import gc
import threading
import time
import weakref
from typing import Any, Optional

import numpy as np

from .metrics import Histogram, render_histogram

# the host-round segment enum — the contract shared by the engine's
# enter() calls, `dynamo_host_round_seconds{segment=...}`, /debug/prof
# and `totals()` (which the benchmark's `sched.host_ms_per_round` and
# `sched.starved_share` read). Order is the approximate order the
# segments run inside _round.
SEGMENTS = (
    "intake",         # _drain_intake: waiting-queue pulls
    "slot_scan",      # bounds enforcement + active/inflight slot scans
    "fetch",          # _process_entries: result fetch + token emission
    "annotate",       # _final_annotations: finishing-output assembly
    "releases",       # _apply_releases: freed-lane patches
    "transfer",       # _process_transfers + export-stream servicing
    "offload",        # _dispatch_offloads + _drain_host_ingest
    "admit",          # _admit: waiting scan, shedding, lane + prefix match
    "admit_pack",     # a prefill dispatch's host assembly: rows, mirrors
    "admit_launch",   # its jnp.asarray uploads + the prefill program call
    "admit_first",    # _finish_prefill: seals + ONE admit_first program a dispatch
    "seal_assembly",  # _take_seal_batch: seal-batch packing
    "dispatch",       # _dispatch_round: fused-round program launch
    "spec_dispatch",  # _dispatch_spec: draft + verify launches
    "seal_flush",     # _flush_seals: standalone overflow seal dispatch
    "metrics_fold",   # metrics build/publish + prof fold
    "other",          # unattributed remainder of the round
)
_SEG_INDEX = {s: i for i, s in enumerate(SEGMENTS)}
_N_SEG = len(SEGMENTS)
_OTHER = _SEG_INDEX["other"]
_FETCH = _SEG_INDEX["fetch"]
ANNOTATION_PREFIX = "host/"
_ANN_NAMES = tuple(ANNOTATION_PREFIX + s for s in SEGMENTS)
# the engine thread outside a pass: the doorbell wait of an empty engine.
# No segment (it is not host work), but a label of the same plane: the
# collector's seconds by segment and tools/trace_gaps.py both use it
IDLE = "idle"
IDLE_ANNOTATION = ANNOTATION_PREFIX + IDLE
# pauses of the whole interpreter, on the profiler's clock beside host/*
PAUSE_PREFIX = "pause/"
GC_ANNOTATION = PAUSE_PREFIX + "gc"
# a fused round's two marks in a profiler trace (mark_round): no segment,
# so outside the prefix that tools/trace_gaps.py labels idle gaps by
ROUND_ANNOTATION = "engine/round"

# host segments run at µs scale — DEFAULT_TIME_BUCKETS' 0.5 ms floor
# would flatten the whole distribution into one bucket. Same ~1.6x step
# ladder, shifted three decades down, topping out at 0.1 s (a host slice
# beyond that is a bug the +Inf bucket makes visible, and a pass that
# long outside `fetch` is booked as a stall: STALL_S).
HOST_BUCKETS = (
    0.000002, 0.000005, 0.00001, 0.00002, 0.000035, 0.00005, 0.000075,
    0.0001, 0.0002, 0.00035, 0.0005, 0.00075,
    0.001, 0.002, 0.0035, 0.005, 0.0075,
    0.01, 0.02, 0.035, 0.05, 0.1,
)

# a pass whose wall outside `fetch` reaches the top edge is a stall
STALL_S = HOST_BUCKETS[-1]
# a consumed fused round is judged against the mean wall of the last this
# many clean rounds (none is judged before that many were seen), and is
# late past this multiple of it
LATE_MIN_CLEAN = 16
LATE_FACTOR = 2.0
LATE_CAUSES = ("gc", "behind_prefill", "host", "other")

HOST_ROUND = ("dynamo_host_round_seconds",
              "host wall time per engine round by attribution segment")
COVERAGE = ("dynamo_host_round_coverage_ratio",
            "sum of attributed segment time / measured round wall "
            "(1.0 = fully attributed)")
SLO_TTFT_BURN = ("dynamo_slo_ttft_burn_rate",
                 "TTFT SLO burn rate: fraction of requests over the "
                 "target divided by the error budget (1-objective); "
                 ">1 burns budget")
SLO_ITL_BURN = ("dynamo_slo_itl_burn_rate",
                "ITL SLO burn rate: fraction of token gaps over the "
                "target divided by the error budget (1-objective); "
                ">1 burns budget")


# ---- the interpreter's cyclic collector, timed -------------------------
#
# ONE hook for the process, installed with the first RoundProf whose
# engine thread registers and removed with the last (tier-1 builds
# hundreds of engines in one process); the RoundProfs are held weakly. A
# collection runs start -> stop on one thread with the GIL held and never
# nests, so its start time is one module-level float.

_gc_profs: list = []            # weakref.ref(RoundProf), registered threads
_gc_lock = threading.RLock()    # re-entrant: a ref's callback may run
#                                 inside a collection that register began
_gc_t0 = 0.0
_gc_ann = None


def _gc_hook(phase: str, info: dict) -> None:
    global _gc_t0, _gc_ann
    if phase == "start":
        for ref in tuple(_gc_profs):
            p = ref()
            if p is not None and p._tracing:
                _gc_ann = p._annotation(
                    GC_ANNOTATION, generation=info["generation"])
                break
        _gc_t0 = time.monotonic()
        return
    dt = time.monotonic() - _gc_t0
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    tid = threading.get_ident()
    for ref in tuple(_gc_profs):
        p = ref()
        if p is not None:
            p._note_gc(info["generation"], dt, tid)


def _gc_forget(ref) -> None:
    with _gc_lock:
        if ref in _gc_profs:
            _gc_profs.remove(ref)
        if not _gc_profs and _gc_hook in gc.callbacks:
            gc.callbacks.remove(_gc_hook)


def _gc_register(prof: "RoundProf") -> None:
    with _gc_lock:
        if not any(ref() is prof for ref in _gc_profs):
            _gc_profs.append(weakref.ref(prof, _gc_forget))
        if _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)


def _gc_unregister(prof: "RoundProf") -> None:
    for ref in tuple(_gc_profs):
        if ref() is prof:
            _gc_forget(ref)


class RoundProf:
    """Per-engine round-segment accumulator (flat switch model).

    Single-writer (the engine thread); readers take snapshots of the
    totals under the GIL via plain dict/list copies — per-field tearing
    across a read is acceptable for a profiler. Always on: the
    benchmark reads ``totals()`` in every cell, so there is no off mode
    to measure in.

    Starved time is an UPPER bound of the time the device stood dry for
    want of the host. The host learns that the device ran dry only when it
    next asks (``poll``, before each dispatch of a model program), so a
    dry poll charges everything the segments ran since the poll before it
    -- after which a program was dispatched, so the device was fed then --
    although the device ran for the first part of that stretch. (A fetch
    that finds its program unfinished, or waits it out, polls too, not
    dry: the host saw the device busy there.) Patches,
    standalone seals and page movers are dispatched without a poll and
    their device time counts as dry too. A stretch that holds an
    unrecorded round (the idle spin) is dropped whole: an engine with no
    request is idle, not starved. Calibrated against the device trace's
    idle share in PERF.md.

    The empty engine's own time is ``idle``: the unrecorded passes and
    the loop's doorbell waits between them, so that recorded wall + idle
    is the engine thread's life. The collector's pauses, stalls and late
    rounds are accounts BESIDE the segments (module docstring): none of
    them takes a second out of a segment.
    """

    RING = 256  # recent per-round records kept for /debug/prof + timeline

    def __init__(self):
        self._acc = [0.0] * _N_SEG     # current round, per segment
        # per-segment sums (total + the open round) at the last poll, and
        # whether the engine spun idle since: what a dry poll charges
        self._poll_mark = np.zeros(_N_SEG)
        self._idle_since_poll = True
        self.starved_total = np.zeros(_N_SEG)
        # profiler annotations: a TraceMe takes its start time when it is
        # CONSTRUCTED, so one is made per switch, and only while a
        # profiler session is on (asked once per round)
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._tracing = False
        self._ann_open = None
        self._seg = _OTHER
        self._t = 0.0
        self._t_begin = 0.0
        self._in_round = False
        # cumulative since engine start (fold-independent, what
        # deltas of totals() read)
        self.total = np.zeros(_N_SEG)
        self.rounds = 0
        self.wall_total = 0.0
        # recent rounds live in PREALLOCATED numpy rings (metrics_fold
        # diet: end_round writes one row, no per-round tuple/list churn,
        # and the fold side reads whole columns vectorized). Record k
        # occupies row k % RING; _rec_n counts records ever written and
        # _fold_mark the count already drained — the unfolded window is
        # the (at most RING) rows between them.
        self._ring_ts = np.zeros(self.RING)       # end unix time
        self._ring_wall = np.zeros(self.RING)     # round wall seconds
        self._ring_acc = np.zeros((self.RING, _N_SEG))
        self._rec_n = 0
        self._fold_mark = 0
        # the empty engine: the doorbell waits and the dropped passes
        self.idle_total = 0.0
        self.idle_waits = 0
        # the engine thread, once its loop runs (register_thread): its
        # ident, when it started and what was booked before it did
        self._thread: Optional[int] = None
        self._t_loop0 = 0.0
        self._booked0 = 0.0
        # the collector (_note_gc, from the module's hook): by generation,
        # on the engine thread, and by the segment the engine thread stood
        # in when the collection ended (last index: idle)
        self.gc_collections = [0, 0, 0]
        self.gc_pause_s = [0.0, 0.0, 0.0]
        self.gc_on_loop_s = 0.0
        self.gc_by_segment = [0.0] * (_N_SEG + 1)
        self.gc_total_s = 0.0
        self._gc_pass_mark = 0.0       # gc_total_s at begin_round
        # stalls: passes whose wall outside fetch reached STALL_S
        self.stall_count = 0
        self.stall_total = 0.0
        self.stall_by_segment = [0.0] * _N_SEG
        # late rounds (judge_round), and the marks of the previous consume
        self.late_rounds = 0
        self.late_judged = 0
        self.late_excess = dict.fromkeys(LATE_CAUSES, 0.0)
        # the last LATE_MIN_CLEAN clean rounds' walls a step: the yardstick
        self._clean_gaps = [0.0] * LATE_MIN_CLEAN
        self._clean_n = 0
        self._consume_rec = 0
        self._consume_gc = 0.0

    # -- engine-thread hot path ----------------------------------------

    def register_thread(self) -> None:
        """The engine thread, as its loop starts: from here recorded wall
        + idle is this thread's life, and the collector is timed."""
        self._thread = threading.get_ident()
        self._t_loop0 = self._t = time.monotonic()
        self._booked0 = self.wall_total + self.idle_total
        _gc_register(self)

    def unregister_thread(self) -> None:
        """The loop has ended: the hook goes with its last RoundProf."""
        _gc_unregister(self)

    def begin_round(self) -> None:
        t = time.monotonic()
        self._acc = [0.0] * _N_SEG
        self._seg = _OTHER
        self._t = t
        self._t_begin = t
        self._in_round = True
        self._gc_pass_mark = self.gc_total_s
        self._tracing = self._annotation.is_enabled()

    def _charge(self) -> None:
        """Charge time since the last switch to the current segment."""
        t = time.monotonic()
        self._acc[self._seg] += t - self._t
        self._t = t

    def enter(self, seg: int) -> None:
        """Charge time since the last switch to the PREVIOUS segment and
        make ``seg`` (an index into SEGMENTS) current."""
        if not self._in_round:
            return
        self._charge()
        self._seg = seg
        self._close_annotation()
        if self._tracing:
            self._ann_open = self._annotation(_ANN_NAMES[seg])

    def poll(self, dry: bool) -> None:
        """The engine is about to dispatch a model program and found the
        newest one it dispatched before finished (``dry``) or still
        queued; or a fetch found its program unfinished (not dry). Dry:
        what each segment ran since the last poll goes to
        ``starved[segment]``, unless the engine spun idle in between."""
        if self._in_round:
            self._charge()
        sums = self.total + self._acc
        if dry and not self._idle_since_poll:
            self.starved_total += sums - self._poll_mark
        self._poll_mark = sums
        self._idle_since_poll = False

    def mark_round(self, **stats) -> None:
        """While a profiler session is on: one ``engine/round`` mark on
        the profiler's clock carrying ``stats`` (the round's ordinal and
        what stood ahead of it at dispatch; its ordinal and gap at
        consume), so that a kept trace sets the host's gaps beside the
        device's (tools/trace_gaps.py --rounds)."""
        if self._tracing:
            self._annotation(ROUND_ANNOTATION, **stats).__exit__(
                None, None, None)

    def push(self, seg: int) -> int:
        """Nested attribution (e.g. annotation build inside the fetch
        segment): switch to ``seg``, return the segment to restore."""
        prev = self._seg
        self.enter(seg)
        return prev

    def _close_annotation(self) -> None:
        if self._ann_open is not None:
            self._ann_open.__exit__(None, None, None)
            self._ann_open = None

    def end_round(self, record: bool = True) -> Optional[dict]:
        """Close the pass. ``record=False``: nothing was live, the pass is
        the empty engine's and its wall goes to ``idle``. Returns the
        stall's account when the pass was one (its wall outside ``fetch``
        reached ``STALL_S``), for the caller's flight recorder."""
        if not self._in_round:
            return None
        self._charge()  # close the open segment
        self._close_annotation()
        self._in_round = False
        wall = self._t - self._t_begin
        if record:
            row = self._rec_n % self.RING
            self._ring_acc[row] = self._acc
            self._ring_wall[row] = wall
            self._ring_ts[row] = time.time()
            self._rec_n += 1
            self.total += self._ring_acc[row]
            self.rounds += 1
            self.wall_total += wall
        else:
            # idle spin — keep µs no-op rounds out of the stats
            self._idle_since_poll = True
            self.idle_total += wall
        host = wall - self._acc[_FETCH]
        return self._book_stall(host) if host >= STALL_S else None

    def _book_stall(self, host: float) -> dict:
        acc = self._acc
        lead = max((i for i in range(_N_SEG) if i != _FETCH),
                   key=acc.__getitem__)
        self.stall_count += 1
        self.stall_total += host
        self.stall_by_segment[lead] += host
        return {
            "host_ms": round(host * 1e3, 3),
            "segment": SEGMENTS[lead],
            "gc_ms": round((self.gc_total_s - self._gc_pass_mark) * 1e3, 3),
            "segments_ms": {SEGMENTS[i]: round(v * 1e3, 3)
                            for i, v in enumerate(acc) if v > 0.0},
        }

    def idle_enter(self) -> None:
        """The pass found nothing to do and the loop is about to wait on
        its doorbell: while a session is on the wait is ``host/idle``."""
        if self._tracing:
            self._ann_open = self._annotation(IDLE_ANNOTATION)

    def idle_exit(self) -> None:
        """The wait is over: everything since the dropped pass closed is
        the empty engine's."""
        t = time.monotonic()
        self.idle_total += t - self._t
        self._t = t
        self.idle_waits += 1
        self._close_annotation()

    def _note_gc(self, generation: int, dt: float, thread: int) -> None:
        """One collection ended (any thread: it held the GIL). Booked to
        the segment this engine's thread stands in NOW: one that ends
        while it waits in ``fetch`` on the device cost it nothing and
        reads so. An overlay: the segment keeps the seconds too."""
        self.gc_collections[generation] += 1
        self.gc_pause_s[generation] += dt
        self.gc_total_s += dt
        if thread == self._thread:
            self.gc_on_loop_s += dt
        self.gc_by_segment[self._seg if self._in_round else _N_SEG] += dt

    def judge_round(self, wall: float, steps: int,
                    behind: bool) -> Optional[tuple]:
        """A fused round of ``steps`` steps was consumed after ``wall``
        seconds, ``behind`` prefill programs or clean. The yardstick comes
        from the run: the mean wall a step of the LAST ``LATE_MIN_CLEAN``
        clean rounds (none is judged before that many were seen; a running
        mean since the engine started would carry warm-up's compiling
        rounds into the window: 95-108 ms against 44 on the chip, PERF.md
        section 6, PR 56). Past ``LATE_FACTOR`` x expected the round is
        LATE and its excess is booked, in this order: to ``gc`` up to the
        collector's seconds since the previous consume; then to
        ``behind_prefill`` if ``behind``; else to ``host`` if the segments
        other than ``fetch`` ran, beside the collector, at least half of
        what is left since the previous consume (the passes the ring holds
        since then, taken whole); else to ``other``: the device's own.
        Returns (cause, {cause: s}, leading host segment, expected) for a
        late round: the cause is where most of the excess went. Every
        other round pays a sum of sixteen, the comparison and a store."""
        late = None
        n = self._clean_n
        if n >= LATE_MIN_CLEAN:
            self.late_judged += 1
            expected = sum(self._clean_gaps) / LATE_MIN_CLEAN * steps
            if wall > LATE_FACTOR * expected:
                late = self._book_late(wall - expected, behind) + (expected,)
        if not behind:
            self._clean_gaps[n % LATE_MIN_CLEAN] = wall / steps
            self._clean_n = n + 1
        self._consume_rec = self._rec_n
        self._consume_gc = self.gc_total_s
        return late

    def _book_late(self, excess: float, behind: bool) -> tuple:
        n = min(self._rec_n - self._consume_rec, self.RING)
        host = self._ring_acc[self._rows(n)].sum(axis=0)
        if self._in_round:
            host += self._acc
        host[_FETCH] = 0.0
        lead = SEGMENTS[int(host.argmax())]
        parts = {}
        gc_s = min(excess, self.gc_total_s - self._consume_gc)
        if gc_s > 0.0:
            parts["gc"] = gc_s
        left = excess - gc_s
        if left > 0.0:
            if behind:
                cause = "behind_prefill"
            elif float(host.sum()) - gc_s >= 0.5 * left:
                cause = "host"
            else:
                cause = "other"
            parts[cause] = left
        self.late_rounds += 1
        for cause, s in parts.items():
            self.late_excess[cause] += s
        return max(parts, key=parts.get), parts, lead

    # -- fold / read side ----------------------------------------------

    def _rows(self, n: int) -> np.ndarray:
        """Ring rows of the newest ``n`` records, oldest first."""
        return np.arange(self._rec_n - n, self._rec_n) % self.RING

    def drain_arrays(self) -> Optional[np.ndarray]:
        """Unfolded per-round segment matrix [n, N_SEG] (None if empty)
        — the vectorized-fold feed. Advances the fold mark."""
        n = min(self._rec_n - self._fold_mark, self.RING)
        self._fold_mark = self._rec_n
        if n <= 0:
            return None
        return self._ring_acc[self._rows(n)]

    def recent(self, n: int = 64) -> list[tuple]:
        n = min(n, self._rec_n, self.RING)
        return [
            (float(self._ring_ts[r]), float(self._ring_wall[r]),
             tuple(self._ring_acc[r]))
            for r in self._rows(n)
        ]

    def totals(self) -> dict[str, Any]:
        """Cumulative attribution since engine start (seconds)."""
        return {
            "rounds": self.rounds,
            "wall_s": self.wall_total,
            "segments": {
                s: float(self.total[i]) for i, s in enumerate(SEGMENTS)
            },
            "starved": {
                "total_s": float(self.starved_total.sum()),
                "segments": {
                    s: float(self.starved_total[i])
                    for i, s in enumerate(SEGMENTS)
                },
            },
            # the collector, an overlay on the segments (any thread's
            # collection holds the GIL); by the engine thread's segment
            # when each ended, `idle` for the empty engine
            "gc": {
                "collections": list(self.gc_collections),
                "pause_s": list(self.gc_pause_s),
                "on_loop_s": self.gc_on_loop_s,
                "by_segment_s": {
                    s: v for s, v in zip(SEGMENTS + (IDLE,),
                                         self.gc_by_segment) if v > 0.0},
            },
            # the empty engine: wall_s + idle.total_s is the thread's life
            "idle": {"total_s": self.idle_total, "waits": self.idle_waits},
            "loop_coverage": self.loop_coverage(),
            "stalls": {
                "count": self.stall_count,
                "total_s": self.stall_total,
                "by_segment_s": {
                    s: v for s, v in zip(SEGMENTS, self.stall_by_segment)
                    if v > 0.0},
            },
            "late": {
                "rounds": self.late_rounds,
                "judged": self.late_judged,
                "excess_s": dict(self.late_excess),
            },
        }

    def coverage(self) -> float:
        return (float(self.total.sum()) / self.wall_total
                if self.wall_total > 0 else 1.0)

    def loop_coverage(self) -> float:
        """(recorded wall + idle) / the engine thread's life up to the
        books' own last reading of the clock; what is missing is the few
        statements between two passes. 1.0 before a thread registers."""
        life = self._t - self._t_loop0
        if self._thread is None or life <= 0.0:
            return 1.0
        booked = self.wall_total + self.idle_total - self._booked0
        if self._in_round:
            booked += sum(self._acc)
        return booked / life

    def summary(self, top: int = 0) -> dict[str, Any]:
        """The /debug/prof payload: cumulative per-segment share plus a
        recent-window (ring) per-round mean, sorted hottest first."""
        totals = self.totals()
        wall = totals["wall_s"]
        n_recent = min(self._rec_n, self.RING)
        rows_idx = self._rows(n_recent)
        r_wall = float(self._ring_wall[rows_idx].sum())
        r_seg = self._ring_acc[rows_idx].sum(axis=0)
        rows = []
        for i, s in enumerate(SEGMENTS):
            tot = totals["segments"][s]
            rows.append({
                "segment": s,
                "total_s": round(tot, 6),
                "share": round(tot / wall, 4) if wall > 0 else 0.0,
                "recent_mean_us": round(
                    float(r_seg[i]) / n_recent * 1e6, 2
                ) if n_recent else 0.0,
            })
        rows.sort(key=lambda r: r["total_s"], reverse=True)
        if top:
            rows = rows[:top]
        return {
            "rounds": totals["rounds"],
            "wall_s": round(wall, 6),
            "recent_rounds": n_recent,
            "recent_wall_ms_per_round": round(
                r_wall / n_recent * 1e3, 4) if n_recent else 0.0,
            "coverage_ratio": round(self.coverage(), 4),
            "segments": rows,
            # host time up to a dispatch that found the device dry, by
            # the segment that ran, hottest first (an upper bound)
            "starved": {
                "total_s": round(totals["starved"]["total_s"], 6),
                "share": round(totals["starved"]["total_s"] / wall, 4)
                if wall > 0 else 0.0,
                "segments": {
                    s: round(v, 6) for s, v in sorted(
                        totals["starved"]["segments"].items(),
                        key=lambda kv: -kv[1]) if v > 0.0
                },
            },
            # the loop's clock closed (totals() has each to the full)
            "loop_coverage": round(totals["loop_coverage"], 4),
            **{k: _rounded(totals[k])
               for k in ("gc", "idle", "stalls", "late")},
        }


def _rounded(v: Any) -> Any:
    """A totals() subtree with its seconds rounded for display."""
    if isinstance(v, dict):
        return {k: _rounded(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_rounded(x) for x in v]
    return round(v, 6) if isinstance(v, float) else v


class ProfRegistry:
    """Process-global render surface for the attribution plane: one
    ``dynamo_host_round_seconds`` histogram per segment plus the
    coverage and SLO burn-rate gauges. Appended to all three scrape
    surfaces exactly like the RESILIENCE / KV_TRANSFER registries —
    live in engine processes, zeros elsewhere."""

    def __init__(self) -> None:
        self._hists = {
            s: Histogram(HOST_ROUND[0], HOST_ROUND[1], HOST_BUCKETS)
            for s in SEGMENTS
        }
        self._lock = threading.Lock()
        self._coverage = 1.0
        self._burn = {"ttft": 0.0, "itl": 0.0}
        # SLO targets (EngineConfig/RuntimeConfig slo_* knobs); engines
        # and frontends configure() at init so scrape-time refreshes use
        # the deployed targets
        self.ttft_target_s = 0.5
        self.itl_target_s = 0.05
        self.objective = 0.99

    def configure(
        self,
        ttft_target_s: float,
        itl_target_s: float,
        objective: float,
    ) -> None:
        with self._lock:
            self.ttft_target_s = ttft_target_s
            self.itl_target_s = itl_target_s
            self.objective = objective

    def fold(self, prof: RoundProf) -> None:
        """Drain a RoundProf's unfolded rounds into the histograms —
        called from the engine thread inside the metrics_fold segment, at
        the publish cadence rather than per round. Vectorized: one
        observe_many per segment COLUMN of the drained [n, N_SEG] matrix
        instead of a Python observe per (round, segment) cell."""
        accs = prof.drain_arrays()
        if accs is not None:
            hists = self._hists
            for i, s in enumerate(SEGMENTS):
                col = accs[:, i]
                hists[s].observe_many(col[col > 0.0])
        with self._lock:
            self._coverage = prof.coverage()

    def fold_burn_rates(
        self,
        ttft_snap: Optional[dict[str, Any]],
        itl_snap: Optional[dict[str, Any]],
        ttft_target_s: Optional[float] = None,
        itl_target_s: Optional[float] = None,
        objective: Optional[float] = None,
    ) -> dict[str, float]:
        """Recompute the SLO burn-rate gauges from live TTFT/ITL
        histogram snapshots. Burn rate = (fraction of observations over
        the target) / (1 - objective): 1.0 means the error budget is
        being consumed exactly at the sustainable rate, >1 faster.
        Targets default to the configure()d ones."""
        with self._lock:
            if ttft_target_s is None:
                ttft_target_s = self.ttft_target_s
            if itl_target_s is None:
                itl_target_s = self.itl_target_s
            if objective is None:
                objective = self.objective
        budget = max(1.0 - objective, 1e-9)
        burn = {
            "ttft": frac_over_target(ttft_snap, ttft_target_s) / budget,
            "itl": frac_over_target(itl_snap, itl_target_s) / budget,
        }
        with self._lock:
            self._burn = burn
        return burn

    def burn_rates(self) -> dict[str, float]:
        with self._lock:
            return dict(self._burn)

    def coverage_ratio(self) -> float:
        with self._lock:
            return self._coverage

    def snapshot(self) -> dict[str, dict[str, Any]]:
        return {s: h.snapshot() for s, h in self._hists.items()}

    def reset(self) -> None:
        for h in self._hists.values():
            h.reset()
        with self._lock:
            self._coverage = 1.0
            self._burn = {"ttft": 0.0, "itl": 0.0}

    def render(self) -> str:
        lines: list[str] = []
        for i, s in enumerate(SEGMENTS):
            seg_lines = render_histogram(
                HOST_ROUND[0], HOST_ROUND[1],
                self._hists[s].snapshot(), label=f'segment="{s}"',
            )
            # one HELP/TYPE head for the family; later segments drop it
            lines.extend(seg_lines if i == 0 else seg_lines[2:])
        with self._lock:
            cov, burn = self._coverage, dict(self._burn)
        for (name, help_), v in (
            (COVERAGE, cov),
            (SLO_TTFT_BURN, burn["ttft"]),
            (SLO_ITL_BURN, burn["itl"]),
        ):
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {round(v, 6)}")
        return "\n".join(lines) + "\n"


def frac_over_target(
    snap: Optional[dict[str, Any]], target_s: float
) -> float:
    """Fraction of a histogram snapshot's observations above ``target_s``,
    linearly interpolated inside the bucket the target falls in (the
    CDF complement of histogram_quantile's estimator). 0.0 when empty."""
    if not snap:
        return 0.0
    total = snap.get("count", 0)
    buckets = snap.get("buckets") or []
    counts = snap.get("counts") or []
    if not total or not buckets or len(counts) != len(buckets) + 1:
        return 0.0
    prev_cum = 0
    lo = 0.0
    for edge, cum in zip(buckets, counts[:-1]):
        if target_s <= edge:
            in_bucket = cum - prev_cum
            width = edge - lo
            frac = (target_s - lo) / width if width > 0 else 1.0
            cum_at = prev_cum + in_bucket * frac
            return max(0.0, min(1.0, (total - cum_at) / total))
        prev_cum = cum
        lo = edge
    # target beyond the top finite edge: only +Inf observations exceed it
    return (total - counts[-2]) / total if len(counts) >= 2 else 0.0


PROF = ProfRegistry()

__all__ = [
    "SEGMENTS",
    "ANNOTATION_PREFIX",
    "PAUSE_PREFIX",
    "GC_ANNOTATION",
    "IDLE",
    "IDLE_ANNOTATION",
    "ROUND_ANNOTATION",
    "STALL_S",
    "LATE_MIN_CLEAN",
    "LATE_FACTOR",
    "LATE_CAUSES",
    "HOST_BUCKETS",
    "HOST_ROUND",
    "COVERAGE",
    "SLO_TTFT_BURN",
    "SLO_ITL_BURN",
    "RoundProf",
    "ProfRegistry",
    "frac_over_target",
    "PROF",
]
