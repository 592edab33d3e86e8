"""Host-budget regression pins: the round-pipelining + segment-diet PR.

Steady decode used to run host + device fully serialized — the engine
was HOST-bound. After the double-buffered round pipeline (dispatch round N+1 before consuming
round N's fetch) and the segment diet (numpy slot-state mirrors, lazy
annotation, vectorized prof fold), steady-state host bookkeeping must
fit under device execution: wall/step ~ max(host, device), not host +
device. These tests pin that by COUNTS the engine's own attribution plane
(telemetry/prof.py) and dispatch books give, so the host loop can't
silently regrow: what the engine did and in which order, never how fast
this CPU did it (a time is the chip's to measure, PERF.md).

Window mechanics follow tests/test_dispatch_budget.py: open the steady
window only after every slot is decoding, close it well before any
request finishes — admission/release patches and one-off XLA compiles
(both legitimately expensive) stay outside the measured window.
"""
import asyncio
import threading

import numpy as np

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.telemetry.prof import SEGMENTS, ProfRegistry

PS = 16

# the dieted segments and the calls (Python and C) a steady-state host
# pass may make inside each (``metrics_fold``: a FOLD, which runs at the
# publish cadence). Counted on the tiny CPU harness, the same at 4 and at
# 16 slots (my run, PR 62): intake 9, slot_scan 30.4-30.9, seal_assembly
# 6.6-9, annotate 0, a fold 215-233; the ``fetch`` segment, whose work IS
# per slot, makes 350 at 4 slots and 1300 at 16. The per-slot Python scans
# the diet replaced made several calls a SLOT a pass, so one ceiling holds
# both widths, about twice what either makes.
SEGMENT_CALL_CEILINGS = {
    "intake": 20,          # queue-empty fast path
    "slot_scan": 60,       # numpy slot-state mirrors, no per-slot scan
    "seal_assembly": 20,   # preallocated batch packing
    "annotate": 5,         # lazy tuples, materialized only at finish
    "metrics_fold": 450,   # publish-cadence numpy fold
}


def _engine(**kw) -> TpuEngine:
    base = dict(
        num_pages=128, page_size=PS, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(64,),
        cache_dtype="float32",
    )
    base.update(kw)
    return TpuEngine(ModelConfig.tiny(dtype="float32"),
                     EngineConfig(**base),
                     mesh_config=MeshConfig(tp=1))


class _Calls:
    """The calls (Python and C: ``sys.setprofile``'s ``call`` and
    ``c_call`` events) the engine thread makes while ``on``, by the
    segment its attribution plane stands in, and the host passes
    (``TpuEngine._round``) it makes. Installed for threads started while
    it is the ``with`` body's profile hook."""

    def __init__(self, eng):
        self.prof = eng.prof
        self.on = False
        self.by_segment = [0] * len(SEGMENTS)
        self.passes = self.folds = 0

    def _hook(self, frame, event, arg):
        if (self.on and event in ("call", "c_call")
                and threading.get_ident() == self.prof._thread):
            if event == "call" and frame.f_code is TpuEngine._round.__code__:
                self.passes += 1
            elif self.prof._in_round:
                self.folds += frame.f_code is ProfRegistry.fold.__code__
                self.by_segment[self.prof._seg] += 1

    def __enter__(self):
        threading.setprofile(self._hook)
        return self

    def __exit__(self, *exc):
        threading.setprofile(None)


async def _steady_window(eng, n_req=4, osl=64, calls=None):
    """Run n_req concurrent decodes and return (steps, dispatches that
    found the device dry, fused rounds dispatched) over the steady
    all-slots-decoding window; ``calls`` counts while it is open."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, 48).tolist() for _ in range(n_req)]
    progress = [0] * n_req

    async def one(i):
        async for out in eng.generate(PreprocessedRequest(
            token_ids=list(prompts[i]),
            stop_conditions=StopConditions(max_tokens=osl,
                                           ignore_eos=True),
        )):
            progress[i] += len(out.token_ids)

    def rounds():
        return eng.dispatch_counts["round"] + eng.dispatch_counts["round_seal"]

    tasks = [asyncio.ensure_future(one(i)) for i in range(n_req)]
    while not all(p >= 4 for p in progress):
        await asyncio.sleep(0.005)
    s0, dry0, r0 = eng.step_count, eng._h_dry.sum, rounds()
    if calls is not None:
        calls.on = True
    # close 20 tokens short of osl: the dispatch front leads emission by
    # the pipeline lag, so release patches stay out of the window
    while not any(p >= osl - 20 for p in progress):
        await asyncio.sleep(0.005)
    if calls is not None:
        calls.on = False
    steps, dry, n = eng.step_count - s0, eng._h_dry.sum - dry0, rounds() - r0
    await asyncio.gather(*tasks)
    return steps, dry, n


def _hold_fetches(eng, asks=2):
    """Every fused round's fetch of ``eng`` becomes a ``_HeldFetch``."""
    track, held = eng._track, []

    def held_track(entry):
        if entry.kind == "round":
            for h in held:
                h.superseded = True
            entry.handle = _HeldFetch(entry.handle, asks)
            held[:] = [entry.handle]
        track(entry)

    eng._track = held_track


async def test_steady_host_fits_under_device():
    """THE pin: steady-decode host bookkeeping must hide under the
    in-flight program. Every dispatch asks whether the newest program
    dispatched before it has finished (``_poll_dry``: the device stood
    DRY while the host worked; ``dynamo_engine_dispatch_found_dry``).
    Under a device step that lasts until the next round stands behind
    it or the host has waited it out (``_HeldFetch``: whatever this CPU's
    speed), a host that dispatches round N+1 without round N's result
    finds the device busy at EVERY steady dispatch; one that reads round
    N first finds it dry at every one, which is host + device in
    series."""
    eng = _engine()
    _hold_fetches(eng, asks=None)
    eng.start()
    steps, dry, rounds = await _steady_window(eng)
    flushed = sum(eng.pipeline_stats()["pipe_flushes"].values())
    await eng.stop()
    assert steps >= 16 and rounds >= 4, (steps, rounds)
    # dry only where the pipeline flushed (a release patch at most, in a
    # window that closes 20 tokens short of the first finish)
    assert dry <= min(flushed, 1), (dry, rounds, flushed)


async def test_dieted_segment_ceilings():
    """Per-segment ceilings on the segments this PR dieted: the calls a
    steady host pass makes inside each stay under one ceiling at 4 and
    at 16 slots, where the per-slot Python scans they replaced grew with
    the slots."""
    for slots in (4, 16):
        eng = _engine(max_decode_slots=slots, num_pages=256)
        with _Calls(eng) as calls:
            eng.start()
        steps, _, _ = await _steady_window(eng, n_req=slots, osl=128,
                                           calls=calls)
        await eng.stop()
        assert steps >= 16 and calls.passes >= 4, (steps, calls.passes)
        # the fold runs at the publish cadence, not every pass
        per_pass = {s: calls.by_segment[i] / (
            max(calls.folds, 1) if s == "metrics_fold" else calls.passes)
            for i, s in enumerate(SEGMENTS)}
        for seg, ceiling in SEGMENT_CALL_CEILINGS.items():
            assert per_pass[seg] <= ceiling, (
                f"segment {seg!r} makes {per_pass[seg]:.1f} calls a pass "
                f"at {slots} slots, over its ceiling of {ceiling}; all: "
                f"{({s: round(v, 1) for s, v in per_pass.items()})}")


class _HeldFetch:
    """A round's fetch that reads as ready (and is waited for) once the
    NEXT round has been dispatched, once the engine has read it, or the
    ``asks``-th time the engine asks (None: never by asking): a device
    step that outlasts the host round that dispatched it and is over by
    the next, whatever this CPU's speed. The pipeline's counters then
    depend on the order the engine does things in, not on how fast it
    did them."""

    def __init__(self, arr, asks=2):
        self.arr = arr
        self.asks = asks
        self.asked = 0
        self.superseded = self.read = False

    def is_ready(self):
        self.asked += 1
        if not (self.superseded or self.read
                or self.asks is not None and self.asked >= self.asks):
            return False
        self.arr.block_until_ready()
        return True

    def __array__(self, *a, **kw):
        self.read = True
        return np.asarray(self.arr, *a, **kw)


async def test_pipeline_engages_in_steady_decode():
    """The pipeline must actually run in steady state: rounds are
    dispatched EARLY (ahead of the previous round's fetch), a second
    round is in flight when they are, and only a flush condition sends
    a dispatch back to the late position."""
    eng = _engine()
    _hold_fetches(eng)
    eng.start()
    await _steady_window(eng)
    stats = eng.pipeline_stats()
    rounds = eng.dispatch_counts["round"] + eng.dispatch_counts["round_seal"]
    await eng.stop()
    early = stats["pipelined_dispatches"]
    assert stats["round_pipeline"] is True
    # the last request admitted decodes 63 tokens in 16 rounds, every one
    # dispatched early but those a release patch flushed (three at most)
    assert early >= 8, stats
    # a dispatch is late only in a round the pipeline flushed, or in
    # the round whose completion half brought a request's first token
    # home and so its slot to life (once for each of the four requests)
    assert rounds - early <= sum(stats["pipe_flushes"].values()) + 4, stats
    # double-buffered: the round before is still tracked at every early
    # dispatch, and never more than the engine allows in flight
    assert 1.0 < stats["pipeline_depth"] <= eng.ecfg.max_inflight_rounds + 1
    # the completion half is a part of the pipelined round's host time
    assert 0.0 < stats["overlap_ratio"] <= 1.0, stats


async def test_pipeline_off_is_serialized():
    """round_pipeline=False (the differential tests' reference order):
    no early dispatches."""
    eng = _engine(round_pipeline=False)
    eng.start()
    steps, _, _ = await _steady_window(eng)
    stats = eng.pipeline_stats()
    await eng.stop()
    assert steps >= 16, steps
    assert stats["round_pipeline"] is False
    assert stats["pipelined_dispatches"] == 0, stats
    assert stats["pipeline_depth"] == 0.0, stats
