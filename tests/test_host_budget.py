"""Host-budget regression pins: the round-pipelining + segment-diet PR.

Steady decode used to run host + device fully serialized — the engine
was HOST-bound. After the double-buffered round pipeline (dispatch round N+1 before consuming
round N's fetch) and the segment diet (numpy slot-state mirrors, lazy
annotation, vectorized prof fold), steady-state host bookkeeping must
fit under device execution: wall/step ~ max(host, device), not host +
device. These tests pin that via the engine's own attribution plane
(telemetry/prof.py) so the host loop can't silently regrow.

Window mechanics follow tests/test_dispatch_budget.py: open the steady
window only after every slot is decoding, close it well before any
request finishes — admission/release patches and one-off XLA compiles
(both legitimately expensive) stay outside the measured window.
"""
import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.telemetry.prof import SEGMENTS

PS = 16

# the dieted segments and their steady-state per-step ceilings (ms).
# Measured values on the tiny CPU harness sit at 0.002-0.02 ms/step;
# the ceilings leave ~10x headroom for shared-runner noise while still
# sitting far below the per-slot-Python-scan costs they replaced.
SEGMENT_CEILINGS_MS = {
    "intake": 0.25,        # queue-empty fast path
    "slot_scan": 0.25,     # numpy slot-state mirrors, no per-slot scan
    "seal_assembly": 0.25,  # preallocated batch packing
    "annotate": 0.25,      # lazy tuples, materialized only at finish
    "metrics_fold": 0.35,   # publish-cadence numpy fold
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


async def _steady_window(eng, n_req=4, osl=64):
    """Run n_req concurrent decodes and return (prof segment deltas in
    seconds, steps) over the steady all-slots-decoding window."""
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

    tasks = [asyncio.ensure_future(one(i)) for i in range(n_req)]
    while not all(p >= 4 for p in progress):
        await asyncio.sleep(0.005)
    p0 = eng.prof.totals()
    s0 = eng.step_count
    t0 = time.monotonic()
    # close 20 tokens short of osl: the dispatch front leads emission by
    # the pipeline lag, so release patches stay out of the window
    while not any(p >= osl - 20 for p in progress):
        await asyncio.sleep(0.005)
    wall = time.monotonic() - t0
    p1 = eng.prof.totals()
    steps = eng.step_count - s0
    await asyncio.gather(*tasks)
    segs = {
        s: p1["segments"][s] - p0["segments"][s] for s in SEGMENTS
    }
    return segs, steps, wall


def _device_ms_per_step(eng, osl, reps=10):
    """Blocking reps of the hot fused round at the engine's own state:
    device time alone. Call after eng.stop() (the loop must not patch
    _dev while the reps donate it)."""
    e = eng.ecfg
    B = e.max_decode_slots
    dev = dict(
        eng._dev,
        ctx=jnp.full((B,), 48 + osl, jnp.int32),
        dest=jnp.arange(B, dtype=jnp.int32),
        tokens=jnp.ones((B,), jnp.int32),
    )

    def one_round(dev):
        out = eng._engine_round_seal(
            eng.params, eng.ctx, eng.ring, dev, eng.cache,
            *eng._zero_seal, e.flush_every, False, False,
        )
        eng.ctx, eng.ring, eng.cache = out[0], out[1], out[3]
        jax.block_until_ready(out)
        return out[2]

    # two warmups: the first call's outputs carry jit-output shardings
    # that key one more compilation
    dev = one_round(one_round(dev))
    t0 = time.monotonic()
    for _ in range(reps):
        dev = one_round(dev)
    return (time.monotonic() - t0) / (reps * e.flush_every) * 1e3


async def test_steady_host_fits_under_device():
    """THE pin: steady-decode host bookkeeping per step must not exceed
    device execution per step, i.e. the pipeline hides host work under
    the in-flight program. Definition:
    host_ms_per_step := wall_ms_per_step - device_ms_per_step. (The
    prof segment sum is NOT usable as "host" here: in the pipelined
    regime the block-wait on the in-flight round lands in whichever
    segment touches the device first — fetch, or dispatch on backends
    that bound enqueue depth — so device time leaks into segments.)"""
    # best of three windows: under six test workers one ~40-step window
    # can catch the engine thread descheduled, which reads as host time
    for _ in range(3):
        eng = _engine()
        eng.start()
        segs, steps, wall = await _steady_window(eng)
        await eng.stop()
        assert steps >= 16, steps
        wall_ms = wall / steps * 1e3
        device_ms = _device_ms_per_step(eng, osl=64)
        host_ms = wall_ms - device_ms
        if host_ms <= device_ms:
            break
    assert host_ms <= device_ms, (
        f"host {host_ms:.4f} ms/step > device {device_ms:.4f} ms/step "
        f"(wall {wall_ms:.4f}); segment breakdown "
        f"{({s: round(v / steps * 1e3, 4) for s, v in segs.items()})}"
    )


async def test_dieted_segment_ceilings():
    """Per-segment ceilings on the segments this PR dieted: each must
    stay well under its pre-diet per-slot-Python-scan cost."""
    eng = _engine()
    eng.start()
    segs, steps, _ = await _steady_window(eng)
    await eng.stop()
    assert steps >= 16, steps
    per_step_ms = {s: v / steps * 1e3 for s, v in segs.items()}
    for seg, ceiling in SEGMENT_CEILINGS_MS.items():
        assert per_step_ms[seg] <= ceiling, (
            f"segment {seg!r} at {per_step_ms[seg]:.4f} ms/step exceeds "
            f"its {ceiling} ms ceiling; full breakdown "
            f"{({s: round(v, 4) for s, v in per_step_ms.items()})}"
        )


class _HeldFetch:
    """A round's fetch that reads as ready (and is waited for) once the
    NEXT round has been dispatched, or the second time the engine asks:
    a device step that outlasts the host round that dispatched it and is
    over by the next, whatever this CPU's speed. The pipeline's counters
    then depend on the order the engine does things in, not on how fast
    it did them."""

    def __init__(self, arr):
        self.arr = arr
        self.asked = 0
        self.superseded = False

    def is_ready(self):
        self.asked += 1
        if not self.superseded and self.asked < 2:
            return False
        self.arr.block_until_ready()
        return True

    def __array__(self, *a, **kw):
        return np.asarray(self.arr, *a, **kw)


async def test_pipeline_engages_in_steady_decode():
    """The pipeline must actually run in steady state: rounds are
    dispatched EARLY (ahead of the previous round's fetch), a second
    round is in flight when they are, and only a flush condition sends
    a dispatch back to the late position."""
    eng = _engine()
    track = eng._track

    held = []

    def held_track(entry):
        if entry.kind == "round":
            for h in held:
                h.superseded = True
            entry.handle = _HeldFetch(entry.handle)
            held[:] = [entry.handle]
        track(entry)

    eng._track = held_track
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
    segs, steps, _ = await _steady_window(eng)
    stats = eng.pipeline_stats()
    await eng.stop()
    assert steps >= 16, steps
    assert stats["round_pipeline"] is False
    assert stats["pipelined_dispatches"] == 0, stats
    assert stats["pipeline_depth"] == 0.0, stats
