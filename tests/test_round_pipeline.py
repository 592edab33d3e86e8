"""Round-pipelining correctness pins (EngineConfig.round_pipeline).

The pipeline dispatches round N+1's fused program before blocking on
round N's packed fetch — pure reordering of host work relative to
device work. Under greedy decoding the token streams must therefore be
BYTE-IDENTICAL with the pipeline on vs off, through every flush point:
admission bursts, mid-stream prefix-hit patches, speculative rounds,
priority preemption, graceful drain, and a chaos kill with a round in
flight (migration replay).

The off mode (``round_pipeline=False``) is the legacy serialized round
order — the differential baseline, kept reachable exactly for these
tests (no flag or runtime setting selects it).
"""
import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.overload.errors import PreemptedError
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.resilience import CHAOS, RESILIENCE

PS = 16


@pytest.fixture(autouse=True)
def _reset_globals():
    RESILIENCE.reset()
    CHAOS.reset()
    yield
    RESILIENCE.reset()
    CHAOS.reset()


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny(dtype="float32")
    return cfg, llama.init_params(cfg, 0)


def _mk(setup, **kw) -> TpuEngine:
    cfg, params = setup
    base = dict(
        num_pages=128, page_size=PS, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(64,),
        cache_dtype="float32",
    )
    base.update(kw)
    return TpuEngine(cfg, EngineConfig(**base), params=params,
                     mesh_config=MeshConfig(tp=1))


def _req(prompt, max_tokens, priority=0):
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        priority=priority,
    )


async def _collect(eng, req):
    toks = []
    async for out in eng.generate(req):
        toks.extend(out.token_ids)
    return toks


async def _run_jobs(eng, jobs):
    """jobs: list of (prompt, max_tokens, delay_s). Staggered submission
    creates admission bursts against live decode; varied max_tokens
    creates mid-window release patches."""
    async def one(p, mt, delay):
        if delay:
            await asyncio.sleep(delay)
        return await _collect(eng, _req(p, mt))

    return await asyncio.gather(
        *[one(p, mt, d) for (p, mt, d) in jobs]
    )


async def _both_modes(setup, jobs, **kw):
    """Run the same job list pipelined and serialized; return
    (tokens_on, tokens_off, pipeline_stats_on)."""
    out = {}
    for mode in (True, False):
        eng = _mk(setup, round_pipeline=mode, **kw)
        eng.start()
        try:
            toks = await _run_jobs(eng, jobs)
            stats = eng.pipeline_stats()
        finally:
            await eng.stop()
        out[mode] = (toks, stats)
    assert out[False][1]["pipelined_dispatches"] == 0
    return out[True][0], out[False][0], out[True][1]


async def test_differential_admission_burst_and_releases(setup):
    """Admission bursts mid-decode + staggered releases: every arrival
    forces a pipeline flush (patches must not race an in-flight round)
    and every early finisher exercises the release flush point."""
    rng = np.random.RandomState(0)
    jobs = [
        (rng.randint(1, 256, 48).tolist(), 40, 0.0),
        (rng.randint(1, 256, 24).tolist(), 12, 0.0),   # early release
        (rng.randint(1, 256, 40).tolist(), 32, 0.15),  # burst arrival
        (rng.randint(1, 256, 17).tolist(), 20, 0.3),   # second burst
    ]
    on, off, stats = await _both_modes(setup, jobs)
    assert on == off, "pipelined tokens diverged from serialized run"
    assert stats["pipelined_dispatches"] > 0, stats
    assert stats["pipe_flushes"]["admission"] > 0, stats


async def test_differential_mid_stream_prefix_hit_patch(setup):
    """A prefix-cache-hit admission lands mid-decode: the load_ctx +
    patch pair against pool state must flush the in-flight round first.
    The shared-prefix follower must emit exactly what the serialized
    engine emits."""
    rng = np.random.RandomState(1)
    head = rng.randint(1, 256, 3 * PS).tolist()  # seals 3 blocks
    jobs = [
        (head + [7], 36, 0.0),
        (rng.randint(1, 256, 32).tolist(), 36, 0.0),
        (head + [9], 24, 0.4),   # arrives mid-decode, hits the prefix
    ]
    on, off, stats = await _both_modes(setup, jobs)
    assert on == off
    assert stats["pipelined_dispatches"] > 0, stats


async def test_differential_spec_rounds(setup):
    """Speculative rounds never overlap a normal in-flight round (the
    verify/rollback patches touch the same slot state): greedy n-gram
    output stays identical with the pipeline on."""
    rng = np.random.RandomState(2)
    pat = rng.randint(1, 256, 8).tolist()
    jobs = [
        (pat * 4, 24, 0.0),                           # spec-friendly
        (rng.randint(1, 256, 20).tolist(), 24, 0.0),  # reject-heavy
    ]
    on, off, stats = await _both_modes(
        setup, jobs, speculative="ngram", num_speculative_tokens=4,
        max_decode_slots=2, num_pages=64, max_pages_per_seq=8,
        prefill_buckets=(32, 64),
    )
    assert on == off, "speculative pipelined run diverged"
    assert stats["pipe_flushes"]["spec"] > 0, stats


async def test_differential_preemption(setup):
    """Priority preemption with a round in flight: in both modes the
    victim fails with the retriable PreemptedError after emitting a
    clean prefix of the unloaded run, and the high-priority request's
    tokens are identical across modes."""
    rng = np.random.RandomState(3)
    victim_p = rng.randint(1, 256, 40).tolist()
    high_p = rng.randint(1, 256, 24).tolist()

    ref_eng = _mk(setup, round_pipeline=True)
    ref_eng.start()
    expected = await _collect(ref_eng, _req(victim_p, 100))
    await ref_eng.stop()

    high_toks = {}
    for mode in (True, False):
        eng = _mk(setup, round_pipeline=mode, max_decode_slots=1,
                  preempt_running=True)
        eng.start()
        got: list[int] = []

        async def run_victim(eng=eng, got=got):
            async for out in eng.generate(_req(victim_p, 100)):
                got.extend(out.token_ids)

        vt = asyncio.ensure_future(run_victim())
        for _ in range(2000):
            if len(got) >= 8:
                break
            await asyncio.sleep(0.005)
        assert len(got) >= 8, "victim never started streaming"
        high_toks[mode] = await _collect(eng, _req(high_p, 6, priority=1))
        with pytest.raises(PreemptedError):
            await vt
        assert eng.preempt_migrations == 1
        # the victim's partial stream is a clean prefix — no torn round
        assert got == expected[:len(got)], mode
        await eng.stop()
    assert high_toks[True] == high_toks[False]


async def test_differential_drain(setup):
    """begin_drain with requests in flight: both modes run the in-flight
    work to completion (identical tokens), refuse new admissions, and
    report drained."""
    from dynamo_tpu.resilience import WorkerDrainingError

    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 256, 32).tolist() for _ in range(3)]
    out = {}
    for mode in (True, False):
        eng = _mk(setup, round_pipeline=mode)
        eng.start()
        tasks = [asyncio.ensure_future(_collect(eng, _req(p, 32)))
                 for p in prompts]
        await asyncio.sleep(0.2)   # let decode get going
        eng.begin_drain()
        with pytest.raises(WorkerDrainingError):
            await _collect(eng, _req(prompts[0], 4))
        out[mode] = await asyncio.gather(*tasks)
        for _ in range(2000):
            if eng.drained():
                break
            await asyncio.sleep(0.005)
        assert eng.drained(), mode
        await eng.stop()
    assert out[True] == out[False]
    assert all(len(t) == 32 for t in out[True])


async def test_chaos_kill_with_round_in_flight_replays_identically(setup):
    """The keystone: a chaos worker-kill fired while the pipelined
    engine has a round in flight must leave the migrated client with
    the BYTE-IDENTICAL stream of an uninterrupted run — the replay
    prefill over prompt+emitted picks up exactly where the dead stream
    stopped, torn in-flight round discarded."""
    from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter
    from dynamo_tpu.kv_router.scheduler import KvRouterConfig

    rng = np.random.RandomState(5)
    prompt = rng.randint(1, 256, 40).tolist()

    ref_eng = _mk(setup, round_pipeline=True)
    ref_eng.start()
    expected = await _collect(ref_eng, _req(prompt, 24))
    await ref_eng.stop()

    eng = _mk(setup, round_pipeline=True)
    eng.start()

    class ChaosWorker:
        """The remote_engine integration shape: the engine stream runs
        through the chaos plane when any point is armed."""

        def __init__(self, inner):
            self.inner = inner

        async def generate(self, req):
            src = self.inner.generate(req)
            if CHAOS.any_armed():
                src = CHAOS.wrap_stream(src)
            async for out in src:
                yield out

    # the same live engine behind two worker ids: the replay lands on a
    # warm engine whose pipeline is already running
    router = KvRouter(PS, KvRouterConfig(router_temperature=0.0))
    push = KvPushRouter(router, {"w0": ChaosWorker(eng),
                                 "w1": ChaosWorker(eng)})
    CHAOS.arm("kill_worker", after_outputs=6, once=True)
    got = []
    async for out in push.generate(_req(prompt, 24)):
        got.extend(out.token_ids)
    stats = eng.pipeline_stats()
    await eng.stop()

    assert got == expected, "migrated stream diverged from clean run"
    assert CHAOS.points["kill_worker"].injected_total == 1
    assert push.migrations == 1
    assert RESILIENCE.get("dynamo_migration_total") == 1
    # the kill really did land with the pipeline active
    assert stats["pipelined_dispatches"] > 0, stats
