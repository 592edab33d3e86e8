"""Batched cross-slot drafting + acceptance-adaptive K (dynamo_tpu/spec/).

Three guarantees on top of tests/test_spec.py's differential keystone:

  - the AdaptiveKController walks each slot's effective K on its rolling
    acceptance rate (grow/shrink/de-speculate thresholds), and greedy
    output stays token-identical to non-speculative decode even while K
    adapts mid-stream;
  - drafting for N speculating slots issues O(1) device dispatches per
    round (ONE llama.batch_draft program), not O(N*K) — and produces
    exactly the tokens the per-slot path produced;
  - the satellite fixes hold: padded prefix loads clamp to the ctx
    region instead of crashing the round, and emits to a closed client
    event loop no longer mask the original engine failure.
"""
import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics, WorkerStats
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.spec.decoder import AdaptiveKController, SpecDecoder
from tests.test_spec import _prompts, make_engine, run_engine

PS = 16


def make_controller(**kw):
    k_max = kw.pop("k_max", 8)
    k_min = kw.pop("k_min", 1)
    base = dict(grow_at=0.8, shrink_at=0.4, despec_at=0.125,
                ewma=0.75, min_obs=8)
    base.update(kw)
    return AdaptiveKController(k_max, k_min, **base)


# ---------------------------------------------------------------------------
# AdaptiveKController (pure host)

def test_adaptive_k_starts_at_cap():
    c = make_controller(k_max=8)
    assert c.k_for(0) == 8
    assert c.k_for(3) == 8  # every slot, not just observed ones


def test_adaptive_k_shrinks_on_low_acceptance_to_floor():
    c = make_controller(k_max=4, k_min=2)
    for _ in range(20):
        c.observe(0, accepted=0, k_used=c.k_for(0))
    assert c.k_for(0) == 2            # floored at k_min
    assert c.shrink_total >= 2        # 4 -> 3 -> 2


def test_adaptive_k_grows_back_on_high_acceptance():
    c = make_controller(k_max=8, k_min=1)
    for _ in range(20):
        c.observe(0, accepted=0, k_used=c.k_for(0))
    assert c.k_for(0) == 1
    for _ in range(30):
        c.observe(0, accepted=c.k_for(0), k_used=c.k_for(0))
    assert c.k_for(0) == 8
    assert c.grow_total >= 7


def test_adaptive_k_hysteresis_band_holds_k():
    """Rates between shrink_at and grow_at leave K untouched."""
    c = make_controller(k_max=8, k_min=1)
    for _ in range(16):
        c.observe(0, accepted=5, k_used=8)   # 0.625: inside the band
    assert c.k_for(0) == 8
    assert c.grow_total == 0 and c.shrink_total == 0


def test_adaptive_k_despec_needs_min_obs_and_collapse():
    c = make_controller(k_max=4, min_obs=8)
    for i in range(7):
        c.observe(0, accepted=0, k_used=4)
        assert not c.should_despec(0)      # too few observations
    c.observe(0, accepted=0, k_used=4)
    assert c.should_despec(0)              # rate 0 <= despec_at, obs >= 8
    # a healthy slot never de-speculates
    for _ in range(20):
        c.observe(1, accepted=4, k_used=4)
    assert not c.should_despec(1)


def test_adaptive_k_release_forgets_slot_state():
    c = make_controller(k_max=4)
    for _ in range(10):
        c.observe(0, accepted=0, k_used=4)
    assert c.k_for(0) < 4
    c.release(0)
    assert c.k_for(0) == 4
    assert not c.should_despec(0)


def test_adaptive_k_ewma_recovers_from_one_bad_step():
    """One rejected round must not collapse a slot with a good history."""
    c = make_controller(k_max=4, min_obs=1)
    for _ in range(10):
        c.observe(0, accepted=4, k_used=4)
    c.observe(0, accepted=0, k_used=4)
    assert not c.should_despec(0)          # EWMA keeps rate ~0.75


def test_adaptive_branch_starts_wide_narrows_on_high_acceptance():
    """Tree axis: a fresh stream hedges WIDE (m = m_max); sustained
    acceptance walks it deep-and-narrow — K up, branches down to 1."""
    c = make_controller(k_max=8, k_min=1, m_max=4)
    assert c.m_for(0) == 4 and c.m_for(7) == 4   # every slot starts wide
    for _ in range(20):
        c.observe(0, accepted=c.k_for(0), k_used=c.k_for(0))
    assert c.k_for(0) == 8
    assert c.m_for(0) == 1
    assert c.branch_shrink_total >= 3            # 4 -> 3 -> 2 -> 1


def test_adaptive_branch_widens_back_on_low_acceptance():
    """Early rejection is exactly what sibling branches catch: low
    acceptance walks the shape shallow-and-wide — K down, branches up,
    bounded by m_max."""
    c = make_controller(k_max=8, k_min=1, m_max=4)
    for _ in range(20):
        c.observe(0, accepted=c.k_for(0), k_used=c.k_for(0))
    assert c.m_for(0) == 1
    for _ in range(30):
        c.observe(0, accepted=0, k_used=c.k_for(0))
    assert c.m_for(0) == 4
    assert c.k_for(0) == 1
    assert c.branch_grow_total >= 3


def test_adaptive_branch_hysteresis_and_tree_off_pins_m_one():
    """Mid-band rates hold the branch fan where it is; a linear-chain
    controller (m_max=1, tree off) never moves off m=1."""
    c = make_controller(k_max=8, m_max=4)
    for _ in range(16):
        c.observe(0, accepted=5, k_used=8)       # 0.625: inside the band
    assert c.m_for(0) == 4
    assert c.branch_grow_total == 0 and c.branch_shrink_total == 0
    lin = make_controller(k_max=8)               # m_max defaults to 1
    for _ in range(16):
        lin.observe(0, accepted=0, k_used=8)
    assert lin.m_for(0) == 1
    assert lin.branch_grow_total == 0


def test_adaptive_branch_despec_on_collapse_and_release_resets():
    """Hedging wider must not save a dead stream: a slot already at
    m_max with collapsed acceptance still de-speculates, and release()
    hands the lane back with the full wide shape."""
    c = make_controller(k_max=4, m_max=4, min_obs=8)
    for _ in range(12):
        c.observe(0, accepted=0, k_used=4)
    assert c.m_for(0) == 4                       # saturated wide...
    assert c.should_despec(0)                    # ...and still despecs
    assert c.k_for(0) < 4
    c.release(0)
    assert c.k_for(0) == 4 and c.m_for(0) == 4
    assert not c.should_despec(0)


def test_round_m_buckets_to_pow2_clamped_at_branches():
    cfg = ModelConfig.tiny(dtype="float32")
    dec = SpecDecoder(
        cfg, EngineConfig(speculative="ngram", num_speculative_tokens=4,
                          spec_tree=True, spec_branches=4),
    )
    assert dec.round_m([1]) == 1
    assert dec.round_m([2, 1]) == 2
    assert dec.round_m([3]) == 4       # pow2 bucket
    assert dec.round_m([4, 2]) == 4    # clamped at --spec-branches
    # tree off: the branch axis is pinned at 1 whatever the slots say
    lin = SpecDecoder(
        cfg, EngineConfig(speculative="ngram", num_speculative_tokens=4),
    )
    assert lin.round_m([1]) == 1
    assert lin.m_for(0) == 1


def test_round_k_buckets_to_pow2_clamped_at_cli_k():
    cfg = ModelConfig.tiny(dtype="float32")
    dec = SpecDecoder(
        cfg, EngineConfig(speculative="ngram", num_speculative_tokens=6),
    )
    assert dec.round_k([1]) == 1
    assert dec.round_k([2, 1]) == 2
    assert dec.round_k([3]) == 4       # pow2 bucket
    assert dec.round_k([5, 2]) == 6    # clamped to the CLI K
    # adaptive off: every slot runs the CLI K
    dec_off = SpecDecoder(
        cfg, EngineConfig(speculative="ngram", num_speculative_tokens=6,
                          spec_adaptive=False),
    )
    assert dec_off.k_for(0) == 6
    assert not dec_off.should_despec(0)


# ---------------------------------------------------------------------------
# Engine integration: greedy equality while K adapts mid-stream

@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny(dtype="float32")
    return cfg, llama.init_params(cfg, 0)


async def test_adaptive_greedy_differential_ngram(setup):
    """Mixed workload — one repetitive prompt (acceptance high, K grows)
    and one random prompt (acceptance collapses, K shrinks and the slot
    de-speculates) — stays token-identical to the baseline while the
    controller provably adjusts K both ways."""
    prompts = _prompts()  # [repetitive, random]
    ref, _, ref_hashes = await run_engine(setup, prompts)
    spec, st, hashes = await run_engine(
        setup, prompts, speculative="ngram", num_speculative_tokens=4,
        spec_adaptive=True, spec_min_k=1,
    )
    for (rt, _), (stk, _) in zip(ref, spec):
        assert rt == stk, "adaptive-K speculative output diverged"
    assert st["spec_adaptive"] is True
    assert st["spec_k_shrink_total"] > 0, "random prompt never shrank K"
    assert hashes == ref_hashes


async def test_adaptive_despec_on_collapsed_acceptance(setup):
    """A slot whose acceptance collapses is handed back to the fused
    round mid-stream (not at the context limit) and the continuation
    stays token-identical."""
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 256, 20).tolist()]  # nothing to look up
    ref, _, _ = await run_engine(setup, prompts, max_tokens=40)
    spec, st, _ = await run_engine(
        setup, prompts, max_tokens=40,
        speculative="ngram", num_speculative_tokens=4,
        spec_adaptive=True,
    )
    assert ref[0][0] == spec[0][0]
    assert st["spec_despec_total"] >= 1
    # despec fired from acceptance collapse: the run was nowhere near
    # the region limit (max_pages_per_seq=8 * 16 = 128 >> 20 + 40)


async def test_adaptive_differential_draft_batched(setup):
    """Batched cross-slot drafting (draft == target) is token-identical
    to the unspeculated baseline."""
    prompts = _prompts()
    ref, _, ref_hashes = await run_engine(setup, prompts)
    batched, bst, bh = await run_engine(
        setup, prompts, draft=True, speculative="draft",
        num_speculative_tokens=4,
    )
    for (rt, _), (bt, _) in zip(ref, batched):
        assert rt == bt, "batched drafting diverged from baseline"
    assert bst["spec_acceptance_rate"] > 0.8
    assert bh == ref_hashes


async def test_batched_drafting_is_one_dispatch_per_round(setup):
    """The tentpole claim at engine level: N speculating slots draft in
    ONE device program per verify round, whatever N and K."""
    prompts = _prompts()
    _, bst, _ = await run_engine(
        setup, prompts, draft=True, speculative="draft",
        num_speculative_tokens=4,
    )
    assert bst["spec_verify_dispatch_total"] > 0
    assert (bst["spec_draft_dispatch_total"]
            == bst["spec_verify_dispatch_total"])


async def test_mixed_spec_and_fused_rounds_stay_token_identical(setup):
    """A speculating slot co-resident with fused-round slots must not be
    advanced by the round's (garbage) column for its parked lane — the
    eligible request's output must equal its solo reference. Pins the
    dispatch-snapshot filter in _dispatch_round."""
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    rng = np.random.RandomState(9)
    elig_prompt = (rng.randint(1, 256, 6).tolist() * 4)
    pen_prompt = rng.randint(1, 256, 12).tolist()
    ref, _, _ = await run_engine(setup, [elig_prompt], max_tokens=24,
                                 speculative="ngram",
                                 num_speculative_tokens=4)
    eng = make_engine(setup, speculative="ngram", num_speculative_tokens=4)
    eng.start()
    try:
        async def one(req):
            toks = []
            async for out in eng.generate(req):
                toks.extend(out.token_ids)
            return toks

        pen = PreprocessedRequest(
            token_ids=pen_prompt,
            stop_conditions=StopConditions(max_tokens=24, ignore_eos=True),
        )
        pen.sampling_options = SamplingOptions(repetition_penalty=1.3)
        elig = PreprocessedRequest(
            token_ids=list(elig_prompt),
            stop_conditions=StopConditions(max_tokens=24, ignore_eos=True),
        )
        got = await asyncio.gather(one(pen), one(elig))
        assert eng.step_count > 0          # fused rounds really ran
        assert eng.spec.verify_steps > 0   # speculation really ran
        assert got[1] == ref[0][0], \
            "spec slot was corrupted by a co-resident fused round"
    finally:
        await eng.stop()


async def test_spec_effective_k_exported(setup):
    """The planner-facing gauge flows engine.metrics() -> WorkerStats ->
    exporter/system-server text."""
    eng = make_engine(setup, draft=True, speculative="draft",
                      num_speculative_tokens=4)
    eng.start()
    try:
        from tests.test_spec import drive

        await drive(eng, _prompts()[:1], max_tokens=16)
        m = eng.metrics()
        # draft == target: acceptance 1.0, so the slot's K never moved
        # off the cap (4) — and the slot may or may not be released yet
        # when metrics() snapshots (0 after release)
        assert m.worker_stats.spec_effective_k in (0.0, 4.0)
        assert eng.spec.effective_k_mean([0]) == 4.0
        assert eng.spec.effective_k_mean([]) == 0.0
    finally:
        await eng.stop()
    from dynamo_tpu.metrics_exporter import MetricsExporter
    from dynamo_tpu.runtime.system_server import SystemServer

    exp = MetricsExporter(kv=None)
    exp.aggregator.update(m)
    assert "dynamo_spec_effective_k" in exp.render()

    class _Stub:
        def metrics(self):
            return m
    assert "dynamo_spec_effective_k" in SystemServer(_Stub()).render()


def test_worker_stats_effective_k_wire_compat():
    """Old payloads without the new field still deserialize."""
    m = ForwardPassMetrics.from_dict({
        "worker_id": "w0",
        "worker_stats": {"spec_proposed_total": 2},
        "kv_stats": {},
    })
    assert m.worker_stats.spec_effective_k == 0.0
    assert WorkerStats(spec_effective_k=2.5).spec_effective_k == 2.5


# ---------------------------------------------------------------------------
# Satellite fixes

def test_load_ctx_pages_clamps_padding_overflow():
    """A pow2-padded page list whose span exceeds the ctx region loads
    the region-sized prefix instead of raising the trace-time
    dynamic_update_slice error that killed whole engine rounds
    (46 pages padded to 64 vs a 52-page region once crashed a run)."""
    cfg = ModelConfig.tiny(dtype="float32")
    ps, n_pages, region_pages = 16, 8, 3
    cache = llama.init_cache(cfg, n_pages, ps, jnp.float32)
    marker = jnp.arange(n_pages, dtype=jnp.float32)[None, None, :, None, None]
    cache = {k: jnp.broadcast_to(marker, v.shape).astype(v.dtype)
             for k, v in cache.items()}
    ctx = llama.init_ctx(cfg, 2, region_pages * ps, jnp.float32)
    # 3 real pages + pow2 padding to 4: span 4*16=64 > region 48
    out = llama.load_ctx_pages(
        ctx, cache, jnp.int32(0), jnp.asarray([5, 6, 7, 0], jnp.int32)
    )
    got = np.asarray(out["k"])[:, :, 0]           # lane 0: [L, kvh, S, hd]
    for b, page in enumerate((5, 6, 7)):
        assert np.all(got[:, :, b * ps:(b + 1) * ps] == float(page))


def test_emit_to_closed_loop_does_not_raise():
    """_fail_all during shutdown used to mask the root-cause exception
    with 'RuntimeError: Event loop is closed' raised from emit."""
    from dynamo_tpu.engine.engine import _Request
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        StopConditions,
    )
    from dynamo_tpu.tokens import TokenBlockSequence

    loop = asyncio.new_event_loop()
    try:
        out: asyncio.Queue = asyncio.Queue()
    finally:
        loop.close()
    r = _Request(
        req=PreprocessedRequest(
            token_ids=[1, 2], stop_conditions=StopConditions(max_tokens=1),
        ),
        seq=TokenBlockSequence.from_tokens([1, 2], PS),
        out=out, loop=loop, tokens=[1, 2],
    )
    r.emit(RuntimeError("engine failure"))  # must not raise
