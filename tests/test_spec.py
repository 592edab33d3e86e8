"""Speculative decoding subsystem (dynamo_tpu/spec/).

The keystone is the differential test: with temperature=0, speculative
decoding — both proposers, several K — must produce token-for-token
identical output to the non-speculative engine, including runs with
mid-batch rejections (KV rollback) and de-speculation at the context
limit, and must leave the prefix-cache block-hash registry in the same
state as a clean run.
"""
import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics, WorkerStats
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.sdk import request_stats
from dynamo_tpu.spec.proposer import NGramProposer
from dynamo_tpu.spec.verifier import accept_tokens

PS = 16


# ---------------------------------------------------------------------------
# NGramProposer (pure host)

def test_ngram_proposes_continuation_of_tail_match():
    p = NGramProposer(k=3, max_n=3, min_n=1)
    #          0  1  2  3  4  5  6  7
    history = [5, 6, 7, 8, 9, 1, 6, 7]
    # tail [6, 7] matched at positions 1..2 -> continuation [8, 9, 1]
    assert p.propose(history) == [8, 9, 1]


def test_ngram_prefers_most_recent_match():
    p = NGramProposer(k=2, max_n=2, min_n=1)
    history = [1, 2, 3, 1, 2, 4, 1, 2]
    # [1, 2] occurs at 0 (-> 3) and 3 (-> 4); rightmost wins
    assert p.propose(history) == [4, 1]


def test_ngram_no_match_pads_zeros():
    p = NGramProposer(k=4, max_n=3, min_n=2)
    assert p.propose([1, 2, 3, 4]) == [0, 0, 0, 0]


def test_ngram_short_continuation_padded():
    p = NGramProposer(k=4, max_n=1, min_n=1)
    # tail [2] matches at index 1; the continuation window reaches the
    # end of history ([3, 2]) and pads with zeros
    assert p.propose([1, 2, 3, 2]) == [3, 2, 0, 0]


# ---------------------------------------------------------------------------
# accept_tokens (the on-device acceptance rule, called directly)

def _logits_for(rows, vocab=16):
    """Row i strongly prefers token rows[i]."""
    out = np.full((len(rows), vocab), -5.0, np.float32)
    for i, t in enumerate(rows):
        out[i, t] = 5.0
    return jnp.asarray(out)


def test_accept_greedy_longest_prefix_and_bonus():
    # target argmax chain: 3, 4, 9, 2 ; proposals 3, 4, 7 -> accept 2,
    # bonus = row 2's argmax (9)
    logits = _logits_for([3, 4, 9, 2])
    toks = jnp.asarray([1, 3, 4, 7], jnp.int32)  # pending=1, proposed 3,4,7
    key = jnp.zeros(2, jnp.uint32)
    out, n, _ = accept_tokens(
        logits, toks, key, jnp.float32(0.0), jnp.int32(0),
        jnp.float32(1.0), max_top_k=8,
    )
    assert int(n) == 3
    assert np.asarray(out)[:3].tolist() == [3, 4, 9]


def test_accept_greedy_all_accepted_gets_bonus_row_k():
    logits = _logits_for([3, 4, 9, 2])
    toks = jnp.asarray([1, 3, 4, 9], jnp.int32)
    out, n, _ = accept_tokens(
        logits, toks, jnp.zeros(2, jnp.uint32), jnp.float32(0.0),
        jnp.int32(0), jnp.float32(1.0), max_top_k=8,
    )
    assert int(n) == 4
    assert np.asarray(out).tolist() == [3, 4, 9, 2]


def test_accept_greedy_full_rejection_corrects_first_token():
    logits = _logits_for([3, 4, 9, 2])
    toks = jnp.asarray([1, 8, 8, 8], jnp.int32)
    out, n, _ = accept_tokens(
        logits, toks, jnp.zeros(2, jnp.uint32), jnp.float32(0.0),
        jnp.int32(0), jnp.float32(1.0), max_top_k=8,
    )
    assert int(n) == 1
    assert int(np.asarray(out)[0]) == 3


def test_accept_sampled_certain_proposal_always_accepted():
    # one token holds ~all mass: rejection sampling must accept it and
    # the bonus resample must also produce it
    logits = jnp.asarray(np.where(
        np.arange(16) == 7, 50.0, -50.0
    )[None].repeat(4, 0).astype(np.float32))
    toks = jnp.asarray([1, 7, 7, 7], jnp.int32)
    out, n, _ = accept_tokens(
        logits, toks, jnp.asarray([3, 9], jnp.uint32), jnp.float32(1.0),
        jnp.int32(0), jnp.float32(1.0), max_top_k=8,
    )
    assert int(n) == 4
    assert np.asarray(out).tolist() == [7, 7, 7, 7]


def test_accept_sampled_impossible_proposal_rejected_with_leftover():
    # proposal has ~zero mass -> always rejected; the leftover resample
    # (proposal masked) must return the dominant token
    logits = jnp.asarray(np.where(
        np.arange(16) == 5, 50.0, -50.0
    )[None].repeat(4, 0).astype(np.float32))
    toks = jnp.asarray([1, 9, 9, 9], jnp.int32)
    out, n, _ = accept_tokens(
        logits, toks, jnp.asarray([3, 9], jnp.uint32), jnp.float32(1.0),
        jnp.int32(0), jnp.float32(1.0), max_top_k=8,
    )
    assert int(n) == 1
    assert int(np.asarray(out)[0]) == 5


# ---------------------------------------------------------------------------
# Engine integration

@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny(dtype="float32")
    params = llama.init_params(cfg, 0)
    return cfg, params


def make_engine(setup, *, draft=False, **kw):
    cfg, params = setup
    base = dict(
        num_pages=64, page_size=PS, max_pages_per_seq=8,
        max_decode_slots=2, prefill_buckets=(32, 64),
        cache_dtype="float32",
    )
    base.update(kw)
    ekw = {}
    if draft:
        # draft == target: proposals match the target argmax, acceptance
        # should be (near-)total
        ekw = dict(draft_config=cfg, draft_params=params)
    return TpuEngine(
        cfg, EngineConfig(**base), params=params,
        mesh_config=MeshConfig(tp=1), **ekw,
    )


def _prompts(vocab=256):
    rng = np.random.RandomState(0)
    pat = rng.randint(1, vocab, 8).tolist()
    return [pat * 4, rng.randint(1, vocab, 20).tolist()]


async def drive(eng, prompts, max_tokens=24, so=None):
    async def one(p):
        toks, outs = [], []
        req = PreprocessedRequest(
            token_ids=list(p),
            stop_conditions=StopConditions(
                max_tokens=max_tokens, ignore_eos=True
            ),
        )
        if so is not None:
            req.sampling_options = so
        async for out in eng.generate(req):
            toks.extend(out.token_ids)
            outs.append(out)
        return toks, outs
    return await asyncio.gather(*[one(p) for p in prompts])


async def run_engine(setup, prompts, max_tokens=24, so=None, draft=False,
                     **kw):
    eng = make_engine(setup, draft=draft, **kw)
    eng.start()
    try:
        res = await drive(eng, prompts, max_tokens, so)
        stats = eng.spec.stats() if eng.spec else None
        hashes = frozenset(eng.allocator._registry)
        return res, stats, hashes
    finally:
        await eng.stop()


async def test_spec_greedy_differential_ngram():
    """Greedy n-gram speculation is token-identical to the baseline for
    K in {2, 4, 8}, with mid-batch rejections exercised, and leaves the
    prefix-cache hash registry identical to a clean run."""
    setup = (ModelConfig.tiny(dtype="float32"), None)
    setup = (setup[0], llama.init_params(setup[0], 0))
    prompts = _prompts()
    ref, _, ref_hashes = await run_engine(setup, prompts)
    for k in (2, 4, 8):
        spec, st, hashes = await run_engine(
            setup, prompts, speculative="ngram", num_speculative_tokens=k,
        )
        for (rt, _), (stk, _) in zip(ref, spec):
            assert rt == stk, f"K={k}: speculative output diverged"
        assert st["spec_verify_steps"] > 0
        # random-weight targets reject n-gram drafts constantly: the
        # KV-rollback path is genuinely exercised
        assert st["spec_reject_events"] > 0
        # KV consistency: the same blocks sealed under the same chained
        # hashes as the clean run, despite rejected optimistic writes
        assert hashes == ref_hashes


async def test_spec_greedy_differential_draft():
    """Draft-model speculation (draft == target here) is token-identical
    to the baseline and accepts (nearly) everything."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    prompts = _prompts()
    ref, _, ref_hashes = await run_engine(setup, prompts)
    for k in (2, 4, 8):
        spec, st, hashes = await run_engine(
            setup, prompts, draft=True,
            speculative="draft", num_speculative_tokens=k,
        )
        for (rt, _), (stk, _) in zip(ref, spec):
            assert rt == stk, f"K={k}: draft speculative output diverged"
        assert st["spec_acceptance_rate"] > 0.8
        assert hashes == ref_hashes


async def test_spec_despec_at_context_limit():
    """Near the region limit the verify no longer fits: the slot is
    handed back to the fused decode round and the tail continues
    token-identically to the baseline."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 256, 20).tolist()]
    # max_context = 4 * PS = 64 -> cap of 44 new tokens
    ref, _, _ = await run_engine(
        setup, prompts, max_tokens=100, max_pages_per_seq=4,
    )
    for mode, draft in (("ngram", False), ("draft", True)):
        spec, st, _ = await run_engine(
            setup, prompts, max_tokens=100, max_pages_per_seq=4,
            speculative=mode, num_speculative_tokens=4, draft=draft,
        )
        assert ref[0][0] == spec[0][0], f"{mode} tail diverged"
        assert len(spec[0][0]) == 44
        assert st["spec_despec_total"] >= 1


async def test_spec_seeded_temperature_reproducible():
    """temperature>0 speculation consumes the per-slot PRNG stream:
    seeded requests reproduce across runs."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    prompts = _prompts()[:1]
    so = SamplingOptions(temperature=0.9, seed=7)
    a, _, _ = await run_engine(
        setup, prompts, so=so, speculative="ngram",
        num_speculative_tokens=4,
    )
    b, _, _ = await run_engine(
        setup, prompts, so=so, speculative="ngram",
        num_speculative_tokens=4,
    )
    assert a[0][0] == b[0][0]
    assert len(a[0][0]) == 24


def test_accept_penalized_zero_counts_matches_plain():
    """With a zero histogram and identity penalties, the scan variant is
    draw-for-draw identical to the vectorized path (same PRNG key
    consumption) — penalty-free slots co-resident in a penalized round
    produce the same tokens either way."""
    from dynamo_tpu.spec.verifier import accept_tokens_penalized

    rng = np.random.RandomState(5)
    logits = jnp.asarray(rng.randn(5, 16).astype(np.float32))
    toks = jnp.asarray([1, 3, 4, 7, 9], jnp.int32)
    key = jnp.asarray([7, 11], jnp.uint32)
    for temp in (0.0, 0.9):
        a = accept_tokens(
            logits, toks, key, jnp.float32(temp), jnp.int32(0),
            jnp.float32(1.0), max_top_k=8,
        )
        b = accept_tokens_penalized(
            logits, toks, key, jnp.float32(temp), jnp.int32(0),
            jnp.float32(1.0), jnp.zeros(16, jnp.int32),
            jnp.float32(0.0), jnp.float32(0.0), jnp.float32(1.0),
            max_top_k=8,
        )
        for x, y in zip(a, b):
            assert np.asarray(x).tolist() == np.asarray(y).tolist(), temp


async def test_spec_penalized_greedy_differential():
    """Satellite (ROADMAP open item): penalized requests SPECULATE — the
    counts histogram advances inside the accept loop, and greedy output
    under frequency/presence/repetition penalties is token-identical to
    the non-speculative engine."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    prompts = _prompts()
    so = SamplingOptions(
        repetition_penalty=1.3, frequency_penalty=0.4,
        presence_penalty=0.2,
    )
    ref, _, ref_hashes = await run_engine(setup, prompts, so=so)
    for mode, draft in (("ngram", False), ("draft", True)):
        spec, st, hashes = await run_engine(
            setup, prompts, so=so, draft=draft,
            speculative=mode, num_speculative_tokens=4,
        )
        for (rt, _), (stk, _) in zip(ref, spec):
            assert rt == stk, f"{mode}: penalized speculation diverged"
        # the penalized slots really speculated (old behavior parked
        # them on the fused round and verify never ran)
        assert st["spec_verify_steps"] > 0
        assert hashes == ref_hashes


async def test_spec_penalized_seeded_temperature_reproducible():
    """Seeded temperature>0 sampling with penalties reproduces across
    speculative runs (the penalized accept path consumes the same
    per-slot PRNG stream)."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    prompts = _prompts()[:1]
    so = SamplingOptions(temperature=0.9, seed=11, presence_penalty=0.5)
    a, sa, _ = await run_engine(
        setup, prompts, so=so, speculative="ngram",
        num_speculative_tokens=4,
    )
    b, _, _ = await run_engine(
        setup, prompts, so=so, speculative="ngram",
        num_speculative_tokens=4,
    )
    assert a[0][0] == b[0][0]
    assert len(a[0][0]) == 24
    assert sa["spec_verify_steps"] > 0


async def test_spec_penalized_despec_restores_counts():
    """Despeculation hands the penalty HISTOGRAM back to the fused
    sampler: the tail after a context-limit despec stays token-identical
    under penalties (a reset histogram would change the penalty terms
    and fork the stream)."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 256, 20).tolist()]
    so = SamplingOptions(repetition_penalty=1.4, frequency_penalty=0.3)
    ref, _, _ = await run_engine(
        setup, prompts, max_tokens=100, max_pages_per_seq=4, so=so,
    )
    spec, st, _ = await run_engine(
        setup, prompts, max_tokens=100, max_pages_per_seq=4, so=so,
        speculative="ngram", num_speculative_tokens=4,
    )
    assert ref[0][0] == spec[0][0], "penalized despec tail diverged"
    assert len(spec[0][0]) == 44
    assert st["spec_despec_total"] >= 1


async def test_spec_ineligible_requests_take_fused_round():
    """A logprobs request decodes on the normal path (it needs the lp
    step variant) while an eligible one speculates — mixed rounds
    coexist in one engine. Penalized requests are NOT ineligible anymore:
    the verifier's histogram-advancing accept path carries them."""
    from dynamo_tpu.protocols.common import OutputOptions

    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    eng = make_engine(setup, speculative="ngram", num_speculative_tokens=4)
    eng.start()
    try:
        rng = np.random.RandomState(3)
        reqs = []
        for lp in (2, None):
            req = PreprocessedRequest(
                token_ids=rng.randint(1, 256, 12).tolist(),
                stop_conditions=StopConditions(
                    max_tokens=16, ignore_eos=True
                ),
            )
            if lp is not None:
                req.output_options = OutputOptions(logprobs=lp)
            reqs.append(req)

        async def one(req):
            toks = []
            async for out in eng.generate(req):
                toks.extend(out.token_ids)
            return toks
        got = await asyncio.gather(*[one(r) for r in reqs])
        assert all(len(t) == 16 for t in got)
        # the eligible request speculated; the logprobs one did not
        assert eng.spec.verify_steps > 0
        assert eng.step_count > 0  # fused rounds ran for the other slot
    finally:
        await eng.stop()


async def test_spec_metrics_and_sdk_request_stats():
    """Acceptance counters flow end-to-end: engine.metrics() ->
    exporter/system-server gauges, and per-request annotations ->
    sdk.request_stats."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    eng = make_engine(setup, draft=True, speculative="draft",
                      num_speculative_tokens=4)
    eng.start()
    try:
        res = await drive(eng, _prompts()[:1], max_tokens=16)
        m = eng.metrics()
        assert m.worker_stats.spec_proposed_total > 0
        assert m.worker_stats.spec_accepted_total > 0
        assert m.worker_stats.spec_acceptance_rate > 0.5
        st = request_stats(res[0][1])
        assert st.output_tokens == 16
        assert st.spec_proposed > 0
        assert st.spec_acceptance_rate is not None
        assert st.finish_reason == "length"
    finally:
        await eng.stop()
    # exporter rendering (no live control plane needed: feed the
    # aggregator directly)
    from dynamo_tpu.metrics_exporter import MetricsExporter

    exp = MetricsExporter(kv=None)
    exp.aggregator.update(m)
    text = exp.render()
    assert "dynamo_spec_proposed_total" in text
    assert "dynamo_spec_acceptance_rate" in text
    # system server renders the same gauges from a live engine handle
    from dynamo_tpu.runtime.system_server import SystemServer

    class _Stub:
        def metrics(self):
            return m
    assert "dynamo_spec_accepted_total" in SystemServer(_Stub()).render()


async def test_spec_repetitive_prompts_exceed_one_token_per_step():
    """At test scale: on repetitive prompts, n-gram
    speculation emits strictly more than one token per verify step."""
    cfg = ModelConfig.tiny(dtype="float32")
    setup = (cfg, llama.init_params(cfg, 0))
    rng = np.random.RandomState(5)
    pat = rng.randint(1, 256, 6).tolist()
    prompts = [pat * 5, (pat[::-1]) * 5]
    _, st, _ = await run_engine(
        setup, prompts, max_tokens=32,
        speculative="ngram", num_speculative_tokens=4,
    )
    steps = st["spec_verify_steps"]
    emitted_per_step = (st["spec_accepted_total"] + steps) / steps
    assert emitted_per_step > 1.0


def test_worker_stats_wire_compat():
    """Old payloads without spec fields still deserialize (defaults)."""
    m = ForwardPassMetrics.from_dict({
        "worker_id": "w0",
        "worker_stats": {"request_active_slots": 1},
        "kv_stats": {},
    })
    assert m.worker_stats.spec_proposed_total == 0
    assert WorkerStats(spec_proposed_total=3).spec_proposed_total == 3


# ---------------------------------------------------------------------------
# tier-2: real multi-layer model shapes (excluded from the tier-1 run)

@pytest.mark.slow
@pytest.mark.asyncio_timeout(600)
async def test_spec_differential_multilayer_model():
    """Same differential guarantee on a deeper/wider model — closer to
    real serving shapes than the 4-layer tiny config."""
    cfg = ModelConfig.tiny(
        dtype="float32", num_layers=8, hidden_size=128,
        intermediate_size=256, vocab_size=512,
    )
    setup = (cfg, llama.init_params(cfg, 0))
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 512, 24).tolist()]
    ref, _, _ = await run_engine(setup, prompts, max_tokens=32)
    spec, st, _ = await run_engine(
        setup, prompts, max_tokens=32,
        speculative="ngram", num_speculative_tokens=4,
    )
    assert ref[0][0] == spec[0][0]
    assert st["spec_verify_steps"] > 0
