"""Dispatch-budget regression pins: the decode-round dispatch diet.

A big slice of the host's cost per step is per-round host→device
dispatches. After the diet (seals fused into the round program, packed patch
uploads, packed logprob fetches, metrics publish throttled), a steady
decode round costs exactly ONE program dispatch + ONE stacked-token
fetch. These tests pin that budget via the engine's own
``dispatch_counts`` accounting so future PRs can't silently regrow it
(on the chip the cost shows as ``sched.host_ms_per_round`` and
``device.idle_share``, PERF.md).
"""
import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    StopConditions,
)
from tests.test_admit_first import _Held

PS = 16


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


async def _steady_window_budget(adapter_ids=None, setup=None, **kw):
    eng = _engine(**kw)
    if setup is not None:
        setup(eng)
    eng.start()
    rng = np.random.RandomState(0)
    n_req, osl = 4, 64
    prompts = [rng.randint(1, 256, 48).tolist() for _ in range(n_req)]
    progress = [0] * n_req

    async def one(i):
        async for out in eng.generate(PreprocessedRequest(
            token_ids=list(prompts[i]),
            stop_conditions=StopConditions(max_tokens=osl,
                                           ignore_eos=True),
            adapter_id=(adapter_ids[i % len(adapter_ids)]
                        if adapter_ids else 0),
            # variant requests carry their own model salt (the frontend
            # contract) so adapter streams never share cached prefixes
            model=(f"m:a{adapter_ids[i % len(adapter_ids)]}"
                   if adapter_ids else ""),
        )):
            progress[i] += len(out.token_ids)

    tasks = [asyncio.ensure_future(one(i)) for i in range(n_req)]
    # window opens once every request is admitted and decoding...
    while not all(p >= 4 for p in progress):
        await asyncio.sleep(0.005)
    d0 = dict(eng.dispatch_counts)
    # ...and closes well before any finishes (the dispatch front runs
    # ahead of emitted tokens by the pipeline lag — flush_every *
    # (max_inflight_rounds + 1) = 12 steps — so closing 20 tokens short
    # of osl keeps release patches out of the window)
    while not any(p >= osl - 20 for p in progress):
        await asyncio.sleep(0.005)
    d1 = dict(eng.dispatch_counts)
    await asyncio.gather(*tasks)
    await eng.stop()

    delta = {k: d1[k] - d0.get(k, 0) for k in d1}
    rounds = delta["round"] + delta["round_seal"]
    # the dispatch front leads emitted progress by the pipeline lag, so
    # the window captures a variable-but-positive round count
    assert rounds >= 5, delta
    # nothing but round programs + their fetches in the window
    assert delta["seal"] == 0, delta          # seals fused, not standalone
    assert delta["patch"] == 0, delta         # no admissions/releases
    assert delta["prefill"] == 0 and delta["prefill_batch"] == 0, delta
    assert delta["load_ctx"] == 0 and delta["admit_first"] == 0, delta
    total = sum(delta.values())
    # 1 program + 1 fetch per round; the snapshot can land between a
    # round's program and fetch increments, so allow one straggler
    # fetch per window edge
    assert total <= 2 * rounds + 2, (total, rounds, delta)
    # blocks complete every PS tokens: with 4 slots x 4 steps/round the
    # fused-seal variant must actually be exercised in the window
    assert delta["round_seal"] >= 1, delta


async def test_steady_decode_round_budget():
    """THE pin: in a steady decode window (every slot active, no
    admissions/releases/transfers), dispatches-per-round must stay at
    1 program + 1 fetch — and seals must ride the round program, never
    a standalone seal_blocks dispatch."""
    await _steady_window_budget()


async def test_steady_decode_round_budget_int8():
    """kv_quant=int8 keeps the identical budget: ring-flush
    requantization and the raw int8 fused seals all ride the round
    program — the in-kernel quant path costs ZERO extra dispatches."""
    await _steady_window_budget(kv_quant="int8")


async def test_steady_decode_round_budget_mixed_adapters():
    """Resident LoRA multiplexing keeps the identical budget: per-slot
    adapter rows are gathered INSIDE the fused round program, so a
    steady decode batch mixing the base model with two live fine-tune
    variants still costs 1 program + 1 fetch per round — adapter
    switching has no dispatch price."""
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.tenancy.adapters import random_adapter

    def setup(eng):
        mc = ModelConfig.tiny(dtype="float32")
        eng.install_adapter(1, random_adapter(mc, 4, seed=5))
        eng.install_adapter(2, random_adapter(mc, 4, seed=6))

    await _steady_window_budget(adapter_ids=(0, 1, 2, 1), setup=setup,
                                lora_adapters=4, lora_rank=4)


async def test_steady_decode_round_budget_tree_spec_configured():
    """Enabling tree speculation must not tax streams that never
    speculate: adapter-variant requests (speculation is confined to the
    base model) keep the exact 1-program + 1-fetch steady round with
    --spec-tree configured on the engine."""
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.tenancy.adapters import random_adapter

    def setup(eng):
        mc = ModelConfig.tiny(dtype="float32")
        eng.install_adapter(1, random_adapter(mc, 4, seed=5))

    await _steady_window_budget(
        adapter_ids=(1, 1, 1, 1), setup=setup,
        lora_adapters=4, lora_rank=4,
        speculative="ngram", num_speculative_tokens=4,
        spec_tree=True, spec_branches=2,
    )


async def test_spec_tree_steady_budget():
    """Tree-speculating slots hold the linear verify's fetch budget: one
    verify program + ONE packed fetch per tree round (tokens + accepted
    path + count + PRNG key in a single array), zero draft dispatches on
    the host-side n-gram proposer, and no stray patches/seals — with
    every slot speculating, no fused round programs run at all."""
    eng = _engine(speculative="ngram", num_speculative_tokens=4,
                  spec_tree=True, spec_branches=2, spec_adaptive=False)
    eng.start()
    rng = np.random.RandomState(0)
    pat = rng.randint(1, 256, 8).tolist()
    n_req, osl = 4, 64
    progress = [0] * n_req

    async def one(i):
        async for out in eng.generate(PreprocessedRequest(
            # repetitive prompts: the n-gram trie proposes real trees and
            # acceptance stays high, so slots never de-speculate
            token_ids=pat * 4,
            stop_conditions=StopConditions(max_tokens=osl,
                                           ignore_eos=True),
            model=f"m:{i}",  # distinct prefixes -> four live slots
        )):
            progress[i] += len(out.token_ids)

    tasks = [asyncio.ensure_future(one(i)) for i in range(n_req)]
    while not all(p >= 8 for p in progress):
        await asyncio.sleep(0.005)
    d0 = dict(eng.dispatch_counts)
    while not any(p >= osl - 24 for p in progress):
        await asyncio.sleep(0.005)
    d1 = dict(eng.dispatch_counts)
    await asyncio.gather(*tasks)
    await eng.stop()

    delta = {k: d1[k] - d0.get(k, 0) for k in d1}
    g = lambda k: delta.get(k, 0)
    assert g("spec_verify") >= 3, delta
    # the packed result array is the ONLY fetch a tree round makes
    # (snapshot can land between a verify's program and fetch
    # increments: allow one straggler per window edge)
    assert abs(g("fetch") - g("spec_verify")) <= 1, delta
    assert g("spec_draft") == 0, delta        # n-gram proposes on host
    assert g("round") == 0 and g("round_seal") == 0, delta
    assert g("patch") == 0, delta
    # speculating slots seal completed blocks via the standalone batched
    # copy (no fused round runs to carry them — the linear-chain path
    # pays the same); bound it by the blocks that can actually complete
    assert g("seal") <= (n_req * osl) // PS, delta
    assert g("prefill") == 0 and g("prefill_batch") == 0, delta


async def test_whole_run_dispatch_budget():
    """Coarse whole-workload pin (admission + prefill + decode + tail):
    the all-in dispatches-per-round number the profile tool reports.
    Pre-diet this sat around ~4.5 (one standalone seal nearly every
    round); pin at 4.0 with the measured value ~3.5."""
    eng = _engine()
    eng.start()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, 48).tolist() for _ in range(4)]

    async def one(p, mt):
        async for _ in eng.generate(PreprocessedRequest(
            token_ids=list(p),
            stop_conditions=StopConditions(max_tokens=mt,
                                           ignore_eos=True),
        )):
            pass

    # warmup compiles, then the measured window
    await asyncio.gather(*[one(p, 8) for p in prompts])
    d0 = dict(eng.dispatch_counts)
    await asyncio.gather(*[one(p, 40) for p in prompts])
    delta = {k: v - d0.get(k, 0) for k, v in eng.dispatch_counts.items()}
    await eng.stop()
    rounds = delta["round"] + delta["round_seal"]
    assert rounds >= 8, delta
    assert sum(delta.values()) / rounds <= 4.0, delta


async def test_logprob_fetch_is_packed():
    """Logprob rounds fetch ONE packed array (chosen + ids + lps), not
    three — and the unpacked values are self-consistent."""
    eng = _engine()
    eng.start()
    rng = np.random.RandomState(2)
    toks, lps, top = [], [], []
    async for out in eng.generate(PreprocessedRequest(
        token_ids=rng.randint(1, 256, 24).tolist(),
        stop_conditions=StopConditions(max_tokens=12, ignore_eos=True),
        output_options=OutputOptions(logprobs=2),
    )):
        toks.extend(out.token_ids)
        lps.extend(out.log_probs or [])
        top.extend(out.top_logprobs or [])
    await eng.stop()
    assert len(toks) == 12 and len(lps) == 12 and len(top) == 12
    for t, lp, pairs in zip(toks, lps, top):
        assert len(pairs) == 2
        # ids survived the f32 packing exactly; greedy chosen == top-1
        assert pairs[0][0] == t
        assert lp == pytest.approx(pairs[0][1], abs=1e-5)
        assert pairs[0][1] >= pairs[1][1]


async def test_fused_seal_round_matches_standalone_pin():
    """Correctness pin for the seal fusion: tokens + the prefix cache a
    fused-seal run produces are identical to what the engine produced
    before the fusion — verified by the warm wave hitting the sealed
    blocks (exact bf16 pool roundtrip) and by forcing a standalone
    flush path via an offload-tier engine (which flushes seals before
    its pool-reading gathers)."""
    outs = {}
    for mode, kw in (("fused", {}),
                     ("standalone", {"host_offload_pages": 16})):
        eng = _engine(**kw)
        eng.start()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 256, 3 * PS + 1).tolist()
                   for _ in range(2)]

        async def one(p):
            got = []
            async for out in eng.generate(PreprocessedRequest(
                token_ids=list(p),
                stop_conditions=StopConditions(max_tokens=8,
                                               ignore_eos=True),
            )):
                got.extend(out.token_ids)
            return got

        w1 = [await one(p) for p in prompts]
        w2 = [await one(p) for p in prompts]  # prefix-hit via the pool
        assert w1 == w2  # bf16 pool: byte-exact roundtrip either path
        outs[mode] = (w1, dict(eng.dispatch_counts))
        await eng.stop()
    assert outs["fused"][0] == outs["standalone"][0]
    # the fused variant was actually exercised (whether the offload
    # engine's pool-reading gathers forced standalone flushes is
    # timing-dependent; token identity above is the invariant)
    assert outs["fused"][1]["round_seal"] >= 1


# ---------------------------------------------------------------------------
# the first-token budget: ONE program and ONE upload a prefill dispatch

class _FirstTokenLedger:
    """Wraps ``eng._finish_prefill``: for every call, the buckets of
    ``dispatch_counts`` it moved, the host arrays it uploaded
    (``jnp.asarray`` of a numpy array) and the first tokens it carried;
    ``dest`` is the device's after the last call."""

    def __init__(self, eng, monkeypatch):
        import jax.numpy as jnp

        self.calls = []
        self._inside = False
        self._uploads = 0
        real_asarray = jnp.asarray
        real_finish = eng._finish_prefill

        def asarray(a, *args, **kw):
            if self._inside and isinstance(a, np.ndarray):
                self._uploads += 1
            return real_asarray(a, *args, **kw)

        def finish(logits, lanes, *args, **kw):
            before = dict(eng.dispatch_counts)
            self._inside, self._uploads = True, 0
            try:
                return real_finish(logits, lanes, *args, **kw)
            finally:
                self._inside = False
                moved = {k: v - before[k]
                         for k, v in eng.dispatch_counts.items()
                         if v != before[k]}
                self.calls.append((moved, self._uploads, len(lanes)))
                # on the engine's thread: nothing donates dev meanwhile
                self.dest = np.asarray(eng._dev["dest"])

        monkeypatch.setattr(jnp, "asarray", asarray)
        eng._finish_prefill = finish


async def _first_token_window(monkeypatch, prompt_lens, spec=False, **kw):
    """Serve one held prompt, then ``prompt_lens`` arriving together;
    returns (the ledger's calls for that wave, the dispatch_counts delta
    from its submission to every request's first token, the engine, the
    device's ``dest`` after the last such dispatch)."""
    sink = _Held()
    base = dict(
        num_pages=128, page_size=PS, max_pages_per_seq=16,
        max_decode_slots=8, prefill_buckets=(64,),
        prefill_chunks_per_round=8, cache_dtype="float32",
    )
    base.update(kw)
    eng = TpuEngine(ModelConfig.tiny(dtype="float32"), EngineConfig(**base),
                    mesh_config=MeshConfig(tp=1),
                    on_dispatch=None if spec else sink)
    rng = np.random.RandomState(7)
    pat = rng.randint(1, 256, 8).tolist()
    progress = {}

    def req(n):
        # a repetitive prompt is what the n-gram proposer speculates on
        toks = (pat * 16)[:n] if spec else rng.randint(1, 256, n).tolist()
        return PreprocessedRequest(
            token_ids=toks, model=f"m:{len(progress)}:{n}",
            stop_conditions=StopConditions(max_tokens=48, ignore_eos=True))

    async def one(key, n):
        progress[key] = 0
        async for out in eng.generate(req(n)):
            progress[key] += len(out.token_ids)

    try:
        # a first wave of the same shapes compiles every program the
        # measured wave runs (a trace would upload constants of its own)
        await asyncio.gather(*[one(("warm", i), n)
                               for i, n in enumerate(prompt_lens)])
        ledger = _FirstTokenLedger(eng, monkeypatch)
        if spec:
            # the warm wave's release patches land a round after its end
            d0 = None
            while d0 != dict(eng.dispatch_counts):
                d0 = dict(eng.dispatch_counts)
                await asyncio.sleep(0.1)
            tasks = [asyncio.ensure_future(one(("w", 0), prompt_lens[0]))]
        else:
            sink.armed = True
            held = asyncio.ensure_future(one(("held", 0), 20))
            while not sink.entered.is_set():
                await asyncio.sleep(0.005)
            d0 = dict(eng.dispatch_counts)
            tasks = [asyncio.ensure_future(one(("w", i), n))
                     for i, n in enumerate(prompt_lens)]
            while eng._intake.qsize() < len(prompt_lens):
                await asyncio.sleep(0.005)
            sink.release.set()
            tasks.append(held)
        while not all(progress.get(("w", i), 0) >= 1
                      for i in range(len(prompt_lens))):
            await asyncio.sleep(0.005)
        d1 = dict(eng.dispatch_counts)
        await asyncio.gather(*tasks)
        verifies = eng.dispatch_counts.get("spec_verify", 0)
    finally:
        sink.release.set()
        await eng.stop()
    delta = {k: d1[k] - d0[k] for k in d1}
    # over the wave's WHOLE life: whether the first verify is dispatched
    # before this thread sees the first token is the two threads' race
    delta["spec_verify_to_the_end"] = verifies - d0.get("spec_verify", 0)
    # the held prompt's own dispatch stands first in the ledger
    calls = ledger.calls if spec else ledger.calls[1:]
    return calls, delta, eng, ledger.dest


@pytest.mark.parametrize("name,prompt_lens,dispatches", [
    # one prompt, one chunk: its own prefill, then ONE admit_first
    ("solo", (40,), [1]),
    # 100 tokens in chunks of 64: the first chunk finishes nothing and
    # launches nothing; the LAST chunk's dispatch carries the first token
    ("chunked_last_chunk", (100,), [1]),
    # four prompts that finish with one [4, 64] dispatch: ONE program
    ("group_of_4", (40, 41, 42, 43), [4]),
    # a group of two in which one lane continues: the finishing lane's
    # first token rides the group's dispatch (the other row names slot
    # B), the continuing lane's rides its own last chunk's
    ("group_one_lane_continues", (40, 100), [1, 1]),
])
async def test_first_token_costs_one_program_and_one_upload(
        monkeypatch, name, prompt_lens, dispatches):
    """Beyond the prefill's own launch, every request that completes its
    prompt with a prefill dispatch is sampled AND admitted by ONE program
    fed by ONE upload, with one token fetch a dispatch: launches a first
    token = (admit_first + patch) / first tokens <= 1, 1 / K for a
    group."""
    calls, delta, eng, _ = await _first_token_window(
        monkeypatch, prompt_lens)
    assert [n for _, _, n in calls] == dispatches, calls
    for moved, uploads, _ in calls:
        assert moved == {"admit_first": 1, "fetch": 1}, calls
        assert uploads == 1, calls
    first_tokens = len(prompt_lens) + 1     # the held prompt's too
    assert delta["patch"] == 0, delta       # no admission patches
    assert delta["admit_first"] == len(dispatches) + 1, delta
    assert (delta["admit_first"] + delta["patch"]) <= first_tokens, delta
    assert "sample_first" not in eng.dispatch_counts


async def test_speculative_admission_rides_admit_first_and_stays_parked(
        monkeypatch):
    """A speculative admission's first token is sampled by the same one
    program; its row names the no-admission slot, so the device lane
    stays parked on the scratch lane and no patch is launched."""
    calls, delta, eng, dest = await _first_token_window(
        monkeypatch, (32,), spec=True,
        speculative="ngram", num_speculative_tokens=4, spec_adaptive=False)
    assert calls == [({"admit_first": 1, "fetch": 1}, 1, 1)], calls
    assert delta["admit_first"] == 1 and delta["patch"] == 0, delta
    assert delta["spec_verify_to_the_end"] >= 1, delta   # it did speculate
    assert (dest == eng._B).all(), dest     # every lane parked
