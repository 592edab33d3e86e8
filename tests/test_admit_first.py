"""``admit_first``: ONE program and ONE upload a prefill dispatch sample
every first token of the dispatch and admit every slot (engine.py
``_build_jits.admit_first`` / ``_finish_prefill``).

Held here, at toy widths on the CPU: the program against the path it
replaced (``sampling.sample_step_impl`` on a row's own logits and key,
then ``patch``'s fields read back from ``dev``), on made-up rows and on
every dispatch of a served engine; a seeded answer served solo, in a
group of 2 and in a group of 4, token for token (logprobs to the
model's own rounding), with and without an adapter; and a follower that replays the one event
ends with the leader's ``dev``.
"""
import asyncio
import functools
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine import sampling
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.multihost import Follower
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.tenancy.adapters import random_adapter

PS = 16
R = engine_mod   # the row's columns live beside the program


def _engine(on_dispatch=None, adapters=False, **kw) -> TpuEngine:
    base = dict(
        num_pages=128, page_size=PS, max_pages_per_seq=16,
        max_decode_slots=8, prefill_buckets=(64,),
        prefill_chunks_per_round=8, cache_dtype="float32",
    )
    if adapters:
        base.update(lora_adapters=4, lora_rank=4)
    base.update(kw)
    mc = ModelConfig.tiny(dtype="float32")
    eng = TpuEngine(mc, EngineConfig(**base), mesh_config=MeshConfig(tp=1),
                    on_dispatch=on_dispatch)
    if adapters:
        eng.install_adapter(1, random_adapter(mc, 4, seed=5))
        eng.install_adapter(2, random_adapter(mc, 4, seed=6))
    return eng


# ---------------------------------------------------------------------------
# the path admit_first replaced, a request at a time

@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _sample_first(logits, key, temp, top_k, top_p, max_top_k, max_lp,
                  want_lp):
    """The parent's ``sample_first``: one row, its own key, zero counts."""
    st = sampling.SamplerState(
        keys=key[None], counts=jnp.zeros((1, logits.shape[0]), jnp.int32))
    sp = sampling.SamplingParams(
        temperature=temp[None], top_k=top_k[None], top_p=top_p[None],
        frequency_penalty=jnp.zeros(1), presence_penalty=jnp.zeros(1),
        repetition_penalty=jnp.ones(1),
    )
    toks, _ = sampling.sample_step_impl(logits[None], st, sp, max_top_k)
    if not want_lp:
        return toks, None
    chosen, ids, lps = sampling.compute_logprobs(logits[None], toks, max_lp)
    return toks, jnp.concatenate(
        [chosen[..., None], ids.astype(jnp.float32), lps], axis=-1)


def _parent_path(eng, dev: dict, logits: np.ndarray, rows: np.ndarray,
                 want_lp: bool):
    """(dev, tokens [K], packed logprobs [K, 1+2N] or None) as the parent
    made them: ``sample_first`` a row, then ``patch`` for a row that is
    admitted (a row at slot B launched no patch)."""
    B = eng._B
    e = eng.ecfg
    dev = {k: jnp.asarray(v) for k, v in dev.items()}
    logits = logits.reshape(len(rows), -1)
    toks, lps = [], []
    for i, row in enumerate(rows):
        ints, floats = row.view(np.int32), row.view(np.float32)
        tok, lp = _sample_first(
            jnp.asarray(logits[i]),
            jnp.asarray(row[R._ROW_KEY:R._ROW_KEY + 2]),
            jnp.float32(floats[R._ROW_TEMP]), jnp.int32(ints[R._ROW_TOP_K]),
            jnp.float32(floats[R._ROW_TOP_P]),
            e.max_top_k, e.max_logprobs, want_lp,
        )
        toks.append(int(tok[0]))
        lps.append(None if lp is None else np.asarray(lp[0]))
        slot = int(ints[R._ROW_SLOT])
        if slot == B:
            continue
        meta = np.array([
            slot, ints[R._ROW_CTX], floats[R._ROW_TEMP],
            ints[R._ROW_TOP_K], floats[R._ROW_TOP_P], floats[R._ROW_FREQ],
            floats[R._ROW_PRES], floats[R._ROW_REP], ints[R._ROW_ADAPTER],
        ], np.float32)
        dev = eng._patch(
            dev, jnp.zeros(B, bool), jnp.asarray(meta), tok,
            jnp.asarray(row[R._ROW_STEP_KEY:R._ROW_STEP_KEY + 2]),
            eng._zero_counts,
        )
    return ({k: np.asarray(v) for k, v in dev.items()}, np.asarray(toks),
            np.stack(lps) if want_lp else None)


def _row(slot, ctx, adapter=0, top_k=0, temp=0.0, top_p=1.0, freq=0.0,
         pres=0.0, rep=1.0, key=(0, 0), step_key=(0, 0)) -> np.ndarray:
    row = np.empty(R._ROW_W, np.uint32)
    row[R._ROW_SLOT:R._ROW_TOP_K + 1] = np.array(
        [slot, ctx, adapter, top_k], np.int32).view(np.uint32)
    row[R._ROW_TEMP:R._ROW_REP + 1] = np.array(
        [temp, top_p, freq, pres, rep], np.float32).view(np.uint32)
    row[R._ROW_KEY:R._ROW_KEY + 2] = key
    row[R._ROW_STEP_KEY:R._ROW_STEP_KEY + 2] = step_key
    return row


def _assert_same_dev(got: dict, want: dict) -> None:
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(want[k]), err_msg=k)


@pytest.fixture(scope="module")
def built():
    """One engine, never started: its programs are what is called."""
    return _engine(adapters=True)


@pytest.mark.parametrize("want_lp", [False, True])
@pytest.mark.parametrize("K", [1, 2, 4])
def test_program_equals_sample_first_then_patch(built, K, want_lp):
    """On made-up logits, a used ``dev`` and rows that sample (a seeded
    key each, top-k / top-p / temperature of their own), carry penalties
    and an adapter, and leave one lane unadmitted (slot B): the same
    tokens, the same packed logprobs, the same ``dev`` leaf for leaf as
    ``sample_first`` + ``patch`` a request."""
    eng = built
    B, V = eng._B, eng.config.vocab_size
    rng = np.random.RandomState(100 + K)
    logits = rng.randn(K, V).astype(np.float32) * 3.0
    dev = {
        "tokens": rng.randint(0, V, B).astype(np.int32),
        "ctx": rng.randint(1, 99, B).astype(np.int32),
        "dest": np.full(B, B, np.int32),
        "keys": rng.randint(0, 2 ** 31, (B, 2)).astype(np.uint32),
        "counts": rng.randint(0, 3, (B, V)).astype(np.int32),
        "temp": rng.rand(B).astype(np.float32),
        "top_k": rng.randint(0, 9, B).astype(np.int32),
        "top_p": rng.rand(B).astype(np.float32),
        "freq": rng.rand(B).astype(np.float32),
        "pres": rng.rand(B).astype(np.float32),
        "rep": (1 + rng.rand(B)).astype(np.float32),
        "adapter": rng.randint(0, 3, B).astype(np.int32),
    }
    slots = rng.permutation(B)[:K]
    rows = np.stack([
        _row(slot=int(slots[i]), ctx=40 + i, adapter=i % 3,
             top_k=(0, 5, -1, 40)[i % 4], temp=(0.0, 0.7, 1.3, 0.9)[i % 4],
             top_p=(1.0, 0.9, 0.5, 1.0)[i % 4], freq=0.1 * i,
             pres=0.2 * i, rep=1.0 + 0.1 * i,
             key=(engine_mod._FIRST_TOKEN_KEY_TAG, 1000 + i),
             step_key=(0, 1000 + i))
        for i in range(K)
    ])
    if K > 1:
        rows[K - 1, R._ROW_SLOT] = B    # a lane that continues
    if K == 1:
        logits = logits[0]              # a solo chunk returns [V]

    want_dev, want_toks, want_lps = _parent_path(
        eng, dev, logits, rows, want_lp)
    got_dev, got_toks, got_lps = eng._admit_first(
        {k: jnp.asarray(v) for k, v in dev.items()},
        jnp.asarray(logits), jnp.asarray(rows), want_lp)

    np.testing.assert_array_equal(np.asarray(got_toks), want_toks)
    if want_lp:
        np.testing.assert_array_equal(np.asarray(got_lps), want_lps)
    else:
        assert got_lps is None
    _assert_same_dev(got_dev, want_dev)
    # it did sample: a row with a temperature is not held to the argmax
    assert got_toks.shape == (K,)
    admitted = [int(s) for s in rows[:, R._ROW_SLOT].view(np.int32)
                if s != B]
    assert (np.asarray(got_dev["dest"])[admitted] == admitted).all()
    assert (np.asarray(got_dev["counts"])[admitted] == 0).all()


# ---------------------------------------------------------------------------
# a served engine: every dispatch against the parent's path, and a seeded
# answer solo against the same answer in a group

class _Held:
    """Once armed, holds the engine thread inside the next solo prefill:
    what arrives meanwhile is admitted together, as ONE group."""

    def __init__(self):
        self.armed = False
        self.entered = threading.Event()
        self.release = threading.Event()
        self.events = []

    def __call__(self, kind, payload):
        self.events.append((kind, payload))
        if kind == "prefill" and self.armed:
            self.armed = False
            self.entered.set()
            assert self.release.wait(60.0)


def _seeded(i: int, adapter: int) -> PreprocessedRequest:
    rng = np.random.RandomState(1000 + i)
    return PreprocessedRequest(
        token_ids=rng.randint(1, 256, 30 + i).tolist(),
        model=f"m:a{adapter}" if adapter else "",
        adapter_id=adapter,
        stop_conditions=StopConditions(max_tokens=10, ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=0.9, top_k=(0, 12)[i % 2], top_p=(0.95, 1.0)[i % 2],
            seed=77 + i,
            # penalties ride the row into dev: the fused steps apply them
            frequency_penalty=0.2 * (i % 2), presence_penalty=0.1,
        ),
        output_options=OutputOptions(logprobs=3),
    )


async def _collect(eng, req):
    toks, lps, tops = [], [], []
    async for out in eng.generate(req):
        toks += out.token_ids
        lps += out.log_probs or []
        tops += out.top_logprobs or []
    return toks, lps, tops


async def _serve(n: int, adapters: bool, grouped: bool):
    """Serve the n seeded requests, one after another or (behind a held
    prompt) as one group; returns (answers, the dispatches recorded as
    (dev before, logits, rows, want_lp, dev after, tokens, logprobs), the
    sink's events, the engine)."""
    sink = _Held()
    eng = _engine(on_dispatch=sink, adapters=adapters)
    seen = []
    real = eng._admit_first

    def recording(dev, logits, rows, want_lp):
        before = {k: np.asarray(v) for k, v in dev.items()}
        lg, rw = np.asarray(logits), np.asarray(rows)
        out = real(dev, logits, rows, want_lp)
        seen.append((before, lg, rw, want_lp,
                     {k: np.asarray(v) for k, v in out[0].items()},
                     np.asarray(out[1]),
                     None if out[2] is None else np.asarray(out[2])))
        return out

    eng._admit_first = recording
    reqs = [_seeded(i, (i % 3) if adapters else 0) for i in range(n)]
    try:
        if not grouped:
            answers = [await _collect(eng, r) for r in reqs]
        else:
            sink.armed = True
            held = asyncio.ensure_future(_collect(eng, _seeded(50, 0)))
            while not sink.entered.is_set():
                await asyncio.sleep(0.005)
            tasks = [asyncio.ensure_future(_collect(eng, r)) for r in reqs]
            while eng._intake.qsize() < n:
                await asyncio.sleep(0.005)
            sink.release.set()
            answers = list(await asyncio.gather(*tasks))
            await held
    finally:
        sink.release.set()
        await eng.stop()
    return answers, seen, sink.events, eng


@pytest.fixture(scope="module")
def solo_answers():
    """The four seeded answers served one at a time, by adapter use."""
    out = {}
    for adapters in (False, True):
        answers, seen, _, eng = asyncio.run(_serve(4, adapters, False))
        assert [len(rows) for _, _, rows, *_ in seen] == [1] * 4
        out[adapters] = answers
    return out


@pytest.mark.parametrize("adapters", [False, True], ids=["base", "adapters"])
@pytest.mark.parametrize("n", [1, 2, 4])
async def test_seeded_answers_equal_the_parents_path_solo_and_grouped(
        solo_answers, n, adapters):
    """Seeded, sampling requests with logprobs and penalties, served solo
    (n = 1: one after another) and as ONE group of 2 and of 4: every
    dispatch's tokens, packed logprobs and ``dev`` equal what
    ``sample_first`` + ``patch`` a request give on the same logits, and
    each whole answer, first token included, is the answer the request
    gets alone (its logprobs to the model's own rounding)."""
    grouped = n > 1
    answers, seen, events, eng = await _serve(
        4 if not grouped else n, adapters, grouped)
    if grouped:
        # the held prompt's dispatch, then ONE for the whole group
        assert [len(rows) for _, _, rows, *_ in seen] == [1, n]
        assert eng.dispatch_counts["admit_first"] == 2
        assert eng.dispatch_counts["prefill_batch"] == 1
    for before, logits, rows, want_lp, after, toks, lps in seen:
        want_dev, want_toks, want_lps = _parent_path(
            eng, before, logits, rows, want_lp)
        np.testing.assert_array_equal(toks, want_toks)
        np.testing.assert_array_equal(lps, want_lps)
        _assert_same_dev(after, want_dev)
    for got, want in zip(answers, solo_answers[adapters]):
        assert got[0] == want[0]                    # every token
        assert len(got[0]) == 10 and len(got[2]) == 10
        # a batched prefill and a fuller round differ from the solo
        # programs in the last bits (the model's, not the sampler's: each
        # dispatch above is exact on its own logits)
        assert got[1] == pytest.approx(want[1], abs=1e-4)
        for row, want_row in zip(got[2], want[2]):
            assert [i for i, _ in row] == [i for i, _ in want_row]
            assert [v for _, v in row] == pytest.approx(
                [v for _, v in want_row], abs=1e-4)
    if grouped:
        # the first token the client got is the row's of the ONE fetch
        _, _, rows, _, _, toks, lps = seen[1]
        for i, (got, _, tops) in enumerate(answers):
            assert got[0] == int(toks[i])
            assert tops[0][0][1] == pytest.approx(
                float(lps[i, 1 + eng.ecfg.max_logprobs]), abs=0)


# ---------------------------------------------------------------------------
# the follower's replay of the one event

@pytest.mark.parametrize("adapters", [False, True], ids=["base", "adapters"])
async def test_follower_replaying_the_one_event_ends_with_the_leaders_dev(
        adapters):
    """The leader emits ONE ``admit_first`` event a prefill dispatch (the
    rows, keys among them) and no admission ``patch``; a follower that
    replays the stream on its own engine (its own prefill logits, the
    same program) ends with the leader's ``dev``, leaf for leaf."""
    answers, seen, events, leader = await _serve(4, adapters, True)
    ops = [op for op, _ in events]
    assert ops.count("admit_first") == 2 and "sample_first" not in ops
    assert all(set(p) == {"clear_slots"} for op, p in events if op == "patch")
    firsts = [p for op, p in events if op == "admit_first"]
    assert [len(p["rows"]) for p in firsts] == [1, 4]
    assert all(len(r) == R._ROW_W for p in firsts for r in p["rows"])

    replica = _engine(adapters=adapters)        # never started
    f = Follower(replica, None, "tt", "e1", "run1", host_index=1)
    for seq, (op, payload) in enumerate(events, start=1):
        f.apply(dict(payload, op=op, seq=seq))
    _assert_same_dev(replica._dev, leader._dev)
    for bucket in ("admit_first", "prefill", "prefill_batch", "patch"):
        assert (replica.dispatch_counts[bucket]
                == leader.dispatch_counts[bucket]), bucket
