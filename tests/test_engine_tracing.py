"""Engine tracing plane (PR 25, PR 43): request phases closed where the
work ends (queue, first_token, decode), the time between decode steps and
what stood ahead of each round, dispatches that found the device dry and
the host segments that ran up to them, work-and-waste counters at the
dispatch, host segments and rounds as profiler annotations, and the
per-layer readers of ``benchmarks/layer_metrics`` that turn them into
metrics.

One tiny engine serves one scenario per module (a cold wave of three
prompts, then one of them again); the parametrised cases read what it
left. All CPU: counts and identities, never a device time.
"""
import asyncio
import importlib.util
import os
import time

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import latent_decode
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.telemetry import prof as tprof
from dynamo_tpu.telemetry.prof import SEGMENTS, RoundProf

PS = 16
REPO = os.path.join(os.path.dirname(__file__), "..")
PROMPT_LENS = (20, 40, 50)
OSL = 9

Q = "dynamo_request_queue_seconds"
TTFT = "dynamo_request_ttft_seconds"
FIRST = "dynamo_request_first_token_seconds"
FRONT = "dynamo_request_frontend_seconds"
PF = "dynamo_engine_prefill_tokens"
PAD = "dynamo_engine_prefill_padded_tokens"
MATCH = "dynamo_engine_prefill_matched_tokens"
LIVE = "dynamo_engine_round_live_lane_steps"
RTOK = "dynamo_engine_round_tokens"
ALIVE = "dynamo_engine_prefill_attn_live_pairs"
ASCORED = "dynamo_engine_prefill_attn_scored_pairs"
TOUCHED = "dynamo_moe_experts_touched"
ROUTED = "dynamo_moe_tokens_routed"
LOADMAX = "dynamo_moe_expert_load_max"
HCRES = "dynamo_hc_sinkhorn_residual"
CONT = "dynamo_prefill_continued_tokens"
ROWS_READ = "dynamo_decode_attn_rows_read"
ROWS_LIVE = "dynamo_decode_attn_rows_live"
GAP = "dynamo_engine_step_gap_seconds"
GAP_CLEAN = "dynamo_engine_step_gap_clean_seconds"
AHEAD = "dynamo_engine_round_prefill_tokens_ahead"
TPOT = "dynamo_request_tpot_seconds"
DRY = "dynamo_engine_dispatch_found_dry"
E2E = "dynamo_request_e2e_seconds"


def _engine(**kw) -> TpuEngine:
    base = dict(
        num_pages=128, page_size=PS, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(32, 64),
        cache_dtype="float32",
    )
    base.update(kw)
    return TpuEngine(ModelConfig.tiny(dtype="float32"),
                     EngineConfig(**base),
                     mesh_config=MeshConfig(tp=1))


def _hists(eng) -> dict:
    return {n: {"sum": h["sum"], "count": h["count"]}
            for n, h in eng.telemetry.snapshot().items()}


def _delta(a: dict, b: dict, name: str, key: str = "sum"):
    return b[name][key] - a[name][key]


async def _settled(eng) -> dict:
    """Histograms once every dispatched round is consumed: a client gets
    its finishing output a moment before the engine thread closes the
    round's books."""
    for _ in range(1000):
        h = _hists(eng)
        if h[LIVE]["count"] == h[RTOK]["count"]:
            return h
        await asyncio.sleep(0.005)
    raise AssertionError("rounds dispatched and never consumed")


async def _one(eng, prompt, osl=OSL, **req_kw):
    toks, last = [], None
    async for out in eng.generate(PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
        **req_kw,
    )):
        toks += out.token_ids
        last = out
    return toks, last.annotations


@pytest.fixture(scope="module")
def served():
    """Cold wave of three distinct prompts, then the 40-token one again."""
    prompts = [[100 * (i + 1) + j for j in range(n)]
               for i, n in enumerate(PROMPT_LENS)]

    async def scenario():
        eng = _engine()
        # what ONE segment around _admit would read, for the four it is
        # split into
        admit_wall, inner = [0.0], eng._admit

        def timed_admit():
            t = time.perf_counter()
            try:
                inner()
            finally:
                admit_wall[0] += time.perf_counter() - t

        eng._admit = timed_admit
        eng.start()
        h0 = _hists(eng)
        t_sent = time.time()
        t0 = time.monotonic()
        cold = await asyncio.gather(*[
            _one(eng, p, received_unix=t_sent if i == 0 else None,
                 request_id=f"rid-{i}")
            for i, p in enumerate(prompts)])
        h1 = await _settled(eng)
        again = await _one(eng, prompts[1])
        h2 = await _settled(eng)
        wall = time.monotonic() - t0
        one = await _one(eng, prompts[0], osl=1)   # a one-token answer
        h3 = await _settled(eng)
        starved = eng.prof.totals()["starved"]
        await eng.stop()
        return {"cold": cold, "again": again, "one": one,
                "h": (h0, h1, h2), "h3": h3, "wall": wall,
                "starved": starved, "flush_every": eng.ecfg.flush_every,
                "prof": eng.prof.totals(), "admit_wall": admit_wall[0],
                "dispatch_counts": dict(eng.dispatch_counts),
                "flight": eng.flight.snapshot()}

    return asyncio.run(scenario())


def _spans(ann, name):
    return [s for s in ann["trace"]["spans"] if s["name"] == name]


# ---- work and waste counters add up ----------------------------------


@pytest.mark.parametrize("case", [
    "prefill_tokens_are_the_prompt_tokens",
    "padded_at_least_real_and_whole_buckets",
    "cold_cache_matches_nothing",
    "repeat_matches_whole_pages",
    "round_tokens_are_completions_less_first",
    "live_lane_steps_cover_round_tokens",
])
def test_counters_add_up(served, case):
    h0, h1, h2 = served["h"]
    if case == "prefill_tokens_are_the_prompt_tokens":
        assert _delta(h0, h1, PF) == sum(PROMPT_LENS)
        assert _delta(h0, h1, PF, "count") >= 1
    elif case == "padded_at_least_real_and_whole_buckets":
        padded = _delta(h0, h1, PAD)
        assert padded >= _delta(h0, h1, PF)
        assert padded % 32 == 0
        assert _delta(h0, h1, PAD, "count") == _delta(h0, h1, PF, "count")
    elif case == "cold_cache_matches_nothing":
        assert _delta(h0, h1, MATCH) == 0
        assert _delta(h0, h1, MATCH, "count") == len(PROMPT_LENS)
    elif case == "repeat_matches_whole_pages":
        # 40 tokens: (40 - 1) // 16 = 2 sealed pages can match
        assert _delta(h1, h2, MATCH) == 2 * PS
        assert _delta(h1, h2, PF) == 40 - 2 * PS
    elif case == "round_tokens_are_completions_less_first":
        received = sum(len(t) for t, _ in served["cold"])
        assert received == OSL * len(PROMPT_LENS)
        assert _delta(h0, h1, RTOK) == received - len(PROMPT_LENS)
        assert _delta(h1, h2, RTOK) == len(served["again"][0]) - 1
    else:
        live, toks = _delta(h0, h2, LIVE), _delta(h0, h2, RTOK)
        assert live >= toks > 0
        assert live % served["flush_every"] == 0


async def test_prefill_dispatches_observe_attention_pairs():
    """A solo fresh dispatch, a batched fresh group of two (two lanes,
    no dummy: the third prompt runs solo, before or after it) and a solo
    continuation after a prefix hit each observe the (query, key)
    pairs their attention had to score and did score, by hand: buckets
    (32, 64) are below the attention's 256-row block, so a live lane
    scores its one bucket x bucket block, and a continuation adds the
    region block(s) below its q_start."""
    eng = _engine()
    eng.start()
    try:
        solo = [7 + j for j in range(20)]
        h0 = _hists(eng)
        await _one(eng, solo)
        h1 = await _settled(eng)
        assert _delta(h0, h1, ALIVE, "count") == 1
        assert _delta(h0, h1, ALIVE) == 20 * 21 // 2
        assert _delta(h0, h1, ASCORED) == 32 * 32

        group = [[100 * (i + 1) + j for j in range(n)]
                 for i, n in enumerate((40, 40, 50))]
        before = eng.batch_prefills
        await asyncio.gather(*[_one(eng, p) for p in group])
        h2 = await _settled(eng)
        assert eng.batch_prefills > before       # 2 lanes, both live
        assert _delta(h1, h2, PAD) == 3 * 64     # no lane but the prompts'
        assert _delta(h1, h2, ALIVE) == 2 * (40 * 41 // 2) + 50 * 51 // 2
        assert _delta(h1, h2, ASCORED) == 3 * 64 * 64
        assert _delta(h1, h2, ALIVE, "count") == _delta(h1, h2, PF, "count")

        # the 20-token prompt again: one sealed page (16 rows) matches, the
        # last 4 tokens run at q_start 16 against one region block (the
        # whole 256-row region is narrower than two blocks)
        await _one(eng, solo)
        h3 = await _settled(eng)
        assert _delta(h2, h3, MATCH) == PS
        assert _delta(h2, h3, ALIVE) == 4 * 16 + 4 * 5 // 2
        assert _delta(h2, h3, ASCORED) == 32 * (256 + 32)
    finally:
        await eng.stop()


async def test_a_wide_dense_bucket_observes_its_live_row_blocks():
    """A 2100-token prompt runs the 4096 bucket, whose row-wise halves
    loop over the row blocks that hold a live row (``llama._live_rows``):
    the padded-positions counter reads the 5 x 512 positions the program
    ran, not the bucket, and so does what stands ahead of the next round.
    A bucket of one or two blocks reads whole, as before."""
    from dynamo_tpu.models import llama

    assert llama.LIVE_ROW_BLOCK == 512
    eng = _engine(page_size=64, max_pages_per_seq=64, num_pages=80,
                  max_decode_slots=2, prefill_buckets=(1024, 4096))
    eng.start()
    try:
        h0 = _hists(eng)
        await _one(eng, [1 + j % 250 for j in range(2100)], osl=2)
        h1 = await _settled(eng)
        assert _delta(h0, h1, PF) == 2100
        assert _delta(h0, h1, PAD) == 2560
        assert _delta(h0, h1, AHEAD) == 2560
        await _one(eng, [3 + j % 250 for j in range(300)], osl=2)
        h2 = await _settled(eng)
        assert _delta(h1, h2, PAD) == 1024
    finally:
        await eng.stop()


# ---- request phases ---------------------------------------------------


@pytest.mark.parametrize("case", [
    "histogram_sums_queue_plus_first_token_is_ttft",
    "per_request_queue_plus_first_token_is_ttft",
    "first_token_span_wraps_its_prefill_child",
    "frontend_observed_only_when_stamped",
    "e2e_is_queue_plus_first_token_plus_decode_and_the_finishing",
    "decode_span_owns_the_decode_round_spans",
    "tpot_is_the_decode_phase_over_tokens_less_one",
    "a_one_token_answer_has_no_decode_phase",
])
def test_request_phases(served, case):
    h0, _, h2 = served["h"]
    anns = [a for _, a in served["cold"]] + [served["again"][1]]
    if case == "e2e_is_queue_plus_first_token_plus_decode_and_the_finishing":
        for ann in anns:
            (ft,), (dec,) = _spans(ann, "first_token"), _spans(ann, "decode")
            timing = ann["timing"]
            assert dec["duration_s"] == timing["decode_s"]
            phases = (timing["queue_s"] + ft["duration_s"]
                      + dec["duration_s"])
            # what is left is the finishing bookkeeping after the last
            # emit, never negative (four values rounded to the microsecond)
            assert phases <= timing["e2e_s"] + 4e-6
            assert timing["ttft_s"] + timing["decode_s"] == pytest.approx(
                phases, abs=4e-6)
            # the phases follow one another on the wall clock too
            assert dec["start_s"] == pytest.approx(
                ft["start_s"] + ft["duration_s"], abs=5e-3)
        return
    if case == "decode_span_owns_the_decode_round_spans":
        for i, ann in enumerate(anns):
            (dec,) = _spans(ann, "decode")
            assert not _spans(ann, "decode_round")   # moved under decode
            rounds = dec["children"]
            assert {c["name"] for c in rounds} == {"decode_round"}
            at = dec["attrs"]
            if i < 3:                   # the repeat drew an id of its own
                assert at["request_id"] == f"rid-{i}"
            assert at["request_id"] == _spans(
                ann, "first_token")[0]["attrs"]["request_id"]
            assert len(rounds) == at["rounds"] == (
                ann["timing"]["decode_rounds"])
            assert sum(c["attrs"]["tokens"] for c in rounds) == (
                at["tokens"]) == OSL - 1
            # a request's own prefill stands ahead of its first round
            assert 1 <= at["rounds_behind_prefill"] <= at["rounds"]
            assert at["prefill_tokens_ahead"] >= 32
            assert 0.0 <= at["behind_prefill_s"] <= dec["duration_s"] + 1e-6
        return
    if case == "tpot_is_the_decode_phase_over_tokens_less_one":
        for ann in anns:
            (dec,) = _spans(ann, "decode")
            assert ann["timing"]["tpot_s"] == pytest.approx(
                dec["duration_s"] / (OSL - 1), abs=2e-6)
        assert _delta(h0, h2, TPOT, "count") == len(anns)
        assert _delta(h0, h2, TPOT) == pytest.approx(
            sum(a["timing"]["tpot_s"] for a in anns), abs=1e-5)
        return
    if case == "a_one_token_answer_has_no_decode_phase":
        toks, ann = served["one"]
        assert len(toks) == 1
        assert not _spans(ann, "decode") and "tpot_s" not in ann["timing"]
        assert _spans(ann, "first_token")
        assert _delta(h2, served["h3"], TPOT, "count") == 0
        assert _delta(h2, served["h3"], E2E, "count") == 1
        return
    if case == "histogram_sums_queue_plus_first_token_is_ttft":
        assert _delta(h0, h2, FIRST, "count") == _delta(h0, h2, TTFT, "count")
        assert _delta(h0, h2, Q) + _delta(h0, h2, FIRST) == pytest.approx(
            _delta(h0, h2, TTFT), abs=1e-9)
    elif case == "per_request_queue_plus_first_token_is_ttft":
        for ann in anns:
            (ft,) = _spans(ann, "first_token")
            timing = ann["timing"]
            # each of the three is rounded to the microsecond
            assert timing["queue_s"] + ft["duration_s"] == pytest.approx(
                timing["ttft_s"], abs=3e-6)
    elif case == "first_token_span_wraps_its_prefill_child":
        for ann, n in zip(anns, PROMPT_LENS + (40,)):
            (ft,) = _spans(ann, "first_token")
            assert not _spans(ann, "prefill")     # moved under first_token
            (child,) = ft["children"]
            assert child["name"] == "prefill"
            end = ft["start_s"] + ft["duration_s"]
            assert end >= child["start_s"] + child["duration_s"] - 1e-5
            assert ft["attrs"]["prompt_tokens"] == n
            assert ft["attrs"]["chunks"] >= 1
            assert ft["attrs"]["rounds_in_flight_at_dispatch"] >= 0
    else:
        assert _delta(h0, h2, FRONT, "count") == 1
        stamped = [a for a in anns if _spans(a, "frontend")]
        assert len(stamped) == 1 and stamped[0] is anns[0]
        assert 0.0 <= _delta(h0, h2, FRONT) < 60.0


def test_received_unix_survives_the_wire():
    req = PreprocessedRequest(token_ids=[1, 2], received_unix=1234.5)
    assert PreprocessedRequest.from_dict(req.to_dict()).received_unix == 1234.5
    old = PreprocessedRequest(token_ids=[1, 2]).to_dict()
    old.pop("received_unix")              # a caller that predates the field
    assert PreprocessedRequest.from_dict(old).received_unix is None


# ---- the time between tokens -------------------------------------------


@pytest.mark.parametrize("case", [
    "clean_plus_behind_is_every_consumed_round",
    "every_padded_token_stands_ahead_of_exactly_one_round",
    "the_gaps_fit_inside_the_wall",
    "every_dispatch_polled_and_the_first_found_the_device_dry",
])
def test_token_gaps_of_a_served_wave(served, case):
    h0, h1, h2 = served["h"]
    if case == "clean_plus_behind_is_every_consumed_round":
        rounds = _delta(h0, h2, RTOK, "count")
        assert rounds > 0 and _delta(h0, h2, GAP, "count") == rounds
        assert (_delta(h0, h2, GAP_CLEAN, "count")
                + _delta(h0, h2, AHEAD, "count")) == rounds
        assert _delta(h0, h2, GAP_CLEAN) <= _delta(h0, h2, GAP)
    elif case == "every_padded_token_stands_ahead_of_exactly_one_round":
        # every request decodes, so every prefill has a round after it
        assert _delta(h0, h2, AHEAD) == _delta(h0, h2, PAD)
        assert 1 <= _delta(h0, h2, AHEAD, "count") <= _delta(
            h0, h2, PAD, "count")
    elif case == "the_gaps_fit_inside_the_wall":
        assert 0.0 < _delta(h0, h2, GAP) * served["flush_every"] <= (
            served["wall"])
    else:
        # one poll a fused round and a prefill program (no speculation)
        assert _delta(h0, h2, DRY, "count") == (
            _delta(h0, h2, LIVE, "count") + _delta(h0, h2, PF, "count"))
        assert 1 <= _delta(h0, h1, DRY) <= _delta(h0, h1, DRY, "count")


class _Clock:
    """The engine module's ``time`` on a script: every reading inside one
    scripted action is the same instant."""

    def __init__(self):
        self.t = 0.0

    def monotonic(self):
        return self.t

    def time(self):
        return 1_000_000.0 + self.t


class _Handle:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


@pytest.fixture(scope="module")
def scripted():
    """An engine that was never started, driven by hand on a scripted
    clock: three fused rounds (no lane holds a request), two prefill
    dispatches booked between the first and the second, the second
    dispatched while the first is in flight, the third after a stretch
    with none in flight."""
    from dynamo_tpu.engine import engine as engine_mod

    eng = _engine()
    clock = _Clock()
    real, engine_mod.time = engine_mod.time, clock
    try:
        h0 = _hists(eng)
        consumed = []

        def dispatch(t, newest=None):
            clock.t = t
            if newest is not None:
                eng._newest = newest
            eng._dispatch_round([0], False, False)
            return eng._entries[-1]

        def consume(t):
            clock.t = t
            eng._consume_entry(eng._entries.pop(0))
            consumed.append(_hists(eng))

        first = dispatch(10.0)                    # nothing tracked: dry
        eng._newest = _Handle(False)
        eng._note_prefill_dispatch(20, 32)        # device busy: not dry
        eng._note_prefill_dispatch(40, 64)
        second = dispatch(11.0, _Handle(False))   # pipelined: not dry
        consume(12.0)
        consume(15.0)
        third = dispatch(20.0, _Handle(True))     # ran dry meanwhile
        consume(21.0)
        return {"h0": h0, "consumed": consumed, "n": eng.ecfg.flush_every,
                "ahead": [e.ahead for e in (first, second, third)]}
    finally:
        engine_mod.time = real


@pytest.mark.parametrize("case", [
    "the_gaps_telescope_to_the_wall",
    "a_prefill_marks_the_next_dispatched_round_and_no_other",
    "clean_plus_behind_is_all",
    "dry_into_an_idle_engine_and_not_in_a_pipelined_steady_state",
])
def test_token_gaps_on_a_scripted_run(scripted, case):
    h0, (c1, c2, c3), n = scripted["h0"], scripted["consumed"], scripted["n"]
    if case == "the_gaps_telescope_to_the_wall":
        # walls 12 - 10, 15 - 12 (dispatched at 11, before the first was
        # consumed) and 21 - 20 (none in flight from 15 to 20)
        assert _delta(h0, c1, GAP) * n == pytest.approx(2.0, abs=1e-12)
        assert _delta(c1, c2, GAP) * n == pytest.approx(3.0, abs=1e-12)
        assert _delta(c2, c3, GAP) * n == pytest.approx(1.0, abs=1e-12)
        assert _delta(h0, c3, GAP) * n == pytest.approx(
            (21.0 - 10.0) - (20.0 - 15.0), abs=1e-12)
    elif case == "a_prefill_marks_the_next_dispatched_round_and_no_other":
        assert scripted["ahead"] == [(0, 0, 0), (1, 2, 96), (2, 0, 0)]
        assert _delta(h0, c1, AHEAD, "count") == 0
        assert (_delta(c1, c2, AHEAD, "count"), _delta(c1, c2, AHEAD)) == (
            1, 96)
        assert _delta(c2, c3, AHEAD, "count") == 0
    elif case == "clean_plus_behind_is_all":
        assert _delta(h0, c3, GAP, "count") == 3
        assert _delta(h0, c3, GAP_CLEAN, "count") == 2
        assert _delta(h0, c3, GAP_CLEAN) * n == pytest.approx(2.0 + 1.0)
    else:
        # five polls: round (nothing tracked: dry), two prefills and the
        # pipelined round (the newest handle not ready), the round after
        # the engine ran dry
        assert _delta(h0, c3, DRY, "count") == 5
        assert _delta(h0, c1, DRY) == 1.0       # all before the first consume
        assert _delta(c1, c2, DRY) == 0.0
        assert _delta(c2, c3, DRY) == 1.0


def test_e2e_is_the_phases_on_a_scripted_clock():
    """queue 0.5 + first_token 0.5 + decode 2.0 + 0.25 of finishing
    bookkeeping = 3.25 s of engine E2E; TPOT = 2.0 / (5 - 1)."""
    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.engine.engine import _Entry, _Request

    eng = _engine()
    r = _Request(req=PreprocessedRequest(token_ids=[1, 2], request_id="r1"),
                 seq=None, out=None, loop=None, tokens=[1, 2],
                 enqueue_time=100.0)
    clock = _Clock()
    real, engine_mod.time = engine_mod.time, clock
    try:
        h0 = _hists(eng)
        r.t_prefill_start = 100.5
        clock.t = 101.0
        r.first_token_time = r.t_last_emit = clock.t
        eng._note_first_token(r)
        r.produced = 5
        clock.t = 102.0
        eng._note_emit(r, 2, _Entry("round", None, t_dispatch=101.5,
                                    ahead=(7, 1, 64)), "decode_round")
        clock.t = 103.0
        eng._note_emit(r, 2, _Entry("round", None, t_dispatch=102.5,
                                    late={"gc": 0.25, "host": 0.5}),
                       "decode_round")
        clock.t = 103.25
        ann = eng._final_annotations(r)
        h1 = _hists(eng)
    finally:
        engine_mod.time = real
    timing = ann["timing"]
    (ft,), (dec,) = _spans(ann, "first_token"), _spans(ann, "decode")
    assert timing["e2e_s"] == 3.25 == (
        timing["queue_s"] + ft["duration_s"] + dec["duration_s"] + 0.25)
    assert (timing["queue_s"], ft["duration_s"], dec["duration_s"]) == (
        0.5, 0.5, 2.0)
    assert dec["start_s"] == ft["start_s"] + ft["duration_s"]
    assert timing["tpot_s"] == 0.5
    assert (_delta(h0, h1, TPOT, "count"), _delta(h0, h1, TPOT)) == (1, 0.5)
    assert dec["attrs"] == {
        "request_id": "r1", "tokens": 4, "rounds": 2,
        "rounds_behind_prefill": 1, "behind_prefill_s": 1.0,
        "prefill_tokens_ahead": 64,
        # the second round was consumed LATE: its excess by cause
        "late_rounds": 1, "late_s": {"gc": 0.25, "host": 0.5}}
    assert (timing["late_rounds"], timing["late_s"]) == (
        1, {"gc": 0.25, "host": 0.5})
    from dynamo_tpu.protocols.common import FinishReason, LLMEngineOutput
    from dynamo_tpu.sdk import request_stats
    st = request_stats([LLMEngineOutput(
        token_ids=[], finish_reason=FinishReason.LENGTH, annotations=ann)])
    assert (st.late_rounds, st.late_s) == (1, {"gc": 0.25, "host": 0.5})
    assert [(c["name"], c["duration_s"], c["attrs"]["tokens"])
            for c in dec["children"]] == [("decode_round", 0.5, 2)] * 2


# ---- dispatches that found the device dry, and starved time -------------


async def test_one_round_in_flight_finds_the_device_dry_at_every_round():
    """One round in flight at most and no early dispatch: every round is
    dispatched after the only round in flight was consumed, so each finds
    the device dry, and what ran since the dispatch before it -- the
    blocking fetch, admission -- is booked as starved by segment."""
    eng = _engine(max_inflight_rounds=0, round_pipeline=False)
    eng.start()
    h0 = _hists(eng)
    toks, _ = await _one(eng, list(range(1, 30)), osl=17)
    h1 = await _settled(eng)
    await eng.stop()
    t = eng.prof.totals()
    rounds = -(-(17 - 1) // eng.ecfg.flush_every)
    assert len(toks) == 17 and _delta(h0, h1, LIVE, "count") == rounds
    # the prefill (into an idle engine) and every round but perhaps the
    # first, which may catch the prefill still running
    assert _delta(h0, h1, DRY, "count") == rounds + 1
    assert _delta(h0, h1, DRY) >= rounds
    starved = t["starved"]["segments"]
    assert starved["fetch"] > 0.0
    assert t["starved"]["total_s"] == pytest.approx(sum(starved.values()))
    # a subset of the segment's own time, never more
    assert all(starved[s] <= t["segments"][s] + 1e-9 for s in SEGMENTS)


def test_a_fetch_that_finds_its_program_unfinished_moves_the_mark():
    """The host saw the device busy: a dry dispatch after it charges only
    what ran since, not the stretch back to the dispatch before."""
    from dynamo_tpu.engine.engine import _Entry

    eng = _engine()
    clock = _Clock()
    real, tprof.time = tprof.time, clock
    try:
        p = eng.prof
        p.begin_round()
        p.enter(SEGMENTS.index("fetch"))
        p.poll(False)                          # a dispatch, at t = 0
        clock.t = 5.0
        eng._entries = [_Entry("round", _Handle(False))]
        eng._process_entries()                 # not ready: busy at t = 5
        assert len(eng._entries) == 1          # and left in flight
        clock.t = 6.0
        eng._newest = _Handle(True)
        eng._poll_dry()                        # dry at t = 6
        p.end_round()
    finally:
        tprof.time = real
    t = p.totals()
    assert t["starved"]["segments"]["fetch"] == 1.0
    assert t["segments"]["fetch"] == 6.0


async def test_idle_engine_records_no_starved_time(served):
    eng = _engine()
    eng.start()
    await asyncio.sleep(0.15)             # spins idle, nothing to serve
    await eng.stop()
    assert eng.prof.totals()["starved"]["total_s"] == 0.0
    assert _hists(eng)[DRY]["count"] == 0     # nothing was dispatched
    # the pipelined scenario kept a round in flight: starved stays a
    # small part of the wall it was measured over
    assert served["starved"]["total_s"] >= 0.0


@pytest.mark.parametrize("case", [
    "a_dry_poll_charges_the_stretch_since_the_poll_before",
    "a_stretch_with_an_idle_spin_in_it_is_dropped",
])
def test_roundprof_poll_on_a_scripted_clock(case):
    clock = _Clock()
    real, tprof.time = tprof.time, clock
    try:
        p = RoundProf()
        i, j = SEGMENTS.index("fetch"), SEGMENTS.index("admit")
        p.begin_round()
        p.enter(i)
        clock.t = 2.0
        p.poll(False)                     # still fed: only moves the mark
        clock.t = 3.0
        p.enter(j)
        clock.t = 7.0
        p.poll(True)                      # dry: fetch 1 + admit 4
        clock.t = 8.0
        p.poll(True)                      # dry again: admit 1, no more
        clock.t = 9.0
        p.end_round()
        if case == "a_stretch_with_an_idle_spin_in_it_is_dropped":
            p.begin_round()
            clock.t = 15.0
            p.end_round(record=False)     # nothing live: the idle spin
            p.begin_round()
            p.enter(j)
            clock.t = 25.0
            p.poll(True)                  # dry for want of work
            p.end_round()
    finally:
        tprof.time = real
    t = p.totals()
    s = t["starved"]["segments"]
    assert (s["fetch"], s["admit"]) == (1.0, 5.0)
    assert t["starved"]["total_s"] == 6.0
    assert t["segments"]["fetch"] == 3.0
    assert p.summary()["starved"]["segments"].keys() == {"fetch", "admit"}


# ---- annotations ------------------------------------------------------


class _StubAnnotation:
    log: list = []
    enabled = True

    def __init__(self, name, **stats):
        self.name = name
        _StubAnnotation.log.append(("open", name, *sorted(stats.items())))

    def __exit__(self, *exc):
        _StubAnnotation.log.append(("close", self.name))

    @classmethod
    def is_enabled(cls):
        return cls.enabled


@pytest.mark.parametrize("session", [True, False])
def test_annotations_balanced_across_enter_push_end(session):
    _StubAnnotation.log = []
    _StubAnnotation.enabled = session
    p = RoundProf()
    p._annotation = _StubAnnotation
    for record in (True, False):
        p.begin_round()
        p.enter(SEGMENTS.index("fetch"))
        prev = p.push(SEGMENTS.index("annotate"))
        p.enter(prev)
        p.poll(True)
        p.enter(SEGMENTS.index("admit"))
        p.poll(False)
        p.end_round(record=record)
    log = _StubAnnotation.log
    if not session:
        assert log == []
        return
    opens = [ev[1] for ev in log if ev[0] == "open"]
    assert opens == ["host/fetch", "host/annotate", "host/fetch",
                     "host/admit"] * 2
    # strictly alternating: never two segments open at once, none left
    assert [ev[0] for ev in log] == ["open", "close"] * len(opens)
    assert all(log[i][1] == log[i + 1][1] for i in range(0, len(log), 2))


@pytest.mark.parametrize("session", [True, False])
def test_round_marks_carry_their_stats_only_in_a_session(session):
    _StubAnnotation.log = []
    _StubAnnotation.enabled = session
    p = RoundProf()
    p._annotation = _StubAnnotation
    p.begin_round()
    p.enter(SEGMENTS.index("dispatch"))
    p.mark_round(dispatched=7, programs_ahead=1, padded_tokens_ahead=64)
    p.end_round()
    marks = [ev for ev in _StubAnnotation.log
             if ev[1] == tprof.ROUND_ANNOTATION]
    if not session:
        assert _StubAnnotation.log == []
        return
    # opened and closed at once, inside the open segment
    assert marks == [
        ("open", "engine/round", ("dispatched", 7),
         ("padded_tokens_ahead", 64), ("programs_ahead", 1)),
        ("close", "engine/round")]


# ---- a late round names its cause, the loop's clock closes --------------


@pytest.fixture(scope="module")
def late():
    """An engine that was never started, its fused rounds dispatched and
    consumed by hand on ONE scripted clock (the engine module's and the
    prof module's), a profiler session stubbed on. Fifteen clean rounds of
    1 s; one of 10 s behind a prefill and a sixteenth clean one, which
    nothing judges yet; then one round a cause, each read off the books
    before and after it, beside the mean of the sixteen clean walls
    before it."""
    from dynamo_tpu.engine import engine as engine_mod

    eng = _engine()
    clock = _Clock()
    real = engine_mod.time, tprof.time
    engine_mod.time = tprof.time = clock
    _StubAnnotation.log = []
    _StubAnnotation.enabled = True
    p = eng.prof
    p._annotation, p._tracing = _StubAnnotation, True
    p.register_thread()
    out = {"n": eng.ecfg.flush_every}
    clean_walls = []

    def run_round(wall, during=None, behind=False, name=None):
        last = clean_walls[-tprof.LATE_MIN_CLEAN:]
        t0, before = clock.t, p.totals()["late"]
        if behind:
            eng._newest = _Handle(False)
            eng._note_prefill_dispatch(40, 64)
        eng._dispatch_round([0], False, False)
        if during is not None:
            during()
        clock.t = t0 + wall
        eng._consume_entry(eng._entries.pop(0))
        clock.t += 1.0                    # none in flight for a second
        if not behind:
            clean_walls.append(wall)
        if name is not None:
            out[name] = {
                "expected": sum(last) / len(last),
                "before": before, "after": p.totals()["late"],
                "flight": [ev for ev in eng.flight.snapshot()
                           if ev["kind"] == "late_round"][-1:]}

    def collection():
        clock.t += 0.5
        tprof._gc_hook("start", {"generation": 2})
        clock.t += 3.0
        tprof._gc_hook("stop", {"generation": 2})

    def host_pass():
        p.begin_round()
        p.enter(SEGMENTS.index("admit_launch"))
        clock.t += 3.0
        p.enter(SEGMENTS.index("fetch"))
        clock.t += 0.5
        p.end_round()
        p._tracing = True                 # the stub session stays on

    try:
        for _ in range(15):
            run_round(1.0)
        run_round(10.0, behind=True, name="too_few_behind")
        run_round(10.0, name="too_few_clean")
        # 16 clean rounds now, the last of 10 s: from here every round is
        # judged against the mean wall of the sixteen clean ones before it
        run_round(1.5, name="on_time")
        run_round(6.0, during=collection, name="gc")
        run_round(9.0, behind=True, name="behind_prefill")
        run_round(7.0, during=host_pass, name="host")
        run_round(5.0, name="other")
        out["marks"] = [ev for ev in _StubAnnotation.log
                        if ev[:2] == ("open", tprof.ROUND_ANNOTATION)
                        and any(k == "consumed" for k, _ in ev[2:])]
        out["totals"] = p.totals()
    finally:
        p.unregister_thread()
        engine_mod.time, tprof.time = real
    return out


@pytest.mark.parametrize("case", [
    "too_few_behind", "too_few_clean", "on_time",
    "gc", "behind_prefill", "host", "other", "the_marks_name_the_cause",
])
def test_a_late_round_names_its_cause(late, case):
    if case == "the_marks_name_the_cause":
        # every consume leaves its mark; only the late ones say so
        causes = [dict(ev[2:]).get("late") for ev in late["marks"]]
        assert causes == [None] * 18 + ["gc", "behind_prefill", "host",
                                        "other"]
        assert late["totals"]["late"]["rounds"] == 4
        assert late["totals"]["late"]["judged"] == 5
        return
    got = late[case]
    before, after = got["before"], got["after"]
    booked = {c: after["excess_s"][c] - before["excess_s"][c]
              for c in tprof.LATE_CAUSES}
    if case.startswith("too_few"):
        # 15 clean rounds seen, then 15 again (the round behind a prefill
        # is no clean one): a wall of ten times theirs is judged by nothing
        assert got["expected"] == 1.0
        assert after == before and after["judged"] == 0
        assert got["flight"] == []
        return
    # (15 x 1 s + 10 s) / 16, then the window of sixteen moves on
    exp = got["expected"]
    assert after["judged"] == before["judged"] + 1
    if case == "on_time":
        assert exp == 25.0 / 16 and 1.5 <= 2 * exp
        assert after["rounds"] == before["rounds"] and got["flight"] == []
        return
    assert after["rounds"] == before["rounds"] + 1
    wall = {"gc": 6.0, "behind_prefill": 9.0, "host": 7.0, "other": 5.0}[case]
    assert wall > 2 * exp
    want = dict.fromkeys(tprof.LATE_CAUSES, 0.0)
    if case == "gc":
        # the collector's 3 s first; the host ran nothing: the rest is
        # the device's own
        want.update(gc=3.0, other=wall - exp - 3.0)
    else:
        want[case] = wall - exp
    assert booked == pytest.approx(want)
    (ev,) = got["flight"]
    assert (ev["cause"], ev["wall_ms"]) == (case, wall * 1e3)
    assert ev["expected_ms"] == pytest.approx(exp * 1e3, abs=1e-3)
    assert ev["excess_ms"] == pytest.approx(
        {c: v * 1e3 for c, v in want.items() if v}, abs=1e-3)
    # the host segment that ran most since the consume before
    assert ev["host_segment"] == (
        "admit_launch" if case == "host" else SEGMENTS[0])


@pytest.mark.parametrize("case", [
    "wall_plus_idle_is_the_threads_life",
    "the_four_admit_segments_are_what_one_read",
    "the_collector_was_timed",
])
def test_the_loops_clock_closes_on_a_served_engine(served, case):
    prof = served["prof"]
    seg = prof["segments"]
    if case == "wall_plus_idle_is_the_threads_life":
        assert sum(seg.values()) == pytest.approx(prof["wall_s"])
        assert prof["idle"]["waits"] >= 1 and prof["idle"]["total_s"] > 0.0
        assert 0.98 <= prof["loop_coverage"] <= 1.0 + 1e-9
    elif case == "the_four_admit_segments_are_what_one_read":
        parts = [seg[s] for s in ("admit", "admit_pack", "admit_launch",
                                  "admit_first")]
        assert all(v > 0.0 for v in parts)
        # all the segment switches inside _admit cost less than this
        assert sum(parts) == pytest.approx(served["admit_wall"], abs=5e-3)
        n = sum(served["dispatch_counts"][k]
                for k in ("prefill", "prefill_batch", "sp_prefill"))
        assert n == len(PROMPT_LENS) + 2
    else:
        gc_t = prof["gc"]
        assert sum(gc_t["collections"]) >= 1
        assert sum(gc_t["by_segment_s"].values()) == pytest.approx(
            sum(gc_t["pause_s"]))
        assert gc_t["on_loop_s"] <= sum(gc_t["pause_s"]) + 1e-12
        # on this box a stall is compile time in admit_launch, and each
        # left its event
        stalls = [ev for ev in served["flight"] if ev["kind"] == "stall"]
        assert prof["stalls"]["count"] >= len(stalls)
        assert prof["stalls"]["total_s"] == pytest.approx(
            sum(prof["stalls"]["by_segment_s"].values()))


def test_segments_land_on_the_profilers_host_plane(tmp_path):
    """A real profiler session at the benchmark's tracer levels: the
    segments are events of the /host:CPU plane, named host/<segment>; the
    empty engine's wait is host/idle and a collection pause/gc beside
    them."""
    import gc

    import jax
    from jax.profiler import ProfileData

    from benchmarks.trace_reduce import find_xplane

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    p = RoundProf()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        p.begin_round()
        p.enter(SEGMENTS.index("admit"))
        time.sleep(0.002)
        p.enter(SEGMENTS.index("dispatch"))
        p.mark_round(dispatched=7, programs_ahead=1, padded_tokens_ahead=64)
        p.mark_round(consumed=7, wall_us=1234, steps=4)
        p.end_round()
        p.register_thread()
        p.begin_round()                   # an empty engine's pass
        gc.collect()
        p.end_round(record=False)
        p.idle_enter()
        time.sleep(0.002)
        p.idle_exit()
    finally:
        p.unregister_thread()
        jax.profiler.stop_trace()
    # the round marks come back with their stats, keyed by ordinal
    gaps = _trace_gaps()
    modules, dispatched, consumed = gaps.read_rounds(
        find_xplane(str(tmp_path)))
    assert modules == []                  # no chip here
    assert {o: v[1:] for o, v in dispatched.items()} == {7: (1, 64)}
    assert {o: v[1:] for o, v in consumed.items()} == {
        7: (1_234_000, 4, None)}
    assert dispatched[7][0] <= consumed[7][0]
    data = ProfileData.from_file(find_xplane(str(tmp_path)))
    found = {ev.name: ev.duration_ns
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith((tprof.ANNOTATION_PREFIX,
                                    tprof.PAUSE_PREFIX))}
    assert {"host/admit", "host/dispatch", "host/idle",
            "pause/gc"} <= set(found)
    assert found["host/admit"] >= 2_000_000
    assert found["host/idle"] >= 2_000_000
    # and tools/trace_gaps.py reads the three kinds apart
    _, segments, pauses = gaps.read_planes(find_xplane(str(tmp_path)))
    assert {"admit", "dispatch", "idle"} <= set(segments)
    assert list(pauses) == ["gc"]
    assert p.totals()["gc"]["collections"][2] >= 1


# ---- the per-layer readers -------------------------------------------


def _reader(name):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _sources():
    def snap(t, hists, starved, admit, prefills, gc_s, idle, stall, late):
        rounds, judged, by_gc, by_host = late
        return {"t_wall": t,
                "histograms": {k: {"sum": s, "count": c}
                               for k, (s, c) in hists.items()},
                "dispatch_counts": dict(zip(
                    ("prefill", "prefill_batch", "sp_prefill", "round"),
                    prefills)),
                "prof": {"rounds": 0, "wall_s": 0.0,
                         "segments": dict(zip(
                             ("admit", "admit_pack", "admit_launch",
                              "admit_first", "fetch"), admit)),
                         "starved": {"total_s": starved, "segments": {}},
                         "gc": {"collections": [9, 9, 9], "pause_s": gc_s,
                                "on_loop_s": 0.0, "by_segment_s": {}},
                         "idle": {"total_s": idle, "waits": 7},
                         "stalls": {"count": 1, "total_s": stall,
                                    "by_segment_s": {}},
                         "late": {"rounds": rounds, "judged": judged,
                                  "excess_s": {
                                      "gc": by_gc, "behind_prefill": 9.0,
                                      "host": by_host, "other": 9.0}}}}

    before = snap(100.0, {
        FRONT: (1.0, 10), FIRST: (5.0, 10), PF: (1000.0, 4),
        PAD: (2000.0, 4), MATCH: (0.0, 4), LIVE: (400.0, 20),
        RTOK: (300.0, 20), ALIVE: (1e6, 4), ASCORED: (4e6, 4),
        TOUCHED: (1000.0, 20), ROUTED: (3000.0, 20), LOADMAX: (100.0, 20),
        HCRES: (2e-6, 20), CONT: (500.0, 4), ROWS_READ: (1e6, 20),
        ROWS_LIVE: (4e5, 20), GAP: (0.5, 20), GAP_CLEAN: (0.2, 15),
        AHEAD: (10000.0, 5), TPOT: (0.3, 10), DRY: (1.0, 24)},
        1.0, (1.0, 0.5, 2.0, 0.5, 50.0), (10, 5, 0, 999),
        [0.25, 0.5, 1.0], 2.0, 0.125, (3, 100, 0.5, 0.25))
    after = snap(150.0, {
        FRONT: (1.5, 60), FIRST: (30.0, 60), PF: (17000.0, 54),
        PAD: (26000.0, 54), MATCH: (4000.0, 54), LIVE: (3600.0, 120),
        RTOK: (2700.0, 120), ALIVE: (7e6, 54), ASCORED: (19e6, 54),
        TOUCHED: (205800.0, 120), ROUTED: (617400.0, 120),
        LOADMAX: (700.0, 120), HCRES: (3.2e-5, 120), CONT: (6900.0, 54),
        ROWS_READ: (9e6, 120), ROWS_LIVE: (2.4e6, 120),
        GAP: (3.0, 120), GAP_CLEAN: (1.0, 95), AHEAD: (110000.0, 25),
        TPOT: (1.8, 60), DRY: (4.0, 174)}, 3.5,
        (2.0, 1.0, 5.0, 1.0, 90.0), (40, 24, 1, 9999),
        [0.5, 0.75, 1.375], 14.5, 0.375, (11, 300, 0.625, 0.5))
    return {"before": before, "after": after,
            "engine_up": {"flush_every": 4},
            "config": {"engine": {"max_decode_slots": 8},
                       "num_hidden_layers": 5, "first_k_dense_replace": 1,
                       "n_routed_experts": 256}}


READERS = {
    "frontend.pre_engine_ms_mean": (0.5 / 50 * 1e3, [FRONT]),
    "sched.first_token_ms_mean": (25.0 / 50 * 1e3, [FIRST]),
    "sched.starved_share": (2.5 / 50.0 * 100, ["starved"]),
    "step.prefill_pad_share": ((1 - 16000 / 24000) * 100, [PF]),
    "step.decode_lane_util": (2400 / (100 * 4 * 8) * 100, [RTOK]),
    "step.decode_garbage_share": ((1 - 2400 / 3200) * 100, [LIVE]),
    "kv.prefix_hit_share": (4000 / (4000 + 16000) * 100, [MATCH]),
    "step.prefill_attn_live_share": (6e6 / 15e6 * 100, [ASCORED]),
    # 100 rounds x 4 steps x 4 expert layers x 256 experts = 409600
    "moe.experts_touched_share": (204800 / 409600 * 100, [TOUCHED]),
    # mean of the rounds' maxima 6 over 614400 / 204800 = 3 a touched expert
    "moe.load_max_over_mean": (6.0 / 3.0, [LOADMAX]),
    # PR 37: the mean of 100 rounds' maxima; 6400 of the window's 16000
    # prompt positions in continuing chunks; 2e6 of 8e6 rows read
    "hc.sinkhorn_residual_max": (3e-5 / 100, [HCRES]),
    "step.prefill_continued_share": (6400 / 16000 * 100, [CONT]),
    "step.decode_attn_live_share": (2e6 / 8e6 * 100, [ROWS_READ]),
    # PR 43: 100 rounds consumed, 80 of them clean at 10 ms a step and
    # 20 behind 5000 padded tokens each, 2.5 s of gaps in all
    "sched.step_gap_ms_mean": (2.5 / 100 * 1e3, [GAP]),
    "sched.step_gap_clean_ms_mean": (0.8 / 80 * 1e3, [GAP_CLEAN]),
    "sched.gap_behind_prefill_share": ((2.5 - 100 * 0.01) / 2.5 * 100,
                                       [GAP_CLEAN]),
    "sched.rounds_behind_prefill_share": (20 / 100 * 100, [AHEAD]),
    "sched.prefill_ktok_ahead_mean": (100000 / 20 / 1e3, [AHEAD]),
    "sched.tpot_engine_ms_mean": (1.5 / 50 * 1e3, [TPOT]),
    "sched.dispatch_dry_share": (3.0 / 150 * 100, [DRY]),
    # PR 56, all of prof.totals(): 12.5 of the 50 s empty; 0.25 + 0.25 +
    # 0.375 s inside collections, 375 ms of them full ones; 250 ms of
    # stalled passes; 8 of 200 judged rounds late, 125 + 250 ms of their
    # excess the host's; the four admit segments 1 + 0.5 + 3 + 0.5 = 5 s
    # over 30 + 19 + 1 prefill dispatches
    "sched.empty_share": (12.5 / 50 * 100, ["idle"]),
    "sched.gc_pause_share": (0.875 / 50 * 100, ["gc"]),
    "sched.gc_full_pause_ms": (375.0, ["gc"]),
    "sched.stall_ms": (250.0, ["stalls"]),
    "sched.late_round_share": (8 / 200 * 100, ["late"]),
    "sched.late_host_ms": (375.0, ["late"]),
    "sched.admit_ms_per_prefill": (5.0 / 50 * 1e3, ["segments"]),
    "sched.admit_launch_share": (3.0 / 5.0 * 100, ["segments"]),
}
# where the key is there and nothing happened in the window: 0.0, not None
QUIET = ("sched.empty_share", "sched.gc_pause_share",
         "sched.gc_full_pause_ms", "sched.stall_ms",
         "sched.late_round_share", "sched.late_host_ms",
         "sched.admit_ms_per_prefill", "sched.admit_launch_share")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_hand_computed_value(name):
    want, _ = READERS[name]
    assert _reader(name)(_sources()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_on_a_program_without_the_counter(name):
    """The parent commit's snapshots lack the new names: nothing to read,
    no exception, and the result line leaves the metric out."""
    _, missing = READERS[name]
    src = _sources()
    for snap in (src["before"], src["after"]):
        for key in missing:
            snap["histograms"].pop(key, None)
            snap["prof"].pop(key, None)
    assert _reader(name)(src) is None


@pytest.mark.parametrize("name", QUIET)
def test_reader_gives_zero_where_nothing_happened(name):
    src = _sources()
    src["after"] = dict(src["before"], t_wall=150.0)
    assert _reader(name)(src) == 0.0


@pytest.fixture(scope="module")
def served_streams():
    """The four-stream latent block (tiny, float32) serving two prompts:
    70 tokens in buckets of 32 (one fresh chunk, two continuing), and 20."""
    from dynamo_tpu.models import llama

    cfg = ModelConfig.tiny_mla_moe_mhc()
    params = llama.init_params(cfg, 11)

    async def scenario():
        eng = TpuEngine(cfg, EngineConfig(
            num_pages=64, page_size=PS, max_pages_per_seq=8,
            max_decode_slots=4, prefill_buckets=(32,),
            cache_dtype="float32"), params=params,
            mesh_config=MeshConfig(tp=1))
        eng.start()
        h0 = _hists(eng)
        await asyncio.gather(
            _one(eng, [7 + i for i in range(70)], osl=9),
            _one(eng, [300 + i for i in range(20)], osl=9))
        h1 = await _settled(eng)
        await eng.stop()
        lens = (np.asarray(eng._dev["ctx"]), eng._ctx_disp.copy())
        return h0, h1, eng.ecfg, lens
    return asyncio.run(scenario())


@pytest.mark.parametrize("case", [
    "continued_tokens_are_the_later_chunks",
    "one_residual_a_consumed_round_and_converged",
    "rows_read_cover_rows_live_in_whole_chunks",
    "a_lane_without_a_request_counts_on_on_the_device",
])
def test_counters_of_the_four_stream_block(served_streams, case):
    h0, h1, e, (dev_lens, host_lens) = served_streams
    if case == "continued_tokens_are_the_later_chunks":
        # 70 = 32 fresh + 32 + 6 continuing; the 20-token prompt is fresh
        assert _delta(h0, h1, CONT) == 38
        assert _delta(h0, h1, CONT, "count") == _delta(h0, h1, PF, "count")
        assert _delta(h0, h1, PF) == 90
    elif case == "one_residual_a_consumed_round_and_converged":
        rounds = _delta(h0, h1, RTOK, "count")
        assert rounds > 0 and _delta(h0, h1, HCRES, "count") == rounds
        # b_res of order 1: 20 iterations leave the slowest token ~1e-3
        assert 0.0 <= _delta(h0, h1, HCRES) / rounds < 2e-2
    elif case == "rows_read_cover_rows_live_in_whole_chunks":
        read, live = _delta(h0, h1, ROWS_READ), _delta(h0, h1, ROWS_LIVE)
        rounds = _delta(h0, h1, ROWS_READ, "count")
        assert rounds == _delta(h0, h1, LIVE, "count")
        assert 0 < live <= read
        # the XLA loop of the CPU meshes: steps x lanes x whole chunks
        # (the region is shorter than one)
        chunk = min(latent_decode.CHUNK, e.max_context)
        assert read % (e.flush_every * e.max_decode_slots * chunk) == 0
    else:
        # two requests, four lanes: every step adds 1 to EVERY lane's
        # device length (the round body), and a lane that never held a
        # request, or was freed, keeps counting from 1 while the host's
        # mirror holds it at 1. So the round hands the attention `live`,
        # and a lane that is not live reads no region row
        # (ops/latent_decode.py; tests/test_mla_moe.py::
        # test_latent_decode_kernel_equals_the_xla_loop_lane_by_lane)
        assert host_lens.tolist() == [1, 1, 1, 1]
        rounds = _delta(h0, h1, ROWS_READ, "count")
        assert (dev_lens[2:] == 1 + rounds * e.flush_every).all()


def test_the_dense_decoder_counts_its_attention_rows_too():
    """PR 53: the dense decoder mirrors what its decode attention reads,
    a layer, into the two histograms the latent block feeds: one
    observation a dispatched round; under the jnp reference of the CPU
    meshes every lane's whole region, the live lanes' own rows beside."""
    from dynamo_tpu.models import llama

    cfg = ModelConfig.tiny()
    params = llama.init_params(cfg, 3)

    async def scenario():
        eng = TpuEngine(cfg, EngineConfig(
            num_pages=64, page_size=PS, max_pages_per_seq=8,
            max_decode_slots=4, prefill_buckets=(32,),
            cache_dtype="float32"), params=params,
            mesh_config=MeshConfig(tp=1))
        eng.start()
        h0 = _hists(eng)
        await _one(eng, [7 + i for i in range(20)], osl=9)
        h1 = await _settled(eng)
        await eng.stop()
        return h0, h1, eng.ecfg
    h0, h1, e = asyncio.run(scenario())
    read, live = _delta(h0, h1, ROWS_READ), _delta(h0, h1, ROWS_LIVE)
    rounds = _delta(h0, h1, ROWS_READ, "count")
    assert rounds == _delta(h0, h1, LIVE, "count") > 0
    assert read == rounds * e.flush_every * e.max_decode_slots * e.max_context
    # one lane, 20 rows at the first round and flush_every more a round
    assert 20 * e.flush_every * rounds <= live < read


@pytest.mark.parametrize("impl,read", [("pallas", 4 * (3 + 1) * 512),
                                       ("reference", 4 * 4 * 3 * 512)])
def test_decode_attn_rows_mirror_by_hand(impl, read):
    """The host's mirror of the latent decode attention's trip counts:
    four lanes of a 2048-row region, two dispatched at 1300 and 512
    region rows (3 chunks and 1 of 512), four steps. The kernel reads the
    dispatched lanes' own chunks; the XLA loop every lane to the longest."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.ops.attention import DecodeAttention

    ecfg = EngineConfig(page_size=64, max_pages_per_seq=32)
    assert latent_decode.CHUNK == 512 and ecfg.max_context == 2048
    # where the mirror lives now: the latent block's, through the front
    # door (the engine asks once, at start-up, and observes the pairs)
    mirror = llama.decode_mirror(ModelConfig.tiny_mla_moe(),
                                 ecfg.max_context, ecfg.flush_every,
                                 DecodeAttention(impl))
    live = np.zeros(4, bool)
    live[[0, 2]] = True
    seen = dict(mirror(np.array([1301, 1, 513, 2000], np.int32), live, 4))
    assert seen == {ROWS_READ: read,
                    ROWS_LIVE: 4 * (1300 + 512)}


def test_hc_scopes_are_in_the_lowered_step():
    """``hc_pre`` / ``hc_post`` name the two sides of each sublayer in
    the program's debug locations, beside ``mla_attn`` and ``moe_*``:
    what a device trace is reduced by."""
    import jax
    import jax.numpy as jnp
    from dynamo_tpu.models import llama, mla_moe
    from dynamo_tpu.ops.attention import REFERENCE

    cfg = ModelConfig.tiny_mla_moe_mhc()
    shapes = lambda c: jax.eval_shape(   # noqa: E731
        lambda: llama.serving_params(c, llama.init_params(c, 0)))
    params = shapes(cfg)
    ctx = jax.eval_shape(lambda: llama.init_ctx(cfg, 4, 64, jnp.float32))
    ring = jax.eval_shape(lambda: llama.init_ring(cfg, 4, 4, jnp.float32))
    i32 = jax.ShapeDtypeStruct((4,), jnp.int32)
    step = jax.jit(mla_moe.decode_step_impl, static_argnums=(0,),
                   static_argnames=("attn",))
    text = step.lower(
        cfg, params, ctx, ring, i32, i32, i32,
        jax.ShapeDtypeStruct((), jnp.int32),
        attn=REFERENCE).as_text(debug_info=True)
    for scope in ("hc_pre", "hc_post", "mla_attn", "moe_route",
                  "moe_experts", "moe_shared"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    plain = step.lower(
        ModelConfig.tiny_mla_moe(), shapes(ModelConfig.tiny_mla_moe()),
        ctx, ring, i32, i32, i32,
        jax.ShapeDtypeStruct((), jnp.int32),
        attn=REFERENCE).as_text(debug_info=True)
    assert "hc_pre" not in plain and "hc_post" not in plain


def test_generator_readers_of_the_chat_decode_mix_by_hand():
    """The load generator's own two numbers in the open-loop chat-decode
    mix: how late it sent, and the backlog carried in less carried out
    (window 10 s: the requests due in it ask for 150 + 50 tokens)."""
    log = [{"ok": True, "asked": 100, "due": -1.0},
           {"ok": True, "asked": 150, "due": 0.0},
           {"ok": True, "asked": 50, "due": 9.9},
           {"ok": True, "asked": 70, "due": 10.0}]
    src = {"gen": {"tok_s": 23.5, "late_ms_p90": 1.4}, "log": log,
           "seconds": 10.0}
    late = _reader("gen.late_ms_p90.chat-decode-open")
    carried = _reader("gen.carried_tok_s.chat-decode-open")
    assert late(src) == 1.4
    assert carried(src) == pytest.approx(23.5 - 200 / 10.0)
    # a failed request: tok_s leaves its tokens out, asked keeps them in
    log[1]["ok"] = False
    assert carried(src) is None
    assert late(dict(src, gen={"tok_s": 23.5})) is None


def test_benchmark_json_names_every_new_reader():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    # every reader this file checks by hand, and every entry of the
    # newest cell (its TTFT readers included)
    new_cells = {"mla-moe-joyai-d5.chat-decode", "xing4-mhc-d7.longdoc",
                 "mistral7b-w8.longprompt"}
    assert new_cells <= set(cells)
    for name in sorted(set(READERS) | {
            n for n, m in per_layer.items()
            if new_cells & set(m.get("workloads", ()))}):
        entry = per_layer[name]
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"))
        # PERF.md section 2: TTFT is judged in no cell since PR 30, and
        # an entry names under `moves` a metric that is judged in EVERY
        # cell the entry is read in (the driver records it only there)
        moved = end_to_end[entry["moves"]]
        assert entry["moves"] != "ttft_ms_p90"
        for cell in entry.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (name, cell)


# ---- tools/trace_gaps.py ---------------------------------------------


def _trace_gaps():
    spec = importlib.util.spec_from_file_location(
        "trace_gaps", os.path.join(REPO, "tools", "trace_gaps.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_gaps_sets_the_hosts_round_walls_beside_the_devices():
    """Four fused rounds on the first chip of which the trace's marks
    name the last three (ordinals 41-43; the first ran a round dispatched
    before the session began), a prefill between the second and the third.
    One offset pairs them: at any other a round would start before its
    dispatch mark or end after its consume mark."""
    mod = _trace_gaps()
    ms = 1_000_000
    R, P = mod.ROUND_MODULE, "jit_prefill_impl"
    modules = [(R, 0, 20 * ms), (R, 20 * ms, 40 * ms),
               (P, 40 * ms, 70 * ms), ("jit_patch", 70 * ms, 71 * ms),
               (R, 72 * ms, 92 * ms), (R, 92 * ms, 112 * ms)]
    dispatched = {41: (5 * ms, 0, 0), 42: (38 * ms, 1, 2048),
                  43: (60 * ms, 0, 0)}
    consumed = {41: (41 * ms, 21 * ms, 4), 42: (93 * ms, 52 * ms, 4),
                43: (113 * ms, 20 * ms, 4)}
    out = mod.rounds_report(modules, dispatched, consumed)
    assert (out["offset"], out["marks_contradicted"]) == (1, 0)
    by = {r["ordinal"]: r for r in out["rounds"]}
    assert sorted(by) == [41, 42, 43]
    behind = by[42]
    assert behind["device_s"] == pytest.approx(0.052)
    assert behind["between_s"] == {P: pytest.approx(0.030),
                                   "jit_patch": pytest.approx(0.001)}
    assert behind["idle_s"] == pytest.approx(0.001)
    assert behind["round_s"] == pytest.approx(0.020)
    assert behind["host_s"] == pytest.approx(0.052)
    means = out["means"]
    assert means["clean"]["rounds"] == 2 and (
        means["behind_prefill"]["rounds"]) == 1
    assert means["clean"]["device_step_s"] == pytest.approx(0.005)
    assert means["clean"]["abs_diff_s"] == pytest.approx(0.0005)
    assert means["all"]["host_s"] == pytest.approx(0.031)
    # two rounds always in flight: every round starts after the NEXT one's
    # dispatch mark, so an offset one too low contradicts nothing either;
    # the last offset that contradicts nothing is the one
    deep = [(R, i * 20 * ms, (i + 1) * 20 * ms) for i in range(6)]
    d2 = {o: ((o - 10) * 20 * ms - 30 * ms, 0, 0) for o in range(10, 15)}
    c2 = {o: ((o - 10) * 20 * ms + 61 * ms, 20 * ms, 4)
          for o in range(10, 15)}
    assert mod.pair_rounds(deep, d2, c2) == (2, 0)
    # no marks, or no round: nothing paired and nothing raised
    assert mod.rounds_report(modules, {}, {})["rounds"] == []
    assert mod.rounds_report([], dispatched, consumed)["rounds"] == []


def test_trace_gaps_labels_by_the_covering_segment():
    mod = _trace_gaps()
    ms = 1_000_000
    ops = {"/device:TPU:0": [(0, 4 * ms), (6 * ms, 10 * ms), (13 * ms, 20 * ms)],
           # this chip's trace starts 3 ms late and stops 1 ms early
           "/device:TPU:1": [(3 * ms, 19 * ms)]}
    segments = {"admit": [(4 * ms, 5 * ms + ms // 2)],   # most of gap 1
                "fetch": [(5 * ms + ms // 2, 6 * ms), (0, 4 * ms)]}
    out = mod.label_gaps(ops, segments)
    c0, c1 = out["chips"]["/device:TPU:0"], out["chips"]["/device:TPU:1"]
    assert out["window_s"] == pytest.approx(0.020)
    assert c0["idle_s"] == pytest.approx(0.005) and c0["edge_s"] == 0.0
    assert c0["idle_by_segment_s"] == {
        "unattributed": pytest.approx(0.003), "admit": pytest.approx(0.002)}
    assert c0["attributed_share"] == pytest.approx(0.4)
    # untraced edges are not idle time and are never given to a segment
    assert c1["idle_s"] == 0.0 and c1["edge_s"] == pytest.approx(0.004)
    assert c1["attributed_share"] == 1.0
    assert mod.label_gaps({}, segments) == {"window_s": 0.0, "chips": {}}


def test_trace_gaps_labels_the_empty_engine_and_the_collector():
    """Three gaps on one chip: 10 ms while the engine waits on its doorbell
    (host/idle, a dropped pass's slot_scan beside it), 8 ms of `releases`
    with 6 ms of a full collection inside, and 4 ms of `admit_launch` that
    a 1 ms collection of the young generation only touches."""
    mod = _trace_gaps()
    ms = 1_000_000
    ops = {"/device:TPU:0": [(0, 10 * ms), (20 * ms, 30 * ms),
                             (38 * ms, 40 * ms), (44 * ms, 50 * ms)]}
    segments = {"idle": [(10 * ms, 14 * ms), (15 * ms, 20 * ms)],
                "slot_scan": [(14 * ms, 15 * ms)],
                "releases": [(30 * ms, 38 * ms)],
                "admit_launch": [(40 * ms, 44 * ms)],
                "fetch": [(0, 10 * ms), (20 * ms, 30 * ms)]}
    pauses = {"gc": [(31 * ms, 37 * ms), (41 * ms, 42 * ms)]}
    out = mod.label_gaps(ops, segments, pauses)
    chip = out["chips"]["/device:TPU:0"]
    assert chip["idle_by_segment_s"] == {
        "idle": pytest.approx(0.010), "releases+gc": pytest.approx(0.008),
        "admit_launch": pytest.approx(0.004)}
    assert [seg for seg, _ in chip["longest_gaps"]] == [
        "idle", "releases+gc", "admit_launch"]
    assert out["pauses"] == {"gc": [2, pytest.approx(0.007)]}
    # without the pauses the labels are the segments', as before PR 56
    plain = mod.label_gaps(ops, segments)["chips"]["/device:TPU:0"]
    assert set(plain["idle_by_segment_s"]) == {
        "idle", "releases", "admit_launch"}
    # a consume mark's `late` reaches the round's row
    R = mod.ROUND_MODULE
    rows = mod.rounds_report(
        [(R, 0, 20 * ms), (R, 20 * ms, 40 * ms), (R, 40 * ms, 110 * ms)],
        {5: (5 * ms, 0, 0), 6: (25 * ms, 0, 0)},
        {5: (41 * ms, 20 * ms, 4, None), 6: (111 * ms, 70 * ms, 4, "gc")},
    )["rounds"]
    assert [(r["ordinal"], r["late"]) for r in rows] == [
        (5, None), (6, "gc")]

