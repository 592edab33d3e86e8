"""Engine tracing plane (PR 25): request phases closed where the work
ends, device-starved time by host segment, work-and-waste counters at
the dispatch, host segments as profiler annotations, and the per-layer
readers of ``benchmarks/layer_metrics`` that turn them into metrics.

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
        eng.start()
        h0 = _hists(eng)
        t_sent = time.time()
        cold = await asyncio.gather(*[
            _one(eng, p, received_unix=t_sent if i == 0 else None)
            for i, p in enumerate(prompts)])
        h1 = await _settled(eng)
        again = await _one(eng, prompts[1])
        h2 = await _settled(eng)
        starved = eng.prof.totals()["starved"]
        await eng.stop()
        return {"cold": cold, "again": again, "h": (h0, h1, h2),
                "starved": starved, "flush_every": eng.ecfg.flush_every}

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


# ---- request phases ---------------------------------------------------


@pytest.mark.parametrize("case", [
    "histogram_sums_queue_plus_first_token_is_ttft",
    "per_request_queue_plus_first_token_is_ttft",
    "first_token_span_wraps_its_prefill_child",
    "frontend_observed_only_when_stamped",
])
def test_request_phases(served, case):
    h0, _, h2 = served["h"]
    anns = [a for _, a in served["cold"]] + [served["again"][1]]
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


# ---- starved time -----------------------------------------------------


async def test_sleep_between_fetch_and_dispatch_is_starved_admit():
    """One round in flight at most and no early dispatch: every round's
    fetch leaves nothing tracked while the slot is live, so admission
    runs with the device starved and its time is booked to ``admit``.
    Order and counts, not durations: what a hook ahead of admission sees
    of the profile's state, round by round."""
    eng = _engine(max_inflight_rounds=0, round_pipeline=False)
    admit = eng._admit
    ADMIT = SEGMENTS.index("admit")
    seen = []            # (device starved?, open segment, decode slot live?)

    def watched_admit():
        seen.append((eng.prof._starved, eng.prof._seg,
                     bool(eng._slot_active.any())))
        admit()

    eng._admit = watched_admit
    eng.start()
    toks, _ = await _one(eng, list(range(1, 30)), osl=17)
    await eng.stop()
    t = eng.prof.totals()
    rounds = -(-(17 - 1) // eng.ecfg.flush_every)
    assert len(toks) == 17
    # admission always runs inside its own segment
    assert all(seg == ADMIT for _, seg, _ in seen)
    # with a decode slot live, the blocking fetch ahead of admission has
    # consumed the only round in flight: starved every time, and at
    # least once for each round but the last (whose fetch ends the slot)
    live = [starved for starved, _, slot_live in seen if slot_live]
    assert all(live) and len(live) >= rounds - 1
    starved = t["starved"]["segments"]
    assert starved["admit"] > 0.0
    assert t["starved"]["total_s"] == pytest.approx(sum(starved.values()))
    # a subset of the segment's own time, never more
    assert all(starved[s] <= t["segments"][s] + 1e-9 for s in SEGMENTS)


async def test_idle_engine_records_no_starved_time(served):
    eng = _engine()
    eng.start()
    await asyncio.sleep(0.15)             # spins idle, nothing to serve
    await eng.stop()
    assert eng.prof.totals()["starved"]["total_s"] == 0.0
    # the pipelined scenario kept a round in flight: starved stays a
    # small part of the wall it was measured over
    assert served["starved"]["total_s"] >= 0.0


@pytest.mark.parametrize("record", [True, False])
def test_roundprof_starved_slices(record):
    p = RoundProf()
    i, j = SEGMENTS.index("fetch"), SEGMENTS.index("admit")
    p.begin_round()
    p.enter(i)
    time.sleep(0.002)
    p.mark_starved()                      # splits the open fetch slice
    time.sleep(0.002)
    p.enter(j)
    time.sleep(0.003)
    p.mark_fed()
    time.sleep(0.002)
    p.end_round(record=record)
    t = p.totals()
    if not record:
        assert t["starved"]["total_s"] == 0.0 and t["rounds"] == 0
        return
    s = t["starved"]["segments"]
    assert 0.002 <= s["fetch"] <= t["segments"]["fetch"] - 0.002
    assert 0.003 <= s["admit"] <= t["segments"]["admit"] - 0.002
    assert p.summary()["starved"]["segments"].keys() == {"fetch", "admit"}


# ---- annotations ------------------------------------------------------


class _StubAnnotation:
    log: list = []
    enabled = True

    def __init__(self, name):
        self.name = name
        _StubAnnotation.log.append(("open", name))

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
        p.mark_starved()
        p.enter(SEGMENTS.index("admit"))
        p.mark_fed()
        p.end_round(record=record)
    log = _StubAnnotation.log
    if not session:
        assert log == []
        return
    opens = [n for k, n in log if k == "open"]
    assert opens == ["host/fetch", "host/annotate", "host/fetch",
                     "host/admit"] * 2
    # strictly alternating: never two segments open at once, none left
    assert [k for k, _ in log] == ["open", "close"] * len(opens)
    assert all(log[i][1] == log[i + 1][1] for i in range(0, len(log), 2))


def test_segments_land_on_the_profilers_host_plane(tmp_path):
    """A real profiler session at the benchmark's tracer levels: the
    segments are events of the /host:CPU plane, named host/<segment>."""
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
        p.end_round()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(find_xplane(str(tmp_path)))
    found = {ev.name: ev.duration_ns
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(tprof.ANNOTATION_PREFIX)}
    assert {"host/admit", "host/dispatch"} <= set(found)
    assert found["host/admit"] >= 2_000_000


# ---- the per-layer readers -------------------------------------------


def _reader(name):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _sources():
    def snap(t, hists, starved):
        return {"t_wall": t,
                "histograms": {k: {"sum": s, "count": c}
                               for k, (s, c) in hists.items()},
                "prof": {"rounds": 0, "wall_s": 0.0, "segments": {},
                         "starved": {"total_s": starved, "segments": {}}}}

    before = snap(100.0, {
        FRONT: (1.0, 10), FIRST: (5.0, 10), PF: (1000.0, 4),
        PAD: (2000.0, 4), MATCH: (0.0, 4), LIVE: (400.0, 20),
        RTOK: (300.0, 20), ALIVE: (1e6, 4), ASCORED: (4e6, 4),
        TOUCHED: (1000.0, 20), ROUTED: (3000.0, 20), LOADMAX: (100.0, 20),
        HCRES: (2e-6, 20), CONT: (500.0, 4), ROWS_READ: (1e6, 20),
        ROWS_LIVE: (4e5, 20)},
        1.0)
    after = snap(150.0, {
        FRONT: (1.5, 60), FIRST: (30.0, 60), PF: (17000.0, 54),
        PAD: (26000.0, 54), MATCH: (4000.0, 54), LIVE: (3600.0, 120),
        RTOK: (2700.0, 120), ALIVE: (7e6, 54), ASCORED: (19e6, 54),
        TOUCHED: (205800.0, 120), ROUTED: (617400.0, 120),
        LOADMAX: (700.0, 120), HCRES: (3.2e-5, 120), CONT: (6900.0, 54),
        ROWS_READ: (9e6, 120), ROWS_LIVE: (2.4e6, 120)}, 3.5)
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
}


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


@pytest.mark.parametrize("impl,read", [("pallas", 4 * (3 + 1) * 512),
                                       ("reference", 4 * 4 * 3 * 512)])
def test_decode_attn_rows_mirror_by_hand(impl, read):
    """The host's mirror of the latent decode attention's trip counts:
    four lanes of a 2048-row region, two dispatched at 1300 and 512
    region rows (3 chunks and 1 of 512), four steps. The kernel reads the
    dispatched lanes' own chunks; the XLA loop every lane to the longest."""
    from dynamo_tpu.ops.attention import DecodeAttention

    class Seen(list):
        observe = list.append

    eng = TpuEngine.__new__(TpuEngine)
    eng._B, eng.decode_attn = 4, DecodeAttention(impl)
    eng.ecfg = EngineConfig(page_size=64, max_pages_per_seq=32)
    assert latent_decode.CHUNK == 512 and eng.ecfg.max_context == 2048
    eng._ctx_disp = np.array([1301, 1, 513, 2000], np.int32)
    eng._h_attn_rows_read, eng._h_attn_rows_live = Seen(), Seen()
    eng._observe_decode_attn_rows(np.array([0, 2]), 4)
    assert eng._h_attn_rows_read == [read]
    assert eng._h_attn_rows_live == [4 * (1300 + 512)]


def test_hc_scopes_are_in_the_lowered_step():
    """``hc_pre`` / ``hc_post`` name the two sides of each sublayer in
    the program's debug locations, beside ``mla_attn`` and ``moe_*``:
    what a device trace is reduced by."""
    import jax
    import jax.numpy as jnp
    from dynamo_tpu.models import llama, mla_moe
    from dynamo_tpu.ops.attention import REFERENCE

    cfg = ModelConfig.tiny_mla_moe_mhc()
    params = jax.eval_shape(lambda: llama.init_params(cfg, 0))
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
        ModelConfig.tiny_mla_moe(), jax.eval_shape(
            lambda: llama.init_params(ModelConfig.tiny_mla_moe(), 0)),
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


def test_trace_gaps_labels_by_the_covering_segment():
    spec = importlib.util.spec_from_file_location(
        "trace_gaps", os.path.join(REPO, "tools", "trace_gaps.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
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
