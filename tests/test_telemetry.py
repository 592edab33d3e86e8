"""Telemetry subsystem tests: histogram bucket accounting, trace-context
round-trip through the runtime protocol, flight-recorder ring wraparound,
and the frontend e2e span tree + populated /metrics histograms
(ISSUE 3 acceptance criteria).
"""
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

from dynamo_tpu.backend import Backend
from dynamo_tpu.frontend import HttpService, ModelChain, ModelManager
from dynamo_tpu.preprocessor import OpenAIPreprocessor
from dynamo_tpu.protocols.common import FinishReason, LLMEngineOutput
from dynamo_tpu.protocols.sse import SseDecoder
from dynamo_tpu.telemetry import (
    TRACES,
    FlightRecorder,
    Histogram,
    TelemetryRegistry,
    TraceStore,
    request_histograms,
)
from dynamo_tpu.telemetry.metrics import (
    percentile_from_snapshot,
    weighted_percentile,
)
from dynamo_tpu.telemetry.trace import Span, span_now
from dynamo_tpu.tokenizer import make_test_tokenizer

WORDS = [f"w{i}" for i in range(50)] + ["hello", "world"]


# ---------------------------------------------------------------------------
# histograms

def test_histogram_bucket_accounting():
    h = Histogram("t_seconds", "test", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    # cumulative counts per le edge, +Inf last
    assert snap["buckets"] == [0.1, 1.0, 10.0]
    assert snap["counts"] == [1, 3, 4, 5]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(0.05 + 0.5 + 0.5 + 5.0 + 50.0)
    text = "\n".join(h.render())
    assert "# HELP t_seconds test" in text
    assert "# TYPE t_seconds histogram" in text
    assert 't_seconds_bucket{le="0.1"} 1' in text
    assert 't_seconds_bucket{le="+Inf"} 5' in text
    assert "t_seconds_count 5" in text
    assert "t_seconds_sum" in text
    # labelled render nests the worker label before le
    labelled = "\n".join(h.render('worker="w0"'))
    assert 't_seconds_bucket{worker="w0",le="+Inf"} 5' in labelled
    assert 't_seconds_count{worker="w0"} 5' in labelled


def test_histogram_weighted_observe_and_reset():
    h = Histogram("x", "x", buckets=(1.0,))
    h.observe(0.5, n=3)
    assert h.count == 3
    assert h.sum == pytest.approx(1.5)
    h.observe(float("nan"))          # ignored, never corrupts the series
    h.observe(0.5, n=0)
    assert h.count == 3
    h.reset()
    assert h.count == 0 and h.snapshot()["counts"] == [0, 0]


def test_histogram_percentile_interpolation():
    h = Histogram("p", "p", buckets=(0.1, 1.0, 10.0))
    assert h.percentile(0.5) is None  # empty
    for _ in range(10):
        h.observe(0.5)                # all in the (0.1, 1.0] bucket
    p50 = h.percentile(0.5)
    assert 0.1 < p50 <= 1.0
    # +Inf observations clamp to the top finite edge
    h2 = Histogram("q", "q", buckets=(1.0,))
    h2.observe(100.0)
    assert h2.percentile(0.99) == 1.0
    # snapshot round-trips through JSON (the ForwardPassMetrics path)
    snap = json.loads(json.dumps(h.snapshot()))
    assert percentile_from_snapshot(snap, 0.5) == pytest.approx(p50)


def test_weighted_percentile():
    assert weighted_percentile([], 0.5) is None
    pairs = [(0.010, 1), (0.002, 8), (0.030, 1)]
    assert weighted_percentile(pairs, 0.5) == pytest.approx(0.002)
    assert weighted_percentile(pairs, 1.0) == pytest.approx(0.030)


def test_registry_render_and_snapshot():
    reg = request_histograms(TelemetryRegistry(), engine=True)
    names = set(reg.snapshot())
    assert names == {
        "dynamo_request_ttft_seconds", "dynamo_request_itl_seconds",
        "dynamo_request_e2e_seconds", "dynamo_request_queue_seconds",
        "dynamo_engine_round_seconds",
        "dynamo_request_first_token_seconds",
        "dynamo_request_frontend_seconds",
        "dynamo_engine_prefill_tokens",
        "dynamo_engine_prefill_padded_tokens",
        "dynamo_engine_prefill_matched_tokens",
        "dynamo_engine_prefill_attn_live_pairs",
        "dynamo_engine_prefill_attn_scored_pairs",
        "dynamo_engine_prefill_attn_blocks",
        "dynamo_engine_prefill_attn_fused_blocks",
        "dynamo_engine_round_live_lane_steps",
        "dynamo_engine_round_tokens",
        "dynamo_moe_experts_touched", "dynamo_moe_tokens_routed",
        "dynamo_moe_expert_load_max", "dynamo_kv_row_bytes",
        "dynamo_kv_cache_planes", "dynamo_loop_steps_run",
        "dynamo_hc_sinkhorn_residual", "dynamo_prefill_continued_tokens",
        "dynamo_decode_attn_rows_read", "dynamo_decode_attn_rows_live",
        "dynamo_moe_picks_routed",
        "dynamo_moe_prefill_rows_sorted",
        "dynamo_moe_prefill_rows_moved",
        "dynamo_ssm_state_bytes",
        "dynamo_sparse_attn_rows_read", "dynamo_sparse_attn_rows_live",
        "dynamo_sparse_prefill_pairs_scored",
        "dynamo_sparse_prefill_pairs_selected",
        "dynamo_moe_groups_kept_here", "dynamo_kda_state_rows_stepped",
        "dynamo_ssm_state_rows_stepped", "dynamo_ssm_scan_positions",
        "dynamo_attn_shared_rows_read", "dynamo_attn_window_rows_read",
        "dynamo_attn_window_rows_bound", "dynamo_prefill_layer_rows",
        "dynamo_decode_attn_q_rows_full", "dynamo_decode_attn_q_rows_window",
        "dynamo_prefill_layer_rows_not_climbed",
        "dynamo_request_tpot_seconds",
        "dynamo_engine_step_gap_seconds",
        "dynamo_engine_step_gap_clean_seconds",
        "dynamo_engine_round_prefill_tokens_ahead",
        "dynamo_engine_dispatch_found_dry",
        "dynamo_layer_parts_run_mixer_ssm", "dynamo_layer_parts_run_mixer_attn",
        "dynamo_layer_parts_run_experts", "dynamo_layer_parts_run_mlp",
    } | {f"dynamo_loop_exit_cdf_at_step_{t}" for t in range(3)}
    reg.get("dynamo_request_ttft_seconds").observe(0.2)
    text = reg.render()
    assert "# TYPE dynamo_request_ttft_seconds histogram" in text
    assert "dynamo_request_ttft_seconds_count 1" in text
    # snapshots carry the help text for remote rendering
    assert reg.snapshot()["dynamo_request_itl_seconds"]["help"]


# ---------------------------------------------------------------------------
# flight recorder

def test_flight_recorder_ring_wraparound():
    f = FlightRecorder(capacity=8)
    for i in range(20):
        f.record("round", n=i)
    assert len(f) == 8
    assert f.recorded_total == 20
    events = f.snapshot()
    assert [e["n"] for e in events] == list(range(12, 20))  # oldest->newest
    assert [e["seq"] for e in events] == list(range(12, 20))
    assert all(e["kind"] == "round" and "ts" in e for e in events)


def test_flight_recorder_exactly_full():
    """The exactly-capacity boundary: _next has wrapped to 0 but the
    ring is full, not empty."""
    f = FlightRecorder(capacity=4)
    for i in range(4):
        f.record("round", n=i)
    assert [e["n"] for e in f.snapshot()] == [0, 1, 2, 3]
    assert len(f) == 4


def test_flight_recorder_dump_logs_events():
    import logging

    f = FlightRecorder(capacity=4)
    f.record("round", slots=[0, 1])
    records = []

    class _Sink(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    log = logging.getLogger("test_flight_dump")
    log.addHandler(_Sink())
    log.setLevel(logging.ERROR)
    f.dump(log, reason="boom")
    assert any("boom" in m for m in records)
    assert any("'slots': [0, 1]" in m for m in records)


# ---------------------------------------------------------------------------
# trace store

def test_trace_store_lifecycle_and_bounds():
    store = TraceStore(max_completed=3)
    tr = store.start("r1")
    assert store.has_active("r1")
    tr.add(Span(name="tokenize", start_s=1.0, duration_s=0.1))
    assert store.add_span("r1", Span(name="route", start_s=1.1))
    assert not store.add_span("missing", Span(name="x", start_s=0.0))
    store.merge("r1", [{"name": "queue", "start_s": 1.2,
                        "duration_s": 0.05}])
    done = store.finish("r1")
    assert done is not None and done.finished
    assert not store.has_active("r1")
    assert store.get("r1").span_names() == ["tokenize", "route", "queue"]
    # completed ring evicts oldest
    for i in range(5):
        store.record_remote(f"x{i}", [{"name": "prefill", "start_s": 0.0}])
    assert store.get("r1") is None
    assert store.recent_ids() == ["x2", "x3", "x4"]


def test_trace_alias_routes_choice_spans_to_parent():
    """n>1 fanout: per-choice request ids alias onto the parent trace,
    so route spans land on one tree and the engine's has_active check
    sees the frontend as the owner."""
    store = TraceStore()
    store.start("parent")
    store.alias("choice-1", "parent")
    assert store.has_active("choice-1")
    assert store.add_span("choice-1", Span(name="route", start_s=1.0))
    tr = store.finish("parent")
    assert tr.span_names() == ["route"]
    # aliases die with the parent
    assert not store.has_active("choice-1")
    assert not store.add_span("choice-1", Span(name="x", start_s=2.0))


def test_span_tree_serialization():
    parent = Span(name="prefill", start_s=10.0, duration_s=0.5,
                  attrs={"slot": 3},
                  children=[Span(name="g2_onboard", start_s=10.1,
                                 duration_s=0.2, attrs={"blocks": 4})])
    d = json.loads(json.dumps(parent.to_dict()))
    back = Span.from_dict(d)
    assert back.name == "prefill" and back.attrs == {"slot": 3}
    assert back.children[0].name == "g2_onboard"
    assert back.children[0].attrs == {"blocks": 4}


# ---------------------------------------------------------------------------
# trace context round-trip through the runtime protocol

class _SpanStubEngine:
    """Engine yielding a token then a finishing output whose annotations
    carry worker-side spans + timing — the remote-worker wire shape."""

    async def generate(self, request):
        import time as _t

        t0 = _t.time()
        yield LLMEngineOutput(token_ids=[1])
        yield LLMEngineOutput(
            token_ids=[2], finish_reason=FinishReason.EOS,
            annotations={
                "timing": {"ttft_s": 0.01, "itl_p50_s": 0.002,
                           "itl_p95_s": 0.004, "e2e_s": 0.1,
                           "queue_s": 0.001},
                "trace": {"spans": [
                    {"name": "queue", "start_s": t0, "duration_s": 0.001},
                    {"name": "prefill", "start_s": t0 + 0.001,
                     "duration_s": 0.05, "attrs": {"slot": 0}},
                    {"name": "decode_round", "start_s": t0 + 0.06,
                     "duration_s": 0.004, "attrs": {"tokens": 2}},
                ]},
            },
        )


async def test_trace_roundtrip_through_runtime_protocol():
    """Frontend-minted trace + worker spans over the real TCP framing:
    the spans survive serve_engine's to_dict -> frame -> from_dict and
    merge into the frontend's span tree keyed by request_id."""
    from dynamo_tpu.protocols.common import PreprocessedRequest
    from dynamo_tpu.runtime.endpoint import EndpointServer, call_endpoint
    from dynamo_tpu.runtime.remote_engine import engine_handler

    server = EndpointServer(engine_handler(_SpanStubEngine()))
    host, port = await server.start()
    try:
        import time as _t

        rid = "trace-rt-1"
        TRACES.start(rid)
        TRACES.add_span(rid, span_now("tokenize", _t.monotonic(), tokens=3))
        req = PreprocessedRequest(token_ids=[1, 2, 3], request_id=rid)
        toks = []
        async for item in call_endpoint(
            host, port, req.to_dict(), request_id=rid
        ):
            out = LLMEngineOutput.from_dict(item)
            toks.extend(out.token_ids)
            spans = (out.annotations.get("trace") or {}).get("spans")
            if spans:
                TRACES.merge(rid, spans)
        tr = TRACES.finish(rid)
        assert toks == [1, 2]
        names = tr.span_names()
        assert names[0] == "tokenize"
        assert {"queue", "prefill", "decode_round"} <= set(names)
        tree = tr.to_dict()
        prefill = next(s for s in tree["spans"] if s["name"] == "prefill")
        assert prefill["attrs"] == {"slot": 0}
    finally:
        await server.stop()
        TRACES.clear()


def test_request_stats_reads_timing_annotation():
    from dynamo_tpu.sdk import request_stats

    outs = [
        LLMEngineOutput(token_ids=[1, 2]),
        LLMEngineOutput(
            token_ids=[], finish_reason=FinishReason.EOS,
            annotations={"timing": {
                "ttft_s": 0.05, "itl_p50_s": 0.002, "itl_p95_s": 0.01,
                "e2e_s": 0.5, "queue_s": 0.003,
            }},
        ),
    ]
    st = request_stats(outs)
    assert st.ttft_s == pytest.approx(0.05)
    assert st.itl_p50_s == pytest.approx(0.002)
    assert st.itl_p95_s == pytest.approx(0.01)
    assert st.e2e_s == pytest.approx(0.5)
    assert st.queue_s == pytest.approx(0.003)


# ---------------------------------------------------------------------------
# frontend e2e: span tree retrievable, /metrics histograms populated

@pytest.fixture(scope="module")
def tiny_routed_manager():
    """Tiny TpuEngine behind a KvPushRouter (so the route span records)
    behind a ModelChain — the full in-process serving stack."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshConfig

    cfg = ModelConfig.tiny(dtype="float32")
    ecfg = EngineConfig(
        num_pages=64, page_size=16, max_pages_per_seq=8,
        max_decode_slots=4, prefill_buckets=(32, 64),
        cache_dtype="float32",
    )
    engine = TpuEngine(cfg, ecfg, mesh_config=MeshConfig(tp=1))
    router = KvPushRouter(KvRouter(block_size=16), workers={1: engine})
    tok = make_test_tokenizer(WORDS)
    chain = ModelChain(
        name="tiny",
        preprocessor=OpenAIPreprocessor(tokenizer=tok, model_name="tiny"),
        engine=router,
        backend=Backend(tok),
    )
    manager = ModelManager()
    manager.register(chain)
    yield manager


async def _with_client(svc):
    client = TestClient(TestServer(svc.app))
    await client.start_server()
    return client


async def test_frontend_span_tree_and_histograms(tiny_routed_manager):
    TRACES.clear()
    svc = HttpService(tiny_routed_manager)
    client = await _with_client(svc)
    completion_tokens = 0
    rids = []
    metrics_events = []
    for _ in range(2):
        r = await client.post(
            "/v1/chat/completions",
            json={
                "model": "tiny",
                "messages": [{"role": "user", "content": "hello world"}],
                "max_tokens": 8,
                "ignore_eos": True,
                "stream": True,
                "stream_options": {"include_usage": True},
                "nvext": {"annotations": ["llm_metrics"]},
            },
        )
        assert r.status == 200
        rid = r.headers["X-Request-Id"]
        rids.append(rid)
        dec = SseDecoder()
        events = []
        async for chunk in r.content.iter_any():
            events.extend(dec.feed(chunk))
        for e in events[:-1]:
            body = e.json()
            if body.get("usage"):
                completion_tokens += body["usage"]["completion_tokens"]
            if body.get("nvext", {}).get("annotation") == "llm_metrics":
                metrics_events.append(body["nvext"]["metrics"])

    # --- span tree: tokenize -> route -> queue -> prefill -> decode ---
    for rid in rids:
        tr = await client.get(f"/debug/trace/{rid}")
        assert tr.status == 200
        tree = await tr.json()
        assert tree["trace_id"] == rid and tree["finished"]
        names = [s["name"] for s in tree["spans"]]
        for expected in ("tokenize", "route", "queue", "first_token",
                         "decode"):
            assert expected in names, (expected, names)
        # the per-round spans hang under the decode phase
        dec = next(s for s in tree["spans"] if s["name"] == "decode")
        assert {c["name"] for c in dec["children"]} == {"decode_round"}
        # the dispatch-only prefill span hangs under first_token
        ft = next(s for s in tree["spans"] if s["name"] == "first_token")
        assert [c["name"] for c in ft["children"]] == ["prefill"]
        route = next(s for s in tree["spans"] if s["name"] == "route")
        assert "overlap_blocks" in route["attrs"]
    idx = await client.get("/debug/trace")
    assert set(rids) <= set((await idx.json())["recent"])
    missing = await client.get("/debug/trace/nope")
    assert missing.status == 404

    # --- finishing llm_metrics annotation surfaces ITL p50/p95 ---
    assert len(metrics_events) == 2
    for m in metrics_events:
        assert m["ttft_s"] is not None
        assert m["itl_p50_s"] is not None
        assert m["itl_p95_s"] is not None
        assert m["itl_p95_s"] >= m["itl_p50_s"]

    # --- /metrics histograms: counts match requests/tokens served ---
    mr = await client.get("/metrics")
    text = await mr.text()
    assert "# TYPE dynamo_request_ttft_seconds histogram" in text
    assert "# TYPE dynamo_request_itl_seconds histogram" in text
    assert "dynamo_request_ttft_seconds_count 2" in text
    # the engine emits the first token alone, so the frontend observes
    # exactly tokens-1 inter-token gaps per request
    assert (f"dynamo_request_itl_seconds_count "
            f"{completion_tokens - 2}") in text
    assert "dynamo_request_e2e_seconds_count 2" in text

    # --- /debug/flight: the router is not an engine, but the worker
    # behind it records; the frontend aggregates engines exposing one ---
    fl = await client.get("/debug/flight")
    assert fl.status == 200  # router chain: no flight attr -> empty dict
    await client.close()
    TRACES.clear()


async def test_frontend_unary_trace_and_ttft(tiny_routed_manager):
    TRACES.clear()
    svc = HttpService(tiny_routed_manager)
    client = await _with_client(svc)
    r = await client.post(
        "/v1/completions",
        json={"model": "tiny", "prompt": "hello world", "max_tokens": 4,
              "ignore_eos": True},
    )
    assert r.status == 200
    rid = r.headers["X-Request-Id"]
    tr = await client.get(f"/debug/trace/{rid}")
    assert tr.status == 200
    names = [s["name"] for s in (await tr.json())["spans"]]
    assert "tokenize" in names and "first_token" in names
    mtext = await (await client.get("/metrics")).text()
    assert "dynamo_request_ttft_seconds_count 1" in mtext
    await client.close()
    TRACES.clear()


async def test_system_server_debug_endpoints():
    """Per-worker surface: /debug/flight serves the engine ring and
    /debug/trace serves the worker-local store."""
    from dynamo_tpu.runtime.system_server import SystemServer

    class _Eng:
        flight = FlightRecorder(capacity=4)

    _Eng.flight.record("round", slots=[0], dispatch_ms=1.0)
    TRACES.record_remote("w-req", [{"name": "queue", "start_s": 1.0,
                                    "duration_s": 0.5}])
    srv = await SystemServer(_Eng(), host="127.0.0.1", port=0,
                             worker_id="w7").start()
    try:
        import aiohttp

        async with aiohttp.ClientSession() as sess:
            async with sess.get(
                f"http://127.0.0.1:{srv.port}/debug/flight"
            ) as resp:
                body = await resp.json()
                assert body["worker_id"] == "w7"
                assert body["events"][0]["kind"] == "round"
            async with sess.get(
                f"http://127.0.0.1:{srv.port}/debug/trace/w-req"
            ) as resp:
                assert resp.status == 200
                assert (await resp.json())["spans"][0]["name"] == "queue"
    finally:
        await srv.stop()
        TRACES.clear()


async def test_engine_round_histogram_and_flight(tiny_routed_manager):
    """The engine-side series: queue/round histograms populate and the
    flight ring records prefill + round dispatches."""
    chain = tiny_routed_manager.get("tiny")
    eng = chain.engine.workers[1]
    snap = eng.telemetry.snapshot()
    assert snap["dynamo_engine_round_seconds"]["count"] > 0
    assert snap["dynamo_request_queue_seconds"]["count"] > 0
    kinds = {e["kind"] for e in eng.flight.snapshot()}
    assert "round" in kinds
    ev = eng.flight.snapshot()[-1]
    assert "dispatch_ms" in ev and "slots" in ev
