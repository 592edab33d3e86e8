"""OpenAI HTTP service on aiohttp (reference lib/llm/src/http/service:
service_v2.rs:50 HttpService, openai.rs:133,287 handlers, metrics.rs:104).

Endpoints:
  POST /v1/chat/completions   (streamed SSE or aggregated JSON)
  POST /v1/completions
  GET  /v1/models
  GET  /health, /live
  GET  /metrics               (Prometheus)
  POST /clear_kv_blocks       (reference clear_kv_blocks.rs)

Streaming honours client disconnect: closing the HTTP connection closes the
response generator, which cancels the engine request (the engine's
drop-to-cancel contract — reference AsyncEngineContext::stop_generating).
"""
from __future__ import annotations

import asyncio
import json
import logging
import time
from collections import deque
from typing import Any, AsyncIterator, Optional

from aiohttp import web
from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
    CONTENT_TYPE_LATEST,
)
from pydantic import ValidationError

from dynamo_tpu.frontend.model_manager import ModelManager, ModelNotFound
from dynamo_tpu.overload import (
    OVERLOAD,
    EngineOverloadedError,
    apply_request_hints,
)
from dynamo_tpu.protocols.common import FinishReason, LLMEngineOutput
from dynamo_tpu.protocols.openai import (
    ChatCompletionRequest,
    CompletionRequest,
    DeltaGenerator,
    chat_completion_response,
    completion_response,
    make_id,
    model_list_response,
)
from dynamo_tpu.protocols.sse import encode_done, encode_event
from dynamo_tpu.tenancy import DEFAULT_TENANT, TENANT
from dynamo_tpu.telemetry import (
    TRACES,
    TelemetryRegistry,
    request_histograms,
)
from dynamo_tpu.telemetry import metrics as tmetrics
from dynamo_tpu.telemetry.forensics import OUTLIERS, ForensicsCapture
from dynamo_tpu.telemetry.timeline import to_chrome_trace
from dynamo_tpu.telemetry.trace import span_now

# OpenMetrics content negotiation: exemplars only ship to scrapers that
# ask for the OpenMetrics exposition format; plain Prometheus text stays
# byte-identical for everyone else
OPENMETRICS_CONTENT_TYPE = (
    "application/openmetrics-text; version=1.0.0; charset=utf-8")


def wants_openmetrics(request: web.Request) -> bool:
    return "application/openmetrics-text" in request.headers.get("Accept", "")

log = logging.getLogger(__name__)


class ServiceMetrics:
    """Frontend Prometheus metrics (reference metrics.rs
    nv_llm_http_service_{requests_total,inflight_requests,request_duration_seconds})."""

    def __init__(self, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        self.requests_total = Counter(
            "dynamo_http_service_requests_total",
            "HTTP requests by model/endpoint/status",
            ["model", "endpoint", "status"],
            registry=self.registry,
        )
        self.inflight = Gauge(
            "dynamo_http_service_inflight_requests",
            "In-flight requests",
            ["model"],
            registry=self.registry,
        )
        self.duration = Histogram(
            "dynamo_http_service_request_duration_seconds",
            "Request duration",
            ["model"],
            registry=self.registry,
        )

    def render(self) -> bytes:
        return generate_latest(self.registry)


def _error(status: int, message: str,
           err_type: str = "invalid_request_error",
           headers: Optional[dict] = None) -> web.Response:
    return web.json_response(
        {"error": {"message": message, "type": err_type, "code": status}},
        status=status,
        headers=headers,
    )


def _overloaded_response(e: EngineOverloadedError) -> web.Response:
    """HTTP 429 with the load-derived Retry-After (whole seconds,
    rounded up — RFC 7231 delta-seconds)."""
    OVERLOAD.inc("dynamo_overload_http_429_total")
    # tenant-sliced 429 accounting: a quota rejection carries the
    # offending tenant on the error; global-backlog rejections ("") land
    # on the default slice so the series totals stay reconcilable
    TENANT.inc("dynamo_tenant_http_429_total", e.tenant or DEFAULT_TENANT)
    retry_after = max(1, int(-(-e.retry_after_s // 1)))
    return _error(
        429, str(e) or "engine overloaded", "overloaded_error",
        headers={"Retry-After": str(retry_after)},
    )


class _ApiError(Exception):
    """Endpoint-local error mapped to an OpenAI error response by
    _run_endpoint (the shared request envelope)."""

    def __init__(self, status: int, message: str,
                 etype: str = "invalid_request_error"):
        super().__init__(message)
        self.status = status
        self.message = message
        self.etype = etype


class _RequestTiming:
    """Per-request latency bookkeeping shared by the unary and streaming
    paths: frontend-observed TTFT / per-token ITL gaps / E2E into the
    service histograms, and worker-side trace spans merged into the
    trace store."""

    def __init__(self, svc: "HttpService", request_id: str, t_start: float,
                 tenant: str = ""):
        self.svc = svc
        self.rid = request_id
        self.t_start = t_start
        self.tenant = tenant
        self.t_first: dict[int, float] = {}
        self.t_last: dict[int, float] = {}
        self.tok_counts: dict[int, int] = {}
        self.gaps: list[tuple[float, int]] = []   # (gap_s, n) all streams
        self.worker_timing: dict[str, Any] = {}   # last timing annotation
        self._finished = False

    def on_output(self, i: int, out: LLMEngineOutput) -> None:
        if out.token_ids:
            now = time.monotonic()
            prev = self.t_last.get(i)
            n = len(out.token_ids)
            if prev is not None:
                gap = (now - prev) / n
                self.svc._h_itl.observe(gap, n, exemplar_id=self.rid)
                if len(self.gaps) < 4096:  # percentile fidelity cap
                    self.gaps.append((gap, n))
            self.t_last[i] = now
            self.t_first.setdefault(i, now)
            self.tok_counts[i] = self.tok_counts.get(i, 0) + n
        ann = out.annotations or {}
        spans = (ann.get("trace") or {}).get("spans")
        if spans:
            TRACES.merge(self.rid, spans)
        if ann.get("timing"):
            self.worker_timing = ann["timing"]

    @property
    def ttft(self) -> Optional[float]:
        if not self.t_first:
            return None
        return min(self.t_first.values()) - self.t_start

    def itl_avg(self) -> Optional[float]:
        # per generation, not the n-way interleave
        itls = [
            (self.t_last[i] - self.t_first[i]) / (self.tok_counts[i] - 1)
            for i in self.t_first
            if self.tok_counts.get(i, 0) > 1
        ]
        return sum(itls) / len(itls) if itls else None

    def itl_percentile(self, q: float) -> Optional[float]:
        return tmetrics.weighted_percentile(self.gaps, q)

    def finish(self) -> None:
        """Observe the request-level histograms (once). Runs from the
        finally paths too — a client that disconnects mid-stream already
        contributed ITL gaps, so TTFT/E2E must count it as well; a
        request that never produced a token contributes to none of the
        three series (counts stay mutually consistent)."""
        if self._finished:
            return
        self._finished = True
        if not self.t_first:
            return
        ttft = self.ttft
        e2e = time.monotonic() - self.t_start
        self.svc._h_ttft.observe(ttft, exemplar_id=self.rid)
        self.svc._h_e2e.observe(e2e, exemplar_id=self.rid)
        # tail-latency forensics: the no-breach path is a couple of float
        # compares — this runs BEFORE run()'s finally calls TRACES.finish,
        # so a breach promotion still adopts the shell's buffered spans
        timing = dict(self.worker_timing)
        if self.tenant:
            # tenant tag rides the timing payload into any dossier this
            # finish promotes — breach triage can slice by tenant
            timing.setdefault("tenant", self.tenant)
        self.svc.forensics.on_finish(
            self.rid,
            ttft_s=ttft,
            itl_p95_s=self.itl_percentile(0.95),
            e2e_s=e2e,
            queue_s=self.worker_timing.get("queue_s"),
            timing=timing,
        )


class HttpService:
    """The OpenAI-compatible frontend over a ModelManager."""

    def __init__(
        self,
        manager: Optional[ModelManager] = None,
        *,
        host: str = "0.0.0.0",
        port: int = 8080,
        trace_sample_rate: float = 1.0,
        forensics_sample_rate: float = 0.0,
    ):
        # fraction of requests minting a FULL trace (--trace-sample-rate):
        # high-QPS deployments trace a sample instead of every request;
        # unsampled requests carry a shell trace that migration/failure
        # paths promote, so those are ALWAYS fully traced from that point
        self.trace_sample_rate = trace_sample_rate
        import random as _random

        self._trace_rng = _random.Random()
        # SLO-breach dossiers: every finishing request runs the cheap
        # breach check; breaches (and a --forensics-sample-rate coin
        # flip) land in the OUTLIERS ring at /debug/outliers
        self.forensics = ForensicsCapture(
            sample_rate=forensics_sample_rate,
            engines_fn=self._local_engines,
        )
        # `is not None`, NOT truthiness: an EMPTY manager (len 0 -> falsy)
        # must be kept — discovery registers models into it later; replacing
        # it would silently split the watcher and the HTTP handlers onto
        # two different registries
        self.manager = manager if manager is not None else ModelManager()
        self.host = host
        self.port = port
        # fleet prefix economy: per-model FleetKvView registry served at
        # /debug/kv_fleet (tools/kv_fleet.py reads it). The launch path
        # points this at the ModelWatcher's live dict so discovered
        # kv-routed models appear without re-wiring.
        self.fleet_views: dict[str, Any] = {}
        self.metrics = ServiceMetrics()
        # request-latency histograms (TTFT / ITL / E2E), observed at the
        # frontend's measurement points and appended to /metrics
        self.telemetry = request_histograms(TelemetryRegistry())
        self._h_ttft = self.telemetry.get(tmetrics.TTFT[0])
        self._h_itl = self.telemetry.get(tmetrics.ITL[0])
        self._h_e2e = self.telemetry.get(tmetrics.E2E[0])
        self.app = web.Application()
        self.app.add_routes(
            [
                web.post("/v1/chat/completions", self.handle_chat),
                web.post("/v1/completions", self.handle_completion),
                web.post("/v1/responses", self.handle_responses),
                web.post("/v1/embeddings", self.handle_embeddings),
                web.get("/v1/models", self.handle_models),
                web.get("/health", self.handle_health),
                web.get("/live", self.handle_health),
                web.get("/metrics", self.handle_metrics),
                web.post("/clear_kv_blocks", self.handle_clear_kv),
                web.get("/debug/trace", self.handle_trace_index),
                web.get("/debug/trace/{request_id}", self.handle_trace),
                web.get("/debug/flight", self.handle_flight),
                web.get("/debug/kv_fleet", self.handle_kv_fleet),
                web.get("/debug/tenants", self.handle_tenants),
                web.get("/debug/outliers", self.handle_outliers),
                web.get("/debug/outliers/{request_id}",
                        self.handle_outlier),
            ]
        )
        self._runner: Optional[web.AppRunner] = None
        self._start_time = time.monotonic()

    # ------------------------------------------------------------------
    # lifecycle

    async def start(self) -> None:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        log.info("http service listening on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    # ------------------------------------------------------------------
    # handlers

    async def handle_health(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "status": "healthy",
                "uptime_s": round(time.monotonic() - self._start_time, 3),
                "models": self.manager.list_models(),
            }
        )

    async def handle_models(self, request: web.Request) -> web.Response:
        return web.json_response(model_list_response(self.manager.list_models()))

    async def handle_metrics(self, request: web.Request) -> web.Response:
        from dynamo_tpu.telemetry.prof import PROF

        # SLO burn-rate gauges refresh at scrape time from the frontend's
        # own end-to-end latency histograms (an in-process engine also
        # folds its view at the publish cadence; either way the gauges
        # track live data)
        if self._h_ttft.count or self._h_itl.count:
            PROF.fold_burn_rates(
                self._h_ttft.snapshot(), self._h_itl.snapshot()
            )
        om = wants_openmetrics(request)
        body = (self.metrics.render()
                + self.telemetry.render(openmetrics=om).encode()
                + tmetrics.render_planes(om).encode())
        if om:
            return web.Response(
                body=body + b"# EOF\n",
                content_type="application/openmetrics-text",
            )
        return web.Response(
            body=body, content_type=CONTENT_TYPE_LATEST.split(";")[0]
        )

    async def handle_kv_fleet(self, request: web.Request) -> web.Response:
        """GET /debug/kv_fleet[?model=NAME][&top=K] — the live fleet
        prefix economy per kv-routed model: replica map and top-K hot
        prefixes (kv_router/fleet.py FleetKvView.to_dict)."""
        try:
            top = int(request.query.get("top", 32))
        except ValueError:
            top = 32
        want = request.query.get("model")
        views = self.fleet_views
        if want is not None:
            if want not in views:
                return web.json_response(
                    {"error": f"no fleet view for model {want!r}"},
                    status=404,
                )
            views = {want: views[want]}
        return web.json_response({
            "models": {
                name: view.to_dict(top=top)
                for name, view in sorted(views.items())
            },
        })

    # ------------------------------------------------------------------
    # debug plane: span trees + flight recorders of in-process engines

    async def handle_trace_index(self, request: web.Request) -> web.Response:
        return web.json_response({"recent": TRACES.recent_ids()})

    async def handle_trace(self, request: web.Request) -> web.Response:
        rid = request.match_info["request_id"]
        tr = TRACES.get(rid)
        if tr is None:
            # the body says WHY: evicted vs unsampled vs never seen
            return web.json_response(TRACES.describe_missing(rid),
                                     status=404)
        return web.json_response(tr.to_dict())

    def _local_engines(self) -> list:
        """In-process engines whose prof/flight rings feed dossiers
        (remote workers assemble their own via the system server)."""
        engines = []
        for name in self.manager.list_models():
            try:
                engines.append(self.manager.get(name).engine)
            except Exception as e:  # noqa: BLE001 — forensics never throws
                log.debug("forensics: skipping engine %s: %s", name, e)
                continue
        return engines

    async def handle_tenants(self, request: web.Request) -> web.Response:
        """GET /debug/tenants — the tenancy plane in one JSON page: the
        frontend's own tenant-sliced metric snapshot plus every local
        engine's quota/queue view (keyed by model; remote workers serve
        the same shape from their system server)."""
        engines: dict[str, Any] = {}
        for name in self.manager.list_models():
            try:
                eng = self.manager.get(name).engine
            except Exception as e:  # noqa: BLE001 — debug page never throws
                log.debug("tenant debug: model %s unavailable: %s", name, e)
                continue
            dbg = getattr(eng, "tenant_debug", None)
            if dbg is None:
                continue
            try:
                engines[name] = dbg()
            except Exception as e:  # noqa: BLE001
                log.debug("tenant debug for %s failed: %s", name, e)
        return web.json_response({
            "tenants": TENANT.snapshot(),
            "engines": engines,
        })

    async def handle_outliers(self, request: web.Request) -> web.Response:
        """GET /debug/outliers — the SLO-breach dossier ring: capture
        stats + newest-first summaries (full dossiers one level down)."""
        return web.json_response(OUTLIERS.index())

    async def handle_outlier(self, request: web.Request) -> web.Response:
        """GET /debug/outliers/{request_id}[?format=perfetto] — one full
        dossier, either as JSON or as a single-request Perfetto/Chrome
        timeline merging its spans, host rounds, flight and stream
        events."""
        rid = request.match_info["request_id"]
        d = OUTLIERS.get(rid)
        if d is None:
            return web.json_response({
                "error": f"no dossier for request {rid!r}",
                "capacity": OUTLIERS.capacity,
                "captured_total": OUTLIERS.captured_total,
                "evicted_total": OUTLIERS.evicted_total,
                "oldest_retained_id": OUTLIERS.oldest_id(),
            }, status=404)
        if request.query.get("format") == "perfetto":
            return web.json_response(to_chrome_trace(
                spans=list(d.trace.get("spans") or []),
                round_records=d.rounds,
                flight_events=d.flight,
                stream_events=d.stream,
                label=rid,
            ))
        return web.json_response(d.to_dict())

    async def handle_flight(self, request: web.Request) -> web.Response:
        """Flight rings of every local engine (keyed by model). Remote
        workers serve their own at the per-worker system server."""
        out = {}
        for name in self.manager.list_models():
            engine = self.manager.get(name).engine
            flight = getattr(engine, "flight", None)
            if flight is not None:
                out[name] = {
                    "recorded_total": flight.recorded_total,
                    "events": flight.snapshot(),
                }
        return web.json_response({"engines": out})

    async def handle_clear_kv(self, request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.remote_engine import invoke_clear

        cleared = []
        for name in self.manager.list_models():
            engine = self.manager.get(name).engine
            reset = getattr(engine, "clear_kv_blocks", None)
            if reset is not None:
                await invoke_clear(reset)
                cleared.append(name)
        return web.json_response({"cleared": cleared})

    async def handle_embeddings(self, request: web.Request) -> web.Response:
        """OpenAI /v1/embeddings over any engine exposing `embed`
        (reference protocols/openai embeddings surface)."""
        from dynamo_tpu.protocols.openai import (
            EmbeddingRequest,
            embedding_response,
        )

        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        try:
            req = EmbeddingRequest(**body)
        except ValidationError as e:
            return _error(400, e.errors()[0].get("msg", "invalid request"))
        try:
            chain = self.manager.get(req.model)
        except ModelNotFound:
            return _error(404, f"model '{req.model}' not found",
                          "not_found_error")
        embed = getattr(chain.engine, "embed", None)
        if embed is None:
            return _error(400, f"model '{req.model}' does not serve "
                               "embeddings")
        # normalize input to a list of token-id lists
        raw = req.input
        if isinstance(raw, str):
            raw = [raw]
        elif raw and isinstance(raw[0], int):
            raw = [raw]
        if not raw:
            return _error(400, "empty input")
        token_lists = [
            chain.preprocessor.tokenizer.encode(item)
            if isinstance(item, str) else list(item)
            for item in raw
        ]
        if any(not t for t in token_lists):
            return _error(400, "empty input")
        try:
            vectors = await asyncio.gather(*[
                asyncio.to_thread(embed, toks) for toks in token_lists
            ])
        except ValueError as e:  # engine-side input bound
            return _error(400, str(e))
        vectors = list(vectors)
        if req.dimensions:
            # OpenAI contract: truncate then re-normalize
            import math as _math

            def shrink(v):
                v = v[: req.dimensions]
                norm = _math.sqrt(sum(x * x for x in v)) or 1.0
                return [x / norm for x in v]

            vectors = [shrink(v) for v in vectors]
        return web.json_response(embedding_response(
            req.model, vectors,
            prompt_tokens=sum(len(t) for t in token_lists),
            encoding_format=req.encoding_format,
        ))

    async def handle_responses(self, request: web.Request) -> web.StreamResponse:
        """OpenAI Responses API (reference protocols/openai/responses.rs):
        lowered onto the chat pipeline via ResponsesRequest.to_chat().
        Stateless — `store`/`previous_response_id` chaining is rejected at
        validation."""
        from dynamo_tpu.protocols.openai import (
            ResponsesRequest,
            responses_response,
        )

        async def run(body: dict, env: dict) -> web.StreamResponse:
            try:
                rreq = ResponsesRequest(**body)
                chat_req = rreq.to_chat()
            except ValidationError as e:
                raise _ApiError(400, e.errors()[0].get("msg", "invalid request"))
            except ValueError as e:
                raise _ApiError(400, str(e))
            env["model"] = rreq.model
            chain = self._resolve_model(rreq.model, chat=True)
            try:
                pre = chain.preprocess(chat_req)
            except ValueError as e:
                raise _ApiError(400, str(e))
            pre.received_unix = env["t0_unix"]

            rid = make_id("resp")
            self.metrics.inflight.labels(rreq.model).inc()
            try:
                if rreq.stream:
                    return await self._stream_responses_api(
                        request, rreq, chain, pre, rid)
                text = ""
                n_tok = 0
                finish: Optional[FinishReason] = None
                stream = chain.generate(pre)
                try:
                    async for out in stream:
                        if out.text:
                            text += out.text
                        n_tok += len(out.token_ids)
                        if out.finish_reason is not None:
                            finish = out.finish_reason
                finally:
                    close = getattr(stream, "aclose", None)
                    if close is not None:
                        try:
                            await close()
                        except Exception:  # noqa: BLE001
                            log.debug("stream close failed",
                                      exc_info=True)
                incomplete = (finish == FinishReason.LENGTH)
                return web.json_response(responses_response(
                    rid=rid, model=rreq.model, text=text,
                    prompt_tokens=len(pre.token_ids),
                    completion_tokens=n_tok,
                    status="incomplete" if incomplete else "completed",
                    incomplete_reason=(
                        "max_output_tokens" if incomplete else None),
                ))
            finally:
                self.metrics.inflight.labels(rreq.model).dec()

        return await self._run_endpoint(request, "responses", run)

    def _resolve_model(self, name: str, *, chat: bool = False,
                       completion: bool = False):
        try:
            return self.manager.get(name, chat=chat, completion=completion)
        except ModelNotFound:
            raise _ApiError(404, f"model '{name}' not found",
                            "not_found_error")

    async def _run_endpoint(self, request: web.Request, endpoint: str, fn):
        """Shared request envelope: JSON-parse, _ApiError mapping, metrics
        accounting (requests_total/duration), 499 on cancellation.
        `fn(body, env)` does the endpoint-specific work and sets
        env["model"] as soon as it is known."""
        env = {"model": "", "t0": time.monotonic(), "t0_unix": time.time()}
        status = "500"
        t0 = env["t0"]
        try:
            try:
                body = await request.json()
            except Exception:
                status = "400"
                return _error(400, "invalid JSON body")
            try:
                resp = await fn(body, env)
            except _ApiError as e:
                status = str(e.status)
                return _error(e.status, e.message, e.etype)
            except EngineOverloadedError as e:
                # overload plane: every worker (or the local engine)
                # refused admission — retriable by construction, so the
                # client gets 429 + Retry-After, never a 500
                status = "429"
                return _overloaded_response(e)
            status = str(resp.status)
            return resp
        except asyncio.CancelledError:
            status = "499"
            raise
        except Exception:
            log.exception("%s handler failed", endpoint)
            return _error(500, "internal error", "internal_server_error")
        finally:
            self.metrics.requests_total.labels(
                env["model"], endpoint, status).inc()
            self.metrics.duration.labels(env["model"]).observe(
                time.monotonic() - t0)

    async def _stream_responses_api(
        self, request: web.Request, rreq, chain, pre, rid: str
    ) -> web.StreamResponse:
        """Responses-API SSE: typed events (response.created →
        response.output_text.delta* → response.completed)."""
        from dynamo_tpu.protocols.openai import responses_response

        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
            },
        )
        await resp.prepare(request)

        async def event(etype: str, data: dict) -> None:
            payload = json.dumps({"type": etype, **data})
            await resp.write(
                f"event: {etype}\ndata: {payload}\n\n".encode())

        snapshot = responses_response(
            rid=rid, model=rreq.model, text="",
            prompt_tokens=len(pre.token_ids), completion_tokens=0,
            status="in_progress",
        )
        await event("response.created", {"response": snapshot})
        text = ""
        n_tok = 0
        finish: Optional[FinishReason] = None
        stream = chain.generate(pre)
        try:
            try:
                async for out in stream:
                    if out.text:
                        text += out.text
                        await event("response.output_text.delta",
                                    {"delta": out.text, "output_index": 0,
                                     "content_index": 0})
                    n_tok += len(out.token_ids)
                    if out.finish_reason is not None:
                        finish = out.finish_reason
            except Exception as e:  # noqa: BLE001 — surface in-band: the
                # stream is already prepared, a 500 can't be returned
                log.warning("responses stream failed: %s", e)
                await event("response.failed", {"response": {
                    "id": rid, "object": "response", "status": "failed",
                    "error": {"message": str(e)},
                }})
                await resp.write_eof()
                return resp
            await event("response.output_text.done",
                        {"text": text, "output_index": 0, "content_index": 0})
            incomplete = (finish == FinishReason.LENGTH)
            final = responses_response(
                rid=rid, model=rreq.model, text=text,
                prompt_tokens=len(pre.token_ids), completion_tokens=n_tok,
                status="incomplete" if incomplete else "completed",
                incomplete_reason="max_output_tokens" if incomplete else None,
            )
            await event(
                "response.incomplete" if incomplete else "response.completed",
                {"response": final})
        except ConnectionResetError:
            # routine client disconnect: not an error; the prepared
            # StreamResponse is all we can return
            log.info("client disconnected mid-stream")
            return resp
        finally:
            close = getattr(stream, "aclose", None)
            if close is not None:
                try:
                    await close()
                except Exception:  # noqa: BLE001
                    log.debug("stream close failed", exc_info=True)
        await resp.write_eof()
        return resp

    async def handle_chat(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_openai(request, chat=True)

    async def handle_completion(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_openai(request, chat=False)

    # ------------------------------------------------------------------
    # core request path

    async def _handle_openai(
        self, request: web.Request, *, chat: bool
    ) -> web.StreamResponse:
        endpoint = "chat_completions" if chat else "completions"

        async def run(body: dict, env: dict) -> web.StreamResponse:
            try:
                req = (ChatCompletionRequest if chat else CompletionRequest)(**body)
            except ValidationError as e:
                raise _ApiError(400, e.errors()[0].get("msg", "invalid request"))
            env["model"] = req.model
            chain = self._resolve_model(req.model, chat=chat,
                                        completion=not chat)
            t_tok = time.monotonic()
            try:
                pre = chain.preprocess(req)
            except ValueError as e:
                raise _ApiError(400, str(e))
            pre.received_unix = env["t0_unix"]
            # trace context: minted here, keyed by the engine-facing
            # request id (it travels through the runtime protocol to the
            # router and worker; their spans come back via output
            # annotations and merge into this tree — /debug/trace/{id}).
            # Below the sample rate, the trace is an unsampled shell the
            # migration/failure paths can still promote.
            sampled = (self.trace_sample_rate >= 1.0
                       or self._trace_rng.random() < self.trace_sample_rate)
            trace = TRACES.start(pre.request_id, sampled=sampled)
            trace.add(span_now(
                "tokenize", t_tok,
                model=req.model, prompt_tokens=len(pre.token_ids),
            ))
            # overload plane: header hints land on top of the nvext
            # fields the preprocessor already applied (headers win;
            # nvext is NOT re-applied — re-minting its deadline here
            # would silently extend it by the tokenize latency)
            apply_request_hints(pre, request.headers, None)

            self.metrics.inflight.labels(req.model).inc()
            try:
                if req.stream:
                    return await self._stream_response(
                        request, req, chain, pre, chat,
                        t_received=env["t0"])
                return await self._unary_response(
                    req, chain, pre, chat, t_received=env["t0"])
            finally:
                self.metrics.inflight.labels(req.model).dec()
                tr = TRACES.finish(pre.request_id)
                # a breach/sample decision made in timing.finish() (which
                # ran inside the stream/unary paths) assembles its
                # dossier here, from the fully merged trace
                self.forensics.on_trace_finished(pre.request_id, tr)

        return await self._run_endpoint(request, endpoint, run)

    def _fanout(self, req, chain, pre) -> list[AsyncIterator[LLMEngineOutput]]:
        """n>1: run n independent engine streams (distinct seeds per choice,
        like the reference's engines do for best-of/n sampling)."""
        n = max(1, req.n)
        streams = []
        for i in range(n):
            p = pre if n == 1 else _with_choice_seed(pre, i)
            if p.request_id != pre.request_id:
                # extra choices get fresh request ids — alias them so
                # their route/worker spans land on the parent's tree
                TRACES.alias(p.request_id, pre.request_id)
            streams.append(chain.generate(p))
        return streams

    async def _unary_response(
        self, req, chain, pre, chat: bool,
        t_received: Optional[float] = None,
    ) -> web.Response:
        streams = self._fanout(req, chain, pre)
        texts = [""] * len(streams)
        tokens = [0] * len(streams)
        finishes: list[FinishReason] = [FinishReason.EOS] * len(streams)
        lp_entries: list[list[dict]] = [[] for _ in streams]
        t_start = t_received if t_received is not None else time.monotonic()
        timing = _RequestTiming(self, pre.request_id, t_start,
                                 tenant=getattr(pre, "tenant", ""))

        async def drain(i: int) -> None:
            try:
                async for out in streams[i]:
                    if out.text:
                        texts[i] += out.text
                    tokens[i] += len(out.token_ids)
                    timing.on_output(i, out)
                    if out.logprob_entries:
                        lp_entries[i].extend(out.logprob_entries)
                    if out.finish_reason is not None:
                        finishes[i] = out.finish_reason
            finally:
                close = getattr(streams[i], "aclose", None)
                if close is not None:
                    await close()

        try:
            results = await asyncio.gather(
                *[drain(i) for i in range(len(streams))],
                return_exceptions=True,
            )
            for r in results:
                if isinstance(r, BaseException):
                    raise r
        finally:
            timing.finish()
        if chat:
            choices = []
            for i in range(len(streams)):
                message: dict = {"role": "assistant", "content": texts[i]}
                finish = finishes[i].to_openai()
                if getattr(req, "tools", None):
                    from dynamo_tpu.tool_calls import (
                        parse_tool_calls_with_content,
                    )

                    calls, content = parse_tool_calls_with_content(
                        texts[i], _declared_tool_names(req)
                    )
                    if calls is not None:
                        message = {"role": "assistant", "content": content,
                                   "tool_calls": calls}
                        finish = "tool_calls"
                choices.append({
                    "index": i,
                    "message": message,
                    "finish_reason": finish,
                    "logprobs": (
                        {"content": lp_entries[i]} if lp_entries[i] else None
                    ),
                })
            body = chat_completion_response(
                rid=make_id("chatcmpl"),
                model=req.model,
                choices=choices,
                prompt_tokens=len(pre.token_ids),
                completion_tokens=sum(tokens),
            )
        else:
            from dynamo_tpu.protocols.openai import completion_logprobs

            choices = [
                {
                    "index": i,
                    "text": texts[i],
                    "finish_reason": finishes[i].to_openai(),
                    "logprobs": (
                        completion_logprobs(lp_entries[i])
                        if lp_entries[i] else None
                    ),
                }
                for i in range(len(streams))
            ]
            body = completion_response(
                rid=make_id("cmpl"),
                model=req.model,
                choices=choices,
                prompt_tokens=len(pre.token_ids),
                completion_tokens=sum(tokens),
            )
        return web.json_response(
            body, headers={"X-Request-Id": pre.request_id}
        )

    async def _stream_response(
        self, request: web.Request, req, chain, pre, chat: bool,
        t_received: Optional[float] = None,
    ) -> web.StreamResponse:
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                # the trace key: GET /debug/trace/{this} after the stream
                "X-Request-Id": pre.request_id,
            },
        )
        gen = DeltaGenerator(req.model, chat=chat, n=max(1, req.n))
        streams = self._fanout(req, chain, pre)
        completion_tokens = 0
        # in-band per-request metrics annotation (reference
        # ANNOTATION_LLM_METRICS, preprocessor.rs:68-90): opt in via
        # nvext {"annotations": ["llm_metrics"]} — the preprocessor has
        # already normalized them onto the request
        want_llm_metrics = "llm_metrics" in pre.annotations
        # per-stream first/last token times: ITL must be per generation,
        # not the n-way interleave; TTFT runs from request RECEIPT
        # (envelope entry — includes preprocess/route time, matching the
        # reference's measurement point)
        t_start = t_received if t_received is not None else time.monotonic()
        timing = _RequestTiming(self, pre.request_id, t_start,
                                 tenant=getattr(pre, "tenant", ""))
        # tool-call detection: hold back tool-shaped text until it parses
        tool_accs: dict[int, Any] = {}
        if chat and getattr(req, "tools", None):
            from dynamo_tpu.tool_calls import ToolCallAccumulator

            allowed = _declared_tool_names(req)
            tool_accs = {i: ToolCallAccumulator(allowed)
                         for i in range(len(streams))}
        queue: asyncio.Queue = asyncio.Queue()
        DONE = object()

        async def pump(i: int) -> None:
            try:
                async for out in streams[i]:
                    await queue.put((i, out))
            except Exception as e:  # surfaced in-band per choice
                await queue.put((i, e))
            finally:
                await queue.put((i, DONE))

        tasks = [asyncio.create_task(pump(i)) for i in range(len(streams))]
        live = len(streams)

        async def close_all() -> None:
            for t in tasks:
                t.cancel()
            for s in streams:
                close = getattr(s, "aclose", None)
                if close is not None:
                    try:
                        await close()
                    except Exception:  # noqa: BLE001
                        log.debug("stream close failed", exc_info=True)

        # overload plane: probe for ADMISSION before preparing the SSE
        # stream. If every choice bounces with EngineOverloadedError
        # before producing anything, the client gets a clean 429 +
        # Retry-After (a prepared 200 stream carrying an error event is
        # unretriable by standard clients). The first real item — or a
        # non-overload error, which keeps its in-band reporting — ends
        # the probe; stashed overload errors then surface in-band too.
        pending_head: deque = deque()
        overload_errs: list = []
        try:
            while live and not pending_head:
                i, item = await queue.get()
                if item is DONE:
                    live -= 1
                    continue
                if isinstance(item, EngineOverloadedError):
                    overload_errs.append((i, item))
                    continue
                pending_head.append((i, item))
        except asyncio.CancelledError:
            await close_all()
            raise
        if not pending_head and not live and overload_errs:
            await close_all()
            raise overload_errs[0][1]  # -> _run_endpoint maps to 429
        pending_head.extend(overload_errs)
        await resp.prepare(request)
        try:
            while live or pending_head:
                if pending_head:
                    i, item = pending_head.popleft()
                else:
                    i, item = await queue.get()
                if item is DONE:
                    live -= 1
                    continue
                if isinstance(item, Exception):
                    # the failed pump's DONE sentinel still arrives and
                    # decrements `live`; just surface the error in-band.
                    # Flush any tool-detection buffer first — held-back
                    # text must not vanish with the error.
                    if i in tool_accs:
                        _calls, leftover = tool_accs[i].finalize()
                        if leftover:
                            await resp.write(encode_event(
                                gen.text_chunk(leftover, index=i)
                            ))
                    log.warning("engine stream %d failed: %s", i, item)
                    # failed requests are always traced (sampling shell
                    # promoted so the failure context survives)
                    TRACES.promote(pre.request_id)
                    await resp.write(
                        encode_event({"error": {"message": str(item)}})
                    )
                    continue
                timing.on_output(i, item)
                completion_tokens += len(item.token_ids)
                text = item.text or ""
                if i in tool_accs and text:
                    text = tool_accs[i].feed(text)
                if text or item.logprob_entries:
                    # entries may arrive on a text-less output (final token
                    # eaten by the stop jail / partial UTF-8) — still owed
                    # to the client, one entry per token
                    await resp.write(
                        encode_event(gen.text_chunk(
                            text, index=i,
                            logprob_entries=item.logprob_entries,
                        ))
                    )
                if item.finish_reason is not None:
                    finish_override = None
                    if i in tool_accs:
                        calls, leftover = tool_accs[i].finalize()
                        if leftover:
                            # hermes prose / text that wasn't a tool call
                            await resp.write(encode_event(
                                gen.text_chunk(leftover, index=i)
                            ))
                        if calls is not None:
                            await resp.write(encode_event(
                                gen.tool_calls_chunk(calls, index=i)
                            ))
                            finish_override = "tool_calls"
                    await resp.write(
                        encode_event(gen.finish_chunk(
                            item.finish_reason, index=i,
                            finish_override=finish_override,
                        ))
                    )
            if req.stream_options and req.stream_options.include_usage:
                await resp.write(
                    encode_event(
                        gen.usage_chunk(len(pre.token_ids), completion_tokens)
                    )
                )
            if want_llm_metrics:
                ttft = timing.ttft
                itl = timing.itl_avg()
                itl_p50 = timing.itl_percentile(0.50)
                itl_p95 = timing.itl_percentile(0.95)
                await resp.write(encode_event({
                    "nvext": {"annotation": "llm_metrics", "metrics": {
                        "prompt_tokens": len(pre.token_ids),
                        "completion_tokens": completion_tokens,
                        "ttft_s": round(ttft, 6) if ttft is not None else None,
                        "itl_avg_s": round(itl, 6) if itl is not None else None,
                        "itl_p50_s": round(itl_p50, 6)
                        if itl_p50 is not None else None,
                        "itl_p95_s": round(itl_p95, 6)
                        if itl_p95 is not None else None,
                    }}
                }))
            await resp.write(encode_done())
        except ConnectionResetError:
            # routine client disconnect: not an error; the prepared
            # StreamResponse is all we can return
            log.info("client disconnected mid-stream")
            return resp
        except asyncio.CancelledError:
            log.info("request cancelled mid-stream")
            raise
        finally:
            # disconnect/cancel paths too: tokens already streamed must
            # count in TTFT/E2E alongside their observed ITL gaps
            timing.finish()
            for t in tasks:
                t.cancel()
            for s in streams:
                close = getattr(s, "aclose", None)
                if close is not None:
                    try:
                        await close()
                    except Exception:  # noqa: BLE001
                        log.debug("stream close failed", exc_info=True)
        await resp.write_eof()
        return resp


def _declared_tool_names(req) -> "Optional[set]":
    """Function names declared in the request's tools (None when they
    can't be extracted — then any well-formed call name is accepted)."""
    names = set()
    for t in getattr(req, "tools", None) or []:
        if isinstance(t, dict):
            n = (t.get("function") or {}).get("name") or t.get("name")
            if n:
                names.add(n)
    return names or None


def _with_choice_seed(pre, i: int):
    """Give choice i>0 a distinct sampling seed so n choices differ."""
    import copy

    if i == 0:
        return pre
    p = copy.copy(pre)
    p.sampling_options = copy.copy(pre.sampling_options)
    if p.sampling_options.seed is not None:
        p.sampling_options.seed = p.sampling_options.seed + i
    else:
        p.sampling_options.seed = 0x5EED ^ (i * 0x9E3779B9)
    import uuid

    p.request_id = uuid.uuid4().hex
    return p
