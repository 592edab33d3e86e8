"""Per-process system HTTP server: /metrics + /health + /debug on every
worker.

Parity: reference lib/runtime/src/http_server.rs:27-45,91 — each process
exposes its own Prometheus endpoint (uptime + process-local stats) so
operators can scrape workers directly, independent of the frontend's
service metrics and the standalone re-exporter. On top of the gauges this
renders the engine's latency histograms (telemetry/metrics.py) and serves
the debug plane:

  /debug/flight               recent engine-round events (flight ring)
  /debug/prof                 host-round attribution summary (top
                              segments, coverage ratio — telemetry/prof)
  /debug/trace/{request_id}   this worker's span tree for a request
  /debug/trace                recent completed trace ids

Resilience controls (dynamo_tpu/resilience/):
  GET/POST /drain             graceful drain state / trigger (stop
                              admitting, finish in-flight, exit)
  GET/POST/DELETE /chaos      list / arm / disarm fault-injection points
                              (tools/chaos.py drives this)
"""
from __future__ import annotations

import logging
import time
from typing import Any, Optional

from aiohttp import web

from dynamo_tpu.resilience.chaos import CHAOS
from dynamo_tpu.telemetry import TRACES
from dynamo_tpu.telemetry.fleet_feed import FLEET_FEED
from dynamo_tpu.telemetry.forensics import OUTLIERS
from dynamo_tpu.telemetry.metrics import render_histogram, render_planes
from dynamo_tpu.telemetry.timeline import to_chrome_trace
from dynamo_tpu.tenancy import TENANT

log = logging.getLogger(__name__)


class SystemServer:
    """Tiny per-process observability server. `engine` is optional: when
    it exposes `metrics()` (ForwardPassMetrics), those gauges — and any
    histogram snapshots it carries — are rendered alongside uptime; when
    it exposes `flight`, the ring serves at /debug/flight. ``drain`` is
    an optional DrainController enabling the /drain control."""

    def __init__(
        self,
        engine: Any = None,
        *,
        host: str = "0.0.0.0",
        port: int = 0,
        worker_id: str = "",
        drain: Any = None,
    ):
        self.engine = engine
        self.host = host
        self.port = port
        self.worker_id = worker_id
        self.drain = drain
        self._started = time.monotonic()
        self._runner: Optional[web.AppRunner] = None
        self.app = web.Application()
        self.app.add_routes([
            web.get("/metrics", self.handle_metrics),
            web.get("/health", self.handle_health),
            web.get("/live", self.handle_health),
            web.get("/debug/flight", self.handle_flight),
            web.get("/debug/kv_fleet", self.handle_kv_fleet),
            web.get("/debug/tenants", self.handle_tenants),
            web.get("/debug/prof", self.handle_prof),
            web.get("/debug/trace", self.handle_trace_index),
            web.get("/debug/trace/{request_id}", self.handle_trace),
            web.get("/debug/outliers", self.handle_outliers),
            web.get("/debug/outliers/{request_id}", self.handle_outlier),
            web.get("/drain", self.handle_drain_status),
            web.post("/drain", self.handle_drain),
            web.get("/chaos", self.handle_chaos_list),
            web.post("/chaos", self.handle_chaos_arm),
            web.delete("/chaos", self.handle_chaos_disarm),
        ])

    async def start(self) -> "SystemServer":
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        log.info("system server on %s:%d", self.host, self.port)
        return self

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    def render(self, openmetrics: bool = False) -> str:
        lines = [
            "# HELP dynamo_system_uptime_seconds process uptime",
            "# TYPE dynamo_system_uptime_seconds gauge",
            f"dynamo_system_uptime_seconds "
            f"{time.monotonic() - self._started:.3f}",
        ]
        metrics_fn = getattr(self.engine, "metrics", None)
        if metrics_fn is not None:
            try:
                m = metrics_fn()
            except Exception:  # noqa: BLE001 — observability must not throw
                log.exception("engine metrics failed")
                m = None
            if m is not None:
                # this worker's histograms feed the (fleet-of-one) merge
                # so dynamo_fleet_* families render here too
                FLEET_FEED.observe(m)
                w = self.worker_id or m.worker_id

                def g(name: str, help_: str, v) -> None:
                    lines.append(f"# HELP {name} {help_}")
                    lines.append(f"# TYPE {name} gauge")
                    lines.append(f'{name}{{worker="{w}"}} {v}')

                ws, ks = m.worker_stats, m.kv_stats
                g("dynamo_worker_active_slots", "requests in decode slots",
                  ws.request_active_slots)
                g("dynamo_worker_total_slots", "decode slot capacity",
                  ws.request_total_slots)
                g("dynamo_worker_waiting_requests", "queued requests",
                  ws.num_requests_waiting)
                g("dynamo_worker_waiting_prefill_tokens",
                  "prompt tokens waiting for prefill",
                  ws.num_waiting_prefill_tokens)
                g("dynamo_worker_max_waiting_requests",
                  "admission queue-depth budget (0 = unbounded)",
                  ws.max_waiting_requests)
                g("dynamo_worker_max_waiting_prefill_tokens",
                  "admission prefill-token budget (0 = unbounded)",
                  ws.max_waiting_prefill_tokens)
                g("dynamo_kv_active_blocks", "KV pages in use",
                  ks.kv_active_blocks)
                g("dynamo_kv_total_blocks", "KV page capacity",
                  ks.kv_total_blocks)
                g("dynamo_kv_usage_perc", "KV pool usage fraction",
                  ks.gpu_cache_usage_perc)
                g("dynamo_kv_hit_rate", "prefix cache hit rate",
                  ks.gpu_prefix_cache_hit_rate)
                g("dynamo_kv_host_blocks", "host-tier (G2) cached pages",
                  ks.host_blocks)
                g("dynamo_spec_proposed_total",
                  "speculative tokens proposed", ws.spec_proposed_total)
                g("dynamo_spec_accepted_total",
                  "speculative tokens accepted", ws.spec_accepted_total)
                g("dynamo_spec_acceptance_rate",
                  "rolling speculative acceptance rate",
                  ws.spec_acceptance_rate)
                g("dynamo_spec_effective_k",
                  "mean acceptance-adaptive effective K over "
                  "speculating slots", ws.spec_effective_k)
                g("dynamo_spec_effective_k_p50",
                  "median per-slot effective K over speculating slots",
                  ws.spec_effective_k_p50)
                g("dynamo_spec_effective_k_p95",
                  "p95 per-slot effective K over speculating slots",
                  ws.spec_effective_k_p95)
                for name, snap in sorted(
                    (getattr(m, "histograms", None) or {}).items()
                ):
                    lines.extend(render_histogram(
                        name, snap.get("help", name), snap,
                        label=f'worker="{w}"',
                        openmetrics=openmetrics,
                    ))
        # the planes' counters of THIS process
        return "\n".join(lines) + "\n" + render_planes(openmetrics)

    async def handle_metrics(self, request: web.Request) -> web.Response:
        if "application/openmetrics-text" in request.headers.get(
                "Accept", ""):
            return web.Response(
                text=self.render(openmetrics=True) + "# EOF\n",
                content_type="application/openmetrics-text",
            )
        return web.Response(text=self.render(), content_type="text/plain")

    async def handle_health(self, request: web.Request) -> web.Response:
        return web.json_response({
            "status": "ok",
            "uptime_s": round(time.monotonic() - self._started, 3),
            "worker_id": self.worker_id,
        })

    async def handle_flight(self, request: web.Request) -> web.Response:
        flight = getattr(self.engine, "flight", None)
        if flight is None:
            return web.json_response(
                {"error": "engine exposes no flight recorder"}, status=404
            )
        return web.json_response({
            "worker_id": self.worker_id,
            "recorded_total": flight.recorded_total,
            "events": flight.snapshot(),
        })

    async def handle_kv_fleet(self, request: web.Request) -> web.Response:
        """GET /debug/kv_fleet — this WORKER's view of the fleet prefix
        economy: the last hint digest the frontend controller applied
        (the frontend's own /debug/kv_fleet serves the full fleet map)."""
        hints = getattr(self.engine, "fleet_hints", None)
        if hints is None:
            return web.json_response(
                {"worker_id": self.worker_id, "hints": None}
            )
        return web.json_response(
            {"worker_id": self.worker_id, "hints": hints.to_dict()}
        )

    async def handle_tenants(self, request: web.Request) -> web.Response:
        """GET /debug/tenants — this WORKER's tenancy plane: the
        engine's quota/queue view per tenant plus the process-local
        tenant metric snapshot (the frontend aggregates its own)."""
        body: dict = {
            "worker_id": self.worker_id,
            "tenants": TENANT.snapshot(),
        }
        dbg = getattr(self.engine, "tenant_debug", None)
        if dbg is not None:
            try:
                body["engine"] = dbg()
            except Exception:  # noqa: BLE001 — debug surface never throws
                log.exception("tenant debug failed")
        return web.json_response(body)

    async def handle_prof(self, request: web.Request) -> web.Response:
        """GET /debug/prof[?top=N] — host-round attribution: per-segment
        totals/shares, recent-window per-round means, coverage ratio, and
        the live SLO burn rates."""
        prof = getattr(self.engine, "prof", None)
        if prof is None:
            return web.json_response(
                {"error": "engine exposes no round profiler"}, status=404
            )
        from dynamo_tpu.telemetry.prof import PROF

        try:
            top = int(request.query.get("top", 0))
        except ValueError:
            top = 0
        body = prof.summary(top=top)
        body["worker_id"] = self.worker_id
        body["slo_burn_rates"] = PROF.burn_rates()
        return web.json_response(body)

    # ---- resilience controls ----

    async def handle_drain_status(self, request: web.Request) -> web.Response:
        if self.drain is None:
            return web.json_response(
                {"error": "no drain controller wired"}, status=404
            )
        return web.json_response(self.drain.status())

    async def handle_drain(self, request: web.Request) -> web.Response:
        """POST /drain: stop admitting, finish in-flight, then exit —
        the operator/planner-facing scale-down control."""
        if self.drain is None:
            return web.json_response(
                {"error": "no drain controller wired"}, status=404
            )
        self.drain.request_drain(reason="http /drain")
        return web.json_response(self.drain.status())

    async def handle_chaos_list(self, request: web.Request) -> web.Response:
        return web.json_response({
            "worker_id": self.worker_id,
            "points": CHAOS.list_points(),
        })

    async def handle_chaos_arm(self, request: web.Request) -> web.Response:
        """POST /chaos {"point": name, "probability": p, "delay_s": t,
        "after_outputs": n, "once": bool} — arm one injection point."""
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "invalid JSON"}, status=400)
        name = body.get("point")
        if name not in CHAOS.points:
            return web.json_response(
                {"error": f"unknown chaos point {name!r}"}, status=400
            )
        try:
            p = CHAOS.arm(
                name,
                probability=float(body.get("probability", 1.0)),
                delay_s=float(body.get("delay_s", 0.0)),
                after_outputs=int(body.get("after_outputs", 0)),
                once=bool(body.get("once", False)),
            )
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": f"invalid chaos parameters: {e}"}, status=400
            )
        return web.json_response(p.to_dict())

    async def handle_chaos_disarm(self, request: web.Request) -> web.Response:
        """DELETE /chaos[?point=name] — disarm one point or all."""
        name = request.query.get("point")
        if name:
            if name not in CHAOS.points:
                return web.json_response(
                    {"error": f"unknown chaos point {name!r}"}, status=400
                )
            CHAOS.disarm(name)
        else:
            CHAOS.disarm_all()
        return web.json_response({"points": CHAOS.list_points()})

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

    async def handle_outliers(self, request: web.Request) -> web.Response:
        """GET /debug/outliers — this worker's SLO-breach dossier ring
        (worker-side captures for requests whose frontend runs in
        another process)."""
        body = OUTLIERS.index()
        body["worker_id"] = self.worker_id
        return web.json_response(body)

    async def handle_outlier(self, request: web.Request) -> web.Response:
        """GET /debug/outliers/{request_id}[?format=perfetto] — one full
        dossier from this worker's ring."""
        rid = request.match_info["request_id"]
        d = OUTLIERS.get(rid)
        if d is None:
            return web.json_response({
                "error": f"no dossier for request {rid!r}",
                "worker_id": self.worker_id,
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
