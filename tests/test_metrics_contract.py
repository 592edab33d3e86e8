"""Metrics contract: every ``dynamo_*`` series rendered by the system
server, the aggregating exporter, and the frontend must carry HELP/TYPE
metadata and be documented in README's Observability section — the
scrape surfaces and the docs cannot drift apart silently.
"""
import os
import re

from dynamo_tpu.kv_router.protocols import (
    ForwardPassMetrics,
    KvStats,
    WorkerStats,
)
from dynamo_tpu.telemetry import TelemetryRegistry, request_histograms

README = os.path.join(os.path.dirname(__file__), "..", "README.md")

# sample-name suffixes that belong to a declared family rather than
# being families themselves (histogram series + prometheus_client extras)
_SUFFIXES = ("_bucket", "_sum", "_count", "_total", "_created")


class _StubEngine:
    """Engine double: gauges + populated histogram snapshots."""

    def __init__(self):
        self.telemetry = request_histograms(TelemetryRegistry(),
                                            engine=True)
        for h in ("dynamo_request_ttft_seconds",
                  "dynamo_request_itl_seconds"):
            self.telemetry.get(h).observe(0.1)

    def metrics(self) -> ForwardPassMetrics:
        return ForwardPassMetrics(
            worker_id="w0",
            worker_stats=WorkerStats(request_active_slots=1,
                                     request_total_slots=4),
            kv_stats=KvStats(kv_active_blocks=2, kv_total_blocks=8),
            histograms=self.telemetry.snapshot(),
        )


def _parse_families(text: str):
    """(declared families with both HELP and TYPE, sample names)."""
    helped, typed, samples = set(), set(), set()
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
        elif line.startswith("# TYPE "):
            typed.add(line.split()[2])
        elif not line.startswith("#"):
            samples.add(re.split(r"[{ ]", line, 1)[0])
    return helped & typed, samples


def _families_of(samples, declared):
    """Map each sample name onto its declared family (or fail)."""
    out = {}
    for s in samples:
        fam = None
        if s in declared:
            fam = s
        else:
            for suf in _SUFFIXES:
                if s.endswith(suf) and s[: -len(suf)] in declared:
                    fam = s[: -len(suf)]
                    break
                # prometheus_client counters: family "x_total" declares
                # HELP/TYPE as x_total but _created samples are x_created
                if s.endswith("_created") and (
                    s[: -len("_created")] + "_total" in declared
                ):
                    fam = s[: -len("_created")] + "_total"
                    break
        assert fam is not None, f"sample {s!r} has no HELP/TYPE family"
        out[s] = fam
    return out


def _assert_contract(text: str, readme: str):
    declared, samples = _parse_families(text)
    fams = _families_of(samples, declared)
    for fam in set(fams.values()):
        # prometheus_client exposes the *_created companion series as its
        # own gauge family — documentation-wise it's part of the parent
        if fam.endswith("_created"):
            fam = fam[: -len("_created")]
        if fam.startswith("dynamo_"):
            assert fam in readme, f"{fam} not documented in README"


def _readme_text() -> str:
    with open(README) as f:
        return f.read()


def test_system_server_render_contract():
    from dynamo_tpu.runtime.system_server import SystemServer

    text = SystemServer(_StubEngine(), worker_id="w0").render()
    # histograms made it into the per-worker render, worker-labelled
    assert 'dynamo_request_ttft_seconds_bucket{worker="w0",le=' in text
    _assert_contract(text, _readme_text())


def test_exporter_render_contract():
    from dynamo_tpu.metrics_exporter import MetricsExporter

    exp = MetricsExporter(kv=None)
    m = _StubEngine().metrics()
    exp.aggregator.update(m)
    m2 = _StubEngine().metrics()
    m2.worker_id = "w1"
    exp.aggregator.update(m2)
    text = exp.render()
    # the satellite fix: dynamo_metrics_workers now has HELP/TYPE
    assert "# HELP dynamo_metrics_workers" in text
    assert "# TYPE dynamo_metrics_workers gauge" in text
    assert "dynamo_metrics_workers 2" in text
    # one HELP/TYPE head per histogram family, both workers' series
    assert text.count("# TYPE dynamo_request_ttft_seconds histogram") == 1
    assert 'dynamo_request_ttft_seconds_count{worker="w0"}' in text
    assert 'dynamo_request_ttft_seconds_count{worker="w1"}' in text
    _assert_contract(text, _readme_text())


def test_frontend_render_contract():
    from dynamo_tpu.frontend.service import HttpService

    svc = HttpService()
    svc.metrics.requests_total.labels("m", "chat_completions", "200").inc()
    svc.metrics.duration.labels("m").observe(0.1)
    svc._h_ttft.observe(0.05)
    text = svc.metrics.render().decode() + svc.telemetry.render()
    _assert_contract(text, _readme_text())


def test_readme_documents_canonical_series():
    readme = _readme_text()
    for name in (
        "dynamo_request_ttft_seconds", "dynamo_request_itl_seconds",
        "dynamo_request_e2e_seconds", "dynamo_request_queue_seconds",
        "dynamo_engine_round_seconds", "dynamo_spec_acceptance_rate",
        "dynamo_spec_effective_k", "dynamo_metrics_workers",
        # KV-transfer data plane (chunk pipeline) + disagg fallback
        "dynamo_kv_transfer_tx_chunks_total",
        "dynamo_kv_transfer_rx_chunks_total",
        "dynamo_kv_transfer_tx_bytes_total",
        "dynamo_kv_transfer_rx_bytes_total",
        "dynamo_kv_transfer_streams_total",
        "dynamo_kv_transfer_errors_total",
        "dynamo_kv_transfer_chunk_seconds",
        "dynamo_kv_transfer_seconds",
        "dynamo_disagg_fallback_total",
        # int8 KV-block economy (dynamo_tpu/kv_quant.py)
        "dynamo_kv_quant_pages_total",
        "dynamo_kv_quant_dequant_pages_total",
        "dynamo_kv_quant_scale_bytes_total",
        "dynamo_kv_quant_dequant_seconds",
        "dynamo_kv_pool_capacity_blocks",
        # in-kernel int8 decode ctx (PR 14: raw pool<->ctx copies +
        # once-per-round ring-flush requantize)
        "dynamo_kv_quant_ctx_seal_raw_pages_total",
        "dynamo_kv_quant_ctx_admit_raw_pages_total",
        "dynamo_kv_quant_ctx_flush_groups_total",
        # KV data-integrity plane (dynamo_tpu/kv_integrity.py)
        "dynamo_kv_integrity_verified_total",
        "dynamo_kv_integrity_failed_total",
        "dynamo_kv_integrity_quarantined_total",
        "dynamo_kv_integrity_recomputed_total",
        "dynamo_kv_integrity_retries_total",
        "dynamo_kv_integrity_g3_scrub_recovered_total",
        "dynamo_kv_integrity_g3_scrub_dropped_total",
        # overload-protection plane (dynamo_tpu/overload/)
        "dynamo_overload_rejected_total",
        "dynamo_overload_shed_total",
        "dynamo_overload_preempted_total",
        "dynamo_overload_preempt_migrations_total",
        "dynamo_overload_http_429_total",
        "dynamo_overload_router_spills_total",
        "dynamo_overload_queue_depth",
        "dynamo_overload_queue_tokens",
        "dynamo_worker_waiting_prefill_tokens",
        "dynamo_worker_max_waiting_requests",
        "dynamo_worker_max_waiting_prefill_tokens",
        # performance-attribution plane (dynamo_tpu/telemetry/prof.py)
        "dynamo_host_round_seconds",
        "dynamo_host_round_coverage_ratio",
        "dynamo_slo_ttft_burn_rate",
        "dynamo_slo_itl_burn_rate",
        # tail-latency forensics (dynamo_tpu/telemetry/forensics.py)
        "dynamo_forensics_dossiers_total",
        "dynamo_forensics_breaches_total",
        "dynamo_forensics_sampled_total",
        "dynamo_forensics_dossiers_evicted_total",
        "dynamo_forensics_ring_size",
        # fleet-merged latency feed (dynamo_tpu/telemetry/fleet_feed.py)
        "dynamo_fleet_request_ttft_seconds",
        "dynamo_fleet_request_itl_seconds",
        "dynamo_fleet_request_e2e_seconds",
        "dynamo_fleet_request_queue_seconds",
        "dynamo_fleet_engine_round_seconds",
        "dynamo_fleet_feed_workers",
        "dynamo_planner_fleet_ttft_p99_seconds",
        "dynamo_planner_fleet_queue_p99_seconds",
        # tenant-sliced serving plane (dynamo_tpu/tenancy/)
        "dynamo_tenant_admitted_total",
        "dynamo_tenant_rejected_total",
        "dynamo_tenant_shed_total",
        "dynamo_tenant_http_429_total",
        "dynamo_tenant_queue_depth",
        "dynamo_tenant_queue_tokens",
        "dynamo_tenant_adapter_rounds_total",
        "dynamo_tenant_request_ttft_seconds",
        "dynamo_tenant_request_queue_seconds",
        # prefill attention work and waste (engine dispatch sites, PR 29)
        "dynamo_engine_prefill_attn_live_pairs",
        "dynamo_engine_prefill_attn_scored_pairs",
        "dynamo_engine_prefill_attn_blocks",
        "dynamo_engine_prefill_attn_fused_blocks",
    ):
        assert name in readme, f"{name} missing from README"
    for endpoint in ("/debug/trace", "/debug/flight", "/debug/prof",
                     "/debug/outliers", "/debug/tenants"):
        assert endpoint in readme


def test_forensics_and_fleet_families_on_all_three_surfaces():
    """The new forensics counters and the fleet-merged histograms render
    with HELP/TYPE on every scrape surface."""
    from dynamo_tpu.frontend.service import HttpService
    from dynamo_tpu.metrics_exporter import MetricsExporter
    from dynamo_tpu.runtime.system_server import SystemServer

    from dynamo_tpu.telemetry.fleet_feed import FLEET_FEED
    from dynamo_tpu.telemetry.forensics import FORENSICS

    exp = MetricsExporter(kv=None)
    exp.aggregator.update(_StubEngine().metrics())
    svc = HttpService()
    frontend = (svc.metrics.render().decode() + svc.telemetry.render()
                + FLEET_FEED.render() + FORENSICS.render())
    for text in (
        SystemServer(_StubEngine(), worker_id="w0").render(),
        exp.render(),
        frontend,
    ):
        assert "# TYPE dynamo_forensics_dossiers_total counter" in text
        assert "# TYPE dynamo_forensics_ring_size gauge" in text
        assert "# TYPE dynamo_fleet_feed_workers gauge" in text


def test_tenant_families_on_all_three_surfaces():
    """The tenant-sliced families render — with HELP/TYPE and the
    ``tenant`` label — on every scrape surface."""
    from dynamo_tpu.frontend.service import HttpService
    from dynamo_tpu.metrics_exporter import MetricsExporter
    from dynamo_tpu.runtime.system_server import SystemServer
    from dynamo_tpu.tenancy import TENANT

    TENANT.inc("dynamo_tenant_admitted_total", "t0")
    TENANT.observe("dynamo_tenant_request_ttft_seconds", "t0", 0.05)
    try:
        exp = MetricsExporter(kv=None)
        exp.aggregator.update(_StubEngine().metrics())
        svc = HttpService()
        frontend = (svc.metrics.render().decode() + svc.telemetry.render()
                    + TENANT.render())
        for text in (
            SystemServer(_StubEngine(), worker_id="w0").render(),
            exp.render(),
            frontend,
        ):
            assert "# TYPE dynamo_tenant_admitted_total counter" in text
            assert "# TYPE dynamo_tenant_queue_depth gauge" in text
            assert ("# TYPE dynamo_tenant_request_ttft_seconds histogram"
                    in text)
            assert text.count(
                "# TYPE dynamo_tenant_admitted_total counter") == 1
            assert 'dynamo_tenant_admitted_total{tenant="t0"} 1' in text
            assert ('dynamo_tenant_request_ttft_seconds_bucket{tenant="t0"'
                    in text)
            _assert_contract(text, _readme_text())
    finally:
        TENANT.reset()


def test_readme_row_names_every_host_segment():
    """`dynamo_host_round_seconds{segment=...}`: the README's row lists
    the enum (telemetry/prof.py SEGMENTS) member for member."""
    import re

    from dynamo_tpu.telemetry.prof import SEGMENTS

    row = next(line for line in _readme_text().splitlines()
               if line.startswith("| `dynamo_host_round_seconds`"))
    listed = re.findall(r"`([a-z_]+)`", row.split("`segment=`", 1)[1])
    assert listed == list(SEGMENTS)


def test_prof_families_on_all_three_surfaces():
    """The attribution plane's families render — with HELP/TYPE and the
    per-segment label — on every scrape surface."""
    from dynamo_tpu.metrics_exporter import MetricsExporter
    from dynamo_tpu.runtime.system_server import SystemServer
    from dynamo_tpu.telemetry.prof import PROF, SEGMENTS, RoundProf

    prof = RoundProf()
    prof.begin_round()
    prof.enter(SEGMENTS.index("dispatch"))
    prof.end_round()
    PROF.fold(prof)
    try:
        for text in (
            SystemServer(_StubEngine(), worker_id="w0").render(),
            MetricsExporter(kv=None).render(),
        ):
            assert "# TYPE dynamo_host_round_seconds histogram" in text
            assert text.count(
                "# TYPE dynamo_host_round_seconds histogram") == 1
            assert 'dynamo_host_round_seconds_bucket{segment=' in text
            assert "# TYPE dynamo_host_round_coverage_ratio gauge" in text
            assert "# TYPE dynamo_slo_ttft_burn_rate gauge" in text
            assert "# TYPE dynamo_slo_itl_burn_rate gauge" in text
            _assert_contract(text, _readme_text())
        from dynamo_tpu.frontend.service import HttpService

        svc = HttpService()
        text = svc.telemetry.render() + PROF.render()
        assert "# TYPE dynamo_host_round_seconds histogram" in text
        _assert_contract(text, _readme_text())
    finally:
        PROF.reset()
