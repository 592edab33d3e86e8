"""Planner + metrics-exporter tests (reference planner_core.py:131-168
observe->decide->scale loop; components/metrics re-exporter).

Keystone e2e: a real planner over a real LocalConnector scales an actual
mocker-worker fleet 1 -> 3 -> 1 as synthetic load comes and goes, with the
load signal flowing worker -> store metrics plane -> planner.
"""
import asyncio
import sys

import pytest

from dynamo_tpu.kv_router.protocols import (
    ForwardPassMetrics,
    KvStats,
    WorkerStats,
)
from dynamo_tpu.planner import LocalConnector, Planner, PlannerConfig
from dynamo_tpu.runtime.client import KvClient
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.store import serve_store


class FakeConnector:
    def __init__(self, n: int = 1):
        self.n = n
        self.calls: list[int] = []

    def current_replicas(self) -> int:
        return self.n

    async def set_replicas(self, n: int) -> None:
        self.calls.append(n)
        self.n = n


def metrics(worker, usage=0.0, waiting=0):
    return ForwardPassMetrics(
        worker_id=worker,
        worker_stats=WorkerStats(num_requests_waiting=waiting),
        kv_stats=KvStats(gpu_cache_usage_perc=usage),
    )


async def test_planner_decision_thresholds():
    server, store = await serve_store(port=0)
    port = server.sockets[0].getsockname()[1]
    kv = await KvClient(port=port).connect()
    conn = FakeConnector(2)
    planner = Planner(kv, conn, PlannerConfig(
        kv_usage_scale_up=0.8, kv_usage_scale_down=0.3,
        waiting_scale_up=4, min_replicas=1, max_replicas=4,
        stable_intervals=2,
    ))
    agg = planner.aggregator

    # in-band load: hold
    agg.update(metrics("w0", usage=0.5))
    assert planner.decide() == 2

    # high KV usage: scale up
    agg.update(metrics("w0", usage=0.9))
    assert planner.decide() == 3

    # deep queue alone: scale up
    agg.update(metrics("w0", usage=0.5, waiting=9))
    assert planner.decide() == 3

    # low load: downscale only after stable_intervals consecutive lows
    agg.update(metrics("w0", usage=0.1))
    assert planner.decide() == 2           # streak 1: hold
    assert planner.decide() == 1           # streak 2: down
    # clamped at min_replicas
    conn.n = 1
    assert planner.decide() == 1
    assert planner.decide() == 1

    # clamped at max_replicas
    conn.n = 4
    agg.update(metrics("w0", usage=0.95))
    assert planner.decide() == 4

    await kv.close()
    server.close()


@pytest.mark.asyncio_timeout(420)
async def test_planner_e2e_scales_mocker_fleet():
    """1 -> 3 -> 1 with REAL subprocess workers: load held open on the
    fleet pushes KV usage over the (low) threshold; the planner spawns
    CLI mocker workers; releasing the load shrinks the fleet."""
    server, store = await serve_store(port=0, sweep_interval_s=0.05)
    port = server.sockets[0].getsockname()[1]
    cp = f"127.0.0.1:{port}"

    worker_cmd = [
        sys.executable, "-m", "dynamo_tpu.cli", "run",
        "in=endpoint", "out=mocker",
        "--control-plane", cp, "--model-name", "pm",
        "--namespace", "plan", "--page-size", "4",
    ]
    conn = LocalConnector(worker_cmd)
    kv = await KvClient(port=port).connect()
    planner = Planner(kv, conn, PlannerConfig(
        adjustment_interval_s=1.0,
        kv_usage_scale_up=0.01,   # ANY active request triggers scale-up
        kv_usage_scale_down=0.005,
        waiting_scale_up=10_000,
        min_replicas=1, max_replicas=3, stable_intervals=2,
        metrics_stale_after_s=30.0,
    ))
    rt = await DistributedRuntime.connect(port=port)
    client = None
    stream = None
    try:
        await conn.set_replicas(1)
        await planner.start()
        client = await rt.namespace("plan").component("backend").endpoint(
            "generate"
        ).client()
        await client.wait_for_instances(1, timeout_s=90)

        # open-ended load: one long-running stream holds pages/slots
        stream = client.generate({
            "token_ids": list(range(1, 40)),
            "stop_conditions": {"max_tokens": 100000, "ignore_eos": True},
        })
        # consume slowly in the background so the request stays active
        async def sip():
            async for _ in stream:
                await asyncio.sleep(0.05)
        sip_task = asyncio.create_task(sip())

        # planner observes load -> scales to 3 (one step per interval)
        for _ in range(240):
            if conn.current_replicas() == 3:
                break
            await asyncio.sleep(0.5)
        assert conn.current_replicas() == 3
        await client.wait_for_instances(3, timeout_s=120)

        # release the load -> metrics decay -> back down to 1
        sip_task.cancel()
        aclose = getattr(stream, "aclose", None)
        if aclose:
            await aclose()
        stream = None
        for _ in range(360):
            if conn.current_replicas() == 1:
                break
            await asyncio.sleep(0.5)
        assert conn.current_replicas() == 1
    finally:
        await planner.stop()
        if stream is not None:
            aclose = getattr(stream, "aclose", None)
            if aclose:
                await aclose()
        if client is not None:
            await client.stop()
        await conn.shutdown()
        await rt.close()
        await kv.close()
        server.close()


async def test_metrics_exporter_prometheus():
    """components/metrics parity: load plane -> Prometheus text."""
    import aiohttp

    from dynamo_tpu.metrics_exporter import MetricsExporter
    from dynamo_tpu.runtime.publisher import WorkerMetricsPublisher

    server, store = await serve_store(port=0)
    port = server.sockets[0].getsockname()[1]
    kv = await KvClient(port=port).connect()
    exp = await MetricsExporter(kv, host="127.0.0.1", port=0).start()

    wkv = await KvClient(port=port).connect()
    pub = WorkerMetricsPublisher(wkv, "w7", min_interval_s=0.0)
    pub.start()
    pub(metrics("w7", usage=0.42, waiting=3))
    await asyncio.sleep(0.3)

    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{exp.port}/metrics") as r:
            assert r.status == 200
            text = await r.text()
    assert 'dynamo_kv_usage_perc{worker="w7"} 0.42' in text
    assert 'dynamo_worker_waiting_requests{worker="w7"} 3' in text
    assert "dynamo_metrics_workers 1" in text

    await pub.stop()
    await exp.stop()
    await wkv.close()
    await kv.close()
    server.close()


async def test_system_server_per_worker():
    """Reference http_server.rs parity: each worker process exposes its
    own /metrics + /health."""
    import aiohttp

    from dynamo_tpu.mocker import MockerArgs, MockerEngine
    from dynamo_tpu.runtime.system_server import SystemServer

    eng = MockerEngine(MockerArgs(speedup_ratio=100.0, page_size=4))
    srv = await SystemServer(eng, host="127.0.0.1", port=0,
                             worker_id="w9").start()
    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{srv.port}/health") as r:
            body = await r.json()
            assert body["status"] == "ok" and body["worker_id"] == "w9"
        async with s.get(f"http://127.0.0.1:{srv.port}/metrics") as r:
            text = await r.text()
    assert "dynamo_system_uptime_seconds" in text
    assert 'dynamo_worker_total_slots{worker="w9"} 8' in text
    await srv.stop()
    await eng.stop()


@pytest.mark.asyncio_timeout(600)
async def test_planner_scales_multihost_engine_groups():
    """engine/multihost.py x planner: DP replicas OF a cross-host engine.
    Each replica the planner adds is a 2-process lockstep group (leader
    in=endpoint + replay follower over one jax.distributed mesh); scale
    1 -> 2 under held load, then back to 1, with registrations following
    (VERDICT r4 #7: planner and multihost had never met)."""
    import os

    from dynamo_tpu.planner import MultihostLocalConnector

    server, store = await serve_store(port=0, sweep_interval_s=0.05)
    port = server.sockets[0].getsockname()[1]
    cp = f"127.0.0.1:{port}"
    cmd = [
        sys.executable, "-m", "dynamo_tpu.cli", "run",
        "in=endpoint", "out=tpu", "--model-config", "tiny_wide",
        "--tensor-parallel-size", "4",
        "--num-nodes", "2", "--node-rank", "{rank}",
        "--leader-addr", "{coord}",
        "--control-plane", cp, "--namespace", "mhplan",
        "--component", "backend-r{replica}", "--model-name", "mh",
        "--page-size", "16", "--num-pages", "32",
        "--max-decode-slots", "2", "--cache-dtype", "float32",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    conn = MultihostLocalConnector(cmd, num_nodes=2, env=env)
    kv = await KvClient(port=port).connect()
    planner = Planner(kv, conn, PlannerConfig(
        adjustment_interval_s=1.0,
        kv_usage_scale_up=0.01,
        kv_usage_scale_down=0.005,
        waiting_scale_up=10_000,
        min_replicas=1, max_replicas=2, stable_intervals=2,
        metrics_stale_after_s=60.0,
    ))
    rt = await DistributedRuntime.connect(port=port)
    client = None
    sip_task = None
    try:
        await conn.set_replicas(1)
        await planner.start()
        client = await rt.namespace("mhplan").component(
            "backend-r0").endpoint("generate").client()
        # liveness-aware bring-up wait: if the spawned group dies (the
        # cross-host smoke can't run in every environment) fail in
        # seconds instead of burning the whole instance timeout
        deadline = asyncio.get_running_loop().time() + 180
        while True:
            assert conn.current_replicas() >= 1, \
                "multihost group died during bring-up"
            try:
                await client.wait_for_instances(1, timeout_s=2.0)
                break
            except TimeoutError:
                if asyncio.get_running_loop().time() > deadline:
                    raise

        stream = client.generate({
            "token_ids": list(range(1, 50)),
            "stop_conditions": {"max_tokens": 100000, "ignore_eos": True},
        })

        async def sip():
            async for _ in stream:
                await asyncio.sleep(0.05)

        sip_task = asyncio.create_task(sip())

        for _ in range(360):
            if conn.current_replicas() == 2:
                break
            await asyncio.sleep(0.5)
        assert conn.current_replicas() == 2
        # the new group registers as its own model instance
        for _ in range(240):
            regs = await kv.get_prefix("dynamo://mhplan/_models/mh/")
            if len(regs) >= 2:
                break
            await asyncio.sleep(0.5)
        assert len(await kv.get_prefix("dynamo://mhplan/_models/mh/")) == 2

        sip_task.cancel()
        try:
            await sip_task  # let the generator unwind before aclose
        except asyncio.CancelledError:
            pass
        sip_task = None
        aclose = getattr(stream, "aclose", None)
        if aclose:
            await aclose()
        for _ in range(360):
            if conn.current_replicas() == 1:
                break
            await asyncio.sleep(0.5)
        assert conn.current_replicas() == 1
    finally:
        await planner.stop()
        if sip_task is not None:
            sip_task.cancel()
        if client is not None:
            await client.stop()
        await conn.shutdown()
        await rt.close()
        await kv.close()
        server.close()
