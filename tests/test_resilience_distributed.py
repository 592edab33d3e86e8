"""Resilience plane through the distributed stack: chaos injection on the
remote-engine path, the /drain control, and the mid-stream kill
differential on real TpuEngines (greedy output must be byte-identical to
an uninterrupted run after a migration).
"""
import asyncio

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from dynamo_tpu.resilience import CHAOS, RESILIENCE
from tests.test_distributed_serving import chat, setup_system, teardown


@pytest.fixture(autouse=True)
def _reset_globals():
    RESILIENCE.reset()
    CHAOS.reset()
    yield
    RESILIENCE.reset()
    CHAOS.reset()


async def _wait_models(manager, n=1):
    for _ in range(200):
        if len(manager) >= n:
            return
        await asyncio.sleep(0.02)
    raise TimeoutError("model discovery timed out")


async def test_chaos_kill_worker_smoke():
    """Tier-1 chaos smoke: arm kill_worker on the worker serving path,
    stream through the full distributed stack, and verify the router
    migrates — the client still gets a complete 200 response and
    dynamo_migration_total increments."""
    server, workers, frontend_rt, watcher, client, manager = (
        await setup_system(2)
    )
    try:
        await _wait_models(manager)
        # clean request first (workers warm, routers built)
        r = await chat(client, "w1 w2 w3 w4 w5", max_tokens=6)
        assert r.status == 200

        CHAOS.arm("kill_worker", after_outputs=2, once=True)
        r = await chat(client, "w1 w2 w3 w4 w5", max_tokens=6)
        assert r.status == 200
        body = await r.json()
        # the stream survived the kill and ran to its finish. (Exact
        # token identity is asserted in the TpuEngine differentials —
        # the mocker's deterministic token function is not
        # continuation-consistent, so counts here are approximate.)
        assert body["choices"][0]["finish_reason"] in ("stop", "length")
        assert body["usage"]["completion_tokens"] >= 4
        assert RESILIENCE.get("dynamo_migration_total") == 1
        assert RESILIENCE.get(
            "dynamo_resilience_chaos_injections_total") == 1
        assert not CHAOS.points["kill_worker"].armed  # once: self-disarmed
    finally:
        await teardown(server, workers, frontend_rt, watcher, client)


async def test_chaos_delay_point_is_benign():
    """delay injections slow streams without failing them."""
    server, workers, frontend_rt, watcher, client, manager = (
        await setup_system(1)
    )
    try:
        await _wait_models(manager)
        CHAOS.arm("delay", delay_s=0.01)
        r = await chat(client, "w1 w2 w3", max_tokens=3)
        assert r.status == 200
        assert CHAOS.points["delay"].injected_total >= 1
        assert RESILIENCE.get("dynamo_migration_total") == 0
    finally:
        await teardown(server, workers, frontend_rt, watcher, client)


async def test_drain_http_control_deregisters_and_finishes():
    """POST /drain on a worker's system server: the worker stops
    admitting, deregisters (discovery drops it), finishes in-flight work
    and reports drained; traffic continues on the survivor."""
    from dynamo_tpu.resilience.drain import DrainController
    from dynamo_tpu.runtime.system_server import SystemServer

    server, workers, frontend_rt, watcher, client, manager = (
        await setup_system(2)
    )
    sys_client = None
    try:
        await _wait_models(manager)
        rt0, eng0, served0 = workers[0]
        drained = asyncio.Event()
        controller = DrainController(
            eng0,
            on_deregister=served0.lease.revoke,
            on_drained=drained.set,
            timeout_s=10.0,
        )
        sysrv = SystemServer(eng0, worker_id=str(served0.lease_id),
                             drain=controller)
        sys_client = TestClient(TestServer(sysrv.app))
        await sys_client.start_server()

        resp = await sys_client.get("/drain")
        assert (await resp.json())["state"] == "serving"
        resp = await sys_client.post("/drain")
        assert resp.status == 200
        assert (await resp.json())["state"] in ("draining", "drained")

        await asyncio.wait_for(drained.wait(), timeout=10.0)
        resp = await sys_client.get("/drain")
        assert (await resp.json())["state"] == "drained"

        # deregistration propagated: the drained worker leaves the
        # frontend's router, and traffic keeps flowing on the survivor
        for _ in range(200):
            push = watcher._routers.get("mock-model")
            if push is not None and len(push.workers) == 1:
                break
            await asyncio.sleep(0.02)
        assert len(watcher._routers["mock-model"].workers) == 1
        for _ in range(3):
            r = await chat(client, "w6 w7 w8")
            assert r.status == 200
        assert RESILIENCE.get("dynamo_resilience_drains_total") == 1
    finally:
        if sys_client is not None:
            await sys_client.close()
        await teardown(server, workers, frontend_rt, watcher, client)


async def test_system_server_chaos_control():
    """tools/chaos.py's wire surface: GET lists points, POST arms,
    DELETE disarms — against a live system server."""
    from dynamo_tpu.runtime.system_server import SystemServer

    sysrv = SystemServer(None, worker_id="w0")
    c = TestClient(TestServer(sysrv.app))
    await c.start_server()
    try:
        resp = await c.get("/chaos")
        names = {p["name"] for p in (await resp.json())["points"]}
        assert names == {"kill_worker", "stall_stream", "drop_response",
                         "delay", "storm", "flip_kv_bits",
                         "corrupt_frame", "truncate_g3",
                         "corrupt_prefetch", "kill_store",
                         "partition_store"}
        resp = await c.post("/chaos", json={
            "point": "kill_worker", "probability": 0.5,
            "after_outputs": 3, "once": True,
        })
        assert resp.status == 200
        assert CHAOS.points["kill_worker"].armed
        assert CHAOS.points["kill_worker"].after_outputs == 3
        resp = await c.post("/chaos", json={"point": "nope"})
        assert resp.status == 400
        resp = await c.delete("/chaos?point=kill_worker")
        assert resp.status == 200
        assert not CHAOS.points["kill_worker"].armed
        # resilience families render on the worker scrape surface
        resp = await c.get("/metrics")
        text = await resp.text()
        assert "# TYPE dynamo_migration_total counter" in text
        assert "dynamo_resilience_draining" in text
    finally:
        await c.close()


# ---------------------------------------------------------------------------
# TpuEngine mid-stream kill differentials


def _tiny_engine(params, cfg):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.parallel.mesh import MeshConfig

    return TpuEngine(
        cfg,
        EngineConfig(num_pages=64, page_size=16, max_pages_per_seq=8,
                     max_decode_slots=2, prefill_buckets=(32, 64),
                     cache_dtype="float32"),
        params=params, mesh_config=MeshConfig(tp=1),
    )


async def test_tpu_engine_migration_differential_greedy():
    """The acceptance differential on REAL engines: two TpuEngines share
    params behind the KV router; the serving worker dies after 3 tokens;
    the migrated stream is token-identical to an uninterrupted run."""
    from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter
    from dynamo_tpu.kv_router.scheduler import KvRouterConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        StopConditions,
    )

    cfg = ModelConfig.tiny(dtype="float32")
    params = llama.init_params(cfg, 0)
    engines = [_tiny_engine(params, cfg) for _ in range(2)]

    def req():
        rng = np.random.RandomState(4)
        return PreprocessedRequest(
            token_ids=rng.randint(1, 256, 20).tolist(),
            stop_conditions=StopConditions(max_tokens=12, ignore_eos=True),
        )

    # uninterrupted reference on engine 0
    ref = []
    async for out in engines[0].generate(req()):
        ref.extend(out.token_ids)
    assert len(ref) == 12

    killed: set = set()

    class Assassin:
        def __init__(self, inner):
            self.inner = inner

        async def generate(self, r):
            arm = r.request_id not in killed
            n = 0
            async for out in self.inner.generate(r):
                yield out
                n += len(out.token_ids)
                if arm and n >= 3:
                    killed.add(r.request_id)
                    raise ConnectionError("tpu worker died mid-stream")

    router = KvRouter(16, KvRouterConfig(router_temperature=0.0))
    push = KvPushRouter(router, {
        "w0": Assassin(engines[0]), "w1": Assassin(engines[1]),
    })
    try:
        got = []
        async for out in push.generate(req()):
            got.extend(out.token_ids)
        assert got == ref, "migrated TPU stream diverged from clean run"
        assert push.migrations == 1
        assert RESILIENCE.get("dynamo_migration_total") == 1
    finally:
        for e in engines:
            await e.stop()


@pytest.mark.slow
async def test_multi_worker_kill_mid_stream_full_stack():
    """Slow tier: the full distributed stack (store + discovery + remote
    workers + HTTP frontend) with REAL TpuEngines sharing params; chaos
    kills the serving worker mid-stream and the client's streamed text is
    identical to a clean run."""
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.watcher import ModelEntry, ModelWatcher, register_llm
    from dynamo_tpu.kv_router.scheduler import KvRouterConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.store import serve_store

    cfg = ModelConfig.tiny(dtype="float32")
    params = llama.init_params(cfg, 0)

    server, store = await serve_store(port=0, sweep_interval_s=0.05)
    port = server.sockets[0].getsockname()[1]
    workers = []
    for i in range(2):
        rt = await DistributedRuntime.connect(port=port)
        eng = _tiny_engine(params, cfg)
        entry = ModelEntry(name="tpu-res", namespace="res",
                           component="backend", block_size=16,
                           router_mode="kv")
        served = await register_llm(rt, eng, entry, lease_ttl_s=0.5)
        workers.append((rt, eng, served))

    frontend_rt = await DistributedRuntime.connect(port=port)
    manager = ModelManager()
    watcher = await ModelWatcher(
        frontend_rt, manager, namespace="res",
        router_config=KvRouterConfig(router_temperature=0.0),
    ).start()
    svc = HttpService(manager)
    client = TestClient(TestServer(svc.app))
    await client.start_server()

    async def completion():
        r = await client.post("/v1/completions", json={
            "model": "tpu-res", "prompt": "w1 w2 w3 w4 w5 w6 w7 w8",
            "max_tokens": 10, "temperature": 0.0,
        })
        assert r.status == 200, await r.text()
        body = await r.json()
        return body["choices"][0]["text"], body["usage"]["completion_tokens"]

    try:
        await _wait_models(manager)
        clean_text, clean_n = await completion()
        assert clean_n == 10

        CHAOS.arm("kill_worker", after_outputs=3, once=True)
        killed_text, killed_n = await completion()
        assert killed_n == 10
        assert killed_text == clean_text, (
            "client-visible stream diverged across the mid-stream kill"
        )
        assert RESILIENCE.get("dynamo_migration_total") == 1
    finally:
        await client.close()
        await watcher.stop()
        await frontend_rt.close()
        for rt, eng, served in workers:
            await served.shutdown()
            await eng.stop()
            await rt.close()
        server.close()
