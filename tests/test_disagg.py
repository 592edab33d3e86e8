"""Disaggregated prefill/decode tests (reference disagg_router.rs,
prefill_worker.py, utils/prefill_queue.py — SURVEY §3.3 flow).

Keystone: frontend-shaped request -> decode engine decides remote -> job on
the durable prefill queue -> prefill worker computes KV + pushes pages over
the block-transfer plane into the decode pool -> decode continues from the
transferred prefix bit-exactly, computing only the sub-page tail.
"""
import asyncio
from dataclasses import replace

import pytest

from dynamo_tpu.disagg import (
    DisaggConfig,
    DisaggConfigWatcher,
    DisaggDecodeEngine,
    PrefillWorker,
    prefill_queue_name,
    set_disagg_config,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.kv_transfer import (
    BlocksetDescriptor,
    BlockTransferServer,
    KvCacheLayout,
    publish_descriptor,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.store import serve_store

PS = 16


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny(dtype="float32")
    ecfg = EngineConfig(
        num_pages=64, page_size=PS, max_pages_per_seq=8,
        max_decode_slots=4, prefill_buckets=(32, 64),
        cache_dtype="float32",
    )
    params = llama.init_params(cfg, 0)
    return cfg, ecfg, params


def mk_engine(setup, wid, **over):
    cfg, ecfg, params = setup
    return TpuEngine(
        cfg, replace(ecfg, worker_id=wid, **over), params=params,
        mesh_config=MeshConfig(tp=1),
    )


async def collect(engine, req):
    toks = []
    async for out in engine.generate(req):
        toks.extend(out.token_ids)
    return toks


def req_for(prompt, n_new=10):
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=n_new, ignore_eos=True),
    )


async def start_rt():
    server, store = await serve_store(port=0, sweep_interval_s=0.05)
    port = server.sockets[0].getsockname()[1]
    rt = await DistributedRuntime.connect(port=port)
    return server, store, rt, port


async def test_disagg_config_watch():
    server, store, rt, port = await start_rt()
    w = await DisaggConfigWatcher(rt.kv, "ns").start()
    assert w.current == DisaggConfig()  # defaults before any put
    await set_disagg_config(rt.kv, "ns", DisaggConfig(
        max_local_prefill_length=99, max_prefill_queue_size=3))
    for _ in range(100):
        if w.current.max_local_prefill_length == 99:
            break
        await asyncio.sleep(0.02)
    assert w.current.max_prefill_queue_size == 3
    await w.stop()
    await rt.close()
    server.close()


async def setup_disagg_pair(setup, rt, namespace="dynamo",
                            prefill_timeout_s=30.0,
                            prefill_chunk_pages=None,
                            wid="dec", pwid="pre"):
    """decode engine + data plane + descriptor + prefill worker.
    ``prefill_chunk_pages`` overrides the prefill engine's
    kv_transfer_chunk_pages (0 = monolithic legacy path)."""
    decode_inner = mk_engine(setup, wid)
    cfg, ecfg, _ = setup
    conf = DisaggConfigWatcher(
        rt.kv, namespace,
        default=DisaggConfig(max_local_prefill_length=PS,
                             max_prefill_queue_size=4),
    )
    await conf.start()
    decode = DisaggDecodeEngine(
        decode_inner, rt, namespace=namespace, worker_id=wid,
        conf=conf, prefill_timeout_s=prefill_timeout_s,
    )
    srv = BlockTransferServer(
        read_fn=decode_inner.export_pages, write_fn=decode.guarded_import
    )
    host, port = await srv.start()
    await publish_descriptor(rt.kv, namespace, BlocksetDescriptor(
        worker_id=wid, host=host, port=port,
        layout=KvCacheLayout(cfg.num_layers, cfg.num_kv_heads, PS,
                             cfg.head_dim, "float32"),
    ))
    over = ({}
            if prefill_chunk_pages is None
            else {"kv_transfer_chunk_pages": prefill_chunk_pages})
    prefill_engine = mk_engine(setup, pwid, **over)
    pworker = await PrefillWorker(
        rt, prefill_engine, namespace=namespace, poll_timeout_s=0.2
    ).start()
    return decode, srv, conf, pworker, prefill_engine


async def test_disagg_remote_prefill_e2e(setup):
    """Long prompt goes through the queue + prefill worker + KV transfer;
    output is bit-exact vs a purely local engine."""
    prompt = list(range(1, 50))  # 49 tokens: 3 complete blocks + tail

    ref_eng = mk_engine(setup, "ref")
    ref = await collect(ref_eng, req_for(prompt))
    await ref_eng.stop()

    server, store, rt, port = await start_rt()
    decode, srv, conf, pworker, pre_eng = await setup_disagg_pair(setup, rt)

    out = await collect(decode, req_for(prompt))
    assert out == ref
    assert decode.remote_prefills == 1
    assert decode.remote_fallbacks == 0
    assert pworker.jobs_handled == 1
    # the decode engine served the transferred blocks from its prefix cache
    assert decode.engine.allocator.hit_blocks >= 3

    # short prompt stays local
    short = await collect(decode, req_for(list(range(1, 10))))
    assert len(short) == 10
    assert decode.local_prefills >= 1

    await pworker.stop()
    await srv.stop()
    await conf.stop()
    await decode.stop()
    await pre_eng.stop()
    await rt.close()
    server.close()


async def test_disagg_remote_prefill_spans_ride_finishing_output(setup):
    """Telemetry satellite: the remote path annotates the finishing
    output with the decode-side disagg_kv_transfer span AND the prefill
    worker's own remote_prefill span (shipped back on the done queue) —
    the remote hop is visible end-to-end in the request's trace tree."""
    prompt = list(range(1, 50))
    server, store, rt, port = await start_rt()
    decode, srv, conf, pworker, pre_eng = await setup_disagg_pair(setup, rt)
    try:
        finishing = None
        async for out in decode.generate(req_for(prompt)):
            if out.finish_reason is not None:
                finishing = out
        assert decode.remote_prefills == 1
        spans = (finishing.annotations.get("trace") or {}).get("spans", [])
        names = [s.get("name") for s in spans]
        assert "disagg_kv_transfer" in names
        assert "remote_prefill" in names
        rp = next(s for s in spans if s["name"] == "remote_prefill")
        assert rp["attrs"]["tokens"] == len(prompt)
        assert rp["attrs"]["blocks"] >= 3
        # the engine's own queue/prefill spans are still there (prefill
        # as the child of the first_token span that ends at the fetch)
        ft = next(s for s in spans if s["name"] == "first_token")
        assert "queue" in names
        assert [c["name"] for c in ft["children"]] == ["prefill"]
    finally:
        await pworker.stop()
        await srv.stop()
        await conf.stop()
        await decode.stop()
        await pre_eng.stop()
        await rt.close()
        server.close()


async def test_disagg_fallback_and_stale_job_write_rejected(setup):
    """No prefill worker at first: decode falls back locally after the
    timeout. When a worker later pops the STALE job, its write must be
    rejected (the fallback freed those pages — they may belong to another
    request by now), not scatter into the decode pool."""
    cfg, ecfg, _ = setup
    prompt = list(range(1, 50))
    ref_eng = mk_engine(setup, "ref2")
    ref = await collect(ref_eng, req_for(prompt))
    await ref_eng.stop()

    server, store, rt, port = await start_rt()
    decode_inner = mk_engine(setup, "dec2")
    conf = DisaggConfigWatcher(
        rt.kv, "dynamo",
        default=DisaggConfig(max_local_prefill_length=PS,
                             max_prefill_queue_size=4),
    )
    decode = DisaggDecodeEngine(
        decode_inner, rt, worker_id="dec2", conf=conf,
        prefill_timeout_s=0.3,
    )
    srv = BlockTransferServer(
        read_fn=decode_inner.export_pages, write_fn=decode.guarded_import
    )
    host, xport = await srv.start()
    await publish_descriptor(rt.kv, "dynamo", BlocksetDescriptor(
        worker_id="dec2", host=host, port=xport,
        layout=KvCacheLayout(cfg.num_layers, cfg.num_kv_heads, PS,
                             cfg.head_dim, "float32"),
    ))

    out = await collect(decode, req_for(prompt))
    assert out == ref
    assert decode.remote_fallbacks == 1
    # the abandoned job is still on the durable queue (no consumer yet)
    assert await rt.kv.qlen(prefill_queue_name("dynamo")) == 1

    # a late prefill worker pops the stale job: it is EXPIRED (decode gave
    # up at its timeout), so the worker drops it without a wasted prefill
    # or a done-queue push, and decode keeps serving correctly
    pre_eng = mk_engine(setup, "pre2")
    pworker = PrefillWorker(rt, pre_eng, namespace="dynamo",
                            poll_timeout_s=0.2)
    pworker.expiry_skew_s = 0.0  # same host: no clock skew
    await pworker.start()
    for _ in range(300):
        if (pworker.jobs_expired + pworker.jobs_failed
                + pworker.jobs_handled) >= 1:
            break
        await asyncio.sleep(0.05)
    assert pworker.jobs_expired == 1
    assert pworker.jobs_failed == 0 and pworker.jobs_handled == 0
    out2 = await collect(decode, req_for(list(range(200, 220))))
    assert len(out2) == 10

    # stale-write protection itself: a write for a cancelled/unknown job id
    # is rejected outright
    with pytest.raises(RuntimeError, match="cancelled"):
        decode.guarded_import([1], None, job_id="long-gone")

    await pworker.stop()
    await pre_eng.stop()
    await srv.stop()
    await decode.stop()
    await rt.close()
    server.close()


async def test_disagg_decision_respects_queue_cap(setup):
    """queue >= max_prefill_queue_size forces the local path."""
    server, store, rt, port = await start_rt()
    # stuff the queue past the cap
    q = prefill_queue_name("dynamo")
    await rt.kv.qpush(q, "{}")
    await rt.kv.qpush(q, "{}")
    decode_inner = mk_engine(setup, "dec3")
    conf = DisaggConfigWatcher(
        rt.kv, "dynamo",
        default=DisaggConfig(max_local_prefill_length=PS,
                             max_prefill_queue_size=2),
    )
    decode = DisaggDecodeEngine(
        decode_inner, rt, worker_id="dec3", conf=conf,
    )
    out = await collect(decode, req_for(list(range(1, 50))))
    assert len(out) == 10
    assert decode.remote_prefills == 0
    assert decode.local_prefills == 1
    assert await rt.kv.qlen(q) == 2  # nothing enqueued
    await decode.stop()
    await rt.close()
    server.close()


async def test_disagg_through_distributed_stack(setup):
    """Full stack: decode worker registered over the runtime (register_llm),
    request arrives via the remote endpoint client, remote prefill rides
    the queue + transfer plane (the SURVEY §3.3 S1-S13 flow on CPU)."""
    from dynamo_tpu.frontend.watcher import ModelEntry, register_llm
    from dynamo_tpu.runtime.remote_engine import RemoteEngine

    prompt = list(range(1, 50))
    ref_eng = mk_engine(setup, "ref3")
    ref = await collect(ref_eng, req_for(prompt))
    await ref_eng.stop()

    server, store, rt, port = await start_rt()
    cfg, ecfg, _ = setup

    # decode worker: disagg wrapper registered as the model engine
    decode_inner = mk_engine(setup, "dec4")
    conf = await DisaggConfigWatcher(
        rt.kv, "test",
        default=DisaggConfig(max_local_prefill_length=PS,
                             max_prefill_queue_size=4),
    ).start()
    decode = DisaggDecodeEngine(
        decode_inner, rt, namespace="test", conf=conf,
        prefill_timeout_s=30.0,
    )
    entry = ModelEntry(name="m", namespace="test", component="backend",
                       block_size=PS, router_mode="kv")
    served = await register_llm(rt, decode, entry)
    decode.worker_id = str(served.lease_id)
    srv = BlockTransferServer(
        read_fn=decode_inner.export_pages, write_fn=decode.guarded_import
    )
    host, xport = await srv.start()
    await publish_descriptor(rt.kv, "test", BlocksetDescriptor(
        worker_id=str(served.lease_id), host=host, port=xport,
        layout=KvCacheLayout(cfg.num_layers, cfg.num_kv_heads, PS,
                             cfg.head_dim, "float32"),
    ))

    # prefill worker on its own runtime connection
    rt2 = await DistributedRuntime.connect(port=port)
    pre_eng = mk_engine(setup, "pre4")
    pworker = await PrefillWorker(
        rt2, pre_eng, namespace="test", poll_timeout_s=0.2
    ).start()

    # request through the distributed data plane
    client = await rt.namespace("test").component("backend").endpoint(
        "generate"
    ).client()
    await client.wait_for_instances(1)
    remote = RemoteEngine(client)
    out = await collect(remote, req_for(prompt))
    assert out == ref
    assert decode.remote_prefills == 1
    assert pworker.jobs_handled == 1

    await client.stop()
    await pworker.stop()
    await srv.stop()
    await conf.stop()
    await served.shutdown()
    await decode.stop()
    await pre_eng.stop()
    await rt2.close()
    await rt.close()
    server.close()


async def test_disagg_chunked_stream_greedy_differential(setup):
    """Tier-1 keystone for the chunk pipeline: chunk-streamed remote
    prefill is greedy byte-identical to the monolithic transfer (same
    113-token prompt through both data planes — the transport change
    must be invisible) AND to pure-local prefill (49-token prompt, the
    shape the e2e tests pin local equality at; longer prompts flip
    near-tie argmaxes on the tiny random model because a prefix-hit
    tail prefill computes its boundary KV in a different padded shape —
    a pre-existing float quirk, not a transfer property). Also: the
    stream really was multi-frame, and the remote_prefill span carries
    per-chunk children."""
    prompt = list(range(1, 114))       # 7 complete blocks + tail
    p49 = list(range(200, 249))        # 3 complete blocks + tail

    ref_eng = mk_engine(setup, "refc")
    ref49 = await collect(ref_eng, req_for(p49))
    await ref_eng.stop()

    server, store, rt, port = await start_rt()
    from dynamo_tpu.kv_transfer_metrics import KV_TRANSFER

    streams0 = KV_TRANSFER.get("dynamo_kv_transfer_streams_total")

    # chunk-streamed pair (2 pages per chunk -> >= 3 frames)
    decode_c, srv_c, conf_c, pw_c, pre_c = await setup_disagg_pair(
        setup, rt, namespace="chunked", prefill_chunk_pages=2,
        wid="dec_c", pwid="pre_c",
    )
    # monolithic pair (legacy single-blob path)
    decode_m, srv_m, conf_m, pw_m, pre_m = await setup_disagg_pair(
        setup, rt, namespace="mono", prefill_chunk_pages=0,
        wid="dec_m", pwid="pre_m",
    )
    try:
        finishing = None
        out_c = []
        async for out in decode_c.generate(req_for(prompt)):
            out_c.extend(out.token_ids)
            if out.finish_reason is not None:
                finishing = out
        chunks_113 = decode_c.last_transfer_chunks
        out_m = await collect(decode_m, req_for(prompt))

        # chunked vs monolithic: same bytes, same decode -> identical
        assert out_c == out_m
        # chunked remote vs pure-local prefill: identical
        out_c49 = await collect(decode_c, req_for(p49))
        assert out_c49 == ref49
        assert decode_c.remote_prefills == 2
        assert decode_c.remote_fallbacks == 0
        assert decode_m.remote_prefills == 1
        # the chunked path really streamed multiple frames...
        assert pw_c.chunks_streamed >= 3
        assert chunks_113 >= 3
        assert pw_c.transfer_overlap_ratio is not None
        assert KV_TRANSFER.get(
            "dynamo_kv_transfer_streams_total") > streams0
        # ...and the monolithic one did not
        assert pw_m.chunks_streamed == 0
        # per-chunk child spans under remote_prefill
        spans = (finishing.annotations.get("trace") or {}).get("spans", [])
        rp = next(s for s in spans if s.get("name") == "remote_prefill")
        kids = rp.get("children", [])
        assert len(kids) >= 3
        assert all(k["name"] == "kv_chunk" for k in kids)
        assert sum(k["attrs"]["blocks"] for k in kids) == rp["attrs"]["blocks"]
    finally:
        for pw, srv, conf, dec, pre in (
            (pw_c, srv_c, conf_c, decode_c, pre_c),
            (pw_m, srv_m, conf_m, decode_m, pre_m),
        ):
            await pw.stop()
            await srv.stop()
            await conf.stop()
            await dec.stop()
            await pre.stop()
        await rt.close()
        server.close()


@pytest.mark.slow
async def test_disagg_chunked_chaos_stall_falls_back(setup):
    """Full-stack chunked remote prefill with a mid-stream stall_stream
    chaos fault: the decode side's timeout fires, it falls back to LOCAL
    prefill (token-identical output, fallback counted on the metrics
    plane), and the stale stream's late writes are rejected by the
    guarded import instead of scribbling on reallocated pages."""
    from dynamo_tpu.frontend.watcher import ModelEntry, register_llm
    from dynamo_tpu.kv_transfer_metrics import KV_TRANSFER
    from dynamo_tpu.resilience.chaos import CHAOS
    from dynamo_tpu.runtime.remote_engine import RemoteEngine

    prompt = list(range(1, 114))
    ref_eng = mk_engine(setup, "refs")
    ref = await collect(ref_eng, req_for(prompt))
    await ref_eng.stop()

    server, store, rt, port = await start_rt()
    cfg, ecfg, _ = setup
    decode_inner = mk_engine(setup, "dec_st")
    conf = await DisaggConfigWatcher(
        rt.kv, "stall",
        default=DisaggConfig(max_local_prefill_length=PS,
                             max_prefill_queue_size=4),
    ).start()
    decode = DisaggDecodeEngine(
        decode_inner, rt, namespace="stall", conf=conf,
        prefill_timeout_s=1.0,
    )
    entry = ModelEntry(name="m", namespace="stall", component="backend",
                       block_size=PS, router_mode="kv")
    served = await register_llm(rt, decode, entry)
    decode.worker_id = str(served.lease_id)
    srv = BlockTransferServer(
        read_fn=decode_inner.export_pages, write_fn=decode.guarded_import
    )
    host, xport = await srv.start()
    await publish_descriptor(rt.kv, "stall", BlocksetDescriptor(
        worker_id=str(served.lease_id), host=host, port=xport,
        layout=KvCacheLayout(cfg.num_layers, cfg.num_kv_heads, PS,
                             cfg.head_dim, "float32"),
    ))
    rt2 = await DistributedRuntime.connect(port=port)
    pre_eng = mk_engine(setup, "pre_st", kv_transfer_chunk_pages=2)
    pworker = await PrefillWorker(
        rt2, pre_eng, namespace="stall", poll_timeout_s=0.2
    ).start()
    fb0 = KV_TRANSFER.get("dynamo_disagg_fallback_total")
    # stall the stream for longer than the decode side's 1.0 s timeout,
    # after the first chunk frame went out (mid-stream, not pre-stream)
    CHAOS.arm("stall_stream", delay_s=4.0, after_outputs=1, once=True)
    try:
        client = await rt.namespace("stall").component("backend").endpoint(
            "generate"
        ).client()
        await client.wait_for_instances(1)
        remote = RemoteEngine(client)
        out = await collect(remote, req_for(prompt))
        assert out == ref  # local fallback is token-identical
        assert decode.remote_fallbacks == 1
        assert decode.remote_prefills == 0
        assert KV_TRANSFER.get("dynamo_disagg_fallback_total") == fb0 + 1
        assert CHAOS.points["stall_stream"].injected_total == 1
        # the worker's stalled job must FAIL at commit (late writes for
        # the cancelled job are rejected by the guarded import)
        for _ in range(200):
            if pworker.jobs_failed + pworker.jobs_handled >= 1:
                break
            await asyncio.sleep(0.05)
        assert pworker.jobs_failed == 1
        assert pworker.jobs_handled == 0
        # decode keeps serving normally afterwards
        out2 = await collect(remote, req_for(list(range(300, 320))))
        assert len(out2) == 10
        await client.stop()
    finally:
        CHAOS.reset()
        await pworker.stop()
        await srv.stop()
        await conf.stop()
        await served.shutdown()
        await decode.stop()
        await pre_eng.stop()
        await rt2.close()
        await rt.close()
        server.close()
