"""Cross-host single-engine test (a model sharded past one host; reference
flags.rs:86-101 MultiNodeConfig + leader_worker_barrier.rs): TWO OS
processes form one jax.distributed mesh (2 hosts x 2 virtual CPU devices,
tp=4); the leader runs the full engine scheduler and broadcasts every
dispatch over the store; the follower replays in lockstep. The served
tokens must equal a single-process engine on an identically-shaped mesh.
"""
import asyncio
import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

from dynamo_tpu.runtime.store import serve_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMMON = """
import os, sys, json, asyncio
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
sys.path.insert(0, {repo!r})
jax.distributed.initialize(coordinator_address="127.0.0.1:{coord}",
                           num_processes=2, process_id={pid})
import numpy as np
from jax.sharding import Mesh
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from dynamo_tpu.runtime.client import KvClient
from dynamo_tpu.runtime.barrier import LeaderBarrier, WorkerBarrier

cfg = ModelConfig.tiny(dtype="float32", num_kv_heads=4, num_heads=8)
ecfg = EngineConfig(num_pages=32, page_size=16, max_pages_per_seq=8,
                    max_decode_slots=2, prefill_buckets=(32, 64),
                    cache_dtype="float32", flush_every=2,
                    max_inflight_rounds=1)
mesh = make_mesh(MeshConfig(tp=4), jax.devices())
params = llama.init_params(cfg, 0)
"""

LEADER = COMMON + """
from dynamo_tpu.engine.multihost import (
    CommandStream, make_dispatch_sink, stop_followers,
)
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions

async def main():
    kv = await KvClient(port={store}).connect()
    await LeaderBarrier(kv, "mh-e1", num_workers=1,
                        timeout_s=60).sync("up")
    stream = CommandStream(kv, asyncio.get_running_loop(),
                           "tt", "e1", "run1", n_followers=1)
    await stream.announce()
    eng = TpuEngine(cfg, ecfg, params=params, mesh=mesh,
                    on_dispatch=make_dispatch_sink(stream))
    outs = []
    for base in (1, 40):
        req = PreprocessedRequest(
            token_ids=list(range(base, base + 20)),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
        )
        toks = []
        async for out in eng.generate(req):
            toks.extend(out.token_ids)
        outs.append(toks)
    await eng.stop()
    await stream.drain()  # batched frames must precede the stop command
    await stop_followers(kv, "tt", "e1", "run1", 1, stream.seq)
    print("RESULT " + json.dumps(outs), flush=True)
    await kv.close()

asyncio.run(main())
"""

FOLLOWER = COMMON + """
from dynamo_tpu.engine.multihost import Follower

async def main():
    kv = await KvClient(port={store}).connect()
    await WorkerBarrier(kv, "mh-e1", "h1", timeout_s=60).sync()
    eng = TpuEngine(cfg, ecfg, params=params, mesh=mesh)  # never started
    f = Follower(eng, kv, "tt", "e1", "run1", host_index=1)
    await f.run()
    print("FOLLOWER OK " + str(f.commands_applied), flush=True)
    await kv.close()

asyncio.run(main())
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.asyncio_timeout(420)
async def test_two_process_lockstep_engine():
    server, store = await serve_store(port=0, sweep_interval_s=0.05)
    store_port = server.sockets[0].getsockname()[1]
    coord = _free_port()

    def spawn(code, pid):
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        return subprocess.Popen(
            [sys.executable, "-c",
             textwrap.dedent(code).format(
                 repo=REPO, coord=coord, pid=pid, store=store_port
             )],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )

    leader = spawn(LEADER, 0)
    follower = spawn(FOLLOWER, 1)
    try:
        l_out, l_err = await asyncio.to_thread(leader.communicate, None, 360)
        f_out, f_err = await asyncio.to_thread(follower.communicate, None, 60)
    except subprocess.TimeoutExpired:
        leader.kill()
        follower.kill()
        raise
    finally:
        server.close()
    assert leader.returncode == 0, f"leader failed:\n{l_err[-3000:]}"
    assert follower.returncode == 0, f"follower failed:\n{f_err[-3000:]}"
    assert "FOLLOWER OK" in f_out
    result_line = [ln for ln in l_out.splitlines()
                   if ln.startswith("RESULT ")][0]
    outs = json.loads(result_line[len("RESULT "):])
    assert all(len(o) == 6 for o in outs)

    # reference: identical mesh SHAPE in one process (same partitioning ->
    # same numerics), same params/seed
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import llama as llama_mod
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        StopConditions,
    )
    import jax

    cfg = ModelConfig.tiny(dtype="float32", num_kv_heads=4, num_heads=8)
    ecfg = EngineConfig(num_pages=32, page_size=16, max_pages_per_seq=8,
                        max_decode_slots=2, prefill_buckets=(32, 64),
                        cache_dtype="float32", flush_every=2,
                        max_inflight_rounds=1)
    mesh = make_mesh(MeshConfig(tp=4), jax.devices()[:4])
    eng = TpuEngine(cfg, ecfg, params=llama_mod.init_params(cfg, 0),
                    mesh=mesh)
    expected = []
    for base in (1, 40):
        req = PreprocessedRequest(
            token_ids=list(range(base, base + 20)),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
        )
        toks = []
        async for out in eng.generate(req):
            toks.extend(out.token_ids)
        expected.append(toks)
    await eng.stop()
    assert outs == expected, (
        "multihost lockstep engine must serve the same tokens as the "
        "single-process engine on an identically-sharded mesh"
    )
