"""`dynamo-tpu run in=<input> out=<engine>` launcher.

Mirrors the reference `dynamo-run` CLI (launch/dynamo-run/src/lib.rs:94-165,
flags.rs): pick an input plane (http | text | stdin | batch:<file>) and an
engine (echo | mocker | tpu), wire the chain, run it.

Inputs (reference entrypoint/input.rs:29-45):
  in=http        OpenAI HTTP frontend on --http-port
  in=text        one-shot prompt from --prompt (or interactive REPL)
  in=stdin       read prompts line-by-line from stdin
  in=batch:FILE  JSONL of {"prompt": ...}; writes completions JSONL to stdout

Engines:
  out=echo       deterministic token echo (tests/smoke)
  out=mocker     simulated paged-KV engine (CPU, timing-faithful)
  out=tpu        the JAX TPU engine (requires --model-path or canned config)
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Any, Optional


def build_parser() -> argparse.ArgumentParser:
    # layered defaults: dataclass <- TOML <- DYNTPU_* env <- CLI flags
    # (reference figment layering, config.rs:103-127)
    from dynamo_tpu.config import load_config

    cfg = load_config()
    p = argparse.ArgumentParser(
        prog="dynamo-tpu run",
        description="Run a dynamo-tpu serving graph",
    )
    p.add_argument("io", nargs="*", help="in=<http|text|stdin|batch:FILE> out=<echo|mocker|tpu>")
    p.add_argument("--model-path", help="local HF model dir (config/tokenizer/safetensors)")
    p.add_argument("--model-name", default=None, help="served model name")
    p.add_argument("--model-config", default=None,
                   help="canned config (tiny|llama3_1b|llama3_8b|"
                        "llama3_8b_int8|llama3_70b) for random-weight "
                        "serving")
    p.add_argument("--quantize", default=None, choices=["int8"],
                   help="weight quantization (w8a16 int8): quantizes "
                        "loaded checkpoints per-output-channel; an 8B "
                        "checkpoint on a 16 GB v5e requires it")
    p.add_argument("--http-host", default=cfg.http_host)
    p.add_argument("--http-port", type=int, default=cfg.http_port)
    p.add_argument("--prompt", default=None, help="prompt for in=text")
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--trace-speedup", type=float, default=0.0,
                   help="in=batch with a mooncake trace: replay arrival "
                        "timestamps at this speed multiple (0 = ignore "
                        "timestamps, submit all at once)")
    p.add_argument("--trace-block-size", type=int, default=64,
                   help="tokens represented by one trace hash id (must "
                        "match the datagen --block-size for the trace's "
                        "prefix sharing to replay faithfully)")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--num-pages", type=int, default=cfg.num_pages)
    p.add_argument("--page-size", type=int, default=cfg.page_size)
    p.add_argument("--max-decode-slots", type=int,
                   default=cfg.max_decode_slots)
    p.add_argument("--cache-dtype", default=cfg.cache_dtype)
    p.add_argument("--kv-quant", default=cfg.kv_quant,
                   choices=["none", "int8"],
                   help="KV quantization: int8 pool pages AND int8 "
                        "decode ctx with per-group scales — the "
                        "flash-decode kernel dequantizes each chunk "
                        "in VMEM, halving live-context HBM traffic, "
                        "pool residency, tier footprint and transfer "
                        "bytes; the write ring stays --cache-dtype")
    p.add_argument("--host-offload-pages", type=int,
                   default=cfg.host_offload_pages,
                   help="host-DRAM KV offload tier capacity in pages "
                        "(KVBM G2); 0 disables")
    p.add_argument("--disk-offload-pages", type=int,
                   default=cfg.disk_offload_pages,
                   help="mmap-backed disk KV tier capacity in pages "
                        "(KVBM G3, spill target of G2); 0 disables")
    p.add_argument("--disk-offload-path", default=cfg.disk_offload_path,
                   help="backing file for the G3 pool "
                        "(default: fresh tempfile); with a path the "
                        "tier journals a sidecar manifest and survives "
                        "engine restarts")
    p.add_argument("--scrub-on-start", action="store_true",
                   default=cfg.scrub_on_start,
                   help="eagerly re-checksum every G3 manifest entry at "
                        "attach, dropping torn/corrupt blocks as misses "
                        "(default: lazy verify at onboard gather)")
    # chunk-pipelined KV transfer plane (kv_transfer.py)
    p.add_argument("--kv-transfer-chunk-pages", type=int,
                   default=cfg.kv_transfer_chunk_pages,
                   help="pages per streamed KV-transfer chunk (disagg "
                        "remote prefill, G4 peer fetch, G2/G3 onboard); "
                        "0 = monolithic single-blob transfers")
    p.add_argument("--kv-transfer-inflight-chunks", type=int,
                   default=cfg.kv_transfer_inflight_chunks,
                   help="chunk gathers/D2H copies in flight per export "
                        "stream (double-buffer depth)")
    p.add_argument("--xfer-op-timeout", type=float,
                   default=cfg.xfer_op_timeout_s,
                   help="deadline in seconds for one queued page "
                        "export/import op (raise for multi-GiB chunked "
                        "imports on slow host links)")
    p.add_argument("--kv-transfer-stream-idle-timeout", type=float,
                   default=cfg.kv_transfer_stream_idle_timeout_s,
                   help="idle-timeout in seconds reclaiming a chunked "
                        "export stream whose receiver stalled (pinned "
                        "gather handles/page refs freed)")
    # overload plane (dynamo_tpu/overload/)
    p.add_argument("--max-waiting-requests", type=int,
                   default=cfg.max_waiting_requests,
                   help="bounded admission: waiting-queue depth budget; "
                        "intake past it is refused with a retriable "
                        "overload error (HTTP 429 + Retry-After at the "
                        "frontend). 0 = unbounded")
    p.add_argument("--max-waiting-prefill-tokens", type=int,
                   default=cfg.max_waiting_prefill_tokens,
                   help="bounded admission: prompt-token budget over "
                        "the waiting queue. 0 = unbounded")
    p.add_argument("--preempt-running",
                   default="on" if cfg.preempt_running else "off",
                   choices=["on", "off"],
                   help="allow a waiting HIGH-priority request to "
                        "force-migrate the lowest-priority RUNNING "
                        "stream (preemption-as-migration via the "
                        "resilience plane; exactly-once, greedy "
                        "token-identical)")
    # performance-attribution plane (telemetry/prof.py)
    p.add_argument("--slo-ttft-target", type=float,
                   default=cfg.slo_ttft_target_s,
                   help="TTFT SLO target in seconds backing the "
                        "dynamo_slo_ttft_burn_rate gauge")
    p.add_argument("--slo-itl-target", type=float,
                   default=cfg.slo_itl_target_s,
                   help="ITL SLO target in seconds backing the "
                        "dynamo_slo_itl_burn_rate gauge")
    p.add_argument("--slo-objective", type=float,
                   default=cfg.slo_objective,
                   help="SLO objective (fraction of observations that "
                        "must meet the target, e.g. 0.99); burn rate = "
                        "frac-over-target / (1 - objective)")
    p.add_argument("--forensics-sample-rate", type=float,
                   default=cfg.forensics_sample_rate,
                   help="fraction of NON-breaching requests that still "
                        "get an SLO-breach-style dossier captured into "
                        "/debug/outliers (breaches are always captured; "
                        "0 disables the healthy-baseline sample)")
    # speculative decoding (dynamo_tpu/spec/)
    p.add_argument("--speculative", default=cfg.speculative,
                   choices=["off", "ngram", "draft"],
                   help="speculative decoding: ngram = model-free "
                        "prompt-lookup proposer; draft = small draft "
                        "model sharing the tokenizer (--draft-model-"
                        "config); eligible requests verify K proposed "
                        "tokens per target forward")
    p.add_argument("--num-speculative-tokens", type=int,
                   default=cfg.num_speculative_tokens,
                   help="K: proposed tokens per verify step (the cap "
                        "when --spec-adaptive is on)")
    p.add_argument("--spec-adaptive",
                   default="on" if cfg.spec_adaptive else "off",
                   choices=["on", "off"],
                   help="acceptance-adaptive K: each slot's effective K "
                        "walks within [--spec-min-k, K] on its rolling "
                        "acceptance rate, and slots whose rate collapses "
                        "de-speculate back to the fused decode round "
                        "(exported as dynamo_spec_effective_k)")
    p.add_argument("--spec-min-k", type=int, default=cfg.spec_min_k,
                   help="adaptive-K floor per slot")
    p.add_argument("--spec-tree",
                   default="on" if cfg.spec_tree else "off",
                   choices=["on", "off"],
                   help="tree speculation: draft up to --spec-branches "
                        "candidates per divergence point and verify the "
                        "whole tree in one forward under a tree-causal "
                        "mask; acceptance keeps the deepest surviving "
                        "root-to-leaf path")
    p.add_argument("--spec-branches", type=int, default=cfg.spec_branches,
                   help="branch fan per tree level (the cap when "
                        "--spec-adaptive walks the branches axis)")
    p.add_argument("--spec-tree-budget", type=int,
                   default=cfg.spec_tree_budget,
                   help="packed tree node budget incl. the root (one "
                        "compiled verify shape serves every tree); 0 = "
                        "auto: 1 + K * branches")
    p.add_argument("--spec-gate-acceptance", type=float,
                   default=cfg.spec_gate_acceptance,
                   help="de-speculate a stream whose live acceptance "
                        "EWMA stays below this for --spec-gate-window "
                        "consecutive verify steps (0 = no gate); gated "
                        "streams may re-arm after --spec-rearm-tokens "
                        "emitted tokens")
    p.add_argument("--spec-gate-window", type=int,
                   default=cfg.spec_gate_window,
                   help="consecutive below-gate verify steps before a "
                        "stream de-speculates")
    p.add_argument("--spec-rearm-tokens", type=int,
                   default=cfg.spec_rearm_tokens,
                   help="emitted tokens before a gated stream re-arms "
                        "speculation (doubles each time it re-gates; "
                        "0 = gated streams never re-arm)")
    p.add_argument("--draft-model-config", default=None,
                   help="canned ModelConfig name for the draft model "
                        "(speculative=draft; must share the target "
                        "vocab, e.g. tiny for --model-config tiny). "
                        "Drafting is batched across speculating slots "
                        "into one device program per round")
    # distributed mode (reference: etcd/NATS endpoints; here the dcp store).
    # --control-plane default stays None (it's the discovery-mode switch);
    # RuntimeConfig.control_plane is None unless the config file or
    # DYNTPU_CONTROL_PLANE opted in explicitly.
    p.add_argument("--control-plane", default=cfg.control_plane,
                   metavar="HOST:PORT",
                   help="control-plane store address; enables discovery")
    p.add_argument("--namespace", default=cfg.namespace)
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint-name", default="generate")
    p.add_argument("--router-mode", default="kv",
                   choices=["kv", "round_robin", "random"])
    p.add_argument("--record-kv-events", default=None, metavar="PATH",
                   help="record the frontend's kv_events stream to a JSONL "
                        "file for later replay (reference KvRecorder)")
    p.add_argument("--system-port", type=int, default=None,
                   help="per-process /metrics + /health server port "
                        "(reference http_server.rs); 0 = ephemeral")
    # resilience plane (dynamo_tpu/resilience/)
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="arm fault-injection points on the worker serving "
                        "path, e.g. 'kill_worker:p=0.1:after=3,delay:t=0.05'"
                        " (also via DYNAMO_CHAOS; tools/chaos.py arms a "
                        "running worker over HTTP)")
    p.add_argument("--trace-sample-rate", type=float, default=1.0,
                   help="fraction of requests fully traced by the frontend "
                        "(high-QPS deployments sample; migrated/failed "
                        "requests are always traced)")
    p.add_argument("--health-heartbeat-ttl", type=float, default=None,
                   help="frontend soft-lease TTL in seconds: a worker "
                        "whose load-metrics heartbeats go silent longer "
                        "than this stops receiving traffic before its "
                        "hard store lease expires (engines heartbeat on "
                        "idle ticks too; set well above ~1s). Default: "
                        "breaker-only health tracking")
    p.add_argument("--drain-timeout", type=float, default=60.0,
                   help="graceful-drain budget: in-flight requests get "
                        "this long to finish after SIGTERM or POST /drain "
                        "before the worker exits anyway")
    # multi-host single-engine bootstrap (reference MultiNodeConfig,
    # flags.rs:86-101 + leader_worker_barrier.rs)
    p.add_argument("--num-nodes", type=int, default=1)
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--leader-addr", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator address (required on "
                        "the leader when --num-nodes > 1; workers discover "
                        "it via the barrier)")
    # disaggregated prefill/decode (reference flags.rs + disagg_router.rs)
    p.add_argument("--role", default="aggregated",
                   choices=["aggregated", "decode", "prefill"],
                   help="worker role for disaggregated serving")
    p.add_argument("--max-local-prefill-length", type=int, default=None,
                   help="prompts with more uncached tokens go to the "
                        "prefill queue (writes the store-watched conf)")
    p.add_argument("--max-prefill-queue-size", type=int, default=None)
    p.add_argument("--remote-kv", action="store_true",
                   help="KVBM G4: serve this worker's sealed KV pool to "
                        "peers and fall through the local tiers to peer "
                        "pools on prefix misses (requires --control-plane "
                        "and a G2 tier via --host-offload-pages)")
    # fleet prefix economy (kv_router/fleet.py + prefetch.py)
    p.add_argument("--kv-replication-target", type=int,
                   default=cfg.kv_replication_target,
                   help="desired fleet copies of a hot KV block: the "
                        "frontend's replication controller pushes "
                        "under-replicated hot prefix chains into workers' "
                        "G2 tiers ahead of demand and warm-starts cold "
                        "joiners from the fleet hot set (<= 1 disables "
                        "the controller)")
    p.add_argument("--kv-prefetch-hot-k", type=int,
                   default=cfg.kv_prefetch_hot_k,
                   help="hot prefix chains examined per controller tick "
                        "and pushed to a cold joiner")
    p.add_argument("--kv-prefetch-interval", type=float,
                   default=cfg.kv_prefetch_interval_s, metavar="SECONDS",
                   help="replication-controller tick period")
    p.add_argument("--kv-freq-halflife", type=float,
                   default=cfg.kv_freq_halflife_s, metavar="SECONDS",
                   help="KV indexer access-heat decay half-life (0 = raw "
                        "undecayed counters, the legacy behavior)")
    p.add_argument("--no-kv-dedup-admission", action="store_true",
                   default=not cfg.kv_dedup_admission,
                   help="disable dedup-by-hash admission hints: G4 "
                        "probes ignore the fleet holder digest")
    p.add_argument("--prefill-timeout", type=float, default=60.0,
                   help="decode-side wait for remote prefill before local "
                        "fallback")
    return p


def _parse_io(io: list[str]) -> tuple[str, str]:
    inp, out = "http", "echo"
    for item in io:
        if item.startswith("in="):
            inp = item[3:]
        elif item.startswith("out="):
            out = item[4:]
        else:
            raise SystemExit(f"unrecognized arg {item!r} (expected in=/out=)")
    return inp, out


def multi_host_bootstrap(args) -> None:
    """Bring up a multi-host single engine: rendezvous all nodes on a
    store barrier (leader distributes the jax coordinator address), then
    jax.distributed.initialize so the engine's mesh spans every host's
    chips (reference: LeaderBarrier/WorkerBarrier + vLLM's ray bootstrap).

    Liveness: each node's barrier lease is its group membership; after
    init, a dead node collapses the jax runtime itself, and the leader's
    registration lease (held by _serve_worker) deregisters the engine."""
    import json as _json

    import jax

    from dynamo_tpu.runtime.barrier import LeaderBarrier, WorkerBarrier
    from dynamo_tpu.runtime.client import KvClient

    host, port = _cp_addr(args)
    barrier_id = f"engine-{args.namespace}-{args.component}"

    async def rendezvous() -> str:
        kv = await KvClient(host, port).connect()
        try:
            if args.node_rank == 0:
                if not args.leader_addr:
                    raise SystemExit(
                        "--leader-addr required on node-rank 0"
                    )
                import uuid as _uuid

                args._mh_run_id = _uuid.uuid4().hex[:12]
                lb = LeaderBarrier(kv, barrier_id, args.num_nodes - 1)
                await lb.sync(_json.dumps({
                    "coordinator": args.leader_addr,
                    "num_nodes": args.num_nodes,
                    "run_id": args._mh_run_id,
                }))
                await lb.close()
                return args.leader_addr
            wb = WorkerBarrier(kv, barrier_id, f"node-{args.node_rank}")
            data = _json.loads(await wb.sync())
            await wb.close()
            args._mh_run_id = data.get("run_id", "r0")
            if data["num_nodes"] != args.num_nodes:
                raise SystemExit(
                    f"node count mismatch: leader says {data['num_nodes']}, "
                    f"this node was started with {args.num_nodes}"
                )
            return data["coordinator"]
        finally:
            await kv.close()

    coordinator = asyncio.run(rendezvous())
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=args.num_nodes,
        process_id=args.node_rank,
    )
    print(
        f"multi-host engine up: node {args.node_rank}/{args.num_nodes}, "
        f"{jax.device_count()} global devices"
    )


def _crosshost_prologue(args, cfg, ecfg, params):
    """Cross-host single-engine wiring. On rank 0, returns the dispatch
    sink (command broadcaster on its own background loop). On other ranks,
    builds the engine replica, REPLAYS the leader's commands forever, and
    exits the process when the stream stops — followers never serve."""
    import threading

    import jax

    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.engine.multihost import (
        CommandStream,
        Follower,
        make_dispatch_sink,
    )
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
    from dynamo_tpu.runtime.client import KvClient

    host, port = _cp_addr(args)
    engine_id = f"{args.component}"
    run_id = getattr(args, "_mh_run_id", "r0")
    mesh = make_mesh(MeshConfig(tp=args.tensor_parallel_size), jax.devices())

    if args.node_rank == 0:
        # dedicated loop thread: the engine thread emits commands without
        # touching the serving loop
        stream_loop = asyncio.new_event_loop()
        threading.Thread(
            target=stream_loop.run_forever, name="mh-cmd-stream", daemon=True
        ).start()
        kv = asyncio.run_coroutine_threadsafe(
            KvClient(host, port).connect(), stream_loop
        ).result(timeout=30)
        stream = CommandStream(
            kv, stream_loop, args.namespace, engine_id, run_id,
            args.num_nodes - 1,
        )
        # leader liveness key: followers exit when it expires
        asyncio.run_coroutine_threadsafe(
            stream.announce(), stream_loop
        ).result(timeout=30)

        def teardown() -> None:
            """Leader-exit discipline: tell followers to stop, then drop
            the liveness lease (close the kv client so its keep-alive
            dies). Without this the leader's atexit jax.distributed
            shutdown barrier waits on followers that are themselves
            waiting on the still-renewed liveness key — a deadlock that
            held the old CLI past test timeouts."""
            from dynamo_tpu.engine.multihost import stop_followers

            try:
                # pending batches must hit the wire before the stop
                # command, or followers see a seq gap
                asyncio.run_coroutine_threadsafe(
                    stream.drain(), stream_loop
                ).result(timeout=30)
                asyncio.run_coroutine_threadsafe(
                    stop_followers(
                        kv, args.namespace, engine_id, run_id,
                        args.num_nodes - 1, stream.seq,
                    ),
                    stream_loop,
                ).result(timeout=30)
            finally:
                # the lease revoke must happen even if the stop push
                # failed — followers fall back to liveness expiry
                try:
                    asyncio.run_coroutine_threadsafe(
                        stream.close(), stream_loop
                    ).result(timeout=10)
                finally:
                    stream_loop.call_soon_threadsafe(stream_loop.stop)

        args._mh_teardown = teardown
        return make_dispatch_sink(stream)

    async def follow() -> None:
        kv = await KvClient(host, port).connect()
        engine = TpuEngine(cfg, ecfg, params=params, mesh=mesh)
        print(
            f"cross-host follower rank {args.node_rank}: replaying "
            f"{args.namespace}/{engine_id} run {run_id} dispatch stream"
        )
        await Follower(
            engine, kv, args.namespace, engine_id, run_id, args.node_rank
        ).run()

    asyncio.run(follow())
    raise SystemExit(0)


def build_chain(args) -> "Any":
    """Construct the ModelChain for the selected engine."""
    from dynamo_tpu.backend import Backend
    from dynamo_tpu.frontend.model_manager import ModelChain
    from dynamo_tpu.preprocessor import OpenAIPreprocessor, PromptFormatter
    from dynamo_tpu.tokenizer import HfTokenizer, make_test_tokenizer

    inp, out = _parse_io(args.io)
    gguf_meta = None

    if args.model_path:
        # path | cached hub id | .gguf (reference local_model.rs:39; no
        # downloads — serving hosts have zero egress)
        from dynamo_tpu.model_resolver import resolve_model

        resolved = resolve_model(args.model_path)
        if resolved.kind == "gguf":
            # single-file serving: config + tokenizer + dequantized
            # weights all come out of the .gguf (reference gguf/ module +
            # llamacpp engine path)
            from dynamo_tpu.gguf import gguf_tokenizer, read_gguf

            gguf_meta, _ = read_gguf(resolved.path)
            tok = gguf_tokenizer(gguf_meta)
            fmt = PromptFormatter()
            name = args.model_name or os.path.basename(
                resolved.path).removesuffix(".gguf")
        else:
            tok = HfTokenizer.from_dir(resolved.path)
            fmt = PromptFormatter.from_dir(resolved.path)
            name = args.model_name or os.path.basename(
                resolved.path.rstrip("/"))
        args.model_path = resolved.path
    else:
        tok = make_test_tokenizer()
        fmt = PromptFormatter()
        name = args.model_name or "echo"

    if out == "echo":
        from dynamo_tpu.engines import EchoEngine

        engine: Any = EchoEngine()
    elif out == "mocker":
        from dynamo_tpu.mocker import MockerArgs, MockerEngine

        engine = MockerEngine(MockerArgs())
    elif out == "tpu":
        from dynamo_tpu.compile_cache import ensure_compile_cache
        from dynamo_tpu.engine.config import EngineConfig
        from dynamo_tpu.engine.engine import TpuEngine
        from dynamo_tpu.models.config import ModelConfig
        from dynamo_tpu.parallel.mesh import MeshConfig

        local_devices = None
        cross_host = False
        if args.num_nodes > 1:
            if not args.control_plane:
                raise SystemExit("--num-nodes > 1 requires --control-plane")
            multi_host_bootstrap(args)
            import jax

            local_devices = jax.local_devices()
            # tp within one host's chips: each rank is an independent DP
            # replica (SURVEY §2.5 DP row). tp BEYOND the local chips: ONE
            # logical engine spans every host — rank 0 runs the scheduler
            # and broadcasts each dispatch, other ranks replay in lockstep
            # (engine/multihost.py).
            cross_host = args.tensor_parallel_size > len(local_devices)
            if cross_host:
                if getattr(args, "role", None) in ("decode", "prefill"):
                    raise SystemExit(
                        "cross-host TP engines cannot join the disagg "
                        "data plane (the page transfer plane is "
                        "single-host); drop --role"
                    )
                if args.tensor_parallel_size > jax.device_count():
                    raise SystemExit(
                        f"--tensor-parallel-size {args.tensor_parallel_size}"
                        f" exceeds the {jax.device_count()} global chips"
                    )
                local_devices = None  # global mesh

        # before the first jit (and after jax.distributed.initialize,
        # which must precede any backend use): a cold start compiles
        # half a dozen whole-model programs; every later start loads them
        cache_dir = ensure_compile_cache()
        if args.model_path and gguf_meta is not None:
            from dynamo_tpu.gguf import config_from_gguf

            cfg = config_from_gguf(gguf_meta)
        elif args.model_path:
            cfg = ModelConfig.from_pretrained(args.model_path)
        elif args.model_config:
            cfg = getattr(ModelConfig, args.model_config)()
        else:
            raise SystemExit("out=tpu needs --model-path or --model-config")
        if args.quantize:
            from dataclasses import replace as _replace

            cfg = _replace(cfg, quant=args.quantize)
        ecfg = EngineConfig(
            num_pages=args.num_pages,
            page_size=args.page_size,
            max_decode_slots=args.max_decode_slots,
            cache_dtype=args.cache_dtype,
            kv_quant=args.kv_quant,
            host_offload_pages=args.host_offload_pages,
            disk_offload_pages=args.disk_offload_pages,
            disk_offload_path=args.disk_offload_path,
            scrub_on_start=args.scrub_on_start,
            speculative=args.speculative,
            num_speculative_tokens=args.num_speculative_tokens,
            spec_adaptive=args.spec_adaptive == "on",
            spec_min_k=args.spec_min_k,
            spec_tree=args.spec_tree == "on",
            spec_branches=args.spec_branches,
            spec_tree_budget=args.spec_tree_budget,
            spec_gate_acceptance=args.spec_gate_acceptance,
            spec_gate_window=args.spec_gate_window,
            spec_rearm_tokens=args.spec_rearm_tokens,
            kv_transfer_chunk_pages=args.kv_transfer_chunk_pages,
            kv_transfer_inflight_chunks=args.kv_transfer_inflight_chunks,
            xfer_op_timeout_s=args.xfer_op_timeout,
            kv_transfer_stream_idle_timeout_s=(
                args.kv_transfer_stream_idle_timeout
            ),
            max_waiting_requests=args.max_waiting_requests,
            max_waiting_prefill_tokens=args.max_waiting_prefill_tokens,
            preempt_running=args.preempt_running == "on",
            slo_ttft_target_s=args.slo_ttft_target,
            slo_itl_target_s=args.slo_itl_target,
            slo_objective=args.slo_objective,
            forensics_sample_rate=args.forensics_sample_rate,
            kv_dedup_admission=not getattr(
                args, "no_kv_dedup_admission", False
            ),
        )
        draft_cfg = None
        if args.speculative == "draft":
            if not args.draft_model_config:
                raise SystemExit(
                    "--speculative draft needs --draft-model-config"
                )
            draft_cfg = getattr(ModelConfig, args.draft_model_config)()
        params = None
        if args.model_path and gguf_meta is not None:
            from dynamo_tpu.gguf import load_gguf_params

            params = load_gguf_params(cfg, args.model_path)
        elif args.model_path:
            from dynamo_tpu.models import llama

            params = llama.load_hf_params(cfg, args.model_path)
        from dynamo_tpu.parallel.mesh import make_mesh

        on_dispatch = None
        if cross_host:
            on_dispatch = _crosshost_prologue(args, cfg, ecfg, params)
        engine = TpuEngine(
            cfg, ecfg, params=params,
            mesh=make_mesh(
                MeshConfig(tp=args.tensor_parallel_size), local_devices
            ) if local_devices is not None else None,
            mesh_config=MeshConfig(tp=args.tensor_parallel_size),
            on_dispatch=on_dispatch,
            draft_config=draft_cfg,
        )
        import jax

        dev0 = jax.devices()[0]
        # one line a supervisor or smoke test can hold the process to:
        # which devices JAX gave it, what the engine compiled for them,
        # and where the weights/ctx/pool actually sit (a tp engine that
        # replicated everything onto chip 0 shows here, not in a trace)
        hbm = [
            round((d.memory_stats() or {}).get("bytes_in_use", 0) / 1e9, 2)
            for d in engine.mesh.devices.flat
            if d.process_index == jax.process_index()  # cross-host mesh
        ]
        ctx_k = engine.ctx["k"]
        print("engine up: " + json.dumps({
            "platform": dev0.platform, "device_kind": dev0.device_kind,
            "devices": len(jax.devices()),
            "tp": args.tensor_parallel_size,
            "decode_attention": engine.decode_attn.impl,
            "ctx_shape": list(ctx_k.shape),
            "ctx_shard": list(ctx_k.addressable_shards[0].data.shape),
            "hbm_gb": hbm, "compile_cache": cache_dir,
        }), flush=True)
    else:
        raise SystemExit(f"unknown engine out={out!r}")

    pre = OpenAIPreprocessor(tokenizer=tok, formatter=fmt, model_name=name)
    return inp, ModelChain(
        name=name, preprocessor=pre, engine=engine, backend=Backend(tok)
    )


async def _serve_http(args, chain) -> None:
    from dynamo_tpu.frontend import HttpService, ModelManager

    manager = ModelManager()
    manager.register(chain)
    svc = HttpService(manager, host=args.http_host, port=args.http_port,
                      trace_sample_rate=args.trace_sample_rate,
                      forensics_sample_rate=args.forensics_sample_rate)
    await svc.start()
    print(f"serving {chain.name!r} on http://{args.http_host}:{args.http_port}")
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await svc.stop()


async def _one_prompt(chain, prompt: str, max_tokens: int) -> str:
    from dynamo_tpu.protocols.openai import ChatCompletionRequest

    req = ChatCompletionRequest(
        model=chain.name,
        messages=[{"role": "user", "content": prompt}],
        max_tokens=max_tokens,
    )
    pre = chain.preprocess(req)
    parts = []
    async for out in chain.generate(pre):
        if out.text:
            parts.append(out.text)
    return "".join(parts)


async def _serve_text(args, chain) -> None:
    if args.prompt is not None:
        print(await _one_prompt(chain, args.prompt, args.max_tokens))
        return
    # interactive REPL
    while True:
        try:
            line = await asyncio.to_thread(input, "> ")
        except EOFError:
            return
        if line.strip():
            print(await _one_prompt(chain, line, args.max_tokens))


async def _serve_stdin(args, chain) -> None:
    for line in sys.stdin:
        if line.strip():
            print(await _one_prompt(chain, line.strip(), args.max_tokens))


async def _serve_batch(args, chain, path: str) -> None:
    """Batch mode doubles as the built-in benchmark (reference
    entrypoint/input/batch.rs:294): plain {"prompt": ...} JSONL runs
    through the chat chain; mooncake trace records (datagen output, with
    hash_ids/input_length/output_length) replay token-level with their
    prefix-sharing structure intact and timestamp pacing via
    --trace-speedup. Both print a summary line at the end."""
    with open(path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    is_trace = bool(recs) and "hash_ids" in recs[0]
    # submit concurrently so the continuous-batching engine actually batches
    sem = asyncio.Semaphore(64)
    ttfts: list[float] = []
    total_tokens = 0
    t0 = time.monotonic()

    async def one(rec):
        nonlocal total_tokens
        async with sem:
            t_sub = time.monotonic()
            first = None
            if is_trace:
                pre = _trace_request(rec, args.trace_block_size)
                n = 0
                async for out in chain.generate(pre):
                    if first is None and out.token_ids:
                        first = time.monotonic() - t_sub
                    n += len(out.token_ids)
                total_tokens += n
                if first is not None:
                    ttfts.append(first)
                return n
            text = await _one_prompt(
                chain, rec.get("prompt", ""),
                rec.get("max_tokens", args.max_tokens),
            )
            ttfts.append(time.monotonic() - t_sub)
            return text

    async def paced(rec, delay_s):
        if delay_s > 0:
            await asyncio.sleep(delay_s)
        return await one(rec)

    if is_trace and args.trace_speedup > 0:
        base_ms = recs[0].get("timestamp", 0)
        tasks = [
            paced(r, (r.get("timestamp", 0) - base_ms) / 1000.0
                  / args.trace_speedup)
            for r in recs
        ]
    else:
        tasks = [one(r) for r in recs]
    results = await asyncio.gather(*tasks)
    wall = time.monotonic() - t0
    if not is_trace:
        for rec, text in zip(recs, results):
            print(json.dumps({"prompt": rec.get("prompt", ""),
                              "text": text}))
    ttfts.sort()
    summary = {
        "requests": len(recs),
        "wall_s": round(wall, 3),
        "requests_per_s": round(len(recs) / wall, 2) if wall else None,
    }
    # trace mode measures a real first-token time; the prompt path only
    # observes whole-request latency — name the metrics honestly
    prefix = "ttft" if is_trace else "latency"
    summary[f"{prefix}_p50_s"] = (
        round(ttfts[len(ttfts) // 2], 4) if ttfts else None
    )
    summary[f"{prefix}_p99_s"] = (
        round(ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 4)
        if ttfts else None
    )
    if is_trace:  # token counts only exist on the token-level replay path
        summary["output_tok_s"] = round(total_tokens / wall, 2) \
            if wall else None
    print(json.dumps({"batch_summary": summary}), file=sys.stderr)


def _trace_request(rec: dict, block_size: int = 64) -> "Any":
    """Mooncake record -> PreprocessedRequest with DETERMINISTIC tokens
    per hash id, so equal hash prefixes produce equal token blocks and the
    prefix cache / KV router see the trace's sharing structure. The hash →
    tokens mapping uses a FIXED block_size (one hash = block_size tokens):
    a per-record size would make the same hash expand differently across
    records and destroy the sharing the replay exists to measure."""
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        StopConditions,
    )

    hash_ids = rec.get("hash_ids") or [0]
    isl = max(1, int(rec.get("input_length", 1)))
    tokens: list[int] = []
    for h in hash_ids:
        base = (int(h) * 2654435761) & 0x7FFFFFFF
        tokens.extend(
            (base + j * 40503) % 30000 + 10 for j in range(block_size)
        )
        if len(tokens) >= isl:
            break
    if len(tokens) < isl:  # trace lengths can exceed hash coverage
        tokens.extend(
            (len(tokens) + j) % 30000 + 10
            for j in range(isl - len(tokens))
        )
    return PreprocessedRequest(
        token_ids=tokens[:isl],
        stop_conditions=StopConditions(
            max_tokens=max(1, int(rec.get("output_length", 16))),
            ignore_eos=True,
        ),
    )


def _cp_addr(args) -> tuple[str, int]:
    host, _, port = args.control_plane.partition(":")
    return host or "127.0.0.1", int(port or 7111)


async def _serve_worker(args, chain) -> None:
    """in=endpoint: register the engine on the runtime and serve forever
    (reference Input::Endpoint, entrypoint/input.rs:43). --role decode adds
    the disagg wrapper + block-transfer data plane."""
    from dynamo_tpu.frontend.watcher import ModelEntry, register_llm
    from dynamo_tpu.runtime.component import DistributedRuntime

    host, port = _cp_addr(args)
    # resync: a store bounce must not unregister a serving worker — the
    # session re-grants the lease and re-puts registration keys
    rt = await DistributedRuntime.connect(host=host, port=port, resync=True)

    engine = chain.engine
    disagg_parts = []
    if args.role == "decode":
        import uuid

        from dynamo_tpu.disagg import (
            DisaggConfig,
            DisaggConfigWatcher,
            DisaggDecodeEngine,
            set_disagg_config,
        )

        if (args.max_local_prefill_length is not None
                or args.max_prefill_queue_size is not None):
            conf = DisaggConfig()
            if args.max_local_prefill_length is not None:
                conf.max_local_prefill_length = args.max_local_prefill_length
            if args.max_prefill_queue_size is not None:
                conf.max_prefill_queue_size = args.max_prefill_queue_size
            await set_disagg_config(rt.kv, args.namespace, conf)
        watcher = await DisaggConfigWatcher(rt.kv, args.namespace).start()
        engine = DisaggDecodeEngine(
            engine, rt, namespace=args.namespace, conf=watcher,
            prefill_timeout_s=args.prefill_timeout,
        )
        disagg_parts.append(watcher)
        # data plane + descriptor up BEFORE the endpoint serves: a request
        # landing in between would enqueue an unroutable prefill job (the
        # descriptor key is a fresh uuid, independent of the lease)
        served_xfer = await _attach_data_plane(
            args, rt, engine, uuid.uuid4().hex
        )
        disagg_parts.append(served_xfer)

    if getattr(args, "remote_kv", False) and args.role != "decode":
        # G4: aggregated workers also join the transfer plane (decode
        # workers already do) and fetch through it on prefix misses
        import uuid as _uuid

        inner = getattr(engine, "engine", engine)
        if getattr(inner, "offload", None) is None:
            raise SystemExit(
                "--remote-kv needs a G2 host tier "
                "(--host-offload-pages > 0)"
            )
        served_xfer = await _attach_data_plane(
            args, rt, engine, _uuid.uuid4().hex
        )
        disagg_parts.append(served_xfer)
    if getattr(args, "remote_kv", False):
        from dynamo_tpu.kv_transfer import RemoteKvFetcher

        inner = getattr(engine, "engine", engine)
        if getattr(inner, "offload", None) is not None:
            inner.remote_kv = RemoteKvFetcher(
                rt.kv, args.namespace, getattr(engine, "worker_id", ""),
                chunk_pages=args.kv_transfer_chunk_pages,
            )

    entry = ModelEntry(
        name=chain.name,
        namespace=args.namespace,
        component=args.component,
        endpoint=args.endpoint_name,
        block_size=args.page_size,
        router_mode=args.router_mode,
        model_path=args.model_path,
    )
    served = await register_llm(rt, engine, entry)

    # graceful drain (resilience/drain.py): SIGTERM (planner scale-down)
    # and POST /drain both stop admissions, let in-flight requests finish,
    # then exit — instead of killing warm KV and live streams
    import signal

    from dynamo_tpu.resilience.drain import DrainController

    drained_exit = asyncio.Event()
    drain = DrainController(
        engine,
        on_deregister=served.lease.revoke,
        on_drained=drained_exit.set,
        timeout_s=args.drain_timeout,
    )
    loop = asyncio.get_running_loop()
    try:
        loop.add_signal_handler(
            signal.SIGTERM,
            lambda: drain.request_drain(reason="SIGTERM"),
        )
    except (NotImplementedError, RuntimeError):
        pass  # platforms/loops without signal support: /drain still works

    if args.system_port is not None:
        from dynamo_tpu.runtime.system_server import SystemServer

        sysrv = await SystemServer(
            engine, port=args.system_port,
            worker_id=str(served.lease_id),
            drain=drain,
        ).start()
        disagg_parts.append(sysrv)  # stopped alongside disagg parts
        print(f"system server on :{sysrv.port}")
    print(
        f"worker {chain.name!r} instance {served.lease_id} "
        f"({args.role}) serving "
        f"{args.namespace}/{args.component}/{args.endpoint_name}"
    )
    try:
        # run until the control plane drops us OR a drain completes
        lost = asyncio.ensure_future(served.lease.lost.wait())
        drained = asyncio.ensure_future(drained_exit.wait())
        done, pending = await asyncio.wait(
            {lost, drained}, return_when=asyncio.FIRST_COMPLETED
        )
        for t in pending:
            t.cancel()
        if drained in done:
            print("drained; shutting down")
        else:
            print("lease lost; shutting down")
    finally:
        for part in disagg_parts:
            await part.stop()
        await served.shutdown()


async def _attach_data_plane(args, rt, engine, worker_id: str):
    """Serve the engine's KV pool on the block-transfer plane + publish the
    blockset descriptor (lease-less: rides the registration lease via the
    same worker id)."""
    from dynamo_tpu.kv_transfer import (
        BlocksetDescriptor,
        BlockTransferServer,
        KvCacheLayout,
        publish_descriptor,
    )

    inner = getattr(engine, "engine", engine)
    engine.worker_id = worker_id
    write_fn = getattr(engine, "guarded_import", None) or inner.import_pages
    srv = BlockTransferServer(
        read_fn=inner.export_pages, write_fn=write_fn,
        read_hashes_fn=getattr(inner, "export_pages_by_hash", None),
        # chunk-pipelined G4 serving: cheap probes + streamed hash reads
        count_hashes_fn=getattr(
            getattr(inner, "allocator", None), "cached_prefix_len", None
        ),
        read_hashes_stream_fn=getattr(inner, "export_hash_stream", None),
    )
    host, port = await srv.start()
    cfg, ecfg = inner.config, inner.ecfg
    await publish_descriptor(rt.kv, args.namespace, BlocksetDescriptor(
        worker_id=worker_id, host=host, port=port,
        layout=KvCacheLayout(
            # the wire's layer axis is the pool's: a plane a layer, a
            # plane a (step, layer) of a looped stack
            num_layers=cfg.cache_planes, num_kv_heads=cfg.num_kv_heads,
            page_size=ecfg.page_size, head_dim=cfg.head_dim,
            # what moves on the wire: int8 payloads (+ header scales)
            # for a quantized pool
            dtype=("int8" if ecfg.kv_quant == "int8"
                   else ecfg.cache_dtype),
        ),
    ))
    return srv


async def _serve_prefill_worker(args, chain) -> None:
    """--role prefill: consume the prefill queue; no model registration
    (reference prefill_worker.py)."""
    from dynamo_tpu.disagg import PrefillWorker
    from dynamo_tpu.runtime.component import DistributedRuntime

    host, port = _cp_addr(args)
    rt = await DistributedRuntime.connect(host=host, port=port, resync=True)
    worker = await PrefillWorker(
        rt, chain.engine, namespace=args.namespace
    ).start()
    print(f"prefill worker consuming {args.namespace}.prefill")
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await worker.stop()
        await rt.close()


async def _serve_http_dynamic(args) -> None:
    """in=http + --control-plane: discover models instead of building a
    local chain (reference EngineConfig::Dynamic, input/common.rs:55-90)."""
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.watcher import ModelWatcher
    from dynamo_tpu.kv_router.prefetch import PrefetchConfig
    from dynamo_tpu.kv_router.scheduler import KvRouterConfig
    from dynamo_tpu.runtime.component import DistributedRuntime

    host, port = _cp_addr(args)
    # resync: the frontend serves from last-known state through an outage
    # (ModelWatcher freezes its health/load views) and resyncs after
    rt = await DistributedRuntime.connect(host=host, port=port, resync=True)
    manager = ModelManager()
    kv_recorder = None
    if args.record_kv_events:
        from dynamo_tpu.recorder import KvRecorder

        kv_recorder = KvRecorder(args.record_kv_events)
    router_config = KvRouterConfig(
        freq_halflife_s=(args.kv_freq_halflife or None),
    )
    # replication target <= 1 means "one copy is enough": no controller
    prefetch_config = None
    if args.kv_replication_target > 1:
        prefetch_config = PrefetchConfig(
            replication_target=args.kv_replication_target,
            hot_k=args.kv_prefetch_hot_k,
            interval_s=args.kv_prefetch_interval,
        )
    watcher = await ModelWatcher(
        rt, manager, namespace=args.namespace, kv_recorder=kv_recorder,
        heartbeat_ttl_s=args.health_heartbeat_ttl,
        router_config=router_config, prefetch_config=prefetch_config,
    ).start()
    svc = HttpService(manager, host=args.http_host, port=args.http_port,
                      trace_sample_rate=args.trace_sample_rate,
                      forensics_sample_rate=args.forensics_sample_rate)
    # /debug/kv_fleet serves the watcher's live per-model fleet views
    svc.fleet_views = watcher.fleet_views
    await svc.start()
    print(
        f"dynamic frontend on http://{args.http_host}:{args.http_port} "
        f"(namespace {args.namespace!r})"
    )
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await svc.stop()
        await watcher.stop()
        await rt.close()


def _shutdown_chain(args, chain) -> None:
    """Tear the engine + cross-host stream down BEFORE interpreter exit.

    Order matters: stop the engine first (so no further dispatches are
    broadcast), then the cross-host teardown (stop command + liveness
    lease drop). Skipping this leaves the engine's daemon thread racing
    jax's atexit distributed shutdown — the backend cache is cleared
    mid-round and the next jnp op re-initializes the cpu client, which
    re-publishes its coordination-service topology key and dies with
    ALREADY_EXISTS; the liveness lease then deadlocks the shutdown
    barrier (leader waits for followers; followers wait for the lease)."""
    try:
        if chain is not None:
            stop = getattr(chain.engine, "stop", None)
            if stop is not None:
                try:
                    asyncio.run(stop())
                except Exception as e:  # noqa: BLE001 - teardown proceeds
                    print(f"engine stop failed: {e}", file=sys.stderr)
    finally:
        # must run even if engine stop is interrupted (a second Ctrl-C):
        # skipping it reinstates the liveness-lease/atexit deadlock
        teardown = getattr(args, "_mh_teardown", None)
        if teardown is not None:
            try:
                teardown()
            except Exception as e:  # noqa: BLE001
                print(f"cross-host teardown failed: {e}", file=sys.stderr)


def run_cli(argv: list[str]) -> int:
    # intermixed: in=/out= positionals may appear between/after flags
    # (graph files and scripts compose argv in any order)
    args = build_parser().parse_intermixed_args(argv)
    chaos_spec = args.chaos or os.environ.get("DYNAMO_CHAOS")
    if chaos_spec:
        from dynamo_tpu.resilience.chaos import CHAOS

        CHAOS.configure(chaos_spec)
    inp, _ = _parse_io(args.io)
    chain = None
    try:
        if inp == "http" and args.control_plane:
            asyncio.run(_serve_http_dynamic(args))
            return 0
        if inp == "endpoint":
            if not args.control_plane:
                raise SystemExit("in=endpoint requires --control-plane")
            _, chain = build_chain(args)
            if args.role == "prefill":
                asyncio.run(_serve_prefill_worker(args, chain))
            else:
                asyncio.run(_serve_worker(args, chain))
            return 0
        inp, chain = build_chain(args)
        engine_start = getattr(chain.engine, "start", None)
        if engine_start is not None:
            engine_start()
        if inp == "http":
            asyncio.run(_serve_http(args, chain))
        elif inp == "text":
            asyncio.run(_serve_text(args, chain))
        elif inp == "stdin":
            asyncio.run(_serve_stdin(args, chain))
        elif inp.startswith("batch:"):
            asyncio.run(_serve_batch(args, chain, inp[len("batch:"):]))
        else:
            raise SystemExit(f"unknown input in={inp!r}")
    except KeyboardInterrupt:
        pass
    finally:
        _shutdown_chain(args, chain)
    return 0
