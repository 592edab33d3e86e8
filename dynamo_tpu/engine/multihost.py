"""Cross-host single-engine controller (a model sharded past one host; reference
MultiNodeConfig launch/dynamo-run/src/flags.rs:86-101 +
leader_worker_barrier.rs:137,230 — vLLM uses ray, TRT-LLM uses MPI; the
TPU-native answer is jax.distributed + SPMD lockstep).

One logical worker backed by N host processes over a single
``jax.distributed`` mesh:

  - Every host builds the SAME engine state (params from the same seed or
    checkpoint, ctx/ring/pool) sharded over the GLOBAL mesh.
  - The LEADER runs the full host scheduler (admission, rounds, seals) and
    broadcasts every device dispatch as a compact JSON command over the
    control-plane store's durable per-follower FIFO queues BEFORE issuing
    it locally.
  - FOLLOWERS replay the commands in order, issuing the identical jits.
    XLA's collectives inside the programs (tp/ep shardings span hosts)
    form the actual lockstep: the leader's device work blocks until every
    follower dispatches the matching program, so followers can lag on the
    host side without correctness impact.
  - Only the leader fetches results / talks to clients — follower hosts
    never read device data (their shards' contribution flows through the
    collectives).

Round pipelining (EngineConfig.round_pipeline) needs no follower-side
change: the leader's _round may now EMIT round N+1's command before it
has finished round N's host bookkeeping (fetch/emit), but commands are
still broadcast in device-dispatch order — which is the only order a
follower ever sees. The replay loop below is the completion-free
"dispatch half" by construction (followers never fetch), so the
pipelined leader simply narrows the host-side lag between itself and
its followers; the lag bound stays flush_every * (max_inflight_rounds
+ 1) steps either way.

Scope: the multihost engine serves the dense/MoE decode+prefill paths,
batched prefill, and the sp ring prefill (its own broadcast command);
host-offload tiers, the page transfer plane, and multimodal injection
remain single-host (asserted at init) — they materialize host copies of
device arrays, which a multi-process mesh shards across hosts.

Bring-up uses the store-backed leader/worker barrier (runtime/barrier.py)
so all hosts enter the replay loop only after every process has built its
engine state.
"""
from __future__ import annotations

import asyncio
import json
import logging
import threading
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)


def cmd_queue(namespace: str, engine_id: str, run_id: str,
              host: int) -> str:
    # run_id (a fresh uuid per leader incarnation, distributed through the
    # bring-up barrier) scopes the durable queues: a restarted follower
    # must never replay a DEAD run's leftover commands onto a fresh engine
    return f"{namespace}.mh.{engine_id}.{run_id}.cmds.{host}"


def leader_key(namespace: str, engine_id: str, run_id: str) -> str:
    return f"dynamo://{namespace}/mh/{engine_id}/{run_id}/leader"


class CommandStream:
    """Leader-side dispatch broadcaster: thread-safe (the engine loop is a
    plain thread), pumped onto the runtime's asyncio loop."""

    def __init__(self, kv: Any, loop: asyncio.AbstractEventLoop,
                 namespace: str, engine_id: str, run_id: str,
                 n_followers: int):
        self.kv = kv
        self.loop = loop
        self.namespace = namespace
        self.engine_id = engine_id
        self.run_id = run_id
        self.queues = [
            cmd_queue(namespace, engine_id, run_id, h + 1)
            for h in range(n_followers)
        ]
        self.seq = 0
        self.lease: Optional[Any] = None
        self._err: Optional[BaseException] = None
        self._pending: list[str] = []
        self._lock = threading.Lock()
        self._flushing = False

    async def announce(self, ttl_s: float = 5.0) -> None:
        """Publish the leader liveness key (lease-bound): followers poll
        it while idle and exit when the leader is gone."""
        self.lease = await self.kv.lease_grant(ttl_s)
        await self.kv.put(
            leader_key(self.namespace, self.engine_id, self.run_id),
            "up", lease=self.lease.id,
        )

    async def drain(self) -> None:
        """Wait until every emitted command is on the wire (call before
        pushing an out-of-band stop: a stop overtaking a pending batch
        would open a seq gap on the followers)."""
        while True:
            with self._lock:
                idle = not self._pending and not self._flushing
            if idle or self._err is not None:
                return
            await asyncio.sleep(0.005)

    async def close(self) -> None:
        """Revoke the liveness key (followers see the leader as gone
        immediately) and stop the keep-alive task."""
        if self.lease is not None:
            await self.lease.revoke()
            self.lease = None
        await self.kv.close()

    def emit(self, op: str, payload: dict) -> None:
        """Thread-safe. Commands are COALESCED: every emit appends to a
        pending batch, and one flush task per wakeup of the stream loop
        drains the whole batch as a single array frame per follower,
        pushed to all followers CONCURRENTLY — per round the leader pays
        one store round-trip, not #commands x #followers (the v5p-64
        scaling concern: 31 followers, several commands per round)."""
        with self._lock:
            self.seq += 1
            raw = json.dumps({"seq": self.seq, "op": op, **payload})
            self._pending.append(raw)
        self.loop.call_soon_threadsafe(self._schedule_flush)
        if self._err is not None:
            raise RuntimeError(f"command broadcast failed: {self._err}")

    def _schedule_flush(self) -> None:
        # stream-loop thread: one flush task at a time keeps per-queue
        # FIFO order (batches are drained in emit order)
        if self._flushing:
            return
        self._flushing = True
        asyncio.ensure_future(self._flush(), loop=self.loop)

    async def _flush(self) -> None:
        try:
            while True:
                with self._lock:
                    batch = self._pending
                    self._pending = []
                if not batch:
                    return
                frame = (
                    batch[0] if len(batch) == 1
                    else "[" + ",".join(batch) + "]"
                )
                try:
                    await asyncio.gather(*[
                        self.kv.qpush(q, frame) for q in self.queues
                    ])
                except BaseException as e:  # noqa: BLE001
                    # surfaced on the NEXT emit; if the leader's device
                    # work is already blocked on a follower that never got
                    # this batch, recovery is the liveness teardown
                    # (leader key expiry -> followers exit -> jax runtime
                    # collapse)
                    log.exception("multihost command broadcast failed")
                    self._err = e
                    return
        finally:
            self._flushing = False
            with self._lock:
                if self._pending and self._err is None:
                    self._schedule_flush()


def make_dispatch_sink(stream: CommandStream):
    """The TpuEngine on_dispatch hook."""

    def sink(op: str, payload: dict) -> None:
        stream.emit(op, payload)

    return sink


class Follower:
    """Replays the leader's dispatch stream on this host's engine replica.

    The engine must be constructed with the same configs/params/mesh as
    the leader's and NEVER started (its host loop stays off); this class
    drives its jits directly.
    """

    def __init__(self, engine: Any, kv: Any, namespace: str,
                 engine_id: str, run_id: str, host_index: int):
        self.engine = engine
        self.kv = kv
        self.queue = cmd_queue(namespace, engine_id, run_id, host_index)
        self.leader_key = leader_key(namespace, engine_id, run_id)
        self.commands_applied = 0
        self._expected_seq = 1

    async def run(self) -> None:
        """Replay until a `stop` command or leader death (liveness key
        expiry — a crashed leader must not leave followers holding the
        jax runtime forever)."""
        while True:
            raw = await self.kv.qpop(self.queue, timeout_s=10.0)
            if raw is None:
                if await self.kv.get(self.leader_key) is None:
                    log.warning("multihost leader gone; follower exiting")
                    return
                continue
            decoded = json.loads(raw)
            # the leader coalesces a round's commands into one frame
            batch = decoded if isinstance(decoded, list) else [decoded]
            for cmd in batch:
                seq = cmd.get("seq", -1)
                if seq != self._expected_seq:
                    raise RuntimeError(
                        f"command stream gap: expected "
                        f"{self._expected_seq}, got {seq} — follower "
                        f"state is no longer lockstep"
                    )
                self._expected_seq += 1
                if cmd["op"] == "stop":
                    return
                self.apply(cmd)
                self.commands_applied += 1

    # replayed op -> the follower's dispatch_counts bucket (parity with
    # the leader's accounting at its own dispatch sites; "round" picks
    # round/round_seal below and "patch" counts inside _dispatch_patch)
    _OP_BUCKETS = {
        "prefill": "prefill", "prefill_batch": "prefill_batch",
        "admit_first": "admit_first", "sp_prefill": "sp_prefill",
        "load_ctx": "load_ctx", "seal": "seal",
    }

    def apply(self, cmd: dict) -> None:
        eng = self.engine
        op = cmd["op"]
        bucket = self._OP_BUCKETS.get(op)
        if bucket is not None:
            eng.dispatch_counts[bucket] += 1
        if op == "round":
            eng.dispatch_counts[
                "round_seal" if cmd.get("seal") else "round"] += 1
            seal = cmd.get("seal")
            if seal:
                # leader fused the round's seal batch into the program
                out = eng._engine_round_seal(
                    eng.params, eng.ctx, eng.ring, eng._dev, eng.cache,
                    jnp.asarray(np.asarray(seal["slots"], np.int32)),
                    jnp.asarray(np.asarray(seal["starts"], np.int32)),
                    jnp.asarray(np.asarray(seal["pages"], np.int32)),
                    cmd["n_steps"], cmd["want_lp"], cmd["want_sample"],
                )
                eng.ctx, eng.ring, eng._dev, eng.cache = (
                    out[0], out[1], out[2], out[3]
                )
            else:
                out = eng._engine_round(
                    eng.params, eng.ctx, eng.ring, eng._dev,
                    cmd["n_steps"], cmd["want_lp"], cmd["want_sample"],
                )
                eng.ctx, eng.ring, eng._dev = out[0], out[1], out[2]
        elif op == "patch":
            eng._dispatch_patch(clear_slots=cmd.get("clear_slots") or [])
        elif op == "prefill":
            from dynamo_tpu.models import llama

            eng.ctx, eng._mh_last_logits = llama.prefill(
                eng.config, eng.params, eng.ctx,
                jnp.asarray(np.asarray(cmd["tokens"], np.int32)),
                jnp.int32(cmd["slot"]),
                jnp.int32(cmd["start"]), jnp.int32(cmd["end"]),
                None, None, jnp.int32(cmd.get("adapter", 0)),
                fresh=int(cmd["start"]) == 0, attn=eng.decode_attn,
            )
        elif op == "prefill_batch":
            from dynamo_tpu.models import llama

            k = len(cmd["slots"])
            eng.ctx, eng._mh_last_logits = llama.batch_prefill(
                eng.config, eng.params, eng.ctx,
                jnp.asarray(np.asarray(cmd["tokens"], np.int32)),
                jnp.asarray(np.asarray(cmd["slots"], np.int32)),
                jnp.asarray(np.asarray(cmd["q_starts"], np.int32)),
                jnp.asarray(np.asarray(cmd["seq_lens"], np.int32)),
                int(cmd["ctx_span"]),
                jnp.asarray(np.asarray(
                    cmd.get("adapter_ids", [0] * k), np.int32)),
                attn=eng.decode_attn,
            )
        elif op == "admit_first":
            # the leader's one program a prefill dispatch, on this host's
            # own replayed logits: same rows (keys among them) -> the same
            # first tokens admitted
            eng._dev, _toks, _lps = eng._admit_first(
                eng._dev, eng._mh_last_logits,
                jnp.asarray(np.asarray(cmd["rows"], np.uint32)),
                cmd["want_lp"],
            )
        elif op == "sp_prefill":
            from dynamo_tpu.models import llama
            from dynamo_tpu.ops.ring_attention import sp_shard

            toks = jnp.asarray(np.asarray(cmd["tokens"], np.int32))
            kv, logits = llama.sp_prefill(
                eng.config, eng.params, sp_shard(toks, eng.mesh),
                jnp.int32(cmd["n"]), eng.mesh,
            )
            eng.ctx = llama.write_ctx_span(
                eng.ctx, jnp.int32(cmd["slot"]), kv
            )
            eng._mh_last_logits = logits
        elif op == "load_ctx":
            from dynamo_tpu.models import llama

            eng.ctx = llama.load_ctx_pages(
                eng.ctx, eng.cache, jnp.int32(cmd["slot"]),
                jnp.asarray(np.asarray(cmd["pages"], np.int32)),
            )
        elif op == "seal":
            from dynamo_tpu.models import llama

            eng.cache = llama.seal_blocks(
                eng.cache, eng.ctx,
                jnp.asarray(np.asarray(cmd["slots"], np.int32)),
                jnp.asarray(np.asarray(cmd["starts"], np.int32)),
                jnp.asarray(np.asarray(cmd["pages"], np.int32)),
                page_size=eng.ecfg.page_size,
            )
        else:
            raise RuntimeError(f"unknown multihost command {op!r}")


async def stop_followers(kv: Any, namespace: str, engine_id: str,
                         run_id: str, n_followers: int, seq: int) -> None:
    raw = json.dumps({"seq": seq + 1, "op": "stop"})
    for h in range(n_followers):
        await kv.qpush(cmd_queue(namespace, engine_id, run_id, h + 1), raw)
