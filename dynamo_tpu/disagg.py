"""Disaggregated prefill/decode — the framework's defining feature.

Reference flow (disagg_router.rs:25-120, examples/llm/components/
prefill_worker.py:157-211, utils/prefill_queue.py:27-49,
docs/architecture/disagg_serving.md:74): a decode worker receiving a
request decides — against a store-watched threshold and the global prefill
queue depth — whether to prefill locally or enqueue a RemotePrefillRequest;
a dedicated prefill worker dequeues it, runs the prefill forward pass, and
writes the KV blocks directly into the decode worker's pre-allocated
blocks; decode then continues from local KV.

TPU redesign: the KV handoff rides the block-transfer plane
(kv_transfer.py — host-staged pages over TCP, ICI-local inside a mesh) and
lands in the decode engine's *prefix cache*: the transferred blocks are
committed under their chained token-block hashes, so the decode engine's
ordinary admission path (`_try_prefill` prefix match) picks them up and
computes only the sub-page tail. That keeps the engine loop disagg-unaware
— remote prefill is a cache warmer with completion semantics — and
degrades gracefully: on any failure/timeout the request simply prefills
locally.

The prefill queue and done-notifications use the store's durable FIFO
queue ops (JetStream work-queue parity).
"""
from __future__ import annotations

import asyncio
import json
import logging
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Any, AsyncIterator, Optional

from dynamo_tpu.kv_transfer import (
    PageStreamWriter,
    get_descriptor,
    write_remote_pages,
)
from dynamo_tpu.kv_transfer_metrics import KV_TRANSFER
from dynamo_tpu.protocols.common import LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.telemetry import timeline as tl
from dynamo_tpu.runtime.client import KvClient
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.tokens import TokenBlockSequence

log = logging.getLogger(__name__)


def disagg_conf_key(namespace: str) -> str:
    return f"dynamo://{namespace}/_disagg/conf"


def prefill_queue_name(namespace: str) -> str:
    return f"{namespace}.prefill"


def prefill_done_queue(namespace: str, request_id: str) -> str:
    return f"{namespace}.prefill_done.{request_id}"


@dataclass
class DisaggConfig:
    """Store-watched disagg thresholds (DisaggRouterConf,
    disagg_router.rs:25-35)."""

    max_local_prefill_length: int = 512
    max_prefill_queue_size: int = 16

    def to_json(self) -> str:
        return json.dumps(asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "DisaggConfig":
        return cls(**json.loads(s))


async def set_disagg_config(
    kv: KvClient, namespace: str, conf: DisaggConfig
) -> None:
    await kv.put(disagg_conf_key(namespace), conf.to_json())


class DisaggConfigWatcher:
    """Live view of the disagg config (etcd-watched conf,
    disagg_router.rs:38-120). Missing key -> defaults."""

    def __init__(self, kv: KvClient, namespace: str,
                 default: Optional[DisaggConfig] = None):
        self.kv = kv
        self.namespace = namespace
        self.current = default or DisaggConfig()
        self._task: Optional[asyncio.Task] = None

    async def start(self) -> "DisaggConfigWatcher":
        watch = await self.kv.watch_prefix(disagg_conf_key(self.namespace))
        for _, v, _ in watch.initial:
            self._apply(v)
        self._task = asyncio.get_running_loop().create_task(self._follow(watch))
        return self

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None

    async def _follow(self, watch) -> None:
        async for ev in watch:
            if ev.get("event") == "put":
                self._apply(ev.get("value"))

    def _apply(self, value: Optional[str]) -> None:
        if not value:
            return
        try:
            self.current = DisaggConfig.from_json(value)
            log.info("disagg config updated: %s", self.current)
        except (ValueError, TypeError):
            log.warning("bad disagg config value ignored: %r", value)


@dataclass
class RemotePrefillRequest:
    """One prefill job on the queue (RemotePrefillRequest equivalent,
    worker.py:187-196): which tokens, and which of the decode worker's
    pages to fill (block m..n of the prompt's chained blocks)."""

    request_id: str
    token_ids: list[int]
    salt: str                      # block-hash salt (= model name)
    dst_worker_id: str             # blockset descriptor key on the store
    dst_pages: list[int]           # decode-side pre-allocated page ids
    first_block: int               # transfer covers blocks [first, first+len)
    done_queue: str
    # unix time after which the decode side has given up (local fallback):
    # workers drop expired jobs instead of wasting a prefill + leaking a
    # done-queue entry nobody will pop. 0 = never expires.
    expires_at: float = 0.0

    def to_json(self) -> str:
        return json.dumps(asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "RemotePrefillRequest":
        return cls(**json.loads(s))


# ---------------------------------------------------------------------------
# Prefill worker


class PrefillWorker:
    """Consumes the prefill queue: prefill locally, STREAM KV pages into
    the decode worker's pool chunk by chunk while the prefill forward is
    still computing, notify on the final frame (prefill_worker.py:157-211
    + the DistServe/Mooncake chunk-pipelined KV movement).

    The engine commits complete prefix blocks incrementally per prefill
    chunk (TpuEngine._seal_prefilled); this worker subscribes to the
    engine's COMMIT EVENT (TpuEngine.subscribe_commits — fired when a
    seal batch's pool copy is dispatched) and exports+ships each new run
    as its own stream frame — so remote-prefill TTFT approaches
    max(prefill, transfer) instead of prefill + transfer, and host
    staging is O(chunk). Engines without the event (mocks) fall back to
    the legacy fixed-cadence committed-prefix poll; either way
    ``commit_wakeups``/``timeout_wakeups``/``poll_wakeups_saved`` count
    how many poll-cadence wakeups the event plane avoided. With
    ``kv_transfer_chunk_pages == 0`` on the engine config, the legacy
    monolithic gather -> one-blob write path is used instead."""

    def __init__(
        self,
        rt: DistributedRuntime,
        engine: Any,                 # TpuEngine (needs allocator+export_pages)
        namespace: str = "dynamo",
        poll_timeout_s: float = 1.0,
        stream_poll_s: float = 0.002,
    ):
        self.rt = rt
        self.engine = engine
        self.namespace = namespace
        self.poll_timeout_s = poll_timeout_s
        # cadence of the committed-prefix poll while prefill runs
        # (fallback when the engine exposes no commit event; also the
        # unit the saved-wakeup accounting is expressed in)
        self.stream_poll_s = stream_poll_s
        self.jobs_handled = 0
        self.jobs_failed = 0
        self.jobs_expired = 0
        # commit-event accounting: wakeups driven by the engine's seal
        # event vs safety-timeout wakeups, and how many fixed-cadence
        # poll wakeups the event subscription avoided
        self.commit_wakeups = 0
        self.timeout_wakeups = 0
        self.poll_wakeups_saved = 0
        self._commit_evt: Optional[asyncio.Event] = None
        self._commit_cb: Optional[Any] = None
        # chunk-pipeline stats (tests read these):
        # transfer seconds spent while the prefill forward was STILL
        # computing count as hidden — overlap_ratio = hidden / total
        self.chunks_streamed = 0
        self.transfer_seconds_total = 0.0
        self.transfer_seconds_hidden = 0.0
        # cross-host clock-skew grace before declaring a job expired
        self.expiry_skew_s = 5.0
        self._task: Optional[asyncio.Task] = None
        self._stopping = False

    @property
    def transfer_overlap_ratio(self) -> Optional[float]:
        if self.transfer_seconds_total <= 0:
            return None
        return self.transfer_seconds_hidden / self.transfer_seconds_total

    async def start(self) -> "PrefillWorker":
        start = getattr(self.engine, "start", None)
        if start is not None:
            start()
        subscribe = getattr(self.engine, "subscribe_commits", None)
        if subscribe is not None:
            # engine-side commit event: the seal flush wakes us exactly
            # when the committed prefix grew (thread -> loop handoff)
            loop = asyncio.get_running_loop()
            evt = asyncio.Event()
            self._commit_evt = evt

            def _on_commit() -> None:
                loop.call_soon_threadsafe(evt.set)

            self._commit_cb = _on_commit
            subscribe(_on_commit)
        self._task = asyncio.get_running_loop().create_task(self._loop())
        return self

    async def stop(self) -> None:
        self._stopping = True
        if self._commit_cb is not None:
            unsub = getattr(self.engine, "unsubscribe_commits", None)
            if unsub is not None:
                unsub(self._commit_cb)
            self._commit_cb = None
        if self._task is not None:
            self._task.cancel()
            self._task = None

    async def _wait_progress(self, gen_task, pending_task) -> None:
        """Park until the committed prefix may have grown: the engine's
        commit event when subscribed (plus the prefill/export tasks and
        a safety timeout — a commit fired between waits stays latched in
        the Event), else the legacy fixed-cadence sleep. Counts how many
        fixed-cadence wakeups the event plane saved."""
        if self._commit_evt is None:
            await asyncio.sleep(self.stream_poll_s)
            return
        t0 = time.monotonic()
        evt_task = asyncio.ensure_future(self._commit_evt.wait())
        wait_set = {evt_task}
        for t in (gen_task, pending_task):
            if t is not None and not t.done():
                wait_set.add(t)
        # The safety timeout is a FALLBACK for a commit notification
        # lost between waits, not the expected wake path — but when the
        # engine batches several blocks into one seal the event can
        # legitimately lag a full fused round, and at the old
        # max(25x, 50 ms) every missed edge stalled the export stream
        # long enough to lose what chunked streaming hides. 5x the poll
        # cadence floors at
        # 10 ms: late commits still coalesce, a lost edge costs at most
        # one round-ish of extra latency.
        done, _ = await asyncio.wait(
            wait_set, timeout=max(self.stream_poll_s * 5, 0.01),
            return_when=asyncio.FIRST_COMPLETED,
        )
        if evt_task in done:
            self.commit_wakeups += 1
            self._commit_evt.clear()
            wake = "commit"
        else:
            # leave the latch alone: a commit that fired while we woke
            # for a task completion must wake the NEXT wait immediately
            evt_task.cancel()
            wake = "task"
            if not done:
                self.timeout_wakeups += 1
                wake = "timeout"
        waited = time.monotonic() - t0
        tl.STREAM_EVENTS.record(tl.COMMIT_WAKEUP, waited, wake=wake)
        self.poll_wakeups_saved += max(
            0, int(waited / self.stream_poll_s) - 1
        )

    async def _loop(self) -> None:
        queue = prefill_queue_name(self.namespace)
        while not self._stopping:
            try:
                raw = await self.rt.kv.qpop(queue, timeout_s=self.poll_timeout_s)
            except (ConnectionError, OSError):
                await asyncio.sleep(0.5)
                continue
            if raw is None:
                continue
            try:
                job = RemotePrefillRequest.from_json(raw)
            except (ValueError, TypeError):
                log.warning("malformed prefill job dropped: %.200r", raw)
                continue
            if job.expires_at and time.time() > job.expires_at + self.expiry_skew_s:
                # the decode side already fell back locally: skip the
                # wasted prefill and don't push to a done queue nobody pops
                self.jobs_expired += 1
                log.info("dropping expired prefill job %s", job.request_id)
                continue
            try:
                await self._handle(job)
                self.jobs_handled += 1
            except Exception as e:  # noqa: BLE001 — report, keep consuming
                self.jobs_failed += 1
                log.exception("prefill job %s failed", job.request_id)
                try:
                    await self.rt.kv.qpush(job.done_queue, json.dumps(
                        {"ok": False, "error": str(e)}
                    ))
                except (ConnectionError, OSError):
                    pass

    async def _handle(self, job: RemotePrefillRequest) -> None:
        t0 = time.monotonic()
        ps = self.engine.ecfg.page_size
        n_blocks = job.first_block + len(job.dst_pages)
        seq = TokenBlockSequence.from_tokens(job.token_ids, ps, salt=job.salt)
        hashes = seq.block_hashes()[:n_blocks]
        chunk_pages = int(getattr(
            self.engine.ecfg, "kv_transfer_chunk_pages", 0
        ))

        # the prefill forward pass through the engine (one sampled token,
        # discarded — the decode side samples its own first token after
        # its tail prefill); the engine commits each chunk's complete
        # blocks into this worker's prefix cache AS PREFILL ADVANCES
        req = PreprocessedRequest(
            token_ids=list(job.token_ids),
            model=job.salt,
        )
        req.stop_conditions.max_tokens = 1
        req.stop_conditions.ignore_eos = True

        async def run_prefill() -> None:
            async for _ in self.engine.generate(req):
                pass

        # descriptor BEFORE prefill: the stream starts mid-compute
        desc = await get_descriptor(self.rt.kv, self.namespace,
                                    job.dst_worker_id)
        if desc is None:
            raise RuntimeError(
                f"no blockset descriptor for {job.dst_worker_id}"
            )

        chunk_spans: list[dict] = []
        overlap: Optional[float] = None
        if chunk_pages <= 0:
            n_send = await self._push_monolithic(job, hashes, run_prefill,
                                                 desc)
        else:
            n_send, chunk_spans, overlap = await self._push_stream(
                job, hashes, run_prefill, desc, chunk_pages
            )
        from dynamo_tpu.telemetry.trace import span_now

        # the prefill worker's own span (per-chunk children for the
        # streamed path), folded into the decode side's trace payload
        # (DisaggDecodeEngine.generate)
        span = span_now(
            "remote_prefill", t0,
            tokens=len(job.token_ids), blocks=n_send,
            chunks=max(len(chunk_spans), 1),
        ).to_dict()
        if chunk_spans:
            span["children"] = chunk_spans
        msg = {
            "ok": True,
            "blocks": n_send,
            "chunks": max(len(chunk_spans), 1),
            "prefill_ms": (time.monotonic() - t0) * 1e3,
            "span": span,
        }
        if overlap is not None:
            msg["overlap_ratio"] = round(overlap, 4)
        await self.rt.kv.qpush(job.done_queue, json.dumps(msg))
        log.info(
            "remote prefill %s: %d tokens, %d blocks (%d chunks) -> %s "
            "in %.1f ms (overlap %s)",
            job.request_id, len(job.token_ids), n_send,
            max(len(chunk_spans), 1), job.dst_worker_id,
            (time.monotonic() - t0) * 1e3,
            f"{overlap:.2f}" if overlap is not None else "n/a",
        )

    async def _push_monolithic(
        self, job: RemotePrefillRequest, hashes: list[int],
        run_prefill, desc,
    ) -> int:
        """Legacy path (kv_transfer_chunk_pages == 0): full prefill, one
        gather, one blob on the wire."""
        await run_prefill()
        src_pages = self.engine.allocator.match_prefix(hashes)
        try:
            # under cache pressure some blocks may already be evicted; send
            # the contiguous run we still have from first_block on
            have = src_pages[job.first_block:]
            n_send = min(len(have), len(job.dst_pages))
            if n_send == 0:
                raise RuntimeError("prefilled blocks evicted before export")
            data = await asyncio.to_thread(
                self.engine.export_pages, have[:n_send]
            )
        finally:
            self.engine.allocator.free(src_pages)
        await write_remote_pages(
            desc.host, desc.port, job.dst_pages[:n_send], data,
            job_id=job.request_id,
        )
        return n_send

    async def _push_stream(
        self, job: RemotePrefillRequest, hashes: list[int],
        run_prefill, desc, chunk_pages: int,
    ) -> tuple[int, list[dict], Optional[float]]:
        """Chunk-pipelined push: poll the committed prefix while the
        prefill forward runs; export+ship every newly complete run of
        ``chunk_pages`` blocks as one stream frame (sub-chunk remainders
        flush once prefill finishes). The decode side scatters each frame
        on arrival and its admission fires on the eof ack — transfer
        rides BEHIND compute instead of after it."""
        from dynamo_tpu.resilience.chaos import CHAOS
        from dynamo_tpu.telemetry.trace import span_now

        first = job.first_block
        n_blocks = len(hashes)
        alloc = self.engine.allocator
        gen_task = asyncio.get_running_loop().create_task(run_prefill())
        writer = PageStreamWriter(desc.host, desc.port,
                                  job_id=job.request_id)
        sent = first                   # blocks written to the wire
        chunk_spans: list[dict] = []
        xfer_total = 0.0
        xfer_hidden = 0.0
        evicted = False
        # sender-side double buffer: one export dispatched beyond the
        # chunk being written, so the gather/D2H of run i+1 overlaps run
        # i's wire drain instead of queueing behind it — without it the
        # stream falls one export+drain behind prefill per chunk and the
        # tail ships after compute ends. (lo, hi, t_start, task)
        pending: Optional[tuple] = None
        t_pf_end: Optional[float] = None  # first observation of done
        try:
            while True:
                prefill_done = gen_task.done()
                if prefill_done:
                    if t_pf_end is None:
                        t_pf_end = time.monotonic()
                    await gen_task  # surface prefill failures
                avail = min(alloc.cached_prefix_len(hashes), n_blocks)
                exported_to = pending[1] if pending is not None else sent
                if (pending is None and not evicted
                        and (avail - exported_to >= chunk_pages
                             or (prefill_done and avail > exported_to))):
                    hi = min(exported_to + chunk_pages, avail)
                    pending = (exported_to, hi, time.monotonic(),
                               asyncio.ensure_future(self._export_run(
                                   hashes, exported_to, hi)))
                    continue
                if pending is not None and pending[3].done():
                    lo, hi, tc, task = pending
                    pending = None
                    data = await task
                    if data is None:
                        evicted = True  # pressure-evicted mid-stream
                        continue
                    # dispatch the NEXT export before awaiting this
                    # chunk's socket drain — that order is the double
                    # buffer (gather/D2H of run i+1 under run i's wire
                    # time); dispatching after the drain would serialize
                    # export and wire again
                    avail = min(alloc.cached_prefix_len(hashes), n_blocks)
                    if (avail - hi >= chunk_pages
                            or (gen_task.done() and avail > hi)):
                        hi2 = min(hi + chunk_pages, avail)
                        pending = (hi, hi2, time.monotonic(),
                                   asyncio.ensure_future(self._export_run(
                                       hashes, hi, hi2)))
                    await writer.write_chunk(
                        job.dst_pages[lo - first: hi - first], data
                    )
                    now = time.monotonic()
                    dur = now - tc
                    xfer_total += dur
                    if t_pf_end is None:
                        # the whole hop ran behind prefill compute
                        xfer_hidden += dur
                    else:
                        # straddling hop: credit the portion that ran
                        # while prefill was still computing
                        xfer_hidden += min(dur, max(0.0, t_pf_end - tc))
                    chunk_spans.append(span_now(
                        "kv_chunk", tc, blocks=hi - lo, first_block=lo,
                    ).to_dict())
                    sent = hi
                    # mid-stream chaos (stall_stream): wedged-link shape —
                    # the decode side's timeout must fire and fall back
                    await CHAOS.maybe_stall(
                        "stall_stream", writer.chunks_sent)
                    continue
                if pending is None and (evicted
                                        or (prefill_done and avail <= sent)):
                    break
                await self._wait_progress(
                    gen_task, pending[3] if pending is not None else None
                )
            if sent <= first:
                raise RuntimeError("prefilled blocks evicted before export")
            # wire-time accounting fix: write_chunk's drain() returns
            # when the KERNEL buffers the bytes, not when the peer has
            # them — the tail of the stream (several chunks of socket
            # buffer on a slow link) used to drain after prefill ended
            # without being counted at all, flattering the overlap
            # ratio. The eof ack arrives only after the receiver has
            # read AND scattered every chunk, so the commit wait IS the
            # unmeasured wire tail; count it (hidden only for whatever
            # part ran before prefill finished — normally none).
            t_commit = time.monotonic()
            await writer.commit()
            tail = time.monotonic() - t_commit
            xfer_total += tail
            if t_pf_end is None:
                xfer_hidden += tail
            else:
                xfer_hidden += min(tail, max(0.0, t_pf_end - t_commit))
        finally:
            if pending is not None:
                pending[3].cancel()
            await writer.close()
            if not gen_task.done():
                gen_task.cancel()
            elif not gen_task.cancelled():
                gen_task.exception()  # retrieve, never leave it unread
        self.chunks_streamed += len(chunk_spans)
        self.transfer_seconds_total += xfer_total
        self.transfer_seconds_hidden += xfer_hidden
        overlap = xfer_hidden / xfer_total if xfer_total > 0 else None
        return sent - first, chunk_spans, overlap

    async def _export_run(
        self, hashes: list[int], lo: int, hi: int
    ):
        """Pin + gather blocks [lo, hi) of the chained run; None when the
        run is no longer fully committed (evicted under pressure).

        The gather goes through export_pages_stream, not export_pages:
        the engine loop dispatches the gather with an ASYNC D2H copy and
        keeps running prefill rounds while the copy completes (this
        worker thread blocks on the chunk queue, which is fine) — a
        synchronous export would stall the forward pass once per chunk
        and eat the very overlap the stream exists to create."""

        def pin_and_export():
            pages = self.engine.allocator.match_prefix(hashes[:hi])
            try:
                if len(pages) < hi:
                    return None
                return next(iter(self.engine.export_pages_stream(
                    pages[lo:hi], chunk_pages=hi - lo,
                )))
            finally:
                self.engine.allocator.free(pages)

        return await asyncio.to_thread(pin_and_export)


# ---------------------------------------------------------------------------
# Decode-side wrapper


class DisaggDecodeEngine:
    """AsyncEngine wrapper adding the conditional-disagg decision to a
    TpuEngine (worker.py:199-248 VllmWorker.generate decision point).

    remote iff  (prompt_len − cached_prefix_tokens) > max_local_prefill_length
            and prefill_queue_len < max_prefill_queue_size
    (multimodal/components/disagg_router.py:48-66). On the remote path the
    transferred blocks enter the local prefix cache before admission, so the
    wrapped engine computes only the sub-page tail."""

    def __init__(
        self,
        engine: Any,
        rt: DistributedRuntime,
        namespace: str = "dynamo",
        worker_id: str = "",
        conf: Optional[DisaggConfigWatcher] = None,
        prefill_timeout_s: float = 60.0,
    ):
        self.engine = engine
        self.rt = rt
        self.namespace = namespace
        self.worker_id = worker_id
        self.conf = conf
        self.prefill_timeout_s = prefill_timeout_s
        self._draining = False
        # live remote-prefill jobs: a write for a job not in here is
        # REJECTED — protects against a stale queued job scribbling over
        # pages that were freed on fallback and reallocated to another
        # request. The lock guards only set membership (never held across
        # device I/O); a fallback racing an in-flight write defers the page
        # free to the writer.
        self._jobs_lock = threading.Lock()
        self._pending_jobs: set[str] = set()
        self._in_write: set[str] = set()
        self._deferred_free: dict[str, list[int]] = {}
        # counters (exposed via metrics/tests); fallbacks also feed the
        # dynamo_disagg_fallback_total series (kv_transfer_metrics)
        self.remote_prefills = 0
        self.local_prefills = 0
        self.remote_fallbacks = 0
        self.last_transfer_chunks = 0
        self.last_overlap_ratio: Optional[float] = None
        # prefill-worker spans shipped back on the done queue, keyed by
        # request id until generate() folds them into the trace payload
        self._remote_spans: dict[str, dict] = {}

    # engine delegation so register_llm/serve_engine treat us as the engine
    @property
    def allocator(self):
        return self.engine.allocator

    @property
    def flight(self):
        """Flight recorder passthrough: /debug/flight must keep working
        when the system server holds this wrapper, not the TpuEngine."""
        return getattr(self.engine, "flight", None)

    @property
    def on_metrics(self):
        return self.engine.on_metrics

    @on_metrics.setter
    def on_metrics(self, sink):
        self.engine.on_metrics = sink

    def start(self) -> None:
        start = getattr(self.engine, "start", None)
        if start is not None:
            start()

    # graceful-drain passthrough (resilience/drain.py contract): the
    # DrainController holds this wrapper when the worker runs disagg.
    # The wrapper keeps its own flag so generate() rejects BEFORE the
    # remote-prefill decision — otherwise a draining worker would pay a
    # full cross-worker KV transfer for a request it then refuses.
    def begin_drain(self) -> None:
        self._draining = True
        begin = getattr(self.engine, "begin_drain", None)
        if begin is not None:
            begin()

    def drained(self) -> bool:
        fn = getattr(self.engine, "drained", None)
        return bool(fn()) if fn is not None else True

    async def stop(self) -> None:
        await self.engine.stop()

    def metrics(self):
        return self.engine.metrics()

    def guarded_import(self, pages, data, job_id=None) -> None:
        """Transfer-server write hook: scatter only while the job is still
        pending (write_fn contract in kv_transfer.py). The scatter runs
        OUTSIDE the jobs lock — holding it across device I/O would stall
        the event loop's own lock acquisitions for the whole transfer."""
        if job_id is None:
            self.engine.import_pages(pages, data)
            return
        with self._jobs_lock:
            if job_id not in self._pending_jobs:
                raise RuntimeError(f"job {job_id} cancelled; write rejected")
            self._in_write.add(job_id)
        try:
            self.engine.import_pages(pages, data)
        finally:
            with self._jobs_lock:
                self._in_write.discard(job_id)
                late_free = self._deferred_free.pop(job_id, None)
            if late_free is not None:
                # fallback cancelled mid-write: the write landed in pages
                # still held for this job; release them now (uncommitted ->
                # straight back to the free list)
                self.engine.allocator.free(late_free)

    async def generate(
        self, request: PreprocessedRequest
    ) -> AsyncIterator[LLMEngineOutput]:
        from dynamo_tpu.telemetry.trace import span_now

        if self._draining:
            from dynamo_tpu.resilience.drain import WorkerDrainingError

            raise WorkerDrainingError(
                "worker draining: not admitting new requests"
            )
        t0 = time.monotonic()
        spans: list = []
        if await self._maybe_remote_prefill(request):
            self.remote_prefills += 1
            # trace the remote KV transfer: injected into the finishing
            # output's span payload so the frontend's span tree carries
            # it alongside the engine's queue/prefill spans. The prefill
            # worker's own remote_prefill span (shipped back on the done
            # queue) rides along, so the remote hop is visible
            # end-to-end in /debug/trace/{request_id}.
            spans.append(span_now("disagg_kv_transfer", t0).to_dict())
            remote_span = self._remote_spans.pop(request.request_id, None)
            if remote_span:
                spans.append(remote_span)
        else:
            self.local_prefills += 1
            self._remote_spans.pop(request.request_id, None)
        async for out in self.engine.generate(request):
            if spans and out.finish_reason is not None:
                tr = out.annotations.setdefault("trace", {})
                tr["spans"] = spans + tr.get("spans", [])
            yield out

    async def _should_remote(self, request: PreprocessedRequest,
                             n_cached_blocks: int) -> bool:
        conf = self.conf.current if self.conf else DisaggConfig()
        ps = self.engine.ecfg.page_size
        effective = len(request.token_ids) - n_cached_blocks * ps
        if effective <= conf.max_local_prefill_length:
            return False
        try:
            qlen = await self.rt.kv.qlen(prefill_queue_name(self.namespace))
        except (ConnectionError, OSError):
            return False
        return qlen < conf.max_prefill_queue_size

    async def _maybe_remote_prefill(self, request: PreprocessedRequest) -> bool:
        """Try the remote path; True if the prefix cache was warmed
        remotely. Any failure falls back to local prefill."""
        alloc = self.engine.allocator
        ps = self.engine.ecfg.page_size
        tokens = request.token_ids
        n_blocks = max(0, (len(tokens) - 1) // ps)
        if n_blocks == 0:
            return False
        seq = TokenBlockSequence.from_tokens(tokens, ps, salt=request.model)
        hashes = seq.block_hashes()[:n_blocks]

        # blocks already cached locally need no transfer (stat-neutral peek
        # — the engine's admission match does the counted lookup)
        m = alloc.cached_prefix_len(hashes)
        if not await self._should_remote(request, m):
            return False
        if m >= n_blocks:
            return False

        dst = alloc.allocate(n_blocks - m)
        if dst is None:
            return False  # no room: let admission/preemption deal with it
        rid = request.request_id
        done_q = prefill_done_queue(self.namespace, rid)
        job = RemotePrefillRequest(
            request_id=rid,
            token_ids=list(tokens),
            salt=request.model,
            dst_worker_id=self.worker_id,
            dst_pages=dst,
            first_block=m,
            done_queue=done_q,
            expires_at=time.time() + self.prefill_timeout_s,
        )
        with self._jobs_lock:
            self._pending_jobs.add(rid)
        settled = False  # success path freed/committed dst itself
        try:
            await self.rt.kv.qpush(prefill_queue_name(self.namespace),
                                   job.to_json())
            raw = await self.rt.kv.qpop(
                done_q, timeout_s=self.prefill_timeout_s
            )
            resp = json.loads(raw) if raw else None
            if not resp or not resp.get("ok"):
                raise RuntimeError(
                    (resp or {}).get("error", "remote prefill timed out")
                )
            n_got = int(resp.get("blocks", 0))
            self.last_transfer_chunks = int(resp.get("chunks", 1))
            self.last_overlap_ratio = resp.get("overlap_ratio")
            if resp.get("span"):
                self._remote_spans[rid] = resp["span"]
            with self._jobs_lock:
                self._pending_jobs.discard(rid)
            # commit the transferred blocks under their chained hashes; the
            # engine's admission prefix-match picks them up
            committed = []
            for pg, blk in zip(dst[:n_got], seq.blocks[m:m + n_got]):
                if alloc.commit(pg, blk.block_hash, blk.parent_hash):
                    committed.append(pg)
            alloc.free(dst)  # committed pages park in LRU; rest return free
            settled = True
            return bool(committed)
        except Exception:  # noqa: BLE001 — disagg is best-effort
            self.remote_fallbacks += 1
            # scraped as dynamo_disagg_fallback_total on every surface
            KV_TRANSFER.inc("dynamo_disagg_fallback_total")
            log.exception("remote prefill failed for %s; local fallback", rid)
            return False
        finally:
            if not settled:
                # runs for BOTH the except path and CancelledError (client
                # dropped while awaiting the done queue): cancel the job and
                # release its pages exactly once. If a guarded write is in
                # flight, the writer frees them after its scatter.
                with self._jobs_lock:
                    self._pending_jobs.discard(rid)
                    if rid in self._in_write:
                        self._deferred_free[rid] = dst
                        dst = None
                if dst is not None:
                    alloc.free(dst)
