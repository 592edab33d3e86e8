"""KV block transfer plane — the TPU-native NIXL equivalent.

Reference shape (lib/llm/src/block_manager.rs:54,120-130
``SerializedNixlBlockSet``, block/nixl.rs RemoteBlock,
examples/llm/utils/nixl.py:116): workers export a *blockset descriptor*
(who am I, where is my data plane, what layout do my blocks have) through
the control-plane store, and peers move whole KV pages directly
worker-to-worker with async one-sided reads/writes.

TPU redesign: there is no peer RDMA between separate engine processes, so
the data plane is **host-staged**: pages are gathered on device ([2, L,
kvh, n, ps, hd] in one fused jit), DMA'd to host, streamed over TCP as
two-part frames (JSON header + raw bytes), and scattered back into the
receiving pool in donated jits. Within a process/mesh the same
gather/scatter jits move pages over ICI without touching the host. The
wire protocol and descriptor flow are transport-independent, so a future
DCN/ICI fast path slots in behind the same API.

Bulk moves are **chunk-pipelined** (DistServe/Mooncake-style): instead of
one monolithic blob, a move is a multi-frame sequence of page chunks over
the same two-part codec — the sender exports+ships chunk i while chunk
i+1 is still being gathered (or, for disagg remote prefill, while the
prefill forward is still computing later chunks), and the receiver
scatters each chunk on arrival. Peak host staging per hop drops from
O(transfer) to O(chunk); the receiver acks once, at eof.

Ops:
  {"op": "write_pages", "pages": [...], "shape": [...], "dtype": "..."} + payload
      -> {"ok": true}
  {"op": "write_pages", ..., "stream": true, "seq": i} + payload
      -> (no reply per chunk; the stream is acked at eof)
  {"op": "write_pages_eof", "chunks": n}
      -> {"ok": true, "chunks": n} | {"ok": false, "error": "..."}
  {"op": "read_pages", "pages": [...]}
      -> {"ok": true, "shape": [...], "dtype": "..."} + payload
  {"op": "read_hashes", "hashes": [...], "probe": true}
      -> {"ok": true, "found": k}                       (no payload)
  {"op": "read_hashes", "hashes": [...], "chunk_pages": c}
      -> {"ok": true, "found": k, "stream": true} then k pages of
         {"seq": i, "shape": [...], "dtype": "...", "eof": bool} + payload
"""
from __future__ import annotations

import asyncio
import json
import logging
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Callable, Iterable, Optional

import ml_dtypes  # noqa: F401 — registers bfloat16 with np.dtype
import numpy as np

from dynamo_tpu.kv_integrity import (
    KV_INTEGRITY,
    KvIntegrityError,
    page_checksums,
    verify_wire_payload,
)
from dynamo_tpu.kv_quant import (
    QuantizedPages,
    attach_wire_scales,
    from_wire,
)
from dynamo_tpu.kv_transfer_metrics import KV_TRANSFER
from dynamo_tpu.runtime.client import KvClient
from dynamo_tpu.telemetry import timeline as tl
from dynamo_tpu.runtime.protocol import (
    encode_frame2,
    encode_frame2_header,
    read_frame2,
)

log = logging.getLogger(__name__)


def _array_header(data) -> tuple[np.ndarray, dict[str, Any]]:
    """(payload array, geometry header fields) for a dense array OR a
    kv_quant.QuantizedPages bundle — int8 payloads ship their per-block
    scale sidecar in the JSON header (it is ~1/(2*kvh*ps*hd) of the
    payload), so a quantized move is ~half a bf16 move's wire bytes.

    KV page frames (the 6-dim [2, L, kvh, n, ps, hd] geometry) also get
    a per-page ``kv_crc`` content-checksum list, computed over the
    pre-serialization value (bundle incl. scales) so the receiver can
    verify before scattering."""
    fields: dict[str, Any] = {}
    if isinstance(data, QuantizedPages):
        attach_wire_scales(fields, data)
        if data.data.ndim == 6:
            fields["kv_crc"] = page_checksums(data)
        data = data.data
    elif getattr(data, "ndim", 0) == 6:
        fields["kv_crc"] = page_checksums(data)
    fields["shape"] = list(data.shape)
    fields["dtype"] = data.dtype.name
    return data, fields


def _decode_payload(header: dict[str, Any], payload: bytes,
                    copy: bool = False, verify: bool = False):
    """Inverse of _array_header: the dense array, re-bundled with its
    scales when the frame carried a quantized payload. ``copy`` detaches
    the result from the frame buffer (writable, own lifetime).

    The declared geometry is validated against the received byte count
    BEFORE np.frombuffer — a malformed header becomes a typed
    BlockTransferError the server answers in-band, not a ValueError that
    kills the connection. ``verify`` additionally checks the payload
    against the frame's ``kv_crc`` list (KvIntegrityError on mismatch)."""
    try:
        dt = np.dtype(str(header["dtype"]))
        shape = tuple(int(x) for x in header["shape"])
    except (KeyError, TypeError, ValueError) as e:
        raise BlockTransferError(f"malformed frame geometry: {e}") from e
    if any(d < 0 for d in shape):
        raise BlockTransferError(
            f"malformed frame geometry: negative dim in {shape}"
        )
    expect = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
    if expect != len(payload):
        raise BlockTransferError(
            f"frame geometry {list(shape)}/{dt.name} declares {expect} "
            f"payload bytes, got {len(payload)}"
        )
    arr = np.frombuffer(payload, dtype=dt).reshape(shape)
    if copy:
        arr = arr.copy()
    try:
        out = from_wire(arr, header)
    except (TypeError, ValueError) as e:
        raise BlockTransferError(f"malformed scale sidecar: {e}") from e
    if verify:
        verify_wire_payload(header, out, context="kv-transfer frame")
    return out


def _err_kind(e: BaseException) -> str:
    return "integrity" if isinstance(e, KvIntegrityError) else "frame"


def _raise_nack(header: dict[str, Any], default: str) -> None:
    """Re-raise a receiver nack client-side with its type preserved:
    ``kind: integrity`` nacks become the retriable KvIntegrityError."""
    msg = header.get("error", default)
    if header.get("kind") == "integrity":
        raise KvIntegrityError(msg)
    raise BlockTransferError(msg)


def _write_array_frame(
    writer: asyncio.StreamWriter, header: dict[str, Any], data
) -> None:
    """Write header + array payload without copying the array: the length
    prefix and header go as one small bytes, the payload as a zero-copy
    byte view (multi-GiB transfers would otherwise pay an extra memcpy and
    2x peak host memory per hop). ``data`` may be a QuantizedPages
    bundle — its scales join the header, its int8 pages the payload."""
    data, fields = _array_header(data)
    header = {**header, **fields}
    data = np.ascontiguousarray(data)
    # chaos corrupt_frame: wire/DMA corruption on a COPY, after the crc
    # was stamped — the receiver's verify must catch it; the sender's
    # pool (which `data` may alias zero-copy) stays clean
    from dynamo_tpu.resilience.chaos import CHAOS

    data = CHAOS.maybe_corrupt_frame(data)
    payload = data.view(np.uint8).reshape(-1)
    writer.write(encode_frame2_header(header, payload.nbytes))
    writer.write(memoryview(payload))

KV_META_PREFIX = "_kvmeta/"


def kvmeta_key(namespace: str, worker_id: str) -> str:
    return f"dynamo://{namespace}/{KV_META_PREFIX}{worker_id}"


@dataclass
class KvCacheLayout:
    """Block geometry; both sides must agree before pages move.
    ``num_layers`` is the pool's leading axis, the model's
    ``cache_planes``: a looped stack holds a plane a (step, layer)."""

    num_layers: int
    num_kv_heads: int
    page_size: int
    head_dim: int
    dtype: str = "bfloat16"

    def page_shape(self, n_pages: int) -> tuple[int, ...]:
        # matches llama.gather_pages: [2(k/v), L, kvh, n, ps, hd]
        return (2, self.num_layers, self.num_kv_heads, n_pages,
                self.page_size, self.head_dim)


@dataclass
class BlocksetDescriptor:
    """What a worker publishes so peers can address its KV pool
    (SerializedNixlBlockSet equivalent)."""

    worker_id: str
    host: str
    port: int
    layout: KvCacheLayout

    def to_json(self) -> str:
        return json.dumps(asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "BlocksetDescriptor":
        d = json.loads(s)
        d["layout"] = KvCacheLayout(**d["layout"])
        return cls(**d)


async def publish_descriptor(
    kv: KvClient, namespace: str, desc: BlocksetDescriptor, lease: int = 0
) -> None:
    """Metadata via the store (reference: NIXL agent metadata via etcd,
    utils/nixl.py:116). Lease-bound: dies with the worker."""
    await kv.put(kvmeta_key(namespace, desc.worker_id), desc.to_json(),
                 lease=lease)


async def get_descriptor(
    kv: KvClient, namespace: str, worker_id: str
) -> Optional[BlocksetDescriptor]:
    v = await kv.get(kvmeta_key(namespace, worker_id))
    return None if v is None else BlocksetDescriptor.from_json(v)


# ---------------------------------------------------------------------------
# Data-plane server

# read_fn(page_ids) -> np.ndarray [2, L, kvh, n, ps, hd]
# write_fn(page_ids, data) -> None — or (page_ids, data, job_id) when the
# writer tags frames with a job id (disagg guarded writes: the owner
# validates the job is still live before scattering)
ReadFn = Callable[[list[int]], np.ndarray]
WriteFn = Callable[..., None]


class BlockTransferServer:
    """Serves a worker's KV pool for peer page reads/writes.

    The owner supplies read/write callables (the engine's thread-safe
    export/import hooks, or direct pool access in tests); they may block on
    device DMA, so they run in the default executor."""

    def __init__(
        self,
        read_fn: Optional[ReadFn] = None,
        write_fn: Optional[WriteFn] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        read_hashes_fn: Optional[
            Callable[[list[int]], tuple[int, Optional[np.ndarray]]]
        ] = None,
        # chunk-pipelined serving hooks (both optional; peers fall back
        # to the monolithic ops when absent):
        # count_hashes_fn(hashes) -> int — cheap committed-prefix length
        # (no gather) for the G4 probe round
        count_hashes_fn: Optional[Callable[[list[int]], int]] = None,
        # read_hashes_stream_fn(hashes, chunk_pages) -> (found, iterator
        # of host chunks) — the engine's export_hash_stream
        read_hashes_stream_fn: Optional[Callable[..., tuple[int, Any]]] = None,
    ):
        self.read_fn = read_fn
        self.write_fn = write_fn
        self.host = host
        self.port = port
        self.read_hashes_fn = read_hashes_fn
        self.count_hashes_fn = count_hashes_fn
        self.read_hashes_stream_fn = read_hashes_stream_fn
        self._server: Optional[asyncio.AbstractServer] = None

    async def start(self) -> tuple[str, int]:
        self._server = await asyncio.start_server(
            self._on_conn, self.host, self.port
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self.host, self.port

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None

    async def _on_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        loop = asyncio.get_running_loop()
        # chunk-stream state for THIS connection: scatter failures inside
        # a stream are remembered (later frames skipped) and reported once
        # in the eof ack — the sender pipelines frames without per-chunk
        # acks, so in-band per-frame errors would desync the protocol
        stream_chunks = 0
        stream_err: Optional[str] = None
        stream_err_kind: Optional[str] = None
        try:
            while True:
                header, payload = await read_frame2(reader)
                op = header.get("op")
                try:
                    if op == "write_pages":
                        if self.write_fn is None:
                            raise RuntimeError("writes not accepted")
                        pages = [int(p) for p in header["pages"]]
                        if header.get("stream"):
                            # one chunk of a pipelined stream: guarded
                            # scatter on arrival, ack deferred to eof
                            stream_chunks += 1
                            if stream_err is not None:
                                continue  # stream already dead
                            t0 = time.monotonic()
                            try:
                                # decode + integrity verify BEFORE the
                                # scatter: corrupt or malformed bytes
                                # never reach the pool
                                data = _decode_payload(
                                    header, payload, verify=True
                                )
                            except (BlockTransferError,
                                    KvIntegrityError) as e:
                                stream_err = str(e)
                                stream_err_kind = _err_kind(e)
                                KV_TRANSFER.inc(
                                    "dynamo_kv_transfer_errors_total"
                                )
                                log.warning(
                                    "chunk rejected mid-stream (job=%s "
                                    "seq=%s kind=%s): %s",
                                    header.get("job"), header.get("seq"),
                                    stream_err_kind, e,
                                )
                                continue
                            args = (pages, data)
                            if header.get("job") is not None:
                                args = (pages, data, header["job"])
                            try:
                                await loop.run_in_executor(
                                    None, self.write_fn, *args
                                )
                            except Exception as e:  # noqa: BLE001
                                stream_err = str(e)
                                stream_err_kind = "scatter"
                                KV_TRANSFER.inc(
                                    "dynamo_kv_transfer_errors_total"
                                )
                                log.warning(
                                    "chunk scatter failed mid-stream "
                                    "(job=%s seq=%s): %s",
                                    header.get("job"),
                                    header.get("seq"), e,
                                )
                            else:
                                KV_TRANSFER.inc(
                                    "dynamo_kv_transfer_rx_chunks_total"
                                )
                                KV_TRANSFER.inc(
                                    "dynamo_kv_transfer_rx_bytes_total",
                                    len(payload),
                                )
                                dt = time.monotonic() - t0
                                KV_TRANSFER.observe(
                                    "dynamo_kv_transfer_chunk_seconds",
                                    dt,
                                )
                                ev_job = header.get("job")
                                tl.STREAM_EVENTS.record(
                                    tl.FRAME_RECV, dt,
                                    seq=header.get("seq"),
                                    pages=len(pages),
                                    bytes=len(payload),
                                    **({"job": ev_job}
                                       if ev_job else {}),
                                )
                            continue  # no per-chunk reply
                        try:
                            data = _decode_payload(
                                header, payload, verify=True
                            )
                        except (BlockTransferError,
                                KvIntegrityError) as e:
                            # typed nack: the sender distinguishes a
                            # retriable integrity miss from a protocol
                            # bug, and the connection stays usable
                            KV_TRANSFER.inc(
                                "dynamo_kv_transfer_errors_total"
                            )
                            log.warning(
                                "write_pages rejected (kind=%s): %s",
                                _err_kind(e), e,
                            )
                            writer.write(encode_frame2(
                                {"ok": False, "error": str(e),
                                 "kind": _err_kind(e)}, b"",
                            ))
                            await writer.drain()
                            continue
                        args = (pages, data)
                        if header.get("job") is not None:
                            args = (pages, data, header["job"])
                        await loop.run_in_executor(
                            None, self.write_fn, *args
                        )
                        KV_TRANSFER.inc("dynamo_kv_transfer_rx_chunks_total")
                        KV_TRANSFER.inc(
                            "dynamo_kv_transfer_rx_bytes_total", len(payload)
                        )
                        writer.write(encode_frame2({"ok": True}, b""))
                    elif op == "write_pages_eof":
                        # close one pipelined stream: single ack carrying
                        # any deferred mid-stream failure (typed, so an
                        # integrity nack stays retriable end-to-end)
                        if stream_err is not None:
                            writer.write(encode_frame2(
                                {"ok": False, "error": stream_err,
                                 "kind": stream_err_kind,
                                 "chunks": stream_chunks}, b"",
                            ))
                        else:
                            writer.write(encode_frame2(
                                {"ok": True, "chunks": stream_chunks}, b"",
                            ))
                        stream_chunks, stream_err = 0, None
                        stream_err_kind = None
                    elif op == "read_pages":
                        if self.read_fn is None:
                            raise RuntimeError("reads not accepted")
                        pages = [int(p) for p in header["pages"]]
                        data = await loop.run_in_executor(
                            None, self.read_fn, pages
                        )
                        _write_array_frame(writer, {"ok": True}, data)
                    elif op == "read_hashes":
                        # G4 remote tier: resolve a chained-hash run
                        # against this worker's sealed pool and export the
                        # longest present prefix (reference
                        # block_manager.rs:69-82 remote CacheLevel)
                        hs = [int(h) for h in header["hashes"]]
                        if header.get("probe") and self.count_hashes_fn:
                            # cheap probe round: committed-prefix length
                            # only, no gather — losers of the peer race
                            # no longer export bytes nobody will use
                            found = await loop.run_in_executor(
                                None, self.count_hashes_fn, hs
                            )
                            writer.write(encode_frame2(
                                {"ok": True, "found": int(found)}, b""
                            ))
                            await writer.drain()
                            continue
                        cp = int(header.get("chunk_pages") or 0)
                        if cp > 0 and self.read_hashes_stream_fn:
                            await self._serve_hash_stream(
                                writer, loop, hs, cp
                            )
                            await writer.drain()
                            continue
                        if self.read_hashes_fn is None:
                            raise RuntimeError("hash reads not accepted")
                        found, data = await loop.run_in_executor(
                            None, self.read_hashes_fn, hs
                        )
                        if not found or data is None:
                            writer.write(encode_frame2(
                                {"ok": True, "found": 0}, b""
                            ))
                        else:
                            _write_array_frame(
                                writer,
                                {"ok": True, "found": int(found)},
                                data,
                            )
                            KV_TRANSFER.inc(
                                "dynamo_kv_transfer_tx_chunks_total")
                            KV_TRANSFER.inc(
                                "dynamo_kv_transfer_tx_bytes_total",
                                data.nbytes,
                            )
                    else:
                        raise RuntimeError(f"unknown op {op!r}")
                except Exception as e:  # noqa: BLE001 — answer in-band
                    log.exception("block transfer op %s failed", op)
                    writer.write(encode_frame2(
                        {"ok": False, "error": str(e)}, b""
                    ))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError,
                BrokenPipeError):
            pass
        except (ValueError, json.JSONDecodeError):
            # desynced/oversized framing from a buggy peer: close cleanly
            log.warning("malformed block-transfer frame; closing connection")
        finally:
            writer.close()

    async def _serve_hash_stream(
        self, writer: asyncio.StreamWriter, loop, hashes: list[int],
        chunk_pages: int,
    ) -> None:
        """Serve one chunk-pipelined hash read: lead frame with the found
        count, then one frame per chunk as the engine's export stream
        yields it — the gather/D2H of chunk i+1 runs while chunk i is on
        the wire, and the serving side never stages the whole run."""
        found, chunks = await loop.run_in_executor(
            None, self.read_hashes_stream_fn, hashes, chunk_pages
        )
        writer.write(encode_frame2(
            {"ok": True, "found": int(found), "stream": True}, b""
        ))
        if not found:
            return
        await writer.drain()
        sent_pages = 0
        seq = 0
        it = iter(chunks)
        # sentinel instead of catching StopIteration: a StopIteration
        # raised inside run_in_executor cannot be set on an asyncio
        # Future (the await would hang forever), so exhaustion must be
        # signalled in-band
        _done = object()
        while sent_pages < found:
            try:
                data = await loop.run_in_executor(None, next, it, _done)
            except Exception as e:  # noqa: BLE001 — report in-band
                log.exception("hash-stream export failed mid-stream")
                KV_TRANSFER.inc("dynamo_kv_transfer_errors_total")
                writer.write(encode_frame2(
                    {"ok": False, "error": str(e)}, b""
                ))
                return
            if data is _done:
                break
            sent_pages += int(data.shape[3])
            _write_array_frame(
                writer,
                {"ok": True, "seq": seq, "eof": sent_pages >= found},
                data,
            )
            await writer.drain()
            KV_TRANSFER.inc("dynamo_kv_transfer_tx_chunks_total")
            KV_TRANSFER.inc("dynamo_kv_transfer_tx_bytes_total", data.nbytes)
            seq += 1
        KV_TRANSFER.inc("dynamo_kv_transfer_streams_total")


# ---------------------------------------------------------------------------
# Data-plane client

class BlockTransferError(RuntimeError):
    pass


async def write_remote_pages(
    host: str, port: int, pages: list[int], data: np.ndarray,
    job_id: Optional[str] = None,
) -> None:
    """One-sided write: push pages into a peer's pool (NIXL-write path —
    prefill pushing computed KV into decode's pre-allocated pages).
    `job_id` tags the frame so the receiver can reject writes for a job it
    has since cancelled (stale-queue protection).

    An integrity nack (the receiver's checksum verify failed — the bytes
    rotted on the wire, not at rest) is retried once before the error
    propagates to the caller's fallback path."""
    for attempt in (0, 1):
        try:
            await _write_remote_pages_once(host, port, pages, data,
                                           job_id)
            return
        except KvIntegrityError:
            if attempt:
                raise
            KV_INTEGRITY.inc("dynamo_kv_integrity_retries_total")
            log.warning(
                "integrity nack on write_pages (job=%s); retrying once",
                job_id,
            )


async def _write_remote_pages_once(
    host: str, port: int, pages: list[int], data: np.ndarray,
    job_id: Optional[str] = None,
) -> None:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        header = {"op": "write_pages", "pages": [int(p) for p in pages]}
        if job_id is not None:
            header["job"] = job_id
        _write_array_frame(writer, header, data)
        await writer.drain()
        KV_TRANSFER.inc("dynamo_kv_transfer_tx_chunks_total")
        KV_TRANSFER.inc("dynamo_kv_transfer_tx_bytes_total", data.nbytes)
        header, _ = await read_frame2(reader)
        if not header.get("ok"):
            KV_TRANSFER.inc("dynamo_kv_transfer_errors_total")
            _raise_nack(header, "write failed")
    finally:
        writer.close()


class PageStreamWriter:
    """One chunk-pipelined page push into a peer's pool.

    The sender writes `write_pages` frames tagged ``stream``/``seq`` as
    chunks become available (for disagg remote prefill: as the prefill
    forward commits each run of complete prefix blocks), with no
    per-chunk ack — chunk i rides the wire while chunk i+1 is still
    being computed/gathered. ``commit()`` sends the eof frame and waits
    for the single ack, which carries any deferred mid-stream scatter
    failure. Use ``abort()``/``close()`` on error paths so a dead stream
    never half-writes silently."""

    def __init__(self, host: str, port: int,
                 job_id: Optional[str] = None):
        self.host = host
        self.port = port
        self.job_id = job_id
        self.chunks_sent = 0
        self.bytes_sent = 0
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._t_open: Optional[float] = None

    async def _ensure_conn(self) -> None:
        if self._writer is None:
            self._reader, self._writer = await asyncio.open_connection(
                self.host, self.port
            )
            self._t_open = time.monotonic()

    async def write_chunk(self, pages: list[int], data: np.ndarray) -> None:
        """Ship one chunk (pages aligned with data's page axis)."""
        await self._ensure_conn()
        header = {
            "op": "write_pages", "pages": [int(p) for p in pages],
            "stream": True, "seq": self.chunks_sent,
        }
        if self.job_id is not None:
            header["job"] = self.job_id
        t0 = time.monotonic()
        _write_array_frame(self._writer, header, data)
        await self._writer.drain()
        self.chunks_sent += 1
        self.bytes_sent += data.nbytes
        KV_TRANSFER.inc("dynamo_kv_transfer_tx_chunks_total")
        KV_TRANSFER.inc("dynamo_kv_transfer_tx_bytes_total", data.nbytes)
        dt = time.monotonic() - t0
        KV_TRANSFER.observe("dynamo_kv_transfer_chunk_seconds", dt)
        tl.STREAM_EVENTS.record(
            tl.FRAME_SEND, dt, seq=self.chunks_sent - 1,
            pages=len(pages), bytes=int(data.nbytes),
            **({"job": self.job_id} if self.job_id else {}),
        )

    async def commit(self) -> int:
        """Eof frame + single ack; returns the receiver's chunk count.
        Raises BlockTransferError if any chunk's scatter failed."""
        await self._ensure_conn()
        self._writer.write(encode_frame2(
            {"op": "write_pages_eof", "chunks": self.chunks_sent,
             **({"job": self.job_id} if self.job_id else {})}, b"",
        ))
        await self._writer.drain()
        t_ack = time.monotonic()
        header, _ = await read_frame2(self._reader)
        tl.STREAM_EVENTS.record(
            tl.EOF_ACK_WAIT, time.monotonic() - t_ack,
            chunks=self.chunks_sent,
            **({"job": self.job_id} if self.job_id else {}),
        )
        if not header.get("ok"):
            KV_TRANSFER.inc("dynamo_kv_transfer_errors_total")
            _raise_nack(header, "chunk stream failed")
        KV_TRANSFER.inc("dynamo_kv_transfer_streams_total")
        if self._t_open is not None:
            KV_TRANSFER.observe(
                "dynamo_kv_transfer_seconds",
                time.monotonic() - self._t_open,
            )
        return int(header.get("chunks", self.chunks_sent))

    async def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None
            self._reader = None


async def write_pages_stream(
    host: str, port: int,
    chunks: Iterable[tuple[list[int], np.ndarray]],
    job_id: Optional[str] = None,
) -> int:
    """Push an iterable of (pages, data) chunks as one pipelined stream;
    returns the number of chunks acked. Convenience over PageStreamWriter
    for callers whose chunks are already materialized (tests, onboarding
    batches); the disagg prefill worker drives the writer directly so it
    can interleave sends with prefill progress.

    Chunks are materialized so an integrity nack at eof can replay the
    whole stream once (the nacked copy never reached the pool)."""
    chunks = list(chunks)
    for attempt in (0, 1):
        w = PageStreamWriter(host, port, job_id=job_id)
        try:
            for pages, data in chunks:
                await w.write_chunk(pages, data)
            return await w.commit()
        except KvIntegrityError:
            if attempt:
                raise
            KV_INTEGRITY.inc("dynamo_kv_integrity_retries_total")
            log.warning(
                "integrity nack on page stream (job=%s); retrying once",
                job_id,
            )
        finally:
            await w.close()


async def read_remote_pages(
    host: str, port: int, pages: list[int]
) -> np.ndarray:
    """One-sided read: pull pages out of a peer's pool (onboard path)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(encode_frame2(
            {"op": "read_pages", "pages": [int(p) for p in pages]}, b""
        ))
        await writer.drain()
        header, payload = await read_frame2(reader)
        if not header.get("ok"):
            _raise_nack(header, "read failed")
        KV_TRANSFER.inc("dynamo_kv_transfer_rx_chunks_total")
        KV_TRANSFER.inc("dynamo_kv_transfer_rx_bytes_total", len(payload))
        return _decode_payload(header, payload, copy=True, verify=True)
    finally:
        writer.close()


async def probe_remote_hashes(
    host: str, port: int, hashes: list[int]
) -> tuple[int, Optional[np.ndarray]]:
    """Cheap G4 probe: how many leading blocks of the chained-hash run
    the peer's pool holds — no page export. A peer without probe support
    answers with the FULL read instead; those bytes already cost a
    gather and a wire trip, so they are decoded and returned (second
    tuple slot) rather than discarded — the caller uses them directly
    instead of asking the peer to export everything again. Raises
    BlockTransferError only when the peer errors outright."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(encode_frame2(
            {"op": "read_hashes", "hashes": [int(h) for h in hashes],
             "probe": True}, b""
        ))
        await writer.drain()
        header, payload = await read_frame2(reader)
        if not header.get("ok"):
            _raise_nack(header, "probe failed")
        found = int(header.get("found", 0))
        if payload and found:
            return found, _decode_payload(header, payload, copy=True,
                                          verify=True)
        return found, None
    finally:
        writer.close()


async def read_remote_hashes(
    host: str, port: int, hashes: list[int],
    chunk_pages: int = 0,
    on_chunk: Optional[Callable[[int, np.ndarray], None]] = None,
) -> tuple[int, Optional[np.ndarray]]:
    """One-sided hash-addressed read: ask a peer for the longest prefix of
    the chained-hash run its pool holds (G4 path). Returns (found, pages
    [2, L, kvh, found, ps, hd]) — (0, None) on full miss.

    With ``chunk_pages`` > 0 the read is chunk-pipelined: the peer
    streams the run as multi-frame chunks (its gather of chunk i+1
    overlaps chunk i's wire time) and each chunk is delivered to
    ``on_chunk(page_offset, array)`` as it arrives — the caller lands it
    (e.g. host-tier put_batch) without ever staging the whole run; the
    returned array is then None. Without ``on_chunk`` the chunks are
    reassembled and returned whole. Peers that don't stream fall back to
    the monolithic reply transparently."""
    reader, writer = await asyncio.open_connection(host, port)
    t0 = time.monotonic()
    try:
        req = {"op": "read_hashes", "hashes": [int(h) for h in hashes]}
        if chunk_pages > 0:
            req["chunk_pages"] = int(chunk_pages)
        writer.write(encode_frame2(req, b""))
        await writer.drain()
        header, payload = await read_frame2(reader)
        if not header.get("ok"):
            _raise_nack(header, "read failed")
        found = int(header.get("found", 0))
        if not found:
            return 0, None
        if not header.get("stream"):
            # monolithic reply (legacy peer or chunking off)
            KV_TRANSFER.inc("dynamo_kv_transfer_rx_chunks_total")
            KV_TRANSFER.inc("dynamo_kv_transfer_rx_bytes_total",
                            len(payload))
            data = _decode_payload(header, payload, copy=True,
                                   verify=True)
            if on_chunk is not None:
                on_chunk(0, data)
                return found, None
            return found, data
        parts: list[np.ndarray] = []
        offset = 0
        while offset < found:
            h, payload = await read_frame2(reader)
            if not h.get("ok"):
                _raise_nack(h, "chunk stream failed")
            arr = _decode_payload(h, payload, copy=True, verify=True)
            KV_TRANSFER.inc("dynamo_kv_transfer_rx_chunks_total")
            KV_TRANSFER.inc("dynamo_kv_transfer_rx_bytes_total",
                            len(payload))
            if on_chunk is not None:
                on_chunk(offset, arr)
            else:
                parts.append(arr)
            offset += int(arr.shape[3])
            if h.get("eof"):
                break
        KV_TRANSFER.observe(
            "dynamo_kv_transfer_seconds", time.monotonic() - t0
        )
        found = min(found, offset)
        if on_chunk is not None:
            return found, None
        return found, np.concatenate(parts, axis=3)
    finally:
        writer.close()


class RemoteKvFetcher:
    """KVBM G4: the remote cache tier (reference block_manager.rs:69-82
    CacheLevel::G4, storage/nixl.rs:403 NIXL-backed remote storage).

    TPU redesign: instead of a dedicated remote store, the "remote tier"
    is every PEER worker's sealed pool, addressed by chained block hash
    over the existing transfer plane. A prefix that misses G1/G2/G3
    locally is fetched from whichever peer holds it (scaled-up workers
    warm themselves from the fleet instead of recomputing), landing in
    the G2 host tier so the normal onboard path takes over.

    With ``chunk_pages`` > 0 the fetch is chunk-pipelined: peers answer a
    CHEAP probe (committed-prefix length, no page export — losers of the
    race no longer gather and ship bytes that get discarded), then the
    winner streams its run chunk by chunk and each chunk lands via
    ``on_chunk`` while later chunks are still on the wire."""

    def __init__(self, kv: KvClient, namespace: str, self_worker_id: str,
                 timeout_s: float = 3.0, chunk_pages: int = 0):
        self.kv = kv
        self.namespace = namespace
        self.self_id = self_worker_id
        self.timeout_s = timeout_s
        self.chunk_pages = chunk_pages
        self.fetches = 0
        self.hits = 0
        self.chunked_fetches = 0

    async def _peers(self) -> list[BlocksetDescriptor]:
        rows = await self.kv.get_prefix(
            f"dynamo://{self.namespace}/{KV_META_PREFIX}"
        )
        peers = []
        for _key, val, _ver in rows:
            try:
                desc = BlocksetDescriptor.from_json(val)
            except (ValueError, KeyError, TypeError):
                continue
            if desc.worker_id != self.self_id:
                peers.append(desc)
        return peers

    async def fetch(
        self, hashes: list[int],
        on_chunk: Optional[Callable[[int, np.ndarray], None]] = None,
        holders: Optional[list[str]] = None,
    ) -> tuple[int, Optional[np.ndarray]]:
        """Probe every peer CONCURRENTLY; the longest returned prefix
        wins. (0, None) if no peer holds anything. timeout_s bounds the
        WHOLE probe round, not each peer — this runs on the
        request-submit path, so dead peers must cost one timeout total,
        never one timeout each. With ``on_chunk`` the winning run is
        delivered incrementally as (page_offset, array) and the returned
        data is None. ``holders`` is the fleet view's hint of which
        worker ids hold the run: hinted peers are consulted alone first
        and the rest of the fleet is only probed when the hint turns out
        stale — dedup admission stops paying a fleet-wide probe round
        for content whose holders are already known."""
        self.fetches += 1
        peers = await self._peers()
        if not peers:
            return 0, None
        if holders:
            hinted_ids = set(holders)
            hinted = [d for d in peers if d.worker_id in hinted_ids]
            rest = [d for d in peers if d.worker_id not in hinted_ids]
            if hinted:
                got = await self._fetch_from(hinted, hashes, on_chunk)
                if got[0] or not rest:
                    return got
                peers = rest  # stale hint: fall back to un-hinted peers
        return await self._fetch_from(peers, hashes, on_chunk)

    async def _fetch_from(
        self, peers: list[BlocksetDescriptor], hashes: list[int],
        on_chunk: Optional[Callable[[int, np.ndarray], None]] = None,
    ) -> tuple[int, Optional[np.ndarray]]:
        if self.chunk_pages > 0 and on_chunk is not None:
            got = await self._fetch_chunked(peers, hashes, on_chunk)
            if got is not None:
                if got:
                    self.hits += 1
                return got, None

        async def probe(desc):
            try:
                return await read_remote_hashes(desc.host, desc.port, hashes)
            except (OSError, BlockTransferError, KvIntegrityError):
                # an integrity failure on a read is just a peer whose
                # copy is bad: treat as a miss (another holder may win)
                return 0, None

        results = await asyncio.gather(
            *[asyncio.wait_for(probe(d), timeout=self.timeout_s)
              for d in peers],
            return_exceptions=True,
        )
        best: tuple[int, Optional[np.ndarray]] = (0, None)
        for res in results:
            if isinstance(res, BaseException):
                continue
            if res[0] > best[0]:
                best = res
        if best[0]:
            self.hits += 1
        if best[0] and on_chunk is not None:
            # legacy monolithic reply: deliver through the same callback
            on_chunk(0, best[1])
            return best[0], None
        return best

    async def _fetch_chunked(
        self, peers: list[BlocksetDescriptor], hashes: list[int],
        on_chunk: Callable[[int, np.ndarray], None],
    ) -> Optional[int]:
        """Probe round + streamed fetch from the winner. None = the
        chunked path couldn't run (probe unsupported everywhere) — the
        caller falls back to the legacy full-read race."""

        async def probe(desc):
            try:
                found, data = await probe_remote_hashes(
                    desc.host, desc.port, hashes
                )
                return found, data, desc
            except (OSError, BlockTransferError, KvIntegrityError):
                return -1, None, desc

        results = await asyncio.gather(
            *[asyncio.wait_for(probe(d), timeout=self.timeout_s)
              for d in peers],
            return_exceptions=True,
        )
        holders: list[tuple[int, BlocksetDescriptor]] = []
        best_full: tuple[int, Optional[np.ndarray]] = (0, None)
        any_answered = False
        for res in results:
            if isinstance(res, BaseException):
                continue
            found, data, desc = res
            if found >= 0:
                any_answered = True
            if found > 0:
                holders.append((found, desc))
                if data is not None and found > best_full[0]:
                    # probe-less peer: it answered with the full export
                    best_full = (found, data)
        if not any_answered:
            # every peer errored/timed out on the probe round; let the
            # caller's legacy full-read race have the last word
            return None
        if not holders:
            return 0
        if best_full[0] >= max(fd[0] for fd in holders):
            # the best run already arrived whole on the probe round (a
            # peer without probe support exports eagerly) — landing it
            # beats asking any peer to gather and ship it all again
            on_chunk(0, best_full[1])
            return best_full[0]
        self.chunked_fetches += 1
        # stream from the longest-prefix holder; a dead/stalled winner
        # must not zero the fetch while a runner-up still holds the run
        # (the legacy full-read race had that redundancy), so walk the
        # holders best-first. Chunks a failed attempt already landed are
        # hash-addressed cache entries — re-delivery is idempotent. ONE
        # stream deadline bounds the whole walk — this runs on the
        # request-submit path, and the pre-chunking contract was a
        # single bounded wait before local-prefill fallback, not one
        # deadline per peer. (The deadline is still far looser than the
        # probe round's: the stream moves real bytes, and a slow host
        # link is not a dead peer.)
        holders.sort(key=lambda fd: fd[0], reverse=True)
        deadline = time.monotonic() + max(self.timeout_s * 20, 60.0)
        for _found, desc in holders:
            budget = deadline - time.monotonic()
            if budget <= 0:
                break
            try:
                found, _ = await asyncio.wait_for(
                    read_remote_hashes(
                        desc.host, desc.port, hashes,
                        chunk_pages=self.chunk_pages, on_chunk=on_chunk,
                    ),
                    timeout=budget,
                )
                return found
            except (OSError, BlockTransferError, KvIntegrityError,
                    asyncio.TimeoutError):
                log.exception("chunked G4 fetch from %s failed",
                              desc.worker_id)
        return 0  # every holder failed or the stream deadline passed


class ArrayFrameServer:
    """One-shot array handoff over the frame2 codec (zero-copy send):
    producers park an array under a ticket; exactly one peer collects it.

    Carries multimodal embedding tensors from the encode worker to the
    LLM worker (reference encode_worker.py:148 moves them via NIXL) —
    a LLaVA-scale image is ~9 MB of f32 rows, which must not transit the
    control-plane RPC as JSON float lists. Unclaimed arrays expire."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0,
                 ttl_s: float = 120.0,
                 advertise_host: Optional[str] = None):
        self.bind_host = host
        # what tickets carry: peers on OTHER machines must be able to
        # reach it (the bind address 0.0.0.0 is not routable; loopback
        # only works intra-host)
        self.host = advertise_host or (
            host if host not in ("0.0.0.0", "") else "127.0.0.1"
        )
        self.port = port
        self.ttl_s = ttl_s
        self._parked: dict[str, tuple[float, np.ndarray]] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._seq = 0

    def park(self, array: np.ndarray) -> str:
        import time

        self._seq += 1
        ticket = f"t{self._seq}"
        now = time.monotonic()
        self._parked[ticket] = (now, np.ascontiguousarray(array))
        # opportunistic expiry sweep (no background task to manage)
        dead = [t for t, (ts, _) in self._parked.items()
                if now - ts > self.ttl_s]
        for t in dead:
            del self._parked[t]
        return ticket

    async def start(self) -> tuple[str, int]:
        self._server = await asyncio.start_server(
            self._on_conn, self.bind_host, self.port
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self.host, self.port

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        self._parked.clear()

    async def _on_conn(self, reader, writer) -> None:
        try:
            while True:
                header, _ = await read_frame2(reader)
                ent = self._parked.pop(header.get("ticket", ""), None)
                if ent is None:
                    writer.write(encode_frame2(
                        {"ok": False, "error": "unknown or expired ticket"},
                        b"",
                    ))
                else:
                    data = ent[1]
                    _write_array_frame(
                        writer,
                        {"ok": True, "shape": list(data.shape),
                         "dtype": data.dtype.name},
                        data,
                    )
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError,
                BrokenPipeError, ValueError):
            pass
        finally:
            writer.close()


async def take_remote_array(host: str, port: int, ticket: str) -> np.ndarray:
    """Collect (and consume) a parked array."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(encode_frame2({"op": "take", "ticket": ticket}, b""))
        await writer.drain()
        header, payload = await read_frame2(reader)
        if not header.get("ok"):
            raise BlockTransferError(header.get("error", "take failed"))
        return np.frombuffer(
            payload, dtype=np.dtype(header["dtype"])
        ).reshape(header["shape"]).copy()
    finally:
        writer.close()
