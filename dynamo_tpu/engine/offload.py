"""Host-side KV offload tiers: G2 DRAM + G3 disk (KVBM — reference
block_manager/offload.rs, block_manager/storage/disk.rs:25).

Reference shape (offload.rs:46-80, pool.rs:156, block_manager.rs:69-82):
blocks leaving the device pool's reuse set are offloaded down the tier
hierarchy (G1 HBM -> G2 DRAM -> G3 disk) through a priority queue with
batched transfers; prefix hits consult lower tiers and onboard blocks back
up. It is for multi-turn traffic whose working set exceeds HBM (the
reference's KV offload to CPU RAM), and G3 extends the reusable corpus
past DRAM.

TPU redesign: offload piggybacks on the engine's pipelined round loop —
candidates are pages PARKED in the allocator's LRU (committed, refcount 0);
once per round the engine validates them (hash still owns the page),
batch-gathers them in one fused jit, and fetches device->host
asynchronously behind compute (same copy_to_host_async pipeline as token
fetches). Nothing blocks the decode path. Onboard is the reverse: at
admission, a contiguous run of G2/G3 blocks extends the G1 prefix match
via one scatter jit (async H2D upload; prefill follows in device order).

The G3 tier is an mmap-backed page pool: G2's LRU evictions spill DOWN
into it (instead of being dropped), and prefix lookups fall through G2
into G3 mid-run, so a run may be assembled from both tiers. Writes go
through the OS page cache (no fsync on the hot path).

Integrity plane (kv_integrity.py): every index entry carries the block's
content crc, minted at first host materialization; ``verify_pages``
checks gathered bytes against it at onboard admission, and a shared
``KvQuarantine`` makes tier puts refuse hashes that ever failed.

Crash consistency (G3): when the tier has an operator-provided ``path``
it journals a sidecar manifest (``<path>.manifest``, JSON lines:
slot -> hash/parent/crc/scale, compacted via atomic rename) and replays
it at attach, so the disk corpus survives an engine restart. A startup
scrub (lazy by default, eager with ``scrub_on_start``) verifies or drops
entries — torn writes come back as plain cache misses.

This module owns only the host pools + hash registries; the device side
(gather/scatter, validation, scheduling) lives in engine.py.
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import tempfile
from collections import OrderedDict
from typing import Callable, Optional

import numpy as np

from dynamo_tpu.kv_fleet_metrics import KV_FLEET
from dynamo_tpu.kv_integrity import (
    KV_INTEGRITY,
    KvQuarantine,
    page_checksum,
)

log = logging.getLogger(__name__)

# journal compaction threshold: rewrite the manifest once the journal
# carries this many times more lines than live entries could need
_JOURNAL_SLACK = 4

# replication-aware eviction scans this many LRU-oldest entries for a
# well-replicated victim before falling back to the plain LRU head —
# bounded so eviction stays O(1)-ish under pressure
_EVICT_SCAN = 8


def _chaos():
    # lazy: resilience.chaos imports metrics/overload; keep the tier
    # importable standalone and pay one module-dict lookup per gather
    from dynamo_tpu.resilience.chaos import CHAOS

    return CHAOS


class _PageTier:
    """Fixed-capacity pool of KV pages keyed by chained block hash.

    Slots hold [2(k/v), L, kvh, ps, hd] per page; the pool array adds the
    page axis at 3. LRU eviction on pressure. Single-owner (the engine
    loop) except for read-only counter access. Subclasses provide the
    backing storage via ``_ensure_pool``."""

    def __init__(self, num_pages: int, page_shape: tuple, dtype,
                 scale_shape: tuple = (),
                 quarantine: Optional[KvQuarantine] = None):
        # page_shape = (2, L, kvh, ps, hd)
        self.num_pages = num_pages
        self.page_shape = tuple(page_shape)
        self.dtype = np.dtype(dtype)
        self._pool = None  # lazy: it can be GBs
        # int8 pools (kv_quant) carry a per-page scale sidecar of this
        # shape (typically (2, L)); scales are tiny and stay in RAM for
        # every tier — the G3 manifest additionally journals them so a
        # restored disk tier can still dequantize
        self.scale_shape = tuple(scale_shape)
        self._scale_pool: Optional[np.ndarray] = None
        # hash -> (slot, parent_hash, crc); insertion order = LRU order
        self._index: "OrderedDict[int, tuple[int, int, int]]" = (
            OrderedDict()
        )
        self._free: list[int] = list(range(num_pages))
        # shared deny-list: hashes that failed verification are refused
        # (puts no-op, lookups miss) until their quarantine TTL lapses
        self.quarantine = quarantine
        # fleet prefix economy: when wired (engine.apply_fleet_hints),
        # maps hash -> known fleet replica count (None = unknown) and
        # eviction prefers well-replicated blocks over the last copy
        self.fleet_replicas: Optional[Callable[[int], Optional[int]]] = None
        # counters
        self.pages_offloaded = 0
        self.onboard_hits = 0
        self.lookups = 0

    @property
    def pool_shape(self) -> tuple:
        return (
            self.page_shape[0], self.page_shape[1], self.page_shape[2],
            self.num_pages, self.page_shape[3], self.page_shape[4],
        )

    def _ensure_pool(self) -> np.ndarray:
        raise NotImplementedError

    def _ensure_scales(self) -> np.ndarray:
        if self._scale_pool is None:
            self._scale_pool = np.zeros(
                self.scale_shape + (self.num_pages,), np.float32
            )
        return self._scale_pool

    def __contains__(self, block_hash: int) -> bool:
        return block_hash in self._index

    def __len__(self) -> int:
        return len(self._index)

    # -- journal hooks (no-ops except for the manifest-backed G3) --

    def _on_put(self, h: int, parent: int, slot: int, crc: int,
                scale: Optional[np.ndarray]) -> None:
        pass

    def _on_drop(self, h: int) -> None:
        pass

    def _pick_victim(self) -> int:
        """Choose the hash to evict. Plain LRU head unless the fleet
        replica hook is wired: then scan the ``_EVICT_SCAN`` oldest
        entries and evict the best-replicated one (>= 2 known fleet
        copies, oldest wins ties), so the fleet's LAST copy of a warm
        block outlives the eighth copy of the same system prompt."""
        head = next(iter(self._index))
        fn = self.fleet_replicas
        if fn is None:
            return head
        best_h = None
        best_r = 1
        for h in itertools.islice(self._index, _EVICT_SCAN):
            try:
                r = fn(h)
            except Exception:  # noqa: BLE001 — stale hints must not block eviction
                log.debug("fleet replica lookup failed for %#x", h,
                          exc_info=True)
                r = None
            if r is not None and r > best_r:
                best_h, best_r = h, r
        if best_h is not None:
            KV_FLEET.inc("dynamo_kv_fleet_replicated_evictions_total")
            return best_h
        try:
            head_r = fn(head)
        except Exception:  # noqa: BLE001 — stale hints must not block eviction
            log.debug("fleet replica lookup failed for %#x", head,
                      exc_info=True)
            head_r = None
        if head_r is not None and head_r <= 1:
            KV_FLEET.inc("dynamo_kv_fleet_last_copy_evictions_total")
        return head

    def _evict_one(self) -> None:
        """Drop one entry to free a slot (hook point for spill)."""
        old_h = self._pick_victim()
        old_slot, _, _ = self._index.pop(old_h)
        self._free.append(old_slot)
        self._on_drop(old_h)

    def put_one(self, h: int, parent: int, page: np.ndarray,
                scale: Optional[np.ndarray] = None,
                checksum: Optional[int] = None) -> bool:
        """Store one page ([2, L, kvh, ps, hd]); False if already held
        or quarantined. ``scale`` ([*scale_shape]) rides along for int8
        pools. ``checksum`` is the block's content crc — minted here
        (first materialization) when the caller doesn't carry one."""
        if self.quarantine is not None and h in self.quarantine:
            return False
        if h in self._index:
            self._index.move_to_end(h)
            return False
        pool = self._ensure_pool()
        if not self._free:
            self._evict_one()
        slot = self._free.pop()
        pool[:, :, :, slot] = page
        if self.scale_shape:
            self._ensure_scales()[..., slot] = (
                scale if scale is not None else 0.0
            )
        if checksum is None:
            checksum = page_checksum(
                pool[:, :, :, slot],
                self._ensure_scales()[..., slot]
                if self.scale_shape else None,
            )
        self._index[h] = (slot, parent, checksum)
        self.pages_offloaded += 1
        self._on_put(h, parent, slot, checksum,
                     scale if self.scale_shape else None)
        return True

    def put_batch(
        self, hashes: list[int], parents: list[int], data,
        scales: Optional[np.ndarray] = None,
        checksums: Optional[list[int]] = None,
    ) -> int:
        """Store gathered pages (data [2, L, kvh, n, ps, hd] — or a
        kv_quant.QuantizedPages bundle — aligned with hashes). Existing
        entries are refreshed in LRU order. Returns the number of new
        pages stored."""
        if scales is None and hasattr(data, "scales"):
            data, scales = data.data, data.scales
        stored = 0
        for i, (h, parent) in enumerate(zip(hashes, parents)):
            stored += bool(self.put_one(
                h, parent, data[:, :, :, i],
                scales[..., i] if scales is not None else None,
                checksums[i] if checksums is not None else None,
            ))
        return stored

    def lookup_run(self, hashes: list[int]) -> list[tuple[int, int]]:
        """Longest leading run of hashes present in the tier; returns
        [(hash, parent_hash), ...] and refreshes their LRU position."""
        self.lookups += len(hashes)
        run: list[tuple[int, int]] = []
        for h in hashes:
            ent = self._index.get(h)
            if ent is None:
                break
            self._index.move_to_end(h)
            run.append((h, ent[1]))
        self.onboard_hits += len(run)
        return run

    def checksum_of(self, block_hash: int) -> Optional[int]:
        ent = self._index.get(block_hash)
        return None if ent is None else ent[2]

    def verify_pages(self, hashes: list[int], data,
                     scales: Optional[np.ndarray] = None) -> list[int]:
        """Check gathered bytes against the stored content crcs; returns
        the indices of mismatching pages (counters updated here)."""
        if scales is None and hasattr(data, "scales"):
            data, scales = data.data, data.scales
        bad: list[int] = []
        for i, h in enumerate(hashes):
            want = self.checksum_of(h)
            if want is None:
                continue
            got = page_checksum(
                data[:, :, :, i],
                scales[..., i] if scales is not None else None,
            )
            if got != want:
                bad.append(i)
        if bad:
            KV_INTEGRITY.inc("dynamo_kv_integrity_failed_total",
                             len(bad))
        KV_INTEGRITY.inc("dynamo_kv_integrity_verified_total",
                         len(hashes) - len(bad))
        return bad

    def gather(self, hashes: list[int]) -> np.ndarray:
        """Pages for the given (present) hashes: [2, L, kvh, n, ps, hd].
        The result is always a copy — chaos bit-flips mutate it without
        touching the pool (a *detectable* in-flight corruption)."""
        pool = self._ensure_pool()
        slots = [self._index[h][0] for h in hashes]
        out = pool[:, :, :, slots]
        _chaos().maybe_flip_bits(out)
        return out

    def gather_scales(self, hashes: list[int]) -> Optional[np.ndarray]:
        """Scale sidecar aligned with ``gather`` ([*scale_shape, n]);
        None for unquantized tiers."""
        if not self.scale_shape:
            return None
        scales = self._ensure_scales()
        slots = [self._index[h][0] for h in hashes]
        return scales[..., slots]

    def read_page(self, block_hash: int) -> np.ndarray:
        """One page [2, L, kvh, ps, hd] (must be present)."""
        pool = self._ensure_pool()
        return pool[:, :, :, self._index[block_hash][0]]

    def read_scale(self, block_hash: int) -> Optional[np.ndarray]:
        if not self.scale_shape:
            return None
        return self._ensure_scales()[..., self._index[block_hash][0]]

    def rot_page(self, block_hash: int) -> bool:
        """Flip one byte of the POOL-RESIDENT copy of a page WITHOUT
        touching its sealed crc — models silent post-seal rot (DRAM
        flip, torn disk write). The next gather+verify_pages over the
        block fails closed: this is what the ``corrupt_prefetch`` chaos
        point fires on fleet-prefetched pages."""
        ent = self._index.get(block_hash)
        if ent is None:
            return False
        pool = self._ensure_pool()
        view = pool[:, :, :, ent[0]]
        idx = (0,) * view.ndim
        raw = bytearray(np.asarray(view[idx]).tobytes())
        raw[0] ^= 0x01
        view[idx] = np.frombuffer(bytes(raw), dtype=self.dtype)[0]
        return True

    def drop(self, block_hash: int) -> None:
        ent = self._index.pop(block_hash, None)
        if ent is not None:
            self._free.append(ent[0])
            self._on_drop(block_hash)

    def drop_everywhere(self, block_hash: int) -> None:
        """Quarantine support: purge the hash from this tier (and any
        lower tier — see HostOffloadTier)."""
        self.drop(block_hash)

    def clear(self) -> int:
        n = len(self._index)
        for h in list(self._index):
            self.drop(h)
        return n


class DiskOffloadTier(_PageTier):
    """G3: mmap-backed page pool (reference storage/disk.rs:25,
    block_manager.rs:69-82 CacheLevel::G3). The file is a plain dense
    array; the OS page cache absorbs write bursts and serves hot reads,
    so spill/onboard never issue synchronous IO on the engine loop.

    With an operator-provided ``path`` the tier is restart-survivable: a
    sidecar manifest (``<path>.manifest``) journals every put/drop and is
    replayed at attach. Pages are written to the mmap BEFORE their
    journal line, so a crash can leave an orphaned page (harmless — the
    slot is reused) but never a journal entry pointing at unwritten
    bytes that would verify; torn journal tails are skipped line-wise."""

    def __init__(self, num_pages: int, page_shape: tuple, dtype,
                 path: Optional[str] = None, scale_shape: tuple = (),
                 quarantine: Optional[KvQuarantine] = None,
                 scrub_on_start: bool = False):
        super().__init__(num_pages, page_shape, dtype,
                         scale_shape=scale_shape, quarantine=quarantine)
        self.path = path
        self._owns_file = path is None
        self.scrub_on_start = bool(scrub_on_start)
        self._journal = None  # open append handle to the manifest
        self._journal_lines = 0
        self.scrub_recovered = 0
        self.scrub_dropped = 0
        if path is not None and os.path.exists(path):
            self._attach()
        elif (self.manifest_path is not None
              and os.path.exists(self.manifest_path)):
            # manifest without its pool file: stale — entries would
            # point into fresh zeros; start clean instead
            os.unlink(self.manifest_path)

    # -- backing file --

    @property
    def manifest_path(self) -> Optional[str]:
        return None if self.path is None else self.path + ".manifest"

    def _ensure_pool(self) -> np.ndarray:
        if self._pool is None:
            if self.path is None:
                fd, self.path = tempfile.mkstemp(
                    prefix="dynamo-tpu-kv-g3-", suffix=".mmap"
                )
                os.close(fd)
            nbytes = int(np.prod(self.pool_shape)) * self.dtype.itemsize
            exists = os.path.exists(self.path)
            size = os.path.getsize(self.path) if exists else 0
            if exists and 0 < size < nbytes:
                # truncated mid-growth (crash) or short operator file:
                # extend sparsely — the zero tail fails crc at scrub and
                # its blocks come back as misses instead of SIGBUS
                os.truncate(self.path, nbytes)
                size = nbytes
            # pre-existing files attach with "r+" (a "w+" open would
            # zero a restart-survivable corpus or an operator's file)
            mode = "r+" if exists and size >= nbytes else "w+"
            self._pool = np.memmap(
                self.path, dtype=self.dtype, mode=mode,
                shape=self.pool_shape,
            )
            log.info(
                "G3 disk tier: %d pages (%.1f MB) at %s (%s)",
                self.num_pages,
                np.prod(self.pool_shape) * self.dtype.itemsize / 1e6,
                self.path, "attached" if mode == "r+" else "created",
            )
        return self._pool

    # -- manifest journal --

    def _meta(self) -> dict:
        return {
            "g3_manifest": 1,
            "num_pages": self.num_pages,
            "page_shape": list(self.page_shape),
            "dtype": self.dtype.name,
            "scale_shape": list(self.scale_shape),
        }

    def _ensure_journal(self):
        if self._journal is None and self.manifest_path is not None:
            fresh = (
                not os.path.exists(self.manifest_path)
                or os.path.getsize(self.manifest_path) == 0
            )
            self._journal = open(self.manifest_path, "a")
            if fresh:
                self._journal.write(json.dumps(self._meta()) + "\n")
                self._journal.flush()
        return self._journal

    def _journal_write(self, rec: dict) -> None:
        j = self._ensure_journal()
        if j is None:
            return
        j.write(json.dumps(rec) + "\n")
        j.flush()
        self._journal_lines += 1
        if self._journal_lines > max(
            _JOURNAL_SLACK * self.num_pages, 256
        ):
            self.compact_manifest()

    def _on_put(self, h: int, parent: int, slot: int, crc: int,
                scale: Optional[np.ndarray]) -> None:
        if self.manifest_path is None or self._owns_file:
            return
        self._journal_write({
            "put": int(h), "parent": int(parent), "slot": int(slot),
            "crc": int(crc),
            "scale": (
                [float(x) for x in np.asarray(scale, np.float32).ravel()]
                if scale is not None else None
            ),
        })

    def _on_drop(self, h: int) -> None:
        if self.manifest_path is None or self._owns_file:
            return
        self._journal_write({"drop": int(h)})

    def compact_manifest(self) -> None:
        """Rewrite the journal as one line per live entry via tmp-file +
        atomic rename — a crash mid-compaction leaves either the old or
        the new manifest, never a half state."""
        if self.manifest_path is None or self._owns_file:
            return
        if self._journal is not None:
            self._journal.close()
            self._journal = None
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps(self._meta()) + "\n")
            for h, (slot, parent, crc) in self._index.items():
                scale = (
                    self._ensure_scales()[..., slot]
                    if self.scale_shape else None
                )
                f.write(json.dumps({
                    "put": int(h), "parent": int(parent),
                    "slot": int(slot), "crc": int(crc),
                    "scale": (
                        [float(x) for x in scale.ravel()]
                        if scale is not None else None
                    ),
                }) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.manifest_path)
        self._journal_lines = len(self._index)

    @staticmethod
    def load_manifest(manifest_path: str):
        """Replay a manifest journal: (meta, live entries {hash: (slot,
        parent, crc, scale-list|None)}, torn/invalid line count). Used
        by attach and by tools/scrub_kv.py."""
        meta = None
        live: "OrderedDict[int, tuple]" = OrderedDict()
        torn = 0
        with open(manifest_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    torn += 1  # torn tail / partial write
                    continue
                if "g3_manifest" in rec:
                    meta = rec
                elif "drop" in rec:
                    live.pop(int(rec["drop"]), None)
                elif "put" in rec:
                    try:
                        ent = (int(rec["slot"]), int(rec["parent"]),
                               int(rec["crc"]), rec.get("scale"))
                    except (KeyError, TypeError, ValueError):
                        torn += 1
                        continue
                    h = int(rec["put"])
                    live.pop(h, None)  # re-put: newest slot wins
                    live[h] = ent
                else:
                    torn += 1
        return meta, live, torn

    def _attach(self) -> None:
        """Restart survival: replay the manifest against the existing
        backing file, scrubbing entries back into the index."""
        mpath = self.manifest_path
        if mpath is None or self._owns_file:
            return
        if not os.path.exists(mpath):
            return  # operator file with no manifest: attach empty
        try:
            meta, live, torn = self.load_manifest(mpath)
        except OSError as e:
            log.warning("G3 manifest unreadable (%s); starting empty", e)
            return
        dropped = torn
        if meta is not None and (
            meta.get("num_pages") != self.num_pages
            or list(meta.get("page_shape", [])) != list(self.page_shape)
            or meta.get("dtype") != self.dtype.name
            or list(meta.get("scale_shape", []))
            != list(self.scale_shape)
        ):
            log.warning(
                "G3 manifest geometry mismatch at %s; dropping %d "
                "entries", mpath, len(live),
            )
            dropped += len(live)
            live.clear()
        pool = self._ensure_pool()
        used: set[int] = set()
        for h, (slot, parent, crc, scale) in live.items():
            scale_arr = None
            if self.scale_shape:
                want_n = int(np.prod(self.scale_shape))
                if scale is None or len(scale) != want_n:
                    dropped += 1
                    continue
                scale_arr = np.asarray(scale, np.float32).reshape(
                    self.scale_shape
                )
            if not (0 <= slot < self.num_pages) or slot in used:
                dropped += 1
                continue
            if self.scrub_on_start and page_checksum(
                pool[:, :, :, slot], scale_arr
            ) != crc:
                dropped += 1
                KV_INTEGRITY.inc("dynamo_kv_integrity_failed_total")
                continue
            used.add(slot)
            self._index[h] = (slot, parent, crc)
            if self.scale_shape:
                self._ensure_scales()[..., slot] = scale_arr
        self._free = [
            s for s in range(self.num_pages) if s not in used
        ]
        self.scrub_recovered = len(self._index)
        self.scrub_dropped = dropped
        KV_INTEGRITY.inc(
            "dynamo_kv_integrity_g3_scrub_recovered_total",
            self.scrub_recovered,
        )
        KV_INTEGRITY.inc(
            "dynamo_kv_integrity_g3_scrub_dropped_total", dropped
        )
        if self.scrub_on_start:
            KV_INTEGRITY.inc(
                "dynamo_kv_integrity_verified_total",
                self.scrub_recovered,
            )
        log.info(
            "G3 attach: %d blocks recovered, %d dropped (%s scrub) "
            "from %s", self.scrub_recovered, dropped,
            "eager" if self.scrub_on_start else "lazy", mpath,
        )
        # start the journal from a compact state so replayed drops/puts
        # from the previous life don't accrete forever
        self.compact_manifest()

    def _maybe_chaos_truncate(self) -> None:
        # chaos truncate_g3: simulate the backing file losing its tail
        # region (dropped writes) — live-safe (ftruncate under an active
        # mmap would SIGBUS), and detectable by the crc verify
        if _chaos().fire("truncate_g3"):
            self._ensure_pool()[:, :, :, self.num_pages // 2:] = 0

    def gather(self, hashes: list[int]) -> np.ndarray:
        self._maybe_chaos_truncate()
        return super().gather(hashes)

    def read_page(self, block_hash: int) -> np.ndarray:
        # the G2 tier's fall-through gather reads G3 page-wise
        self._maybe_chaos_truncate()
        return super().read_page(block_hash)

    def close(self) -> None:
        if not self._owns_file:
            self.compact_manifest()
        if self._journal is not None:
            self._journal.close()
            self._journal = None
        if self._pool is not None:
            self._pool._mmap.close()
            self._pool = None
        if self._owns_file and self.path and os.path.exists(self.path):
            os.unlink(self.path)
            self.path = None


class HostOffloadTier(_PageTier):
    """G2: host-DRAM pool. With a ``spill`` tier attached, LRU evictions
    cascade DOWN into it (G2 -> G3) instead of being dropped, and
    ``lookup_run``/``gather`` fall through to it mid-run, so one onboard
    can be assembled from both tiers (reference offload.rs tier walk)."""

    def __init__(self, num_pages: int, page_shape: tuple, dtype,
                 spill: Optional[_PageTier] = None,
                 scale_shape: tuple = (),
                 quarantine: Optional[KvQuarantine] = None):
        super().__init__(num_pages, page_shape, dtype,
                         scale_shape=scale_shape, quarantine=quarantine)
        self.spill = spill

    def _ensure_pool(self) -> np.ndarray:
        if self._pool is None:
            self._pool = np.zeros(self.pool_shape, self.dtype)
        return self._pool

    def _evict_one(self) -> None:
        old_h = self._pick_victim()
        old_slot, old_parent, old_crc = self._index.pop(old_h)
        if self.spill is not None:
            # the crc travels with the block down the spill: G3 inherits
            # G2's seal-time checksum instead of re-minting over bytes
            # that may already have rotted in DRAM
            self.spill.put_one(
                old_h, old_parent, self._ensure_pool()[:, :, :, old_slot],
                (self._ensure_scales()[..., old_slot]
                 if self.scale_shape else None),
                checksum=old_crc,
            )
        self._free.append(old_slot)
        self._on_drop(old_h)

    def lookup_run(self, hashes: list[int]) -> list[tuple[int, int]]:
        self.lookups += len(hashes)
        run: list[tuple[int, int]] = []
        for h in hashes:
            ent = self._index.get(h)
            if ent is not None:
                self._index.move_to_end(h)
                run.append((h, ent[1]))
                continue
            if self.spill is not None:
                sub = self.spill.lookup_run([h])
                if sub:
                    run.append(sub[0])
                    continue
            break
        self.onboard_hits += len(run)
        return run

    def checksum_of(self, block_hash: int) -> Optional[int]:
        ent = self._index.get(block_hash)
        if ent is not None:
            return ent[2]
        if self.spill is not None:
            return self.spill.checksum_of(block_hash)
        return None

    def gather(self, hashes: list[int]) -> np.ndarray:
        out = np.empty(
            self.page_shape[:3] + (len(hashes),) + self.page_shape[3:],
            self.dtype,
        )
        for i, h in enumerate(hashes):
            if h in self._index:
                out[:, :, :, i] = self.read_page(h)
            else:
                out[:, :, :, i] = self.spill.read_page(h)
        _chaos().maybe_flip_bits(out)
        return out

    def gather_scales(self, hashes: list[int]) -> Optional[np.ndarray]:
        if not self.scale_shape:
            return None
        out = np.empty(self.scale_shape + (len(hashes),), np.float32)
        for i, h in enumerate(hashes):
            if h in self._index:
                out[..., i] = self.read_scale(h)
            else:
                out[..., i] = self.spill.read_scale(h)
        return out

    def drop_everywhere(self, block_hash: int) -> None:
        self.drop(block_hash)
        if self.spill is not None:
            self.spill.drop(block_hash)

    def clear(self) -> int:
        n = super().clear()
        if self.spill is not None:
            n += self.spill.clear()
        return n
