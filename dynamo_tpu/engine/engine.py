"""TpuEngine: pipelined continuous batching over contiguous per-slot KV.

Architecture (TPU-first redesign of what the reference delegates to vLLM —
SURVEY.md §7 step 3; round-4 layout, see models/llama.py module doc). The
defining constraints: a device→host read stalls the host for a round trip
(not yet measured on the v5e host; PERF.md) while dispatches and
host→device uploads are cheap and asynchronous, and paged gathers/scatters
in the per-step program waste bandwidth. The engine therefore NEVER blocks a
decode step on host data, and keeps PAGING OUT of the hot path:

  - Serving context is contiguous per slot (``ctx_kv``); the paged pool is
    prefix-cache storage, copied in at admission (load_ctx_pages) and out
    at block seal (seal_blocks). Decode attention streams dense slabs
    (ops/flash_decode.py).
  - All decode state lives on device: last tokens, context lengths, write
    destinations, sampler keys/counts, per-slot sampling params. One fused
    jit (decode + sample + state advance) steps every slot;
    all-greedy rounds skip the full sampler (static want_sample gate).
  - The host loop dispatches steps ahead in rounds of ``flush_every``; each
    round's sampled tokens are stacked on device ([F, B]) and fetched with
    ``copy_to_host_async`` — fetches pipeline behind compute, so results
    arrive a bounded LAG behind dispatch without ever stalling the device.
  - Host processing (token emission, stop detection, block sealing,
    admission) runs on lagged results. State changes are applied via a
    patch jit dispatched between rounds — device-order semantics make this
    race-free: a step dispatched before a patch sees pre-patch state, and
    a seal copy dispatched before a lane's re-prefill reads the pre-reuse
    content.
  - Slots finished on host keep garbage-decoding until their release patch
    lands (≤ pipeline lag steps). Safety: the release patch redirects the
    lane's writes to the scratch lane (dest), so a lane being prefilled
    for its next request is never corrupted; before release, garbage
    writes advance monotonically past every sealed position.
  - Prefill runs per request at bucketed padded lengths into the slot's
    region; the first token is sampled on device and patched into the slot
    without a host round trip. Admission needs only a free lane — active
    requests can never run out of KV space, so there is no preemption.

The engine implements the AsyncEngine contract: ``generate(request)`` yields
LLMEngineOutput deltas; dropping the iterator cancels (reference
engine.rs:124-140 AsyncEngineContext::stop_generating).
"""
from __future__ import annotations

import asyncio
import functools
import logging
import os
from collections import deque
import queue as queue_mod
import threading
import time
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.cache import PageAllocator
from dynamo_tpu.engine.config import EngineConfig, pow2_cover  # noqa: F401
# (pow2_cover re-exported: engine.engine was its historical home)
from dynamo_tpu.engine import sampling
from dynamo_tpu.kv_fleet_metrics import KV_FLEET
from dynamo_tpu.kv_integrity import KV_INTEGRITY, KvQuarantine
from dynamo_tpu.kv_quant import KV_QUANT, QuantizedPages, to_pool_dtype
from dynamo_tpu.kv_router.protocols import (
    ForwardPassMetrics,
    KvCacheEvent,
    KvStats,
    WorkerStats,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import (
    decode_attention_for,
    prefill_attention_pairs,
)
from dynamo_tpu.overload import (
    OVERLOAD,
    PRIORITY_HIGH,
    AdmissionController,
    EngineOverloadedError,
    PreemptedError,
)
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from dynamo_tpu.spec.metrics import SPEC
from dynamo_tpu.spec.proposer import comb_parents
from dynamo_tpu.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
)
from dynamo_tpu.telemetry import (
    TRACES,
    FlightRecorder,
    TelemetryRegistry,
    request_histograms,
)
from dynamo_tpu.telemetry import metrics as tmetrics
from dynamo_tpu.telemetry import prof as tprof
from dynamo_tpu.telemetry.prof import PROF, RoundProf
from dynamo_tpu.telemetry.trace import Span, span_now
from dynamo_tpu.tenancy.metrics import TENANT
from dynamo_tpu.tenancy.quotas import TenantQuotas
from dynamo_tpu.tokens import TokenBlockSequence

log = logging.getLogger(__name__)

_FIRST_TOKEN_KEY_TAG = 0x46697273  # distinct PRNG stream for first tokens

# columns of the packed uint32 row a lane of ``admit_first`` (floats ride
# as their bits; the two keys take two columns each)
(_ROW_SLOT, _ROW_CTX, _ROW_ADAPTER, _ROW_TOP_K, _ROW_TEMP, _ROW_TOP_P,
 _ROW_FREQ, _ROW_PRES, _ROW_REP, _ROW_KEY, _ROW_STEP_KEY) = (
    0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 11)
_ROW_W = 13

# per-request trace spans shipped back in the finishing annotation are
# capped (a 10k-token generation must not grow a 10k-entry span list);
# the total decode-round count still travels in the timing annotation
# and in the ``decode`` span. Deep enough that a request promoted to a
# forensics dossier at finish (a breach is only known then) is whole.
_MAX_ROUND_SPANS = 256


def _span_dict(name: str, t0_monotonic: float, **attrs) -> dict:
    """Span ending now that began at monotonic ``t0_monotonic`` — the
    annotation-ready wire form (telemetry.trace.span_now)."""
    return span_now(name, t0_monotonic, **attrs).to_dict()


# attribution-segment indices (telemetry/prof.py SEGMENTS), bound once so
# the round loop's enter() calls pass ints, not strings
_SEG_INTAKE = tprof.SEGMENTS.index("intake")
_SEG_SLOT_SCAN = tprof.SEGMENTS.index("slot_scan")
_SEG_FETCH = tprof.SEGMENTS.index("fetch")
_SEG_ANNOTATE = tprof.SEGMENTS.index("annotate")
_SEG_RELEASES = tprof.SEGMENTS.index("releases")
_SEG_TRANSFER = tprof.SEGMENTS.index("transfer")
_SEG_OFFLOAD = tprof.SEGMENTS.index("offload")
_SEG_ADMIT = tprof.SEGMENTS.index("admit")
_SEG_ADMIT_PACK = tprof.SEGMENTS.index("admit_pack")
_SEG_ADMIT_LAUNCH = tprof.SEGMENTS.index("admit_launch")
_SEG_ADMIT_FIRST = tprof.SEGMENTS.index("admit_first")
_SEG_SEAL_ASM = tprof.SEGMENTS.index("seal_assembly")
_SEG_DISPATCH = tprof.SEGMENTS.index("dispatch")
_SEG_SPEC = tprof.SEGMENTS.index("spec_dispatch")
_SEG_SEAL_FLUSH = tprof.SEGMENTS.index("seal_flush")
_SEG_METRICS = tprof.SEGMENTS.index("metrics_fold")




@dataclass
class _Request:
    req: PreprocessedRequest
    seq: TokenBlockSequence
    out: asyncio.Queue
    loop: asyncio.AbstractEventLoop
    # the prompt — kept separate from req.token_ids so engine-side state
    # never mutates the caller's request object
    tokens: list[int] = field(default_factory=list)
    matched_blocks: int = 0
    # prompt blocks already copy-committed into the prefix cache; chunked
    # prefill seals complete blocks INCREMENTALLY (each chunk's full pages
    # become prefix-hittable while later chunks still compute — what lets
    # the disagg prefill worker stream them mid-prefill)
    sealed_prefix: int = 0
    # chunked-prefill progress: tokens already in cache (-1 = not started).
    # Prefill runs ONE chunk per scheduling round so decode rounds
    # interleave with long prompts instead of stalling behind them.
    prefill_pos: int = -1
    slot: int = -1
    produced: int = 0
    last_token: int = -1          # newest processed token, not yet in seq
    cancelled: bool = False
    finished: bool = False
    # overload plane: this request's prompt tokens are counted in the
    # engine's waiting-prefill-token backlog (set at intake, cleared
    # exactly once when the request gets a lane or leaves the queue)
    counted: bool = False
    enqueue_time: float = field(default_factory=time.monotonic)
    first_token_time: Optional[float] = None
    # telemetry: worker-side span dicts (queue/prefill/decode rounds —
    # telemetry/trace.py), round-batched inter-token gaps as (gap_s, n),
    # and timestamps backing them
    trace_spans: list[dict] = field(default_factory=list)
    # per-round decode/spec spans accumulate as raw tuples
    # (kind, t0_monotonic, duration_s, n_tokens, spec_host) and are
    # materialized into span dicts ONCE at finish (_final_annotations) —
    # the per-round dict/round() churn was measurable annotate tax on
    # the hot loop, paid even for requests whose trace nobody reads
    round_spans: list[tuple] = field(default_factory=list)
    itl_gaps: list[tuple] = field(default_factory=list)
    t_prefill_start: Optional[float] = None
    t_last_emit: Optional[float] = None
    # first_token span attrs: prefill dispatches this request took part
    # in, and decode rounds in flight when its last one was dispatched
    prefill_chunks: int = 0
    rounds_at_dispatch: int = 0
    decode_rounds: int = 0
    # the ``decode`` phase (_note_emit): the rounds of them dispatched
    # behind a prefill program, this request's own gaps at those rounds,
    # and the padded prompt positions that stood ahead of them
    rounds_behind_prefill: int = 0
    behind_prefill_s: float = 0.0
    prefill_tokens_ahead: int = 0
    # its LATE rounds (RoundProf.judge_round) and their excess by cause
    late_rounds: int = 0
    late_s: dict = field(default_factory=dict)
    # speculative decoding (spec/): a speculating slot's device lane
    # stays PARKED (dest=scratch) — its real state lives here on the
    # host and in the ctx region, driven by verify dispatches instead of
    # the fused decode round.
    spec: bool = False
    spec_ready: bool = False       # host knows the pending token
    spec_inflight: bool = False    # a verify dispatch is outstanding
    # full sequence incl. the pending token (region holds KV for all but
    # the last element) — the proposers' lookup corpus
    spec_tokens: list[int] = field(default_factory=list)
    spec_keys: Optional[np.ndarray] = None  # [2] uint32 PRNG key
    # host mirror of the sampler's output-token counts histogram [V] —
    # allocated only for penalized requests (the verifier's penalized
    # accept path consumes it; despec restores it onto the device state)
    spec_counts: Optional[np.ndarray] = None
    spec_proposed: int = 0
    spec_accepted: int = 0
    # acceptance gating: a gated stream runs on the fused round
    # (spec=False) but keeps mirroring its sequence/counts through
    # _spec_gated_advance so speculation can re-arm mid-stream
    spec_gated: bool = False
    spec_rearm_left: int = 0     # fused tokens until a re-arm attempt
    spec_gate_backoff: int = 1   # re-arm budget multiplier (doubles)
    # two-phase re-arm drain: in-flight round entries whose dispatch-time
    # snapshot still steps this lane (the clear patch lands after them
    # in program order; their tokens are real and must be mirrored)
    spec_rearm_wait: int = 0
    # tenancy plane: SFQ virtual finish-time stamp minted at enqueue
    # (tenant virtual clock + prompt cost / weight) — orders
    # same-priority waiting entries so a storming tenant self-paces
    # behind its own stamps (see _enqueue_waiting)
    vft: float = 0.0

    @property
    def tenant(self) -> str:
        return getattr(self.req, "tenant", "") or "default"

    @property
    def adapter_id(self) -> int:
        return int(getattr(self.req, "adapter_id", 0) or 0)

    @property
    def prompt_len(self) -> int:
        return len(self.tokens)

    def max_new_tokens(self, max_context: int) -> int:
        mt = self.req.stop_conditions.max_tokens
        cap = max_context - self.prompt_len
        return min(mt, cap) if mt is not None else cap

    def emit(self, item: LLMEngineOutput | Exception) -> None:
        # the client's event loop can be gone by the time the engine
        # thread flushes (interpreter/test teardown, _fail_all during
        # shutdown) — a raise here would mask the ORIGINAL engine
        # failure with "RuntimeError: Event loop is closed"
        try:
            self.loop.call_soon_threadsafe(self.out.put_nowait, item)
        except RuntimeError:
            log.debug("dropped emit to a closed event loop (shutdown)")


@dataclass
class _Entry:
    """One in-flight fetch: either a round of stacked step tokens or a
    request's prefill first-token."""

    kind: str                      # "round" | "first"
    handle: Any                    # device array being copied to host
    # round:
    slots: list[Optional[_Request]] = field(default_factory=list)  # snapshot
    n_steps: int = 0
    # first: the request, and its row of the dispatch's shared handles
    # (admit_first returns one token and one packed logprob row a lane)
    request: Optional[_Request] = None
    row: int = 0
    # offload: hashes/parents aligned with the gathered pages
    hashes: list[int] = field(default_factory=list)
    parents: list[int] = field(default_factory=list)
    # logprobs: ONE packed f32 handle — [F, B, 1+2K] for rounds,
    # [K, 1+2N] for "first" entries (chosen | top ids as f32 | top lps;
    # see _build_jits.pack_lp / _unpack_lp)
    lp_handle: Optional[Any] = None
    # spec verify: (slot, request, history-length-at-dispatch) per live
    # row, aligned with the leading rows of the fetched arrays
    rows: list[tuple] = field(default_factory=list)
    # spec verify: (n_out [B], new_keys [B, 2]) device handles fetched
    # alongside `handle` (the [B, K+1] accepted-token array)
    aux: Any = None
    # telemetry: dispatch time, for dynamo_engine_round_seconds
    t_dispatch: float = 0.0
    # round: (ordinal, prefill programs, their padded tokens) dispatched
    # since the round before: what stood ahead of it on the device
    ahead: tuple = (0, 0, 0)
    # round, once consumed LATE: its excess by cause (judge_round)
    late: Optional[dict] = None
    # spec verify: (draft_s, verify_s) host dispatch walls — become the
    # spec_draft / spec_verify child spans under the round span
    spec_host: Any = None


# sentinel closing an export stream's chunk queue (engine loop -> consumer)
_STREAM_EOS = object()


@dataclass
class _ExportStream:
    """One in-flight chunked page export: the engine loop advances it a
    little every round (dispatch up to ``inflight`` padded gathers with
    copy_to_host_async, convert ready heads, feed the consumer queue) —
    the loop never blocks on the consumer, and the D2H of chunk i
    overlaps the gather/compute behind chunk i+1."""

    ids: list[int]
    chunk_pages: int
    inflight: int
    out_q: queue_mod.Queue
    pos: int = 0                      # next page index to gather
    # (n_real_pages, data handle, scales handle|None) per dispatched,
    # unconsumed chunk
    pending: deque = field(default_factory=deque)
    # hash-addressed exports pin their matched refs until every gather
    # is dispatched (device order then protects the reads)
    free_pages: Optional[list[int]] = None
    # last time this stream moved (dispatch/convert), seeded with the
    # registration time: a stream whose consumer vanished mid-pull (or
    # before pulling anything) parks with a full queue forever, which
    # would leak its pinned pages — the loop reclaims it after the
    # transfer deadline of inactivity
    last_progress: float = field(default_factory=time.monotonic)


class TpuEngine:
    """Pipelined continuous-batching paged-KV engine on a jax mesh."""

    def __init__(
        self,
        model_config: ModelConfig,
        engine_config: Optional[EngineConfig] = None,
        *,
        params: Any = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        mesh_config: Optional[MeshConfig] = None,
        rng_seed: int = 0,
        on_kv_event: Optional[Callable[[KvCacheEvent], None]] = None,
        on_metrics: Optional[Callable[[ForwardPassMetrics], None]] = None,
        on_dispatch: Optional[Callable[[str, dict], None]] = None,
        draft_config: Any = None,
        draft_params: Any = None,
    ):
        self.config = model_config
        self.ecfg = engine_config or EngineConfig()
        self.mesh = mesh or make_mesh(mesh_config)
        # which decode attention every round program is traced with: the
        # compiled Pallas kernel on TPU devices, the jnp reference on the
        # CPU test meshes — decided here, once, from the mesh's devices
        self.decode_attn = decode_attention_for(self.mesh)
        # what the model's block says of itself (models/llama.py: the
        # block protocol), asked once: the counter row its round brings
        # home, and what of its state the row-only planes cannot carry
        self._stats_layout = llama.stats_layout(model_config)
        multiple = llama.page_multiple(model_config)
        if self.ecfg.page_size % multiple:
            raise ValueError(
                f"page_size={self.ecfg.page_size} is no multiple of the "
                f"sparse attention's block ({multiple}): a prefill "
                "chunk starts on a page and has to start on a block")
        what = llama.state_called(model_config)
        if what is not None:
            self._refuse_row_only_planes(self.ecfg, on_dispatch,
                                         draft_config, what)
        dev0 = self.mesh.devices.flat[0]
        log.info(
            "engine devices: platform=%s device_kind=%s mesh=%s "
            "decode_attention=%s",
            dev0.platform, dev0.device_kind, dict(self.mesh.shape),
            self.decode_attn.impl,
        )
        self.on_metrics = on_metrics
        # multihost leader hook: every device dispatch is broadcast to the
        # follower hosts BEFORE being issued locally (engine/multihost.py).
        # Followers replay the identical jit sequence (incl. the sp ring
        # prefill, its own command); host-offload tiers, the page-transfer
        # plane and multimodal injection are single-host features and are
        # rejected below/at their call sites.
        self.on_dispatch = on_dispatch
        if on_dispatch is not None:
            if (self.ecfg.host_offload_pages > 0
                    or self.ecfg.disk_offload_pages > 0):
                raise ValueError(
                    "multihost engine: host/disk offload tiers are "
                    "single-host features"
                )
            if self.ecfg.speculative != "off":
                raise ValueError(
                    "multihost engine: speculative decoding is a "
                    "single-host feature (the verify/propose dispatch "
                    "sequence is data-dependent on fetched results)"
                )

        c, e = self.config, self.ecfg
        cache_dtype = jnp.dtype(e.cache_dtype)
        # int8 KV-block economy: the paged pool (and every tier/transfer
        # consumer downstream of it) stores int8 pages + per-block
        # scales; the serving ctx region stays cache_dtype
        self.kv_quant = e.kv_quant == "int8"
        # ctx region quantized too (in-kernel dequant decode hot path);
        # one flag so mixed-precision experiments can split them later
        self.ctx_quant = self.kv_quant
        # ring-flush requantize geometry: every lane rewrites the same
        # window of scale groups once per round (see llama._flush_ctx_quant)
        _g = max(1, e.page_size)
        _nG = -(-e.max_context // _g)
        self._flush_groups_per_round = e.max_decode_slots * min(
            -(-e.flush_every // _g) + 1, _nG)
        p_sh = llama.param_shardings(c, self.mesh)
        if params is None:
            params = llama.init_params(c, rng_seed)
        self.params = llama.serving_params(c, jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, p_sh))
        # resident LoRA adapter bank (tenancy plane): rides INSIDE the
        # params pytree so every jitted program carries it with zero
        # signature churn — the model fns look it up via
        # params.get("adapters") (a trace-time presence check; engines
        # without a bank trace the identical pre-tenancy programs). Row
        # 0 is the all-zeros identity = the base model, exactly.
        self.n_adapters = max(0, e.lora_adapters)
        if self.n_adapters > 0:
            from dynamo_tpu.tenancy.adapters import (
                init_adapter_bank,
                replicate_bank,
            )

            self.params = dict(
                self.params,
                adapters=replicate_bank(
                    init_adapter_bank(c, self.n_adapters, e.lora_rank),
                    self.mesh,
                ),
            )
        # paged pool: prefix-cache STORAGE (sealed blocks copied in,
        # admission prefixes copied out — models/llama.py module doc)
        self.cache = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            llama.init_cache(c, e.num_pages, e.page_size, cache_dtype,
                             kv_quant=e.kv_quant),
            llama.cache_shardings(c, self.mesh, kv_quant=e.kv_quant),
        )
        # contiguous per-slot serving context (+1 scratch lane for freed
        # slots' in-flight garbage steps). Under kv_quant=int8 the ctx
        # region is int8 too (group == page_size scale grid), so the
        # decode kernel streams half the live-KV bytes and pool<->ctx
        # copies at seal/admission are raw int8 moves.
        self.ctx = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            llama.init_ctx(c, e.max_decode_slots, e.max_context, cache_dtype,
                           kv_quant=e.kv_quant, group=e.page_size),
            llama.ctx_shardings(c, self.mesh, kv_quant=e.kv_quant),
        )
        # decode write ring: the round's steps write here; flush_ctx
        # writes it into the ctx region once per round (keeping the
        # GB-scale region read-only inside the round — see llama.init_ring)
        self.ring = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            llama.init_ring(c, e.max_decode_slots, e.flush_every, cache_dtype),
            llama.ring_shardings(c, self.mesh),
        )
        # a page of K/V rows without the recurrent state at its boundary
        # cannot resume a prompt: a model with recurrent layers takes no
        # prefix match and seals nothing (a preempted or migrated stream
        # recomputes from position 0)
        prefix_caching = e.enable_prefix_caching and llama.pages_resume(c)
        if e.enable_prefix_caching and not prefix_caching:
            log.info("prefix cache bypassed: this model's layers carry a "
                     "recurrent state that pages of K/V rows do not hold")
        self.allocator = PageAllocator(
            e.num_pages, e.page_size,
            worker_id=e.worker_id,
            on_event=on_kv_event,
            enable_prefix_caching=prefix_caching,
        )
        # host-DRAM offload tier (KVBM G2): parked pages are batch-gathered
        # once per round and fetched to host behind compute. A deque:
        # on_park appends from BOTH the engine loop and the disagg asyncio
        # thread; the dispatcher drains with popleft (both thread-safe),
        # never a swap that could drop a concurrent append.
        self.offload = None
        self._offload_cands: deque = deque()
        # KV integrity plane (kv_integrity.py): one quarantine shared by
        # every host tier — a block that ever failed verification is
        # dropped everywhere and refused re-admission until its TTL
        # lapses, so the stream recomputes it instead of re-serving rot
        self.kv_quarantine = KvQuarantine()
        if e.disk_offload_pages > 0 and e.host_offload_pages <= 0:
            raise ValueError(
                "disk_offload_pages (G3) requires host_offload_pages (G2): "
                "the tier hierarchy is strict (block_manager.rs:69-82)"
            )
        if e.host_offload_pages > 0:
            from dynamo_tpu.engine.offload import (
                DiskOffloadTier,
                HostOffloadTier,
            )

            page_shape = (
                2, c.cache_planes, c.num_kv_heads, e.page_size, c.head_dim
            )
            # tiers store what the pool stores: int8 pages + per-page
            # scale sidecars under kv_quant, so G2/G3 hold ~2x the
            # blocks per byte too
            tier_dtype = np.int8 if self.kv_quant else cache_dtype
            scale_shape = (2, c.cache_planes) if self.kv_quant else ()
            spill = None
            if e.disk_offload_pages > 0:
                spill = DiskOffloadTier(
                    e.disk_offload_pages, page_shape, tier_dtype,
                    path=e.disk_offload_path, scale_shape=scale_shape,
                    quarantine=self.kv_quarantine,
                    scrub_on_start=e.scrub_on_start,
                )
            self.offload = HostOffloadTier(
                e.host_offload_pages, page_shape, tier_dtype, spill=spill,
                scale_shape=scale_shape, quarantine=self.kv_quarantine,
            )
            self.allocator.on_park = (
                lambda p, h, par: self._offload_cands.append((p, h, par))
            )

        # speculative decoding (dynamo_tpu/spec/): proposers, the fused
        # verifier, acceptance counters. Eligible slots bypass the fused
        # decode round entirely — see _dispatch_spec.
        self.spec = None
        if e.speculative != "off":
            from dynamo_tpu.spec import SpecDecoder

            self.spec = SpecDecoder(
                c, e, mesh=self.mesh,
                draft_config=draft_config, draft_params=draft_params,
                rng_seed=rng_seed,
            )

        # telemetry: latency histograms (scraped by the system server,
        # shipped to the exporter inside ForwardPassMetrics) + the
        # flight-recorder ring of recent dispatches
        self.telemetry = request_histograms(TelemetryRegistry(), engine=True)
        self._h_ttft = self.telemetry.get(tmetrics.TTFT[0])
        self._h_itl = self.telemetry.get(tmetrics.ITL[0])
        self._h_e2e = self.telemetry.get(tmetrics.E2E[0])
        self._h_queue = self.telemetry.get(tmetrics.QUEUE[0])
        self._h_round = self.telemetry.get(tmetrics.ROUND[0])
        self._h_first_token = self.telemetry.get(tmetrics.FIRST_TOKEN[0])
        self._h_frontend = self.telemetry.get(tmetrics.FRONTEND[0])
        self._h_tpot = self.telemetry.get(tmetrics.TPOT[0])
        self._h_step_gap = self.telemetry.get(tmetrics.STEP_GAP[0])
        self._h_step_gap_clean = self.telemetry.get(
            tmetrics.STEP_GAP_CLEAN[0])
        self._h_pf_ahead = self.telemetry.get(
            tmetrics.ROUND_PREFILL_AHEAD[0])
        self._h_dry = self.telemetry.get(tmetrics.DISPATCH_DRY[0])
        self._h_pf_tokens = self.telemetry.get(tmetrics.PREFILL_TOKENS[0])
        self._h_pf_padded = self.telemetry.get(tmetrics.PREFILL_PADDED[0])
        self._h_pf_matched = self.telemetry.get(tmetrics.PREFILL_MATCHED[0])
        self._h_pf_live = self.telemetry.get(tmetrics.PREFILL_ATTN_LIVE[0])
        self._h_pf_scored = self.telemetry.get(
            tmetrics.PREFILL_ATTN_SCORED[0])
        self._h_live_steps = self.telemetry.get(
            tmetrics.ROUND_LIVE_LANE_STEPS[0])
        self._h_round_tokens = self.telemetry.get(tmetrics.ROUND_TOKENS[0])
        self._h_pf_continued = self.telemetry.get(
            tmetrics.PREFILL_CONTINUED[0])
        self._h_moe_pf_sorted = self.telemetry.get(
            tmetrics.MOE_PREFILL_ROWS_SORTED[0])
        self._h_moe_pf_moved = self.telemetry.get(
            tmetrics.MOE_PREFILL_ROWS_MOVED[0])
        # the block's counters, one observation per consumed decode
        # round; they ride the round's stacked-token fetch (an extra
        # row): (column, histogram, is a float32's bits) for every column
        # of the declared layout that feeds one
        self._stats_table = [
            (col, self.telemetry.get(counter.metric), counter.f32_bits)
            for col, counter in enumerate(self._stats_layout)
            if counter.metric is not None]
        # the host's mirrors of what the block's attention reads in a
        # dispatched round and a prefill dispatch (None: it has none)
        self._decode_mirror = llama.decode_mirror(
            c, e.max_context, e.flush_every, self.decode_attn)
        self._prefill_mirror = llama.prefill_mirror(
            c, self.decode_attn, e.kv_quant)
        # bytes a token holds in the ctx region over how many planes of
        # rows, and bytes a lane holds in recurrent state whatever its
        # context: observed once, here
        recurrent = llama.state_kinds(self.ctx)
        kv_row_bytes = sum(
            x.nbytes for n, leaf in self.ctx.items() if n not in recurrent
            for x in jax.tree.leaves(leaf)
        ) / ((e.max_decode_slots + 1) * e.max_context)
        planes = max(self.ctx[n].shape[0] for n in llama.row_kinds(self.ctx))
        self.telemetry.get(tmetrics.KV_ROW_BYTES[0]).observe(kv_row_bytes)
        self.telemetry.get(tmetrics.KV_CACHE_PLANES[0]).observe(planes)
        log.info("engine state: kv_row_bytes=%d cache_planes=%d lanes=%d "
                 "max_context=%d", kv_row_bytes, planes, e.max_decode_slots,
                 e.max_context)
        if recurrent:
            self.telemetry.get(tmetrics.SSM_STATE_BYTES[0]).observe(sum(
                x.nbytes for n in recurrent
                for x in jax.tree.leaves(self.ctx[n])
            ) / (e.max_decode_slots + 1))
        # histogram snapshots are built per metrics() call, which the
        # engine loop makes EVERY round via on_metrics while the
        # publisher throttles to ~4 Hz — cache at the publish cadence so
        # the per-round cost is a timestamp compare, not 5 locked walks
        self._hist_snap: tuple[float, dict] = (0.0, {})
        # flight recorder (telemetry/flight.py): a ring of the most
        # recent engine-round events (FlightRecorder's own capacity),
        # served at /debug/flight and dumped to the log when an engine
        # round fails
        self.flight = FlightRecorder()
        # performance-attribution plane (telemetry/prof.py): per-round
        # host-segment switch timers, folded into the process-global
        # PROF registry at the metrics-publish cadence and served at
        # /debug/prof; the benchmark reads prof.totals() in every cell
        self.prof = RoundProf()
        PROF.configure(e.slo_ttft_target_s, e.slo_itl_target_s,
                       e.slo_objective)
        # tail-latency forensics (telemetry/forensics.py): worker-side
        # breach capture for remote-worker mode — dossiers assembled
        # straight from this engine's prof/flight rings into OUTLIERS
        from dynamo_tpu.telemetry.forensics import ForensicsCapture
        self._forensics = ForensicsCapture(
            sample_rate=e.forensics_sample_rate,
            ttft_target_s=e.slo_ttft_target_s,
            itl_target_s=e.slo_itl_target_s,
            engines_fn=lambda: [self],
        )

        B = e.max_decode_slots
        self._B = B
        self._slots: list[Optional[_Request]] = [None] * B
        # slots reserved by an in-progress (multi-chunk) prefill: occupied
        # but NOT decoding — their dev lane stays parked on scratch until
        # admit_first admits them
        self._prefilling: dict[int, _Request] = {}
        # host mirror of dispatch-time context lengths
        self._ctx_disp = np.ones(B, np.int32)
        # numpy-backed slot-state mirrors (the slot_scan diet): updated
        # incrementally at every slot transition (_slot_on/_slot_off —
        # admission, despeculation, finish, release, fail_all) so the
        # per-round scheduling decisions are O(1) numpy reductions over
        # these instead of per-slot Python attribute walks.
        #   _slot_active: occupied AND not finished AND not speculating
        #   _slot_spec:   speculating (lane parked, verify-driven)
        #   _slot_lp / _slot_sampler: the slot's contribution to the
        #       round's want_lp / want_sample flags when active
        self._slot_active = np.zeros(B, bool)
        self._slot_spec = np.zeros(B, bool)
        self._slot_lp = np.zeros(B, bool)
        self._slot_sampler = np.zeros(B, bool)
        # cached (active list, want_lp, want_sample); invalidated on any
        # slot transition — steady decode recomputes it zero times/round
        self._active_cache: Optional[tuple[list[int], bool, bool]] = None

        # device state dict
        self._dev = {
            "tokens": jnp.zeros(B, jnp.int32),
            "ctx": jnp.ones(B, jnp.int32),
            # live slots write their own ctx lane; freed slots write the
            # scratch lane B (protects lanes being re-prefilled)
            "dest": jnp.full((B,), B, jnp.int32),
            "keys": jnp.zeros((B, 2), jnp.uint32),
            "counts": jnp.zeros((B, c.vocab_size), jnp.int32),
            "temp": jnp.zeros(B, jnp.float32),
            "top_k": jnp.zeros(B, jnp.int32),
            "top_p": jnp.ones(B, jnp.float32),
            "freq": jnp.zeros(B, jnp.float32),
            "pres": jnp.zeros(B, jnp.float32),
            "rep": jnp.ones(B, jnp.float32),
            # per-slot resident LoRA bank row (0 = identity base model);
            # gathered inside the fused round program — mixed adapters
            # in one decode batch cost zero extra dispatches
            "adapter": jnp.zeros(B, jnp.int32),
        }

        self._build_jits()

        self._intake: queue_mod.Queue = queue_mod.Queue()
        self._xfer: queue_mod.Queue = queue_mod.Queue()  # page export/import
        # idle-loop doorbell: producers (submit intake, _xfer_op page
        # ops) set it after enqueueing so the idle sleep in _run_loop
        # wakes immediately instead of finishing its 20 ms nap — the
        # decode-side import latency that capped disagg chunk streaming
        self._wake_evt = threading.Event()
        # chunked page exports in flight (kv_transfer chunk pipeline):
        # advanced a little every round, never blocking the loop
        self._xfer_streams: list[_ExportStream] = []
        # G4 remote tier: pages fetched from peer pools land here (from
        # the serving asyncio thread) and drain into the G2 host tier on
        # the engine loop before admission (kv_transfer.RemoteKvFetcher)
        self.remote_kv: Any = None
        self._host_ingest: queue_mod.Queue = queue_mod.Queue()
        self.remote_onboard_blocks = 0
        # fleet prefix economy: the frontend's replica/holder hint digest
        # (kv_router/fleet.py FleetHints), applied via apply_fleet_hints;
        # consulted by dedup admission and tier eviction. None until the
        # first hint push arrives.
        self.fleet_hints: Any = None
        self._waiting: list[_Request] = []
        # overload plane (dynamo_tpu/overload/): bounded admission over
        # the not-yet-prefilling backlog. The token counter is updated
        # from BOTH the asyncio intake side and the engine thread, so it
        # takes the lock; reads for budget checks are advisory.
        self.admission = AdmissionController(
            e.max_waiting_requests,
            e.max_waiting_prefill_tokens,
            queue_wait_s=lambda: self._h_queue.percentile(0.5),
        )
        self._waiting_tokens = 0
        self._wt_lock = threading.Lock()
        # tenancy plane (dynamo_tpu/tenancy/): per-tenant slices of the
        # backlog budgets + SFQ fair-share state. The per-tenant
        # counters ride the same `counted` flag / _wt_lock as
        # _waiting_tokens (inc at intake, dec exactly once at lane
        # acquisition or queue exit).
        self.tenant_quotas = TenantQuotas(
            e.tenant_max_waiting_requests,
            e.tenant_max_waiting_prefill_tokens,
            weights=e.tenant_weights,
        )
        self._tenant_waiting: dict[str, int] = {}
        self._tenant_tokens: dict[str, int] = {}
        # SFQ virtual clocks: per-tenant virtual finish time of the last
        # enqueued request, and the global clock advanced as requests
        # start service — a light tenant's fresh arrival stamps near the
        # global clock, i.e. near the queue head (engine thread only)
        self._tenant_vnow: dict[str, float] = {}
        self._vclock = 0.0
        self.sheds = 0                # deadline-expired waiting requests
        self.waiting_preemptions = 0  # waiting entries evicted by priority
        self.preempt_migrations = 0   # running streams force-migrated
        self._entries: list[_Entry] = []
        # the time between tokens, from inside: prefill programs and
        # their padded tokens dispatched since the last fused round (what
        # stands ahead of the next one), an output of the newest model
        # program dispatched (the device runs one in-order queue: when it
        # is ready the device stands dry), the last round's consume time
        self._ahead = [0, 0]
        self._newest: Any = None
        # (rows moved on the device, rows sorted) of the prefill programs
        # in flight whose expert layers move rows in the looped form
        self._moe_rows: deque = deque()
        self._t_round_consumed = 0.0
        # sealed blocks awaiting the batched ctx->pool copy:
        # (slot, start_pos, pool_page)
        self._seal_queue: list[tuple[int, int, int]] = []
        self._to_release: list[_Request] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._started = False
        # graceful drain (resilience/drain.py): begin_drain() stops
        # admissions; drained() flips once in-flight work finishes
        self._draining = False
        self._drained_evt = threading.Event()
        self.step_count = 0
        self.tokens_generated = 0
        self.sp_prefills = 0
        self.batch_prefills = 0     # batched-prefill dispatches (K >= 2)
        # dispatch-budget accounting (pinned per round by
        # tests/test_dispatch_budget.py; copied into every snapshot of
        # benchmarks/server.py): every host->device program launch or
        # async D2H fetch initiation increments its bucket
        self.dispatch_counts: dict[str, int] = {
            "round": 0, "round_seal": 0, "seal": 0, "patch": 0,
            "prefill": 0, "prefill_batch": 0, "sp_prefill": 0,
            "load_ctx": 0, "admit_first": 0, "fetch": 0, "encode": 0,
            "offload_gather": 0, "xfer_gather": 0, "xfer_scatter": 0,
            # speculative path: the fused batch-draft and verify
            # programs
            "spec_draft": 0, "spec_verify": 0,
        }
        # prefix-commit event plane: subscribers (the disagg streaming
        # export, offload candidacy, future replication) are notified
        # when a seal batch's pool copy is DISPATCHED — exporting after
        # the callback is device-order safe — instead of polling the
        # allocator on a fixed cadence (the PR 5 2 ms poll)
        self._commit_cbs: list[Callable[[], None]] = []
        self._commit_lock = threading.Lock()
        self._last_metrics_pub = 0.0
        # round-pipeline accounting (ecfg.round_pipeline): early-dispatch
        # counters behind pipeline_stats() — pipeline_depth is the mean
        # rounds in flight right after an early dispatch, overlap_ratio
        # the fraction of pipelined-round host time spent in the
        # completion half (i.e. running WHILE the early dispatch executes
        # on device). pipe_flushes counts why the pipeline fell back to
        # the strict order, per flush point.
        self._pipe_dispatches = 0
        self._pipe_depth_sum = 0
        self._pipe_hidden_s = 0.0
        self._pipe_host_s = 0.0
        self.pipe_flushes: dict[str, int] = {
            "drain": 0, "admission": 0, "release": 0,
            "seal_overflow": 0, "spec": 0,
        }

    # ------------------------------------------------------------------
    # jitted programs

    def _build_jits(self) -> None:
        c, e = self.config, self.ecfg
        max_top_k = e.max_top_k
        max_context = e.max_context
        # fused-seal width: ONE static shape so the fused round program
        # compiles exactly once per (n_steps, lp, sample) combo — a
        # pow2-per-batch width would compile the whole round program per
        # width bucket (measured +40% on the CPU test suite). Sized for
        # a full aligned burst (every slot completing blocks the same
        # round); larger admission-time bursts overflow to the
        # standalone seal_blocks path.
        self._seal_fuse_w = pow2_cover(max(
            e.max_decode_slots,
            e.max_decode_slots * e.flush_every // max(e.page_size, 1),
            1,
        ))

        max_logprobs = e.max_logprobs

        def pack_lp(chosen, ids, lps):
            """One f32 row [..., 1+2K] per step: chosen logprob, top ids
            (exact in f32 — vocab << 2^24), top logprobs. Packing means
            ONE stacked fetch per lp round instead of three separate
            copy_to_host_async pipelines (the dispatch diet)."""
            return jnp.concatenate(
                [chosen[..., None], ids.astype(jnp.float32), lps], axis=-1
            )

        def round_body(params, ctx_kv, ring, dev, n_steps, want_lp,
                       want_sample):
            """A FULL scheduling round in one program: n_steps fused
            decode+sample steps via lax.fori_loop (body compiles once) and
            the ring->ctx flush — one dispatch + one result fetch per
            round instead of n_steps+2, the single biggest lever on
            per-step host overhead. The ctx region is READ-ONLY until the
            tail flush (write/read interleave on it forces XLA copies —
            llama.init_ring). `want_lp` adds the logprob computation only
            for rounds that asked for it; `want_sample` gates the full
            sampler — all-greedy rounds (the common serving case) take a
            bare argmax instead of top-k over the vocab."""
            B = dev["tokens"].shape[0]
            ring_base = jnp.maximum(dev["ctx"] - 1, 0)
            # a block that counts: one more row (more where its columns
            # outnumber the lanes) carries the round's counters
            # (llama.stats_layout) home in the same fetch
            rides = -(-len(llama.stats_layout(c)) // B)
            toks_out = jnp.zeros((n_steps + rides, B), jnp.int32)
            stats = llama.stats_zero(c)
            # the region's leaves a decode STEP writes (recurrent state,
            # rows a step completes) where its other rows are read-only
            # until the flush: they ride the loop's carry ({} for a block
            # whose step writes the ring only)
            stepped = {n: ctx_kv[n] for n in llama.stepped_kinds(c, ctx_kv)}
            lp_out = (
                jnp.zeros((n_steps, B, 1 + 2 * max_logprobs), jnp.float32)
                if want_lp else None
            )
            sp = sampling.SamplingParams(
                temperature=dev["temp"], top_k=dev["top_k"], top_p=dev["top_p"],
                frequency_penalty=dev["freq"], presence_penalty=dev["pres"],
                repetition_penalty=dev["rep"],
            )

            # the lanes that hold a request: a freed lane's device length
            # keeps counting up, so every block's decode attention and
            # step kernels walk a work list of these lanes only, and
            # freed / garbage lanes claim no expert capacity (masking
            # keeps outputs batch-independent)
            live = dev["dest"] != B

            def body(s, carry):
                ring, dev, toks_out, lp_out, stats, stepped = carry
                ring, stepped, logits, stats = llama.round_step(
                    c, params, ctx_kv, ring, stepped, dev["tokens"],
                    dev["ctx"], ring_base, s, live, dev["adapter"], stats,
                    attn=self.decode_attn,
                )
                if want_sample:
                    toks, st = sampling.sample_step_impl(
                        logits,
                        sampling.SamplerState(dev["keys"], dev["counts"]),
                        sp, max_top_k,
                    )
                    keys, counts = st.keys, st.counts
                else:
                    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    keys, counts = dev["keys"], dev["counts"]
                toks_out = jax.lax.dynamic_update_index_in_dim(
                    toks_out, toks, s, 0
                )
                if want_lp:
                    chosen, ids, lps = sampling.compute_logprobs(
                        logits, toks, max_logprobs
                    )
                    lp_out = jax.lax.dynamic_update_index_in_dim(
                        lp_out, pack_lp(chosen, ids, lps), s, 0
                    )
                dev = dict(
                    dev,
                    tokens=toks,
                    ctx=jnp.minimum(dev["ctx"] + 1, max_context),
                    keys=keys,
                    counts=counts,
                )
                return ring, dev, toks_out, lp_out, stats, stepped

            ring, dev, toks_out, lp_out, stats, stepped = jax.lax.fori_loop(
                0, n_steps, body,
                (ring, dev, toks_out, lp_out, stats, stepped)
            )
            if rides == 1:   # as it always was: those programs' text stays
                toks_out = toks_out.at[n_steps, :stats.shape[0]].set(stats)
            elif rides:
                toks_out = toks_out.at[n_steps:].set(jnp.pad(
                    stats, (0, rides * B - stats.shape[0])
                ).reshape(rides, B))
            # round boundary: the ring goes into the ctx region, one
            # in-place span a lane, after every read (llama.flush_ctx_impl)
            valid = jnp.minimum(jnp.int32(n_steps), max_context - ring_base)
            ctx_kv = llama.flush_ctx_impl(
                ctx_kv, ring, dev["dest"], ring_base, valid
            )
            if stepped:
                ctx_kv = dict(ctx_kv, **stepped)
            return ctx_kv, ring, dev, toks_out, lp_out

        engine_round = functools.partial(
            jax.jit, donate_argnums=(1, 2, 3), static_argnums=(4, 5, 6)
        )(round_body)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4),
                           static_argnums=(8, 9, 10))
        def engine_round_seal(params, ctx_kv, ring, dev, cache,
                              seal_slots, seal_starts, seal_pages,
                              n_steps, want_lp, want_sample):
            """engine_round with the round's pending ctx->pool seal batch
            FUSED onto the tail — in steady decode a block completes
            nearly every round, so the previously separate seal_blocks
            program was a per-round straggler dispatch. The seal runs
            after the flush and reads positions written by already-
            dispatched programs (the host only queues a seal for
            positions whose results it has processed, which lag the
            dispatch front by at least a round)."""
            ctx_kv, ring, dev, toks_out, lp_out = round_body(
                params, ctx_kv, ring, dev, n_steps, want_lp, want_sample
            )
            cache = llama.seal_blocks_impl(
                cache, ctx_kv, seal_slots, seal_starts, seal_pages,
                e.page_size,
            )
            return ctx_kv, ring, dev, cache, toks_out, lp_out

        @functools.partial(jax.jit, donate_argnums=(0,))
        def patch(dev, clear_mask, admit_meta, admit_tok, admit_keys,
                  admit_counts):
            """State patch: releases, and the one admission that brings
            its own token and histogram (a slot despeculating back to the
            fused round; a prompt's admission rides ``admit_first``).
            ``admit_meta`` is ONE packed f32[9] row — [slot, ctx, temp,
            top_k, top_p, freq, pres, rep, adapter] — instead of eleven
            scalar device_puts per admission (every int here is exact in
            f32; ctx and adapter ids < 2^24). slot == B is the
            no-admission sentinel: every .at[] update is dropped."""
            B = dev["tokens"].shape[0]
            dev = dict(dev)
            dev["ctx"] = jnp.where(clear_mask, 1, dev["ctx"])
            dev["tokens"] = jnp.where(clear_mask, 0, dev["tokens"])
            dev["temp"] = jnp.where(clear_mask, 0.0, dev["temp"])
            dev["counts"] = jnp.where(clear_mask[:, None], 0, dev["counts"])
            # freed slots park on the scratch lane so their in-flight
            # garbage steps can't touch a lane being re-prefilled
            dev["dest"] = jnp.where(
                clear_mask, B, dev["dest"]
            ).astype(jnp.int32)
            dev["adapter"] = jnp.where(clear_mask, 0, dev["adapter"])
            s = admit_meta[0].astype(jnp.int32)
            dev["tokens"] = dev["tokens"].at[s].set(admit_tok[0])
            dev["ctx"] = dev["ctx"].at[s].set(admit_meta[1].astype(jnp.int32))
            dev["dest"] = dev["dest"].at[s].set(s)
            dev["keys"] = dev["keys"].at[s].set(admit_keys)
            # fresh admissions pass the cached zero row; a penalized slot
            # despeculating back to the fused round restores its histogram
            dev["counts"] = dev["counts"].at[s].set(admit_counts)
            dev["temp"] = dev["temp"].at[s].set(admit_meta[2])
            dev["top_k"] = dev["top_k"].at[s].set(
                admit_meta[3].astype(jnp.int32)
            )
            dev["top_p"] = dev["top_p"].at[s].set(admit_meta[4])
            dev["freq"] = dev["freq"].at[s].set(admit_meta[5])
            dev["pres"] = dev["pres"].at[s].set(admit_meta[6])
            dev["rep"] = dev["rep"].at[s].set(admit_meta[7])
            dev["adapter"] = dev["adapter"].at[s].set(
                admit_meta[8].astype(jnp.int32)
            )
            return dev

        @functools.partial(jax.jit, donate_argnums=(0,),
                           static_argnums=(3,))
        def admit_first(dev, logits, rows, want_lp):
            """The first token of every request that finished its prompt
            with ONE prefill dispatch, and their admissions: the
            prefill's logits as it returned them (``[K, V]``, or ``[V]``
            from a solo chunk) and ONE packed ``uint32[K, _ROW_W]``
            upload, a row a lane (``_first_row``). Each row samples with
            its own key (zero counts, no penalties on a first token: what
            a fused step does to a fresh histogram) and is admitted as
            ``patch`` admits: token, ctx, dest, keys, a zero counts row,
            the six sampling scalars, adapter. A row whose slot is B (a
            lane that continues, a dummy, a speculative admission, which
            stays parked) samples and admits nothing: every .at[] update
            is dropped."""
            K = rows.shape[0]
            logits = logits.reshape(K, -1)

            def i32(col):
                return jax.lax.bitcast_convert_type(rows[:, col], jnp.int32)

            def f32(col):
                return jax.lax.bitcast_convert_type(rows[:, col], jnp.float32)

            temp, top_k, top_p = f32(_ROW_TEMP), i32(_ROW_TOP_K), f32(_ROW_TOP_P)
            st = sampling.SamplerState(
                keys=rows[:, _ROW_KEY:_ROW_KEY + 2],
                counts=jnp.zeros(logits.shape, jnp.int32),
            )
            sp = sampling.SamplingParams(
                temperature=temp, top_k=top_k, top_p=top_p,
                frequency_penalty=jnp.zeros(K), presence_penalty=jnp.zeros(K),
                repetition_penalty=jnp.ones(K),
            )
            toks, _ = sampling.sample_step_impl(logits, st, sp, max_top_k)
            lp = (pack_lp(*sampling.compute_logprobs(
                      logits, toks, max_logprobs))
                  if want_lp else None)
            s = i32(_ROW_SLOT)
            dev = dict(
                dev,
                tokens=dev["tokens"].at[s].set(toks),
                ctx=dev["ctx"].at[s].set(i32(_ROW_CTX)),
                dest=dev["dest"].at[s].set(s),
                keys=dev["keys"].at[s].set(
                    rows[:, _ROW_STEP_KEY:_ROW_STEP_KEY + 2]),
                counts=dev["counts"].at[s].set(0),
                temp=dev["temp"].at[s].set(temp),
                top_k=dev["top_k"].at[s].set(top_k),
                top_p=dev["top_p"].at[s].set(top_p),
                freq=dev["freq"].at[s].set(f32(_ROW_FREQ)),
                pres=dev["pres"].at[s].set(f32(_ROW_PRES)),
                rep=dev["rep"].at[s].set(f32(_ROW_REP)),
                adapter=dev["adapter"].at[s].set(i32(_ROW_ADAPTER)),
            )
            return dev, toks, lp  # [K] i32, optional packed [K, 1+2N] f32

        self._engine_round = engine_round
        self._engine_round_seal = engine_round_seal
        self._patch = patch
        self._admit_first = admit_first
        # reusable zero counts row for ordinary admissions (no per-patch
        # [V]-sized H2D upload) + the no-admission token placeholder
        self._zero_counts = jnp.zeros(c.vocab_size, jnp.int32)
        self._zero_tok = jnp.zeros(1, jnp.int32)
        # cached all-scratch dummy seal batch: seal-less rounds reuse it
        # so the fused round costs ZERO extra H2D uploads
        z = jnp.zeros(self._seal_fuse_w, jnp.int32)
        self._zero_seal = (z, z, z)

    # ------------------------------------------------------------------
    # lifecycle

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._thread = threading.Thread(
            target=self._run_loop, name="tpu-engine-loop", daemon=True
        )
        self._thread.start()

    async def stop(self) -> None:
        self._stop.set()
        if self._thread:
            await asyncio.to_thread(self._thread.join, 30.0)
        # items raced in after the loop's own exit drain
        self._drain_xfer_queue()
        if self.offload is not None and self.offload.spill is not None:
            self.offload.spill.close()

    # ---- graceful drain (resilience/drain.py DrainController contract) --

    def begin_drain(self) -> None:
        """Stop admitting: subsequent generate() calls raise the retriable
        WorkerDrainingError; requests already accepted run to completion."""
        self._draining = True
        if not self._started:
            # the loop never ran: nothing can be in flight
            self._drained_evt.set()

    def drained(self) -> bool:
        return self._drained_evt.is_set()

    # ---- prefix-commit event plane ----

    def subscribe_commits(self, cb: Callable[[], None]) -> None:
        """Register a callback fired (from the engine thread) whenever
        the committed prefix grew: sealed blocks became MATCHABLE
        (_queue_seal) or a seal batch's pool copies were dispatched.
        Exporting on this signal is device-order safe because every
        engine-loop export path flushes queued seal copies before its
        pool read. Replaces fixed-cadence allocator polling for
        streaming export / offload candidacy / replication consumers;
        callbacks must be cheap and non-blocking (bounce to your own
        loop/queue)."""
        with self._commit_lock:
            if cb not in self._commit_cbs:
                self._commit_cbs.append(cb)

    def unsubscribe_commits(self, cb: Callable[[], None]) -> None:
        with self._commit_lock:
            if cb in self._commit_cbs:
                self._commit_cbs.remove(cb)

    def _notify_commits(self) -> None:
        with self._commit_lock:
            cbs = list(self._commit_cbs)
        for cb in cbs:
            try:
                cb()
            except Exception:  # noqa: BLE001 — never kill the loop
                log.exception("commit listener failed")

    # ------------------------------------------------------------------
    # tenancy plane: resident adapters

    def install_adapter(self, adapter_id: int, weights: dict) -> None:
        """Install one fine-tune variant's LoRA factors into the
        resident bank (site -> {"a": [L, d_in, r], "b": [L, r, d_out]}).
        Swapping the bank is a pure buffer replacement — shapes/dtypes
        are unchanged, so no jitted program retraces."""
        from dynamo_tpu.tenancy.adapters import replicate_bank, set_adapter

        bank = (self.params or {}).get("adapters")
        if bank is None:
            raise ValueError(
                "engine has no adapter bank (EngineConfig.lora_adapters=0)"
            )
        self.params = dict(
            self.params,
            adapters=replicate_bank(
                set_adapter(bank, adapter_id, weights), self.mesh
            ),
        )

    # ------------------------------------------------------------------
    # AsyncEngine surface

    async def generate(
        self, request: PreprocessedRequest
    ) -> AsyncIterator[LLMEngineOutput]:
        """Stream engine outputs (token-id deltas) for one request."""
        if self._draining:
            from dynamo_tpu.resilience.drain import WorkerDrainingError

            raise WorkerDrainingError(
                "worker draining: not admitting new requests"
            )
        if not self._started:
            self.start()
        if len(request.token_ids) == 0:
            raise ValueError("empty prompt")
        if len(request.token_ids) >= self.ecfg.max_context:
            raise ValueError(
                f"prompt length {len(request.token_ids)} exceeds max context "
                f"{self.ecfg.max_context}"
            )
        tenant = getattr(request, "tenant", "") or "default"
        adapter_id = int(getattr(request, "adapter_id", 0) or 0)
        if adapter_id and not (0 < adapter_id < max(1, self.n_adapters)):
            raise ValueError(
                f"adapter_id {adapter_id} out of range: engine bank has "
                f"{self.n_adapters} adapter slots"
            )
        # overload plane: a deadline that expired before intake is shed
        # immediately — zero tokens, the DEADLINE finish reason, never an
        # error (the client's budget ran out, nothing failed)
        if (request.deadline is not None
                and time.time() > request.deadline):
            self.sheds += 1
            OVERLOAD.inc("dynamo_overload_shed_total")
            yield LLMEngineOutput(
                token_ids=[], finish_reason=FinishReason.DEADLINE,
                annotations={"shed": {"reason": "deadline",
                                      "queued_s": 0.0}},
            )
            return
        # bounded admission: a full waiting queue refuses intake with the
        # retriable overload error (router spills to a peer, frontend
        # answers 429 + Retry-After). A HIGH-priority arrival is admitted
        # anyway — the engine loop restores the budget by preempting the
        # lowest-priority waiting entry (_enforce_bounds).
        if self.admission.bounded:
            waiting = (sum(1 for w in self._waiting if w.slot < 0)
                       + self._intake.qsize())
            with self._wt_lock:
                tokens = self._waiting_tokens
            try:
                self.admission.check(waiting, tokens)
            except EngineOverloadedError:
                if request.priority < PRIORITY_HIGH:
                    OVERLOAD.inc("dynamo_overload_rejected_total")
                    TENANT.inc("dynamo_tenant_rejected_total", tenant)
                    raise
        # per-tenant admission slice: one tenant's storm exhausts its
        # OWN budget (429 + Retry-After derived from that tenant's own
        # queue waits) before it can crowd the global queue. HIGH
        # priority is force-admitted like the global check —
        # _enforce_bounds restores the budget from the same tenant.
        if self.tenant_quotas.bounded:
            with self._wt_lock:
                t_waiting = self._tenant_waiting.get(tenant, 0)
                t_tokens = self._tenant_tokens.get(tenant, 0)
            try:
                self.tenant_quotas.check(tenant, t_waiting, t_tokens)
            except EngineOverloadedError:
                if request.priority < PRIORITY_HIGH:
                    OVERLOAD.inc("dynamo_overload_rejected_total")
                    TENANT.inc("dynamo_tenant_rejected_total", tenant)
                    raise
        TENANT.inc("dynamo_tenant_admitted_total", tenant)
        # multimodal requests salt their block hashes with the image digest:
        # placeholder tokens are identical across different images, and a
        # prefix-cache hit keyed on tokens alone would serve the wrong
        # image's KV
        salt = request.model
        if request.multimodal and request.multimodal.get("digest"):
            salt = f"{salt}|mm:{request.multimodal['digest']}"
        r = _Request(
            req=request,
            seq=TokenBlockSequence.from_tokens(
                request.token_ids, self.ecfg.page_size, salt=salt
            ),
            out=asyncio.Queue(),
            loop=asyncio.get_running_loop(),
            tokens=list(request.token_ids),
        )
        if request.received_unix is not None:
            # everything before the engine, on the unix clock the stamp
            # crossed processes on (the deadline's convention)
            pre_engine = max(0.0, time.time() - request.received_unix)
            self._h_frontend.observe(
                pre_engine, exemplar_id=request.request_id or None)
            r.trace_spans.append(Span(
                "frontend", request.received_unix, pre_engine).to_dict())
        if self.remote_kv is not None and self.offload is not None:
            await self._remote_prefetch(r)
        r.counted = True
        with self._wt_lock:
            self._waiting_tokens += len(r.tokens)
            self._tenant_waiting[tenant] = (
                self._tenant_waiting.get(tenant, 0) + 1
            )
            self._tenant_tokens[tenant] = (
                self._tenant_tokens.get(tenant, 0) + len(r.tokens)
            )
        self._intake.put(r)
        self._wake_evt.set()
        try:
            while True:
                item = await r.out.get()
                if isinstance(item, Exception):
                    raise item
                yield item
                if item.finished:
                    return
        finally:
            r.cancelled = True

    # ------------------------------------------------------------------
    # padded page I/O (shared by transfers, offload, onboard): page lists
    # are pow2-bucketed for compile-cache reuse; padding targets scratch
    # page 0 (garbage by contract)

    def _refuse_row_only_planes(self, e: EngineConfig, on_dispatch,
                                draft_config, what: str) -> None:
        """Planes that know one row geometry (a K and a V of [kv_heads,
        head_dim], addressable by position) refuse a model whose lanes
        hold ``what`` (the block's own name for it: a latent row, a
        recurrent state) at start-up, by name: none reinterprets the row,
        and none snapshots a recurrent state."""
        planes = {
            "kv_quant=int8 (the int8 KV plane)": e.kv_quant != "none",
            "host/disk offload tiers and their kv_integrity frames "
            "(engine/offload.py)": (e.host_offload_pages > 0
                                    or e.disk_offload_pages > 0),
            "speculative decoding (spec/)": (e.speculative != "off"
                                             or draft_config is not None),
            "resident LoRA adapters (tenancy/)": e.lora_adapters > 0,
            "sequence-parallel prefill (sp_prefill_threshold)":
                e.sp_prefill_threshold is not None,
            "the multihost leader/follower replay": on_dispatch is not None,
        }
        for plane, on in planes.items():
            if on:
                raise ValueError(
                    f"{plane} cannot carry {what} yet; "
                    "turn it off for this model")

    def _refuse_latent_transfer(self) -> None:
        why = llama.transfer_refusal(self.config)
        if why is not None:
            raise ValueError(why)

    def _gather_padded(self, pages: list[int]):
        """Device gather of whole pages; returns DEVICE arrays
        ``(data [2, L, kvh, pow2(n), ps, hd], scales|None)`` — callers
        slice [:len(pages)] on the page axis after fetching. Quantized
        pools return the int8 payload plus its [2, L, pow2(n)] scale
        sidecar."""
        w = pow2_cover(len(pages))
        padded = np.zeros(w, np.int32)
        padded[: len(pages)] = pages
        if self.kv_quant:
            return llama.gather_pages_q(self.cache, jnp.asarray(padded))
        return llama.gather_pages(self.cache, jnp.asarray(padded)), None

    def _host_pages(self, data_h, scales_h, n: int):
        """Fetch a padded device gather to host and trim the padding:
        a QuantizedPages bundle for int8 pools, a dense array else."""
        data = np.asarray(data_h)[:, :, :, :n]
        if scales_h is None:
            return data
        return QuantizedPages(data, np.asarray(scales_h)[:, :, :n])

    def _scatter_padded(self, pages: list[int], data) -> None:
        """Scatter host pages [2, L, kvh, n, ps, hd] into the pool.
        ``data`` may be a dense array or a QuantizedPages bundle; either
        is converted to what THIS pool stores at the boundary (a bf16
        peer's push quantizes on the way in; an int8 bundle landing in a
        bf16 pool dequantizes)."""
        data = to_pool_dtype(
            data, self.kv_quant, np.dtype(self.cache["k"].dtype)
        )
        n = len(pages)
        w = pow2_cover(n)
        padded = np.zeros(w, np.int32)
        padded[:n] = pages
        self.dispatch_counts["xfer_scatter"] += 1
        if self.kv_quant:
            d, s = data.data, data.scales
            if w > n:
                d = np.concatenate(
                    [d, np.zeros(d.shape[:3] + (w - n,) + d.shape[4:],
                                 d.dtype)], axis=3,
                )
                s = np.concatenate(
                    [s, np.zeros(s.shape[:2] + (w - n,), s.dtype)], axis=2,
                )
            self.cache = llama.scatter_pages_q(
                self.cache, jnp.asarray(padded),
                jnp.asarray(d), jnp.asarray(s),
            )
            return
        if w > n:
            pad_shape = list(data.shape)
            pad_shape[3] = w - n
            data = np.concatenate(
                [data, np.zeros(pad_shape, data.dtype)], axis=3
            )
        self.cache = llama.scatter_pages(
            self.cache, jnp.asarray(padded), jnp.asarray(data)
        )

    # ------------------------------------------------------------------
    # KV page export/import (block-transfer data plane hooks;
    # kv_transfer.py BlockTransferServer read_fn/write_fn)

    def export_pages(self, page_ids: list[int]) -> np.ndarray:
        """Gather whole pages to host: [2, L, kvh, n, ps, hd] (a
        kv_quant.QuantizedPages bundle — int8 + scales — for quantized
        pools). Thread-safe — blocks the CALLER until the engine loop
        services it at a round boundary (device-order safe w.r.t.
        in-flight steps)."""
        return self._xfer_op("export", page_ids, None)

    def import_pages(self, page_ids: list[int], data: np.ndarray) -> None:
        """Scatter host pages into the pool (inverse of export_pages)."""
        self._xfer_op("import", page_ids, data)

    def export_pages_by_hash(
        self, hashes: list[int]
    ) -> tuple[int, Optional[np.ndarray]]:
        """G4 serving side: the longest committed run of the chained-hash
        prefix this pool holds, as (found, pages [2, L, kvh, found, ps,
        hd]). Thread-safe (serviced by the engine loop like
        export_pages)."""
        return self._xfer_op("export_hash", [int(h) for h in hashes], None)

    # ---- chunked export streams (kv_transfer chunk pipeline) ----

    def export_pages_stream(
        self, page_ids: list[int], chunk_pages: int = 0, inflight: int = 0,
    ):
        """Chunked thread-safe export: an iterator of host arrays
        [2, L, kvh, <=chunk_pages, ps, hd] covering ``page_ids`` in
        order. The engine loop double-buffers the per-chunk gathers
        (``kv_transfer_inflight_chunks`` D2H copies in flight) and keeps
        serving between chunks — peak host staging is O(chunk), and a
        consumer streaming chunks over TCP overlaps the wire time with
        the next chunk's gather."""
        out_q = self._start_stream("export_stream", list(page_ids),
                                   chunk_pages, inflight)
        return self._consume_stream(out_q)

    def export_hash_stream(
        self, hashes: list[int], chunk_pages: int = 0, inflight: int = 0,
    ) -> tuple[int, Any]:
        """G4 serving side, chunked: resolve the longest committed run of
        the chained-hash prefix and export it as (found, chunk iterator)
        — the streaming analogue of export_pages_by_hash, without ever
        staging the whole run on host."""
        out_q = self._start_stream(
            "export_hash_stream", [int(h) for h in hashes],
            chunk_pages, inflight,
        )
        first = self._next_stream_item(out_q)  # ("found", k) | Exception
        if isinstance(first, Exception):
            raise first
        found = int(first[1])
        return found, self._consume_stream(out_q)

    def _start_stream(
        self, kind: str, ids: list[int], chunk_pages: int, inflight: int,
    ) -> queue_mod.Queue:
        self._refuse_latent_transfer()
        if self.on_dispatch is not None:
            raise RuntimeError(
                "multihost engine: the page transfer plane is single-host"
            )
        if self._stop.is_set():
            raise RuntimeError("engine stopped")
        if not self._started:
            self.start()
        e = self.ecfg
        chunk_pages = int(chunk_pages or e.kv_transfer_chunk_pages
                          or max(len(ids), 1))
        inflight = max(1, int(inflight or e.kv_transfer_inflight_chunks))
        out_q: queue_mod.Queue = queue_mod.Queue()
        self._xfer.put((kind, ids, (chunk_pages, inflight, out_q),
                        threading.Event(), {}))
        self._wake_evt.set()
        return out_q

    def _next_stream_item(self, out_q: queue_mod.Queue) -> Any:
        """One queue item with the same stop/deadline discipline as
        _xfer_op (the engine may stop or wedge mid-stream)."""
        deadline = time.monotonic() + self.ecfg.xfer_op_timeout_s
        stop_grace: Optional[float] = None
        while True:
            try:
                item = out_q.get(timeout=1.0)
                # the consumer pull just freed an inflight slot — ring
                # the doorbell so a throttled export stream dispatches
                # its next chunk now, not after the idle sleep
                self._wake_evt.set()
                return item
            except queue_mod.Empty:
                now = time.monotonic()
                if self._stop.is_set():
                    if stop_grace is None:
                        stop_grace = now + 10.0
                    elif now > stop_grace:
                        raise RuntimeError(
                            "engine stopped during page export stream"
                        )
                elif now > deadline:
                    raise TimeoutError("page export stream timed out")

    def _consume_stream(self, out_q: queue_mod.Queue):
        while True:
            item = self._next_stream_item(out_q)
            if item is _STREAM_EOS:
                return
            if isinstance(item, Exception):
                raise item
            yield item

    def _service_export_streams(self) -> bool:
        """Advance every in-flight chunked export a little (called once
        per round): convert ready head chunks for the consumer, dispatch
        new gathers up to the double-buffer depth. Returns True if any
        stream made progress (keeps the loop cycling while exports
        drain)."""
        if not self._xfer_streams:
            return False
        if self._seal_queue:
            # stream gathers read the pool: queued seal copies first
            self._flush_seals()
        now = time.monotonic()
        keep: list[_ExportStream] = []
        progressed = False
        for st in self._xfer_streams:
            try:
                moved = self._advance_stream(st)
            except Exception as e:  # noqa: BLE001 — surface to the consumer
                if st.free_pages is not None:
                    self.allocator.free(st.free_pages)
                    st.free_pages = None
                st.out_q.put(e)
                st.out_q.put(_STREAM_EOS)
                progressed = True
                continue
            if moved:
                st.last_progress = now
                progressed = True
            if st.pos >= len(st.ids) and not st.pending:
                st.out_q.put(_STREAM_EOS)
                progressed = True
            elif (not moved and now - st.last_progress
                    > self.ecfg.kv_transfer_stream_idle_timeout_s):
                # consumer vanished mid-stream (dead peer connection /
                # stalled receiver): reclaim the pinned gather handles
                # and page refs instead of leaking them for the full
                # xfer-op deadline — an export stream that moved nothing
                # for the idle window is abandoned, however long a
                # HEALTHY transfer is allowed to take
                if st.free_pages is not None:
                    self.allocator.free(st.free_pages)
                    st.free_pages = None
                st.out_q.put(RuntimeError("export stream abandoned"))
                st.out_q.put(_STREAM_EOS)
                progressed = True
            else:
                keep.append(st)
        self._xfer_streams = keep
        return progressed

    def _advance_stream(self, st: _ExportStream) -> bool:
        progressed = False
        # convert ready heads — bounded by consumer pull so a stalled
        # peer can't grow unbounded host staging (BOTH handles must be
        # ready: np.asarray on a pending scale copy would block the loop)
        while (st.pending and st.pending[0][1].is_ready()
               and (st.pending[0][2] is None
                    or st.pending[0][2].is_ready())
               and st.out_q.qsize() < st.inflight):
            n, handle, scales_h = st.pending.popleft()
            st.out_q.put(self._host_pages(handle, scales_h, n))
            progressed = True
        # dispatch the next gathers (async D2H behind compute)
        while (st.pos < len(st.ids) and len(st.pending) < st.inflight
               and st.out_q.qsize() < st.inflight):
            chunk = st.ids[st.pos: st.pos + st.chunk_pages]
            self.dispatch_counts["xfer_gather"] += 1
            out, scales = self._gather_padded(chunk)
            out.copy_to_host_async()
            self.dispatch_counts["fetch"] += 1
            if scales is not None:
                scales.copy_to_host_async()
            st.pending.append((len(chunk), out, scales))
            st.pos += len(chunk)
            progressed = True
        if st.pos >= len(st.ids) and st.free_pages is not None:
            # every gather is dispatched: device order protects the
            # reads, drop the pins now (same contract as export_hash)
            self.allocator.free(st.free_pages)
            st.free_pages = None
        return progressed

    def _xfer_op(self, kind: str, page_ids: list[int], data) -> Any:
        self._refuse_latent_transfer()
        if self.on_dispatch is not None and kind in (
            "export", "import", "export_hash",
        ):
            raise RuntimeError(
                "multihost engine: the page transfer plane is single-host"
            )
        if self._stop.is_set():
            raise RuntimeError("engine stopped")
        if not self._started:
            self.start()
        done = threading.Event()
        box: dict[str, Any] = {}
        self._xfer.put((kind, list(page_ids), data, done, box))
        self._wake_evt.set()
        # wait in slices. On stop, the loop-exit drain (or stop()'s final
        # drain) errors still-queued items; an in-flight op completes and
        # reports its real result — we only bound the wait, never clobber
        # the box ourselves (that would misreport a completed transfer).
        deadline = time.monotonic() + self.ecfg.xfer_op_timeout_s
        stop_grace: Optional[float] = None
        while not done.wait(timeout=1.0):
            now = time.monotonic()
            if self._stop.is_set():
                if stop_grace is None:
                    stop_grace = now + 10.0
                elif now > stop_grace:
                    raise RuntimeError(f"engine stopped during page {kind}")
            elif now > deadline:
                raise TimeoutError(f"page {kind} timed out")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def _process_transfers(self) -> bool:
        """Service queued page-transfer ops. Returns True when at least
        one op was processed — transfer traffic IS work, and counting it
        keeps the loop hot while a disagg import stream is chunking
        pages in (otherwise each chunk eats an idle-path sleep)."""
        processed = False
        while True:
            try:
                kind, ids, data, done, box = self._xfer.get_nowait()
            except queue_mod.Empty:
                return processed
            processed = True
            if kind != "import" and self._seal_queue:
                # pool reads (exports, hash matches, clears) must see
                # queued seal copies dispatched first — commits are
                # matchable the moment _queue_seal runs, but with seals
                # riding the fused round their device copy may still be
                # pending this round
                self._flush_seals()
            try:
                if kind == "export":
                    self.dispatch_counts["xfer_gather"] += 1
                    out, scales = self._gather_padded(ids)
                    box["result"] = self._host_pages(out, scales, len(ids))
                elif kind == "export_stream":
                    chunk_pages, inflight, out_q = data
                    self._xfer_streams.append(_ExportStream(
                        ids=ids, chunk_pages=chunk_pages,
                        inflight=inflight, out_q=out_q,
                    ))
                elif kind == "export_hash_stream":
                    # resolve + pin on the engine loop; the stream frees
                    # the pins once every gather is dispatched
                    chunk_pages, inflight, out_q = data
                    pages = self.allocator.match_prefix(ids)
                    out_q.put(("found", len(pages)))
                    if not pages:
                        out_q.put(_STREAM_EOS)
                    else:
                        self._xfer_streams.append(_ExportStream(
                            ids=pages, chunk_pages=chunk_pages,
                            inflight=inflight, out_q=out_q,
                            free_pages=pages,
                        ))
                elif kind == "export_hash":
                    # G4 peer-serving side: ids are chained block hashes;
                    # resolve the longest committed run, export it, drop
                    # the refs the match pinned
                    pages = self.allocator.match_prefix(ids)
                    if not pages:
                        box["result"] = (0, None)
                    else:
                        self.dispatch_counts["xfer_gather"] += 1
                        out, scales = self._gather_padded(pages)
                        data = self._host_pages(out, scales, len(pages))
                        self.allocator.free(pages)
                        box["result"] = (len(pages), data)
                elif kind == "clear":
                    n = self.allocator.clear()
                    self._offload_cands.clear()  # parked refs now stale
                    if self.offload is not None:
                        n += self.offload.clear()
                        # in-flight D2H offload batches would repopulate
                        # the tiers after the clear — drop them (their
                        # fetches complete harmlessly, results unused)
                        self._entries = [
                            en for en in self._entries
                            if en.kind != "offload"
                        ]
                    box["result"] = n
                else:
                    self._scatter_padded(ids, data)
                    box["result"] = None
            except Exception as e:  # noqa: BLE001 — surface to the caller
                box["error"] = e
                if kind in ("export_stream", "export_hash_stream"):
                    # stream consumers wait on the chunk queue, not the box
                    data[2].put(e)
                    data[2].put(_STREAM_EOS)
            finally:
                done.set()

    def clear_kv_blocks(self) -> int:
        """Drop all reusable cached pages across every tier (G1 HBM LRU +
        G2 DRAM + G3 disk) — the /clear_kv_blocks operation (reference
        http/service/clear_kv_blocks.rs). In-use pages survive. Thread-safe:
        serviced by the engine loop at a round boundary."""
        return self._xfer_op("clear", [], None)

    def embed(self, token_ids: list[int]) -> list[float]:
        """Mean-pooled normalized embedding of a prompt (the /v1/embeddings
        surface). Cache-free encoder pass over read-only params — safe to
        call from any thread, concurrent with serving. Bounded by
        max_context: the O(T^2) one-shot attention would otherwise let one
        long input OOM the device serving everyone."""
        if self.on_dispatch is not None:
            # llama.encode is an SPMD program over the global mesh; it is
            # not in the broadcast command set, so dispatching it on the
            # leader alone would deadlock the cross-host collectives
            raise RuntimeError(
                "multihost engine: embeddings are a single-host feature"
            )
        if not token_ids:
            raise ValueError("empty input")
        if len(token_ids) > self.ecfg.max_context:
            raise ValueError(
                f"input length {len(token_ids)} exceeds max context "
                f"{self.ecfg.max_context}"
            )
        T = pow2_cover(max(len(token_ids), 8))
        toks = np.zeros(T, np.int32)
        toks[: len(token_ids)] = token_ids
        self.dispatch_counts["encode"] += 1
        out = llama.encode(
            self.config, self.params, jnp.asarray(toks),
            jnp.int32(len(token_ids)),
        )
        return np.asarray(out, np.float32).tolist()

    def _histograms_snapshot(self) -> dict:
        """Telemetry snapshot refreshed at most every 0.25 s (the
        publisher's own throttle) — metrics() runs every round."""
        now = time.monotonic()
        t, snap = self._hist_snap
        if now - t >= 0.25:
            snap = self.telemetry.snapshot()
            self._hist_snap = (now, snap)
        return snap

    def metrics(self) -> ForwardPassMetrics:
        a = self.allocator
        # "gpu cache usage" must reflect LIVE serving occupancy, not the
        # pool: in the contiguous-ctx design the paged pool holds parked
        # (refcount-0, reclaimable) prefix blocks, so a.usage() reads ~0
        # under full decode load and the planner would never scale up.
        # The live analogue of vLLM's metric is ctx-region token
        # occupancy, floored by pool pressure.
        live_tokens = sum(
            int(self._ctx_disp[i])
            for i, s in enumerate(self._slots) if s is not None
        )
        ctx_usage = live_tokens / float(self._B * self.ecfg.max_context)
        e = self.ecfg
        num_waiting = (sum(1 for r in self._waiting if r.slot < 0)
                       + self._intake.qsize())
        with self._wt_lock:
            waiting_tokens = self._waiting_tokens
        # process-level overload gauges (all three scrape surfaces)
        OVERLOAD.set("dynamo_overload_queue_depth", num_waiting)
        OVERLOAD.set("dynamo_overload_queue_tokens", waiting_tokens)
        # tenant-sliced backlog gauges
        with self._wt_lock:
            t_waiting = dict(self._tenant_waiting)
            t_tokens = dict(self._tenant_tokens)
        for t in set(t_waiting) | set(t_tokens):
            TENANT.set("dynamo_tenant_queue_depth", t,
                       t_waiting.get(t, 0))
            TENANT.set("dynamo_tenant_queue_tokens", t,
                       t_tokens.get(t, 0))
        # pool capacity in blocks: the kv_quant=int8 headline — the same
        # HBM budget holds ~2x the blocks of a bf16 pool
        KV_QUANT.set("dynamo_kv_pool_capacity_blocks", a.total_pages)
        spec_k_mean = spec_k_p50 = spec_k_p95 = 0.0
        if self.spec is not None:
            # per-slot adaptive-K distribution over currently-speculating
            # slots: the mean alone hid bimodal fleets (half the slots
            # collapsed to min_k, half pinned at the cap)
            spec_k_mean, spec_k_p50, spec_k_p95 = self.spec.effective_k_dist(
                np.flatnonzero(self._slot_spec).tolist()
            )
            SPEC.set(
                "dynamo_spec_accept_rate", self.spec.acceptance_rate()
            )
        return ForwardPassMetrics(
            worker_id=self.ecfg.worker_id,
            worker_stats=WorkerStats(
                request_active_slots=(
                    sum(s is not None for s in self._slots)
                    + len(self._prefilling)
                ),
                request_total_slots=self._B,
                # in-prefill requests count as active (they hold a lane),
                # not waiting
                num_requests_waiting=num_waiting,
                # overload plane: backlog + budgets, so routers spill
                # away from a saturating worker before its bound sheds
                num_waiting_prefill_tokens=waiting_tokens,
                max_waiting_requests=e.max_waiting_requests,
                max_waiting_prefill_tokens=e.max_waiting_prefill_tokens,
                spec_proposed_total=(
                    self.spec.proposed_total if self.spec else 0
                ),
                spec_accepted_total=(
                    self.spec.accepted_total if self.spec else 0
                ),
                spec_acceptance_rate=(
                    self.spec.acceptance_rate() if self.spec else 0.0
                ),
                # adaptive-K distribution over currently-speculating
                # slots — the planner-facing signal for how deep
                # speculation actually runs (0 when off / idle)
                spec_effective_k=spec_k_mean,
                spec_effective_k_p50=spec_k_p50,
                spec_effective_k_p95=spec_k_p95,
                spec_tree_nodes_total=(
                    self.spec.tree_nodes_total if self.spec else 0
                ),
                spec_tree_accepted_path_len_total=(
                    self.spec.tree_path_len_total if self.spec else 0
                ),
                spec_gated_despecs_total=(
                    self.spec.gated_despec_total if self.spec else 0
                ),
            ),
            histograms=self._histograms_snapshot(),
            kv_stats=KvStats(
                kv_active_blocks=a.active_pages,
                kv_total_blocks=a.total_pages,
                gpu_cache_usage_perc=max(a.usage(), ctx_usage),
                gpu_prefix_cache_hit_rate=a.hit_rate(),
                host_blocks=len(self.offload) if self.offload else 0,
                host_total_blocks=(
                    self.offload.num_pages if self.offload else 0
                ),
                host_onboard_hits=(
                    self.offload.onboard_hits if self.offload else 0
                ),
                disk_blocks=(
                    len(self.offload.spill)
                    if self.offload and self.offload.spill else 0
                ),
                disk_total_blocks=(
                    self.offload.spill.num_pages
                    if self.offload and self.offload.spill else 0
                ),
            ),
        )

    def tenant_debug(self) -> dict:
        """Per-tenant quota/backlog/metric view — the engine half of the
        /debug/tenants surface (runtime/system_server.py; the frontend
        merges its own HTTP-side slice)."""
        with self._wt_lock:
            t_waiting = dict(self._tenant_waiting)
            t_tokens = dict(self._tenant_tokens)
        quotas = self.tenant_quotas.snapshot()
        metrics_snap = TENANT.snapshot()
        tenants: dict[str, dict[str, Any]] = {}
        for t in (set(t_waiting) | set(t_tokens) | set(quotas)
                  | set(metrics_snap)):
            tenants[t] = {
                "waiting_requests": t_waiting.get(t, 0),
                "waiting_prefill_tokens": t_tokens.get(t, 0),
                **(quotas.get(t) or {}),
                "metrics": metrics_snap.get(t, {}),
            }
        return {
            "bounded": self.tenant_quotas.bounded,
            "max_waiting_requests": (
                self.ecfg.tenant_max_waiting_requests
            ),
            "max_waiting_prefill_tokens": (
                self.ecfg.tenant_max_waiting_prefill_tokens
            ),
            "n_adapters": self.n_adapters,
            "tenants": tenants,
        }

    # ------------------------------------------------------------------
    # engine loop

    def _run_loop(self) -> None:
        prof = self.prof
        prof.register_thread()
        try:
            self._loop(prof)
        finally:
            prof.unregister_thread()
        self._drain_xfer_queue()

    def _loop(self, prof: tprof.RoundProf) -> None:
        last_idle_beat = 0.0
        while not self._stop.is_set():
            try:
                did_work = self._round()
            except Exception as exc:  # noqa: BLE001 — engine loop must survive
                log.exception("engine round failed")
                # the last N dispatches before the failure are the
                # postmortem; logs alone never have them
                self.flight.dump(log, reason=repr(exc))
                try:
                    self._fail_all(
                        RuntimeError("engine step failed; see logs")
                    )
                except Exception:  # noqa: BLE001 — never mask the root cause
                    log.exception("fail_all cleanup itself failed")
                did_work = False
            if not did_work:
                # the engine is EMPTY: the heartbeat and the doorbell wait
                # are booked as idle, so recorded passes + idle = this
                # thread's life (prof.totals()["loop_coverage"])
                prof.idle_enter()
                # idle heartbeat: busy rounds publish metrics themselves;
                # an IDLE engine must keep heartbeating too, or the
                # health plane's soft leases (resilience/health.py
                # heartbeat_ttl_s) would read silence as wedged
                now = time.monotonic()
                if self.on_metrics is not None and now - last_idle_beat >= 0.5:
                    last_idle_beat = now
                    try:
                        self.on_metrics(self.metrics())
                    except Exception:  # noqa: BLE001 — never kill the loop
                        log.exception("idle metrics publish failed")
                # wait on the doorbell, not intake alone: _xfer_op page
                # imports (disagg decode side) and intake both ring it,
                # so either wakes the loop immediately. Clear BEFORE the
                # non-blocking drain — a set racing the clear is seen on
                # the next wait.
                self._wake_evt.wait(timeout=0.02)
                self._wake_evt.clear()
                try:
                    self._waiting.append(self._intake.get_nowait())
                except queue_mod.Empty:
                    pass
                prof.idle_exit()

    def _drain_xfer_queue(self) -> None:
        """Abandon queued transfer ops with an error, not a long stall.
        Only touches items still IN the queue — an in-flight op finishes
        normally and reports its real result."""
        while True:
            try:
                kind, _ids, data, done, box = self._xfer.get_nowait()
            except queue_mod.Empty:
                break
            box["error"] = RuntimeError("engine stopped")
            if kind in ("export_stream", "export_hash_stream"):
                data[2].put(box["error"])
                data[2].put(_STREAM_EOS)
            done.set()
        # in-flight chunk streams: close their consumer queues too
        for st in self._xfer_streams:
            st.out_q.put(RuntimeError("engine stopped"))
            st.out_q.put(_STREAM_EOS)
        self._xfer_streams = []

    def _round(self) -> bool:
        """One scheduling round: process ready results, flush seal copies,
        apply patches (releases, admissions), dispatch a round of steps.

        With ``ecfg.round_pipeline`` the round runs double-buffered: when
        the pipeline is clear (_pipeline_clear — nothing would mutate
        slot state under an in-flight program) the NEXT fused program is
        dispatched BEFORE this round's packed fetch is consumed, so the
        completion half (fetch, emit, releases, transfer/offload
        servicing) overlaps device execution and steady-state wall
        approaches max(host, device) instead of host + device. Any flush
        condition falls back to the exact pre-pipelining
        process-then-dispatch order (counted in pipe_flushes)."""
        e = self.ecfg
        prof = self.prof
        prof.begin_round()
        t_round = time.monotonic()
        prof.enter(_SEG_INTAKE)
        self._drain_intake()
        prof.enter(_SEG_SLOT_SCAN)
        self._enforce_bounds()
        rounds_in_flight = sum(1 for en in self._entries if en.kind == "round")
        dispatched = False
        t_pipe = 0.0
        if (e.round_pipeline
                and rounds_in_flight <= e.max_inflight_rounds
                and self._pipeline_clear()):
            # dispatch half FIRST (round pipelining): launch round N+1
            # before consuming round N's fetch — everything below the
            # dispatch runs while the device executes. The seal batch
            # taken here is last round's (this round's completions queue
            # theirs for the NEXT dispatch: one extra round of commit
            # latency, still device-order safe).
            active, want_lp, want_sample = self._active_slots()
            if active:
                prof.enter(_SEG_DISPATCH)
                self._dispatch_round(active, want_lp, want_sample)
                dispatched = True
                rounds_in_flight += 1
                self._pipe_dispatches += 1
                self._pipe_depth_sum += rounds_in_flight
                t_pipe = time.monotonic()
        prof.enter(_SEG_FETCH)
        self._process_entries(block=rounds_in_flight > e.max_inflight_rounds)
        # seals queued by result processing are NOT flushed here: they
        # ride the next fused dispatch (_dispatch_round). Pool
        # readers below (transfers, streams, offload, prefill_begin)
        # flush standalone first themselves.
        prof.enter(_SEG_RELEASES)
        self._apply_releases()
        prof.enter(_SEG_TRANSFER)
        xfer_work = self._process_transfers()
        stream_work = self._service_export_streams()
        prof.enter(_SEG_OFFLOAD)
        self._dispatch_offloads()
        self._drain_host_ingest()  # G4 pages land before admission
        prof.enter(_SEG_ADMIT)
        self._admit()
        prof.enter(_SEG_SLOT_SCAN)

        # mid-flight prefills ARE work: without this a multi-chunk
        # (disagg-shaped) prefill pays the idle-path intake sleep between
        # every chunk — the r07 chunked-TTFT regression
        did_work = (dispatched or bool(self._entries) or stream_work
                    or xfer_work or bool(self._prefilling))
        rounds_in_flight = sum(1 for en in self._entries if en.kind == "round")
        if not dispatched and rounds_in_flight <= e.max_inflight_rounds:
            # flushed / disabled pipeline: dispatch at the legacy
            # position — after every patch above, the exact
            # pre-pipelining order (what `round_pipeline=False` pins
            # in the differential tests). Dispatch only for LIVE
            # requests: a round for finished-awaiting-release slots is
            # pure garbage work that also queues ahead of the next
            # arrival's prefill. Speculating slots are excluded — their
            # lanes are parked and they advance through verify
            # dispatches instead.
            active, want_lp, want_sample = self._active_slots()
            if active:
                prof.enter(_SEG_DISPATCH)
                self._dispatch_round(active, want_lp, want_sample)
                did_work = dispatched = True
        if self.spec is not None and bool(self._slot_spec.any()):
            prof.enter(_SEG_SPEC)
            if self._dispatch_spec():
                did_work = dispatched = True
        if self._seal_queue and (
                not e.round_pipeline or not dispatched
                or len(self._seal_queue) > self._seal_fuse_w):
            # no fused ride is coming (nothing dispatched / pipelining
            # off leaves no next-round ride guarantee) or the queue
            # outgrew the fused width (admission burst): dispatch
            # standalone rather than letting commits sit
            prof.enter(_SEG_SEAL_FLUSH)
            self._flush_seals()
            did_work = True
        if t_pipe:
            now = time.monotonic()
            # completion-half host time that ran with the early dispatch
            # in flight on device (the overlap_ratio numerator) vs the
            # pipelined round's total host time
            self._pipe_hidden_s += now - t_pipe
            self._pipe_host_s += now - t_round
        # fold prof + refresh the SLO burn-rate gauges at the publish
        # cadence, not once per round — building ForwardPassMetrics every
        # round was measurable host tax and the pub/sub plane throttles
        # to ~4 Hz anyway
        now = time.monotonic()
        if now - self._last_metrics_pub >= 0.1:
            self._last_metrics_pub = now
            prof.enter(_SEG_METRICS)
            PROF.fold(prof)
            PROF.fold_burn_rates(
                self._h_ttft.snapshot(), self._h_itl.snapshot(),
                e.slo_ttft_target_s, e.slo_itl_target_s,
                e.slo_objective,
            )
            if self.on_metrics is not None:
                self.on_metrics(self.metrics())
        if (not dispatched and self._entries
                and self._intake.empty() and not self._waiting):
            # nothing to overlap with the in-flight fetches (e.g. every
            # live slot is waiting on its verify result) — block on the
            # head entry instead of spinning the loop
            prof.enter(_SEG_FETCH)
            self._process_entries(block=True)
        if (self._draining
                and not self._entries and not self._waiting
                and not self._prefilling and self._intake.empty()
                and all(s is None for s in self._slots)):
            self._drained_evt.set()
        stall = prof.end_round(record=did_work)
        if stall is not None:
            # the pass ran 0.1 s outside fetch: what it ran, the
            # collector's part, and the round that waited on it
            self.flight.record("stall", round=next(
                (en.ahead[0] for en in self._entries if en.kind == "round"),
                None), **stall)
        return did_work

    def _pipeline_clear(self) -> bool:
        """True when the dispatch half may run BEFORE the completion half
        (round pipelining): nothing pending may mutate slot state under
        the in-flight program. Each False increments its pipe_flushes
        bucket — the explicit flush points: drain, admissions
        (waiting / mid-prefill / fresh intake), pending release patches,
        seal-queue overflow past the fused width, and speculating slots
        (their verify results re-shape the next round)."""
        if self._draining:
            self.pipe_flushes["drain"] += 1
            return False
        if self._waiting or self._prefilling or not self._intake.empty():
            self.pipe_flushes["admission"] += 1
            return False
        if self._to_release:
            self.pipe_flushes["release"] += 1
            return False
        if len(self._seal_queue) > self._seal_fuse_w:
            self.pipe_flushes["seal_overflow"] += 1
            return False
        if self.spec is not None and bool(self._slot_spec.any()):
            self.pipe_flushes["spec"] += 1
            return False
        return True

    def _active_slots(self) -> tuple[list[int], bool, bool]:
        """(active slot list, want_lp, want_sample) reduced from the
        numpy slot-state mirrors; cached until the next slot transition
        — steady decode pays zero per-slot Python scans per round."""
        cached = self._active_cache
        if cached is None:
            idx = np.flatnonzero(self._slot_active)
            cached = (
                idx.tolist(),
                bool(self._slot_lp[idx].any()),
                bool(self._slot_sampler[idx].any()),
            )
            self._active_cache = cached
        return cached

    def _slot_on(self, slot: int, r: _Request) -> None:
        """Mirror a slot becoming LIVE (fused-decode driven) into the
        slot-state arrays. A slot needs the sampler if it samples OR
        carries penalties — penalties apply to greedy decoding too, and
        the counts histogram must advance for them to be correct."""
        so = r.req.sampling_options
        self._slot_active[slot] = True
        self._slot_spec[slot] = False
        self._slot_lp[slot] = r.req.output_options.logprobs is not None
        self._slot_sampler[slot] = (
            (so.temperature or 0.0) > 0.0
            or (so.frequency_penalty or 0.0) != 0.0
            or (so.presence_penalty or 0.0) != 0.0
            or (so.repetition_penalty or 1.0) != 1.0
        )
        self._active_cache = None

    def _slot_off(self, slot: int, spec: bool = False) -> None:
        """Mirror a slot leaving the fused decode round (finish, release,
        or — with ``spec`` — speculative admission/parking)."""
        self._slot_active[slot] = False
        self._slot_spec[slot] = spec
        self._slot_lp[slot] = False
        self._slot_sampler[slot] = False
        self._active_cache = None

    def pipeline_stats(self) -> dict:
        """Round-pipeline effectiveness counters (read by
        tests/test_round_pipeline.py and tests/test_host_budget.py):
        mean in-flight depth right after an early dispatch, the fraction
        of pipelined-round host time spent in the completion half
        (running under device execution), and the per-reason flush
        counts."""
        n = self._pipe_dispatches
        return {
            "round_pipeline": bool(self.ecfg.round_pipeline),
            "pipelined_dispatches": n,
            "pipeline_depth": round(self._pipe_depth_sum / n, 4) if n else 0.0,
            "overlap_ratio": (
                round(self._pipe_hidden_s / self._pipe_host_s, 4)
                if self._pipe_host_s > 0 else 0.0
            ),
            "pipe_flushes": dict(self.pipe_flushes),
        }

    def _drain_intake(self) -> None:
        if self._intake.empty():
            return  # steady decode: skip the Empty-exception round trip
        while True:
            try:
                self._enqueue_waiting(self._intake.get_nowait())
            except queue_mod.Empty:
                return

    def _enqueue_waiting(self, r: _Request) -> None:
        """Weighted fair share (SFQ) within a priority class; a
        high-priority arrival still queues ahead of every lower-priority
        entry that has NOT started prefill (entries holding a lane are
        active work, never jumped).

        Each request is stamped with a virtual finish time — the
        tenant's virtual clock advanced by prompt-cost / weight — and
        inserts before the first not-started same-priority entry with a
        LARGER stamp. A storming tenant's backlog carries ever-growing
        stamps while a light tenant's fresh arrival starts at the global
        virtual clock (advanced at service start, _note_queue_wait), so
        it lands near the head. Single-tenant traffic degrades to exact
        FIFO: one tenant's stamps are monotonic by construction."""
        t = r.tenant
        vstart = max(self._tenant_vnow.get(t, 0.0), self._vclock)
        r.vft = vstart + max(1, len(r.tokens)) / self.tenant_quotas.weight(t)
        self._tenant_vnow[t] = r.vft
        if r.req.priority > 0:
            for i, w in enumerate(self._waiting):
                if w.prefill_pos < 0 and w.req.priority < r.req.priority:
                    self._waiting.insert(i, r)
                    return
            self._waiting.append(r)
            return
        for i, w in enumerate(self._waiting):
            if (w.prefill_pos < 0 and w.req.priority == r.req.priority
                    and w.vft > r.vft):
                self._waiting.insert(i, r)
                return
        self._waiting.append(r)

    # ---- overload plane: budgets, deadline shedding, preemption ----

    def _uncount_waiting(self, r: _Request) -> None:
        """Drop a request's prompt from the waiting-token backlog
        (idempotent — first lane acquisition or queue exit wins)."""
        if not r.counted:
            return
        r.counted = False
        t = r.tenant
        with self._wt_lock:
            self._waiting_tokens -= len(r.tokens)
            self._tenant_waiting[t] = max(
                0, self._tenant_waiting.get(t, 0) - 1
            )
            self._tenant_tokens[t] = max(
                0, self._tenant_tokens.get(t, 0) - len(r.tokens)
            )

    def _shed_waiting(self, r: _Request, reason: str) -> None:
        """Drop a still-WAITING request from the queue. ``deadline``
        sheds finish cleanly (zero tokens, DEADLINE reason — the budget
        ran out, nothing failed); preemption/bound sheds surface the
        retriable overload error so the router re-routes them."""
        self._uncount_waiting(r)
        r.finished = True
        if reason == "deadline":
            self.sheds += 1
            OVERLOAD.inc("dynamo_overload_shed_total")
            r.emit(LLMEngineOutput(
                token_ids=[], finish_reason=FinishReason.DEADLINE,
                annotations={"shed": {
                    "reason": "deadline",
                    "queued_s": round(
                        time.monotonic() - r.enqueue_time, 3),
                }},
            ))
        else:
            t = r.tenant
            TENANT.inc("dynamo_tenant_shed_total", t)
            if self.tenant_quotas.bounded:
                # pressure is tenant-confined, so the hint is too: this
                # tenant's own queue-wait p50 x its own backlog depth
                with self._wt_lock:
                    t_waiting = self._tenant_waiting.get(t, 0)
                retry = self.tenant_quotas.retry_after_s(t, t_waiting)
            else:
                retry = self.admission.retry_after_s(
                    sum(1 for w in self._waiting if w.slot < 0)
                )
            r.emit(EngineOverloadedError(
                f"request shed while waiting ({reason})",
                retry_after_s=retry,
                tenant=t,
            ))

    def _enforce_bounds(self) -> None:
        """Restore the admission budgets after a HIGH-priority arrival
        was force-admitted past them: evict the lowest-priority, newest
        waiting entry until the backlog fits. When every candidate has
        the same priority there is no one to preempt FOR — the newest
        arrival bounces instead (the budget stays honest either way)."""
        self._enforce_tenant_bounds()
        adm = self.admission
        if not adm.bounded:
            return
        while True:
            cands = [r for r in self._waiting
                     if r.prefill_pos < 0 and not r.cancelled
                     and not r.finished]
            n = len(cands)
            with self._wt_lock:
                tokens = self._waiting_tokens
            over = ((adm.max_waiting_requests
                     and n > adm.max_waiting_requests)
                    or (adm.max_waiting_prefill_tokens
                        and tokens > adm.max_waiting_prefill_tokens))
            if not over or not cands:
                return
            lo = min(r.req.priority for r in cands)
            hi = max(r.req.priority for r in cands)
            victim = max(
                (r for r in cands if r.req.priority == lo),
                key=lambda r: r.enqueue_time,
            )
            if lo < hi:
                self.waiting_preemptions += 1
                OVERLOAD.inc("dynamo_overload_preempted_total")
                self._shed_waiting(victim, "preempted by priority")
            else:
                OVERLOAD.inc("dynamo_overload_rejected_total")
                self._shed_waiting(victim, "queue budget exceeded")
            self._waiting.remove(victim)

    def _enforce_tenant_bounds(self) -> None:
        """Per-tenant half of _enforce_bounds: a HIGH-priority arrival
        force-admitted past its tenant's budget is paid for WITHIN that
        tenant — the victim is always the offending tenant's own
        lowest-priority, newest waiting entry, never another tenant's
        work."""
        tq = self.tenant_quotas
        if not tq.bounded:
            return
        while True:
            by_tenant: dict[str, list[_Request]] = {}
            for r in self._waiting:
                if r.prefill_pos < 0 and not r.cancelled and not r.finished:
                    by_tenant.setdefault(r.tenant, []).append(r)
            victim = None
            for t, rs in by_tenant.items():
                toks = sum(len(r.tokens) for r in rs)
                over = ((tq.max_waiting_requests
                         and len(rs) > tq.max_waiting_requests)
                        or (tq.max_waiting_prefill_tokens
                            and toks > tq.max_waiting_prefill_tokens))
                if not over:
                    continue
                lo = min(r.req.priority for r in rs)
                hi = max(r.req.priority for r in rs)
                victim = max(
                    (r for r in rs if r.req.priority == lo),
                    key=lambda r: r.enqueue_time,
                )
                if lo < hi:
                    self.waiting_preemptions += 1
                    OVERLOAD.inc("dynamo_overload_preempted_total")
                    self._shed_waiting(victim, "preempted by priority "
                                               "(tenant budget)")
                else:
                    OVERLOAD.inc("dynamo_overload_rejected_total")
                    self._shed_waiting(victim, "tenant budget exceeded")
                self._waiting.remove(victim)
                break
            if victim is None:
                return

    def _maybe_preempt_running(self) -> None:
        """Running half of priority preemption (behind
        ``preempt_running``): a HIGH-priority request blocked on a lane
        force-migrates the lowest-priority RUNNING stream — its client
        stream fails with the retriable PreemptedError, the router
        replays it on a peer (exactly-once, greedy token-identical, the
        PR-4 migration plane), and the freed lane admits the
        high-priority request at the next round. At most one victim per
        round; lanes mid-prefill are never preempted (their replay
        would waste the whole prefill for no freed decode capacity
        yet)."""
        if not self.ecfg.preempt_running:
            return
        hp = next(
            (r for r in self._waiting
             if r.prefill_pos < 0 and not r.cancelled
             and r.req.priority > 0),
            None,
        )
        if hp is None or self._free_slot() is not None:
            return
        victims = [
            s for s in self._slots
            if s is not None and not s.finished and not s.cancelled
            and s.req.priority < hp.req.priority
        ]
        if not victims:
            return
        # tenant-confined preference: when tenant budgets are set, a
        # victim is drawn from a tenant that is OVER its own budget
        # whenever one is running — an innocent tenant's stream is only
        # preempted when no over-budget tenant holds a lane
        if self.tenant_quotas.bounded:
            with self._wt_lock:
                tw = dict(self._tenant_waiting)
                tt = dict(self._tenant_tokens)
            over = [
                v for v in victims
                if self.tenant_quotas.over_budget(
                    tw.get(v.tenant, 0), tt.get(v.tenant, 0))
            ]
            if over:
                victims = over
        lo = min(v.req.priority for v in victims)
        victim = max(
            (v for v in victims if v.req.priority == lo),
            key=lambda v: v.enqueue_time,
        )
        self.preempt_migrations += 1
        OVERLOAD.inc("dynamo_overload_preempt_migrations_total")
        log.warning(
            "preempting running request %s (priority %d) for "
            "high-priority arrival %s",
            victim.req.request_id, victim.req.priority,
            hp.req.request_id,
        )
        victim.emit(PreemptedError(
            "preempted by a higher-priority request; stream migrates"
        ))
        self._finish(victim, None)

    # ---- dispatch side ----

    def _dispatch_round(
        self, active: list[int], want_lp: bool, want_sample: bool
    ) -> None:
        """Dispatch flush_every fused steps + one stacked-token fetch.
        ``active``/``want_lp``/``want_sample`` come precomputed from the
        slot-state mirrors (_active_slots) — plain-greedy rounds skip
        the full sampler (argmax only), lp-free rounds skip the packed
        logprob pipeline."""
        e = self.ecfg
        n = e.flush_every
        # the round's pending seal batch rides the SAME program (the
        # dispatch diet: in steady decode a block completes nearly every
        # round, and the separate seal_blocks program was a per-round
        # straggler dispatch). Fixed width = one compiled variant;
        # admission-burst overflow drains via the standalone flush at
        # the end of _round.
        prev_seg = self.prof.push(_SEG_SEAL_ASM)
        seal = self._take_seal_batch(width=self._seal_fuse_w)
        self.prof.enter(prev_seg)
        if self.on_dispatch is not None:
            # followers must replay the identical (fused) program, so
            # the seal arrays always travel — zeros for seal-less rounds
            w = self._seal_fuse_w
            payload = {
                "n_steps": n, "want_lp": want_lp,
                "want_sample": want_sample,
                "seal": ({
                    "slots": seal[0].tolist(),
                    "starts": seal[1].tolist(),
                    "pages": seal[2].tolist(),
                } if seal is not None else {
                    "slots": [0] * w, "starts": [0] * w,
                    "pages": [0] * w,
                }),
            }
            self.on_dispatch("round", payload)
        # one fused program: n decode+sample steps + flush + seal. The
        # SAME program runs whether the round has seals or not (seal-
        # less rounds pass the cached all-scratch dummy batch — page 0
        # is garbage by contract) — one compiled variant per engine, not
        # one per seal-width plus a plain variant, which is what keeps
        # the fusion free at compile time too.
        self._poll_dry()
        t_disp = time.monotonic()
        counts = self.dispatch_counts
        ahead = (counts["round"] + counts["round_seal"], *self._ahead)
        self._ahead = [0, 0]
        self.prof.mark_round(dispatched=ahead[0], programs_ahead=ahead[1],
                             padded_tokens_ahead=ahead[2])
        if seal is not None:
            self.dispatch_counts["round_seal"] += 1
            seal_dev = (jnp.asarray(seal[0]), jnp.asarray(seal[1]),
                        jnp.asarray(seal[2]))
        else:
            self.dispatch_counts["round"] += 1
            seal_dev = self._zero_seal
        (self.ctx, self.ring, self._dev, self.cache, stacked,
         lp_stacked) = self._engine_round_seal(
            self.params, self.ctx, self.ring, self._dev, self.cache,
            *seal_dev, n, want_lp, want_sample,
        )
        if seal is not None:
            if self.kv_quant:
                if self.ctx_quant:
                    # ctx and pool share the int8 representation: the
                    # fused seal moved raw pages, nothing requantized
                    KV_QUANT.inc(
                        "dynamo_kv_quant_ctx_seal_raw_pages_total",
                        seal[3])
                else:
                    KV_QUANT.inc("dynamo_kv_quant_pages_total", seal[3])
            self._notify_commits()
        if self.ctx_quant:
            # ring flush requantized its per-lane window groups inside
            # the same fused program (deterministic geometry: every lane
            # touches the same window width each round)
            KV_QUANT.inc("dynamo_kv_quant_ctx_flush_groups_total",
                         self._flush_groups_per_round)
        self.flight.record(
            "round", slots=list(active), n_steps=n,
            # post-PR 7 round shape: seals ride the fused program
            # (seal_w = real seal-batch width, 0 on seal-less rounds)
            # and token fetches are packed (1 stacked + 1 packed-logprob
            # pipeline, never 3) — recorded so /debug/flight matches
            # dispatch_counts
            seal_w=int(seal[3]) if seal is not None else 0,
            fetches=1 + (1 if lp_stacked is not None else 0),
            spec_slots=np.flatnonzero(self._slot_spec).tolist(),
            dispatch_ms=round((time.monotonic() - t_disp) * 1e3, 3),
        )
        if self._decode_mirror is not None:
            live = np.zeros(self._B, bool)
            live[active] = True
            self._observe(self._decode_mirror(self._ctx_disp, live, n))
        # only dispatched lanes advance (spec slots track their own
        # lengths through verify processing)
        self._ctx_disp[active] = np.minimum(
            self._ctx_disp[active] + n, e.max_context
        )
        if self.n_adapters:
            # adapter-switch-overhead observability: which tenants'
            # rounds gathered a non-base bank row (the row gather is
            # fused into this same program — zero extra dispatches)
            seen: set[str] = set()
            for i in active:
                r = self._slots[i]
                if r is not None and r.adapter_id and r.tenant not in seen:
                    seen.add(r.tenant)
                    TENANT.inc("dynamo_tenant_adapter_rounds_total",
                               r.tenant)
        self.step_count += n
        self._h_live_steps.observe(len(active) * n)
        stacked.copy_to_host_async()
        self.dispatch_counts["fetch"] += 1
        if lp_stacked is not None:
            # packed: ONE extra fetch pipeline, not three
            lp_stacked.copy_to_host_async()
            self.dispatch_counts["fetch"] += 1
        self._track(
            _Entry(
                kind="round",
                t_dispatch=t_disp,
                ahead=ahead,
                handle=stacked,
                # snapshot EXCLUDES speculating slots: their device lanes
                # are parked, so their columns in this round's stacked
                # tokens are garbage — advancing them from here would
                # corrupt the verify-driven history (the slot's spec flag
                # may flip by the time the fetch lands, so the filter
                # must happen at dispatch time, not at processing)
                slots=[
                    (r if r is None or not r.spec else None)
                    for r in self._slots
                ],
                n_steps=n,
                lp_handle=lp_stacked,
            )
        )

    def _dispatch_patch(
        self,
        clear_slots: list[int] = (),
        admit: Optional[dict[str, Any]] = None,
    ) -> None:
        if self.on_dispatch is not None:
            # a follower replays releases only: the one admission left
            # here is a despeculating slot's, and spec is rejected
            # multihost
            self.on_dispatch("patch", {"clear_slots": list(clear_slots)})
        B = self._B
        clear = np.zeros(B, bool)
        for s in clear_slots:
            clear[s] = True
        a = admit or {}
        counts = a.get("counts")
        # one packed f32 row instead of ten scalar uploads (the patch
        # jit unpacks; see _build_jits.patch)
        meta = np.array([
            a.get("slot", B), a.get("ctx", 1),
            a.get("temp", 0.0), a.get("top_k", 0), a.get("top_p", 1.0),
            a.get("freq", 0.0), a.get("pres", 0.0), a.get("rep", 1.0),
            a.get("adapter", 0),
        ], np.float32)
        self.dispatch_counts["patch"] += 1
        self._dev = self._patch(
            self._dev,
            jnp.asarray(clear),
            jnp.asarray(meta),
            a.get("tok", self._zero_tok),
            jnp.asarray(a.get("keys", np.zeros(2, np.uint32))),
            self._zero_counts if counts is None
            else jnp.asarray(counts, jnp.int32),
        )

    # ---- speculative decoding (spec/): propose -> fused verify ----

    def _dispatch_spec(self) -> bool:
        """Collect spec-ready slots, draft K tokens for ALL of them in at
        most ONE device dispatch (llama.batch_draft / host n-gram lookup),
        and dispatch ONE fused score+accept program (static width B; dummy
        rows target the scratch lane) — O(1) device dispatches per round
        in the number of speculating slots AND in K (the draft steps run
        inside a fori_loop). The verify optimistically writes K+1 KV rows
        per slot; the host later commits only the accepted prefix —
        rollback is pointer truncation because attention masks by
        sequence length and the next write over the lane overwrites the
        dead span.

        K here is the ROUND width: the bucketed max of the participants'
        per-slot effective K (acceptance-adaptive; spec/decoder.py) —
        when every participant's acceptance sags, the whole round
        shrinks. Returns True if anything was dispatched.
        """
        if self.spec.tree:
            return self._dispatch_spec_tree()
        e = self.ecfg
        K_cap = self.spec.k
        ready = [
            (i, r) for i, r in enumerate(self._slots)
            if r is not None and r.spec and r.spec_ready
            and not r.finished and not r.cancelled and not r.spec_inflight
        ]
        if not ready:
            return False
        rows: list[tuple[int, _Request, int, int]] = []
        dispatched = False
        for slot, r in ready:
            n_hist = len(r.spec_tokens)
            # the verify writes up to K_cap+1 rows at [N, N+K+1); when
            # that no longer fits the region, hand the slot back to the
            # fused decode round for its final tokens (checked against
            # the CAP, not the round K — the round width isn't known yet)
            if (n_hist - 1) + K_cap + 1 > e.max_context:
                self._despeculate(slot, r)
                dispatched = True
                continue
            rows.append((slot, r, n_hist, self.spec.k_for(slot)))
        if not rows:
            return dispatched
        K = self.spec.round_k([k for *_, k in rows])
        B = self._B
        toks = np.zeros((B, K + 1), np.int32)
        slots_a = np.full(B, B, np.int32)     # dummies -> scratch lane
        q_starts = np.zeros(B, np.int32)
        seq_lens = np.zeros(B, np.int32)      # 0: dummy rows fully masked
        keys = np.zeros((B, 2), np.uint32)
        temps = np.zeros(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        top_ps = np.ones(B, np.float32)
        # penalties: built only when some row carries them — the [B, V]
        # counts upload (and the verifier's histogram-advancing scan
        # variant) costs nothing on penalty-free rounds
        penalties = None
        if any(r.spec_counts is not None for _, r, _, _ in rows):
            penalties = (
                np.zeros((B, self.config.vocab_size), np.int32),
                np.zeros(B, np.float32),          # freq
                np.zeros(B, np.float32),          # pres
                np.ones(B, np.float32),           # rep
            )
        for j, (slot, r, n_hist, _k) in enumerate(rows):
            toks[j, 0] = r.spec_tokens[-1]    # pending token
            slots_a[j] = slot
            q_starts[j] = n_hist - 1
            seq_lens[j] = n_hist + K
            keys[j] = r.spec_keys
            so = r.req.sampling_options
            temps[j] = so.temperature or 0.0
            top_ks[j] = so.top_k or 0
            top_ps[j] = so.top_p if so.top_p is not None else 1.0
            if penalties is not None and r.spec_counts is not None:
                penalties[0][j] = r.spec_counts
                penalties[1][j] = so.frequency_penalty or 0.0
                penalties[2][j] = so.presence_penalty or 0.0
                penalties[3][j] = so.repetition_penalty or 1.0
        self._poll_dry()
        t_disp = time.monotonic()
        drafted = None
        if self.spec.draft is not None:
            # ONE multi-slot multi-token draft program; the [B, K] device
            # result splices into the verify tokens INSIDE the verify jit
            self.dispatch_counts["spec_draft"] += 1
            drafted = self.spec.propose_batch(
                [(slot, r.spec_tokens) for slot, r, _, _ in rows], B, K,
            )
        else:
            # n-gram: host tokens, per slot by nature
            for j, (_slot, r, _n, _k) in enumerate(rows):
                toks[j, 1:] = self.spec.propose(r.spec_tokens, K)
        t_draft_end = time.monotonic()
        self.dispatch_counts["spec_verify"] += 1
        self.ctx, out_toks, n_out, new_keys = self.spec.verify(
            self.params, self.ctx, jnp.asarray(toks), drafted, slots_a,
            q_starts, seq_lens, keys, temps, top_ks, top_ps,
            penalties=penalties,
        )
        for arr in (out_toks, n_out, new_keys):
            arr.copy_to_host_async()
            self.dispatch_counts["fetch"] += 1
        t_verify_end = time.monotonic()
        self.flight.record(
            "spec_verify", slots=[slot for slot, *_ in rows], k=K,
            fetches=3,
            dispatch_ms=round((t_verify_end - t_disp) * 1e3, 3),
        )
        for slot, r, _, _ in rows:
            r.spec_ready = False
            r.spec_inflight = True
        self._track(_Entry(
            kind="spec", handle=out_toks, rows=rows,
            aux=(n_out, new_keys), n_steps=K, t_dispatch=t_disp,
            spec_host=(t_draft_end - t_disp, t_verify_end - t_draft_end),
        ))
        return True

    def _dispatch_spec_tree(self) -> bool:
        """Tree-speculation round (--spec-tree): same dispatch budget as
        the linear path — at most ONE draft program + ONE fused verify —
        but the fetch count IMPROVES to ONE packed [B, 2D+4] handle
        (tokens | accepted path | n_out | keys; see spec_verify_tree)
        instead of three.

        Each row carries a packed token tree (flat tokens + parent
        pointers, node 0 = pending token): the n-gram proposer merges
        its top-M continuations into a trie on the host; the draft model
        emits a comb (M branches per depth off a greedy spine) from the
        SAME fused batch_draft program. The verify scores every node
        under a tree-causal ancestor mask in one q_start>0 forward,
        walks the deepest surviving root-to-leaf path on device, and
        commits only that path's KV rows — sibling rows are never
        written, so rollback stays pointer truncation.

        Round shape (D depths x M branches) is the bucketed max of the
        per-slot adaptive controller's (k, m) votes — the branch axis
        moves OPPOSITE to depth (high acceptance -> deep + narrow; low
        -> shallow + wide hedging), see AdaptiveKController.observe.
        """
        e = self.ecfg
        T_cap = self.spec.tree_budget
        ready = [
            (i, r) for i, r in enumerate(self._slots)
            if r is not None and r.spec and r.spec_ready
            and not r.finished and not r.cancelled and not r.spec_inflight
        ]
        if not ready:
            return False
        rows: list[tuple[int, _Request, int, int, int]] = []
        dispatched = False
        for slot, r in ready:
            n_hist = len(r.spec_tokens)
            # the dense-mode commit spans up to T_cap rows at [N, N+T);
            # when that no longer fits the region, hand the slot back
            # (checked against the BUDGET — the round T isn't known yet)
            if (n_hist - 1) + T_cap > e.max_context:
                self._despeculate(slot, r)
                dispatched = True
                continue
            rows.append((
                slot, r, n_hist,
                self.spec.k_for(slot), self.spec.m_for(slot),
            ))
        if not rows:
            return dispatched
        K = self.spec.round_k([k for *_, k, _m in rows])
        M = self.spec.round_m([m for *_, m in rows])
        draft_mode = self.spec.draft is not None
        # comb drafts pack exactly 1 + K*M nodes (the [B, K*M] device
        # draft splices in verbatim), so M clamps to the budget; n-gram
        # tries use the full budget so every trie shape compiles to ONE
        # program shape
        while draft_mode and 1 + K * M > T_cap and M > 1:
            M //= 2
        T = 1 + K * M if draft_mode else T_cap
        B = self._B
        toks = np.zeros((B, T), np.int32)
        parents = np.full((B, T), -2, np.int32)   # -2 = padding node
        parents[:, 0] = -1                        # node 0 = root
        slots_a = np.full(B, B, np.int32)         # dummies -> scratch
        q_starts = np.zeros(B, np.int32)
        seq_lens = np.zeros(B, np.int32)          # 0: dummy rows masked
        keys = np.zeros((B, 2), np.uint32)
        temps = np.zeros(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        top_ps = np.ones(B, np.float32)
        nodes_used = np.zeros(B, np.int32)
        penalties = None
        if any(r.spec_counts is not None for _, r, *_ in rows):
            penalties = (
                np.zeros((B, self.config.vocab_size), np.int32),
                np.zeros(B, np.float32),          # freq
                np.zeros(B, np.float32),          # pres
                np.ones(B, np.float32),           # rep
            )
        comb = (
            np.asarray(comb_parents(K, M), np.int32)
            if draft_mode else None
        )
        for j, (slot, r, n_hist, _k, _m) in enumerate(rows):
            toks[j, 0] = r.spec_tokens[-1]        # pending token
            slots_a[j] = slot
            q_starts[j] = n_hist - 1
            seq_lens[j] = (n_hist - 1) + T
            keys[j] = r.spec_keys
            so = r.req.sampling_options
            temps[j] = so.temperature or 0.0
            top_ks[j] = so.top_k or 0
            top_ps[j] = so.top_p if so.top_p is not None else 1.0
            if draft_mode:
                parents[j] = comb
                nodes_used[j] = T - 1
            if penalties is not None and r.spec_counts is not None:
                penalties[0][j] = r.spec_counts
                penalties[1][j] = so.frequency_penalty or 0.0
                penalties[2][j] = so.presence_penalty or 0.0
                penalties[3][j] = so.repetition_penalty or 1.0
        self._poll_dry()
        t_disp = time.monotonic()
        drafted = None
        if draft_mode:
            # tree drafting always takes the fused batch path: the comb
            # shape IS a batch_draft output (spine + per-depth top-M)
            self.dispatch_counts["spec_draft"] += 1
            drafted = self.spec.propose_batch_tree(
                [(slot, r.spec_tokens) for slot, r, *_ in rows], B, K, M,
            )
        else:
            for j, (slot, r, _n, _k, _m) in enumerate(rows):
                tks, prs = self.spec.propose_tree(r.spec_tokens, K, M)
                n = min(len(tks), T - 1)
                toks[j, 1:1 + n] = tks[:n]
                parents[j, 1:1 + n] = prs[:n]
                nodes_used[j] = n
        t_draft_end = time.monotonic()
        self.dispatch_counts["spec_verify"] += 1
        self.ctx, packed = self.spec.verify_tree(
            self.params, self.ctx, jnp.asarray(toks), drafted,
            jnp.asarray(parents), slots_a, q_starts, seq_lens, keys,
            temps, top_ks, top_ps, K, penalties=penalties,
        )
        packed.copy_to_host_async()
        self.dispatch_counts["fetch"] += 1
        t_verify_end = time.monotonic()
        self.flight.record(
            "spec_verify_tree", slots=[slot for slot, *_ in rows],
            k=K, m=M, nodes=T - 1, fetches=1,
            dispatch_ms=round((t_verify_end - t_disp) * 1e3, 3),
        )
        for slot, r, *_ in rows:
            r.spec_ready = False
            r.spec_inflight = True
        self._track(_Entry(
            kind="spec_tree", handle=packed, rows=rows,
            aux=(M, parents, nodes_used), n_steps=K, t_dispatch=t_disp,
            spec_host=(t_draft_end - t_disp, t_verify_end - t_draft_end),
        ))
        return True

    def _despeculate(
        self, slot: int, r: _Request, gated: bool = False
    ) -> None:
        """Hand a speculating slot back to the fused decode round: the
        admit patch restores the exact device state the non-spec path
        would carry (pending token, ctx length, PRNG keys) — the
        continuation is token-identical.

        ``gated=True`` marks an acceptance-gate despec (--spec-gate-
        acceptance): the stream keeps mirroring its sequence on the
        fused round (_spec_gated_advance) and re-arms speculation after
        --spec-rearm-tokens fused tokens; each re-gate doubles that
        budget so a persistently incompressible stream converges to the
        plain fused round."""
        so = r.req.sampling_options
        r.spec = False
        r.spec_ready = False
        if gated:
            r.spec_gated = True
            r.spec_rearm_left = (
                self.ecfg.spec_rearm_tokens * r.spec_gate_backoff
            )
            r.spec_gate_backoff *= 2
            SPEC.inc("dynamo_spec_tree_gated_despecs_total")
            self.spec.on_gated_despec(slot)
        else:
            self.spec.on_despec(slot)
        self._slot_on(slot, r)  # back into the fused round's active set
        self._ctx_disp[slot] = len(r.spec_tokens)
        self._dispatch_patch(admit=dict(
            slot=slot,
            ctx=len(r.spec_tokens),
            tok=jnp.asarray([r.spec_tokens[-1]], jnp.int32),
            keys=np.asarray(r.spec_keys, np.uint32),
            temp=so.temperature or 0.0,
            top_k=so.top_k or 0,
            top_p=so.top_p if so.top_p is not None else 1.0,
            # penalized slots restore their sampler state in full: the
            # fused continuation must see the same histogram the verify
            # loop advanced, or penalties would reset mid-request
            freq=so.frequency_penalty or 0.0,
            pres=so.presence_penalty or 0.0,
            rep=so.repetition_penalty or 1.0,
            counts=r.spec_counts,
        ))

    def _process_spec(self, entry: _Entry) -> None:
        """Consume one verify result: emit the accepted prefix + bonus
        token per slot, advance host history and PRNG keys, roll the
        draft model's KV pointer back to the accepted length.

        Adaptive K lands here: every verified token is emitted (the
        round already paid the forward for the full bucketed-max width —
        discarding accepted tokens would waste exactly the mixed-K
        rounds the controller creates), acceptance is accounted at the
        ROUND width, the rolling rate updates, and a slot whose rate
        collapsed is handed back to the fused decode round instead of
        re-arming. Per-slot effective K shapes the NEXT round's width
        vote, not this round's emission."""
        out = np.asarray(entry.handle)          # [B, K+1]
        n_out_arr = np.asarray(entry.aux[0])    # [B]
        new_keys = np.asarray(entry.aux[1])     # [B, 2]
        k_round = entry.n_steps
        for j, (slot, r, hist_len, _k_eff) in enumerate(entry.rows):
            r.spec_inflight = False
            if r.finished or self._slots[slot] is not r:
                continue
            if r.cancelled:
                self._finish(r, None)
                continue
            n = int(n_out_arr[j])
            accepted = n - 1
            self.spec.on_result(slot, hist_len, accepted, k_round)
            r.spec_proposed += k_round
            r.spec_accepted += accepted
            toks = [int(t) for t in out[j, :n]]
            batch: list[int] = []
            finish: Optional[FinishReason] = None
            for tok in toks:
                finish = self._advance_token(r, tok)
                if finish is FinishReason.EOS:
                    break  # stop token itself is not emitted
                batch.append(tok)
                if finish is not None:
                    break
            if batch:
                self._note_emit(r, len(batch), entry, "spec_verify_round")
            if batch or finish is not None:
                extra = (
                    {"annotations": self._final_annotations(r)}
                    if finish is not None else {}
                )
                r.emit(LLMEngineOutput(
                    token_ids=batch, finish_reason=finish, **extra
                ))
            self.tokens_generated += len(batch)
            if finish is not None:
                self._finish(r, None)
                continue
            if r.spec_counts is not None:
                # host mirror of the penalty histogram: every emitted
                # token counts (matching the fused sampler's per-token
                # advance; the request's first-ever token is excluded
                # there too — see _process_first)
                for t in toks:
                    r.spec_counts[t] += 1
            r.spec_tokens.extend(toks)  # accepted + bonus, all emitted
            r.spec_keys = new_keys[j]
            if self.spec.should_gate(slot):
                # acceptance EWMA pinned under the gate for a full
                # window: this workload isn't speculation-shaped right
                # now — run it fused, revisit after the re-arm budget
                self._despeculate(slot, r, gated=True)
                continue
            if self.spec.should_despec(slot):
                # acceptance collapsed: every verify here costs a full
                # forward for ~1 emitted token — strictly worse than the
                # fused round. Token-identical continuation, like the
                # context-limit despec.
                self._despeculate(slot, r)
                continue
            r.spec_ready = True
            self._ctx_disp[slot] = len(r.spec_tokens)

    def _process_spec_tree(self, entry: _Entry) -> None:
        """Consume one tree-verify result: the single packed [B, 2D+4]
        fetch carries, per row, the accepted-path tokens + bonus
        (cols [0, D]), the accepted node indices (cols [D+1, 2D]), the
        emitted count n_out (col 2D+1) and the advanced PRNG key
        bitcast to i32 (cols 2D+2, 2D+3) — see spec_verify_tree.

        Emission is identical to the linear path; the extra tree
        bookkeeping is the per-branch acceptance histogram + draft-KV
        spine rollback (on_result_tree), the tree counters on the SPEC
        scrape registry, and the acceptance gate (a stream whose EWMA
        pins under --spec-gate-acceptance de-speculates with re-arm
        armed instead of permanently)."""
        packed = np.asarray(entry.handle)       # [B, 2D+4] i32
        D = entry.n_steps                       # round depth (d_max)
        m_round, parents, nodes_used = entry.aux
        for j, (slot, r, hist_len, _k, _m) in enumerate(entry.rows):
            r.spec_inflight = False
            if r.finished or self._slots[slot] is not r:
                continue
            if r.cancelled:
                self._finish(r, None)
                continue
            n = int(packed[j, 2 * D + 1])
            accepted = n - 1
            self.spec.on_result_tree(
                slot, hist_len, accepted, D, m_round,
                int(nodes_used[j]),
                [int(x) for x in packed[j, D + 1:D + 1 + accepted]],
                [int(x) for x in parents[j]],
            )
            SPEC.inc("dynamo_spec_tree_nodes_total", int(nodes_used[j]))
            SPEC.inc("dynamo_spec_tree_accepted_path_len_total", accepted)
            # acceptance stays tokens-per-depth — directly comparable
            # to the linear chain at the same K
            r.spec_proposed += D
            r.spec_accepted += accepted
            toks = [int(t) for t in packed[j, :n]]
            batch: list[int] = []
            finish: Optional[FinishReason] = None
            for tok in toks:
                finish = self._advance_token(r, tok)
                if finish is FinishReason.EOS:
                    break  # stop token itself is not emitted
                batch.append(tok)
                if finish is not None:
                    break
            if batch:
                self._note_emit(r, len(batch), entry, "spec_verify_round")
            if batch or finish is not None:
                extra = (
                    {"annotations": self._final_annotations(r)}
                    if finish is not None else {}
                )
                r.emit(LLMEngineOutput(
                    token_ids=batch, finish_reason=finish, **extra
                ))
            self.tokens_generated += len(batch)
            if finish is not None:
                self._finish(r, None)
                continue
            if r.spec_counts is not None:
                for t in toks:
                    r.spec_counts[t] += 1
            r.spec_tokens.extend(toks)  # accepted path + bonus
            r.spec_keys = np.ascontiguousarray(
                packed[j, 2 * D + 2:2 * D + 4]
            ).view(np.uint32)
            if self.spec.should_gate(slot):
                self._despeculate(slot, r, gated=True)
                continue
            if self.spec.should_despec(slot):
                self._despeculate(slot, r)
                continue
            r.spec_ready = True
            self._ctx_disp[slot] = len(r.spec_tokens)

    def _note_emit(
        self, r: _Request, n_tokens: int, entry: _Entry, kind: str
    ) -> None:
        """Telemetry for one round's emitted batch: per-token gaps into
        the ITL histogram (the batch arrives together — its gap is the
        round wall split over the tokens), the request's ``decode`` phase
        (which of its rounds stood behind a prefill, and what they cost
        it) and a capped round span."""
        now = time.monotonic()
        _, programs, padded = entry.ahead
        if r.t_last_emit is not None:
            wait = now - r.t_last_emit
            gap = wait / n_tokens
            self._h_itl.observe(gap, n_tokens,
                                exemplar_id=r.req.request_id or None)
            if len(r.itl_gaps) < 4096:
                r.itl_gaps.append((gap, n_tokens))
            if programs:
                r.behind_prefill_s += wait
        if programs:
            r.rounds_behind_prefill += 1
            r.prefill_tokens_ahead += padded
        if entry.late is not None:
            r.late_rounds += 1
            for cause, s in entry.late.items():
                r.late_s[cause] = r.late_s.get(cause, 0.0) + s
        r.t_last_emit = now
        r.decode_rounds += 1
        if (len(r.trace_spans) + len(r.round_spans) < _MAX_ROUND_SPANS
                and entry.t_dispatch):
            # annotate diet: the hot loop records one raw tuple; the
            # span dicts (and spec draft/verify children) are built
            # lazily at finish, when something actually reads the trace
            r.round_spans.append((
                kind, entry.t_dispatch, now - entry.t_dispatch,
                n_tokens, entry.spec_host,
            ))

    def _final_annotations(self, r: _Request) -> dict:
        """Annotations for the FINISHING output: speculation counters,
        per-request timing (TTFT / ITL p50/p95 / queue / E2E — what
        sdk.request_stats folds), and the worker-side trace spans the
        frontend merges into its span tree. Called exactly once per
        normally-finished request; also registers the spans in the
        worker-local trace store when no frontend owns the trace in this
        process (remote-worker mode)."""
        prev_seg = self.prof.push(_SEG_ANNOTATE)
        try:
            return self._final_annotations_inner(r)
        finally:
            self.prof.enter(prev_seg)

    def _final_annotations_inner(self, r: _Request) -> dict:
        ann = self._spec_annotations(r)
        now = time.monotonic()
        e2e = now - r.enqueue_time
        self._h_e2e.observe(e2e, exemplar_id=r.req.request_id or None)
        timing: dict[str, Any] = {
            "e2e_s": round(e2e, 6),
            "output_tokens": r.produced,
            "decode_rounds": r.decode_rounds,
        }
        if r.first_token_time is not None:
            timing["ttft_s"] = round(
                r.first_token_time - r.enqueue_time, 6
            )
        if r.t_prefill_start is not None:
            timing["queue_s"] = round(
                r.t_prefill_start - r.enqueue_time, 6
            )
        for key, q in (("itl_p50_s", 0.50), ("itl_p95_s", 0.95)):
            v = tmetrics.weighted_percentile(r.itl_gaps, q)
            if v is not None:
                timing[key] = round(v, 6)
        ann["timing"] = timing
        if r.decode_rounds and r.first_token_time is not None:
            r.trace_spans.append(self._decode_span(r, timing))
        if r.trace_spans:
            ann["trace"] = {"spans": list(r.trace_spans)}
            rid = r.req.request_id
            if rid and not TRACES.has_active(rid):
                TRACES.record_remote(rid, r.trace_spans)
                # worker-side forensics: in remote-worker mode no
                # in-process frontend sees this finish, so the breach /
                # sample decision runs here and the dossier is assembled
                # directly from the engine's own rings
                self._forensics.worker_finish(
                    rid, timing=timing,
                    worker_id=str(self.ecfg.worker_id),
                    trace_spans=r.trace_spans,
                )
        return ann

    def _decode_span(self, r: _Request, timing: dict) -> dict:
        """The ``decode`` phase: ``first_token`` ended with the first
        token on the host, this one ends with the last emitted batch, so
        engine E2E = queue + first_token + decode + the finishing
        bookkeeping. The per-round spans become its children, as
        ``prefill`` became ``first_token``'s."""
        rid = r.req.request_id or None
        dur = r.t_last_emit - r.first_token_time
        timing["decode_s"] = round(dur, 6)
        # its late rounds and what they cost it by cause, beside the
        # behind-prefill split: a p90 request's own account
        late_s = {c: round(v, 6) for c, v in r.late_s.items()}
        timing["late_rounds"] = r.late_rounds
        timing["late_s"] = late_s
        if r.produced > 1:
            tpot = dur / (r.produced - 1)
            timing["tpot_s"] = round(tpot, 6)
            self._h_tpot.observe(tpot, exemplar_id=rid)
        wall_now = time.time()
        mono_now = time.monotonic()
        sp = Span(
            "decode", wall_now - (mono_now - r.first_token_time), dur,
            attrs=dict(
                request_id=rid, tokens=r.produced - 1,
                rounds=r.decode_rounds,
                rounds_behind_prefill=r.rounds_behind_prefill,
                behind_prefill_s=round(r.behind_prefill_s, 6),
                prefill_tokens_ahead=r.prefill_tokens_ahead,
                late_rounds=r.late_rounds, late_s=late_s,
            )).to_dict()
        # materialize the lazily-accumulated round spans (the unix start
        # is anchored off the shared monotonic clock)
        rounds = sp["children"] = []
        for kind, t0, round_s, n_toks, spec_host in r.round_spans:
            rs: dict[str, Any] = {
                "name": kind,
                "start_s": round(wall_now - (mono_now - t0), 6),
                "duration_s": round(round_s, 6),
                "attrs": {"tokens": n_toks},
            }
            if spec_host is not None:
                # spec rounds carry draft/verify child spans so the
                # speculation cost shows up inside timelines, not
                # just as one opaque round span
                draft_s, verify_s = spec_host
                t0_w = rs["start_s"]
                rs["children"] = [
                    Span("spec_draft", t0_w, draft_s).to_dict(),
                    Span("spec_verify", t0_w + draft_s,
                         verify_s).to_dict(),
                ]
            rounds.append(rs)
        r.round_spans = []
        return sp

    def _spec_annotations(self, r: _Request) -> dict:
        """Per-request speculation stats for the finishing output — the
        SDK reads these back as request stats (sdk.request_stats), which
        is what lets a planner gate speculation on observed acceptance."""
        if r.spec_proposed <= 0:
            return {}
        return {"spec": {
            "proposed": r.spec_proposed,
            "accepted": r.spec_accepted,
            "acceptance_rate": r.spec_accepted / r.spec_proposed,
        }}

    # ---- block sealing (ctx -> pool prefix-cache copies) ----

    def _queue_seal(self, r: _Request, position: int,
                    block_hash: int, parent_hash: int) -> None:
        """Copy-commit one sealed block into the prefix cache. Best-effort:
        a full pool (no free/evictable page) skips the commit — the prefix
        cache is a cache, not required state."""
        got = self.allocator.allocate(1)
        if got is None:
            return
        page = got[0]
        if not self.allocator.commit(page, block_hash, parent_hash):
            self.allocator.free([page])  # duplicate hash: already cached
            return
        self._seal_queue.append((r.slot, position * self.ecfg.page_size, page))
        # release our reference: the page parks in the LRU (prefix-hittable,
        # offload-candidate) once the seal copy below is dispatched
        self.allocator.free([page])

    def _seal_prefilled(self, r: _Request, limit: Optional[int] = None) -> None:
        """Copy-commit the prompt blocks fully covered by prefill so far
        (beyond what was prefix-matched). Called after EVERY prefill
        chunk, not only at prompt completion: complete prefix blocks
        become prefix-hittable while later chunks still compute — local
        concurrent duplicates hit them, and the disagg prefill worker
        streams them to the decode pool mid-prefill (the chunk-pipelined
        transfer plane's unit of overlap)."""
        ps = self.ecfg.page_size
        done_blocks = min(
            r.prefill_pos // ps if limit is None else limit,
            len(r.seq.blocks),
        )
        for blk in r.seq.blocks[r.sealed_prefix:done_blocks]:
            self._queue_seal(r, blk.position, blk.block_hash, blk.parent_hash)
        if done_blocks > r.sealed_prefix:
            # blocks are MATCHABLE the moment _queue_seal commits them —
            # notify now, not when their pool copy dispatches. On a
            # prefill-only engine (disagg prefill worker) nothing else
            # dispatches seals between export runs, so the deferred
            # notification left the export stream riding its 10 ms
            # safety timeout once per chunk; every engine-loop export
            # path flushes queued seals before any pool read, so the
            # earlier wake stays device-order safe.
            self._notify_commits()
        r.sealed_prefix = max(r.sealed_prefix, done_blocks)

    def _take_seal_batch(self, width: Optional[int] = None):
        """Pop + pad the pending seal queue as (slots, starts, pages)
        int32 arrays (padding rows -> scratch page 0), or None.

        With ``width`` (the fused-round path) at most ``width`` entries
        are taken and the arrays are padded to EXACTLY that width — one
        static shape, one compile. Without it (standalone flush) the
        whole queue is taken at a pow2-bucketed width."""
        if not self._seal_queue:
            return None
        if width is None:
            batch = self._seal_queue
            self._seal_queue = []
            w = pow2_cover(len(batch))
        else:
            batch = self._seal_queue[:width]
            self._seal_queue = self._seal_queue[width:]
            w = width
        slots = np.zeros(w, np.int32)
        starts = np.zeros(w, np.int32)
        pages = np.zeros(w, np.int32)  # padding -> scratch page 0
        for i, (s, st, pg) in enumerate(batch):
            slots[i], starts[i], pages[i] = s, st, pg
        return slots, starts, pages, len(batch)

    def _flush_seals(self) -> None:
        """Dispatch the batched ctx->pool seal copy standalone (pow2-
        padded). Device order makes this safe: the sealed positions were
        written by already-dispatched programs, and any admission/
        offload/export that READS these pool pages is dispatched after
        this. The steady-decode path doesn't come here — its seals ride
        the fused round program (_dispatch_round); this covers admission
        boundaries and rounds that read the pool before dispatching."""
        batch = self._take_seal_batch()
        if batch is None:
            return
        slots, starts, pages, n_real = batch
        if self.on_dispatch is not None:
            self.on_dispatch("seal", {
                "slots": slots.tolist(), "starts": starts.tolist(),
                "pages": pages.tolist(),
            })
        self.dispatch_counts["seal"] += 1
        self.cache = llama.seal_blocks(
            self.cache, self.ctx,
            jnp.asarray(slots), jnp.asarray(starts), jnp.asarray(pages),
            page_size=self.ecfg.page_size,
        )
        if self.kv_quant:
            if self.ctx_quant:
                KV_QUANT.inc(
                    "dynamo_kv_quant_ctx_seal_raw_pages_total", n_real)
            else:
                KV_QUANT.inc("dynamo_kv_quant_pages_total", n_real)
        self._notify_commits()

    # ---- offload (G2 tier) ----

    def _dispatch_offloads(self) -> None:
        """Batch-gather validated park candidates and fetch them to host
        behind compute. Runs BEFORE admission so same-round allocations
        cannot recycle a candidate page between validation and the gather
        dispatch (device-order then guarantees the gather reads the
        pre-recycle content anyway; validation just avoids wasted work)."""
        if self.offload is None or not self._offload_cands:
            return
        batch: list[tuple[int, int, int]] = []
        while len(batch) < self.ecfg.offload_batch:
            try:
                cand = self._offload_cands.popleft()
            except IndexError:
                break
            page, h, _parent = cand
            if h in self.offload:
                continue
            if self.allocator.page_for_hash(h) != page:
                continue  # evicted/recycled since parking
            batch.append(cand)
        if not batch:
            return
        if self._seal_queue:
            # the gather reads the pool: queued seal copies first
            self._flush_seals()
        t_disp = time.monotonic()
        self.dispatch_counts["offload_gather"] += 1
        out, scales = self._gather_padded([p for p, _, _ in batch])
        out.copy_to_host_async()
        self.dispatch_counts["fetch"] += 1
        if scales is not None:
            scales.copy_to_host_async()
        self.flight.record(
            "g2_offload", pages=len(batch),
            dispatch_ms=round((time.monotonic() - t_disp) * 1e3, 3),
        )
        self._track(_Entry(
            kind="offload", handle=out, n_steps=len(batch),
            hashes=[h for _, h, _ in batch],
            parents=[par for _, _, par in batch],
            aux=scales,
        ))

    def _onboard_from_host(
        self, hashes: list[int], matched_pages: list[int]
    ) -> list[int]:
        """Extend a G1 prefix match with a contiguous run held in the G2
        host tier: allocate pages, scatter (async H2D — prefill follows in
        device order), commit under the same chained hashes."""
        if self.offload is None:
            return matched_pages
        m = len(matched_pages)
        run = self.offload.lookup_run(hashes[m:])
        if not run:
            return matched_pages
        pages = self.allocator.allocate(len(run))
        if pages is None:
            return matched_pages
        # chunked H2D: gather+scatter kv_transfer_chunk_pages at a time —
        # peak host staging is O(chunk) instead of O(run), and the
        # uniform chunk width reuses one compiled scatter shape
        cp = self.ecfg.kv_transfer_chunk_pages or len(pages)
        good = len(run)
        for i in range(0, len(pages), cp):
            chunk = run[i:i + cp]
            hs = [h for h, _ in chunk]
            data = self.offload.gather(hs)
            scales = self.offload.gather_scales(hs)
            # admission verify: gathered G2/G3 bytes are checked against
            # their seal-time crcs BEFORE the scatter — corrupt tier
            # content must never reach the device pool
            bad = self.offload.verify_pages(hs, data, scales)
            k = bad[0] if bad else len(chunk)
            if k:
                self._scatter_padded(
                    pages[i:i + k],
                    QuantizedPages(data[:, :, :, :k], scales[..., :k])
                    if scales is not None else data[:, :, :, :k],
                )
            if bad:
                # quarantine the failed blocks (drop from every tier,
                # refuse re-admission); the chained run must stay
                # contiguous, so everything past the first bad block is
                # surrendered and recomputed as prefill — corruption
                # costs latency, never wrong tokens
                for j in bad:
                    self.kv_quarantine.add(hs[j])
                    self.offload.drop_everywhere(hs[j])
                good = i + k
                KV_INTEGRITY.inc(
                    "dynamo_kv_integrity_recomputed_total",
                    len(run) - good,
                )
                log.warning(
                    "KV integrity: %d corrupt block(s) in onboard run "
                    "quarantined; %d of %d blocks recomputed as prefill",
                    len(bad), len(run) - good, len(run),
                )
                break
        if good < len(run):
            self.allocator.free(pages[good:])
            pages, run = pages[:good], run[:good]
        for pg, (h, parent) in zip(pages, run):
            self.allocator.commit(pg, h, parent)
        log.debug("onboarded %d blocks from host tier", len(pages))
        return matched_pages + pages

    # ---- G4 remote tier (kv_transfer.RemoteKvFetcher) ----

    async def _remote_prefetch(self, r: _Request) -> None:
        """Before admission: if the prompt's block-hash run is uncovered
        by G1/G2/G3, ask peer workers for it (G4). Fetched pages are
        queued for the engine loop to land in the G2 host tier, where the
        normal onboard path (_onboard_from_host) picks them up — the
        remote tier needs no scatter path of its own. Coverage checks
        here are read-only hints from another thread; a stale answer
        costs one wasted fetch or one recompute, never correctness."""
        ps = self.ecfg.page_size
        blocks = r.seq.blocks
        matchable = blocks[: max(0, (len(r.tokens) - 1) // ps)]
        if not matchable:
            return
        covered = self.allocator.cached_prefix_len(
            [b.block_hash for b in matchable]
        )
        off = self.offload
        i = covered
        while i < len(matchable) and (
            matchable[i].block_hash in off
            or (off.spill is not None and matchable[i].block_hash in off.spill)
        ):
            i += 1
        missing = matchable[i:]
        if not missing:
            return
        # dedup-by-hash admission: consult the fleet hint digest before
        # probing. Fleet-known holders are probed first; a miss whose
        # blocks the fleet hot set doesn't know at all skips the probe
        # round (recomputing a fleet-unique prefix is the right call —
        # probing every peer for it is pure wasted wire).
        holder_hint: Optional[list[str]] = None
        hints = self.fleet_hints
        if (self.ecfg.kv_dedup_admission and hints is not None
                and hints.applied):
            known = [h for b in missing
                     for h in hints.holders(b.block_hash)]
            if known:
                # dedupe, first-seen order (leading blocks first)
                holder_hint = list(dict.fromkeys(known))
            elif all(hints.replicas(b.block_hash) is None
                     for b in missing):
                KV_FLEET.inc("dynamo_kv_fleet_dedup_skipped_probes_total")
                return
        t_fetch = time.monotonic()
        chunk_spans: list[dict] = []
        t_prev = t_fetch

        def land(offset: int, arr: np.ndarray) -> None:
            # one streamed chunk: into the host-ingest queue immediately
            # (the G2 tier fills while later chunks are still on the
            # wire) + a child span under g4_fetch
            nonlocal t_prev
            n = int(arr.shape[3])
            sub = missing[offset:offset + n]
            # mode boundary: an int8 peer's bundle lands as-is in an
            # int8 tier; cross-mode payloads convert here
            payload = to_pool_dtype(arr, self.kv_quant, off.dtype)
            if not isinstance(payload, QuantizedPages):
                payload = np.asarray(payload, dtype=off.dtype)
            self._host_ingest.put((
                [b.block_hash for b in sub],
                [b.parent_hash for b in sub],
                payload,
            ))
            self._wake_evt.set()
            chunk_spans.append(_span_dict(
                "g4_chunk", t_prev, blocks=n, offset=offset,
            ))
            t_prev = time.monotonic()

        try:
            # every fetch path (chunk-streamed, probe full reply, legacy
            # monolithic race) delivers pages through `land` — data is
            # always None here
            found, _ = await self.remote_kv.fetch(
                [b.block_hash for b in missing], on_chunk=land,
                holders=holder_hint,
            )
        except Exception:  # noqa: BLE001 — G4 is best-effort
            log.exception("G4 remote fetch failed")
            return
        if not found:
            return
        # every fetched block is a prefill block this worker did NOT
        # recompute — the dedup economy's headline counter
        KV_FLEET.inc(
            "dynamo_kv_fleet_recompute_avoided_blocks_total", int(found)
        )
        # trace the peer-pool fetch (with its chunk children): rides the
        # request's worker-side span list so migration replays / disagg
        # flows show the G4 hop end-to-end in /debug/trace/{request_id}
        sp = _span_dict(
            "g4_fetch", t_fetch,
            blocks=int(found), requested=len(missing),
            chunks=max(len(chunk_spans), 1),
        )
        if chunk_spans:
            sp["children"] = chunk_spans
        r.trace_spans.append(sp)

    def _drain_host_ingest(self) -> None:
        from dynamo_tpu.resilience.chaos import CHAOS

        while True:
            try:
                hashes, parents, data = self._host_ingest.get_nowait()
            except queue_mod.Empty:
                return
            if self.offload is None:
                return
            n = self.offload.put_batch(hashes, parents, data)
            self.remote_onboard_blocks += n
            if n and CHAOS.fire("corrupt_prefetch"):
                # rot a just-landed page AFTER its crc was sealed at put
                # (silent DRAM corruption of prefetched content): the
                # onboard-admission verify must quarantine it before it
                # can reach the device pool
                self.offload.rot_page(hashes[0])

    def apply_fleet_hints(self, digest: dict) -> None:
        """Frontend hint push (kv_router/prefetch.py): retain the fleet
        replica/holder digest for dedup admission and wire replica counts
        into G2/G3 eviction. Hint maps are swapped wholesale, so the
        engine thread racing a push sees the old or the new digest, never
        a torn one."""
        from dynamo_tpu.kv_router.fleet import FleetHints

        if self.fleet_hints is None:
            self.fleet_hints = FleetHints(digest)
        else:
            self.fleet_hints.apply(digest)
        if self.offload is not None:
            self.offload.fleet_replicas = self.fleet_hints.replicas
            if getattr(self.offload, "spill", None) is not None:
                self.offload.spill.fleet_replicas = (
                    self.fleet_hints.replicas
                )

    async def prefetch_hashes(
        self, hashes: list[int], parents: Optional[list[int]] = None
    ) -> int:
        """Fleet replication push (kv_router/prefetch.py): pull the given
        chained-hash run from peer pools into the G2 host tier AHEAD of
        demand. Blocks already held in G1/G2/G3 are skipped; fetched
        pages ride the same host-ingest queue as demand G4 fetches.
        Returns blocks landed."""
        off = self.offload
        if self.remote_kv is None or off is None or not hashes:
            return 0
        if parents is None:
            # best-effort chain: within the run each block's parent is
            # its predecessor; the head's true parent is unknown here
            parents = [0, *hashes[:-1]]
        par = dict(zip(hashes, parents))
        missing = [
            h for h in hashes
            if h not in off
            and (off.spill is None or h not in off.spill)
            and self.allocator.page_for_hash(h) is None
        ]
        if not missing:
            return 0

        def land(offset: int, arr: np.ndarray) -> None:
            n = int(arr.shape[3])
            sub = missing[offset:offset + n]
            payload = to_pool_dtype(arr, self.kv_quant, off.dtype)
            if not isinstance(payload, QuantizedPages):
                payload = np.asarray(payload, dtype=off.dtype)
            self._host_ingest.put((sub, [par[h] for h in sub], payload))
            self._wake_evt.set()

        try:
            found, _ = await self.remote_kv.fetch(missing, on_chunk=land)
        except Exception:  # noqa: BLE001 — prefetch is best-effort
            log.exception("fleet prefetch fetch failed")
            return 0
        found = int(found or 0)
        if found:
            KV_FLEET.inc("dynamo_kv_fleet_prefetched_blocks_total", found)
        return found

    # ---- admission / prefill ----

    def _admit(self) -> None:
        now = time.time()
        kept = []
        for r in self._waiting:
            if r.cancelled:
                self._abort_prefill(r)
            elif (r.prefill_pos < 0 and r.req.deadline is not None
                    and now > r.req.deadline):
                # deadline-aware shedding: a still-WAITING request whose
                # deadline passed would only prefill dead work. Never a
                # request that already started (mid-prefill/mid-stream
                # work is delivered, not discarded).
                self._shed_waiting(r, "deadline")
            else:
                kept.append(r)
        self._waiting = kept
        self._maybe_preempt_running()
        # bounded prefill budget per round: a long prompt advances one
        # chunk at a time with decode rounds in between (ITL isolation,
        # the local form of what disagg provides globally). Concurrent
        # same-bucket chunks batch into ONE [K, T] program (batch_prefill)
        # — the TTFT lever under bursty arrivals.
        budget = max(1, self.ecfg.prefill_chunks_per_round)
        while budget > 0 and self._waiting:
            group, width = self._collect_prefill_group(budget)
            if not group:
                return  # head is blocked on a free lane
            if len(group) == 1:
                r = group[0]
                status = self._prefill_step(r)
                budget -= 1
                if status in ("done", "failed"):
                    self._waiting.remove(r)
            else:
                budget -= len(group)
                for r in self._batch_prefill_group(group, width):
                    self._waiting.remove(r)
            # a dispatch site leaves in admit_pack / _launch / _first
            self.prof.enter(_SEG_ADMIT)

    def _needs_solo_prefill(self, r: _Request) -> bool:
        """Paths the batched program doesn't carry: multimodal embedding
        injection and the sequence-parallel ring prefill."""
        if (r.req.multimodal or {}).get("embeddings"):
            return True
        e = self.ecfg
        if (r.prefill_pos < 0
                and e.sp_prefill_threshold is not None
                and r.adapter_id == 0
                and self.mesh.shape.get("sp", 1) > 1):
            ps = e.page_size
            hashes = r.seq.block_hashes()
            matchable = hashes[: max(0, (len(r.tokens) - 1) // ps)]
            cached = self.allocator.cached_prefix_len(matchable)
            if len(r.tokens) - cached * ps >= e.sp_prefill_threshold:
                return True
        return False

    def _chunk_width(self, remaining: int) -> int:
        """Padded (bucketed, page-aligned) width of the next chunk for a
        request with `remaining` unprefilled tokens — mirrors
        _prefill_step's chunk shape exactly."""
        e = self.ecfg
        ps = e.page_size
        max_chunk = ((e.prefill_buckets[-1] + ps - 1) // ps) * ps
        pad_t = e.bucket_for(min(remaining, max_chunk)) or max_chunk
        return ((pad_t + ps - 1) // ps) * ps

    def _collect_prefill_group(
        self, budget: int
    ) -> tuple[list[_Request], int]:
        """Walk the waiting queue head and collect a FIFO prefix of
        requests whose next chunks share one bucket width (one compiled
        [K, T] shape). Requests are *begun* (lane + prefix match) as they
        are considered — a member whose bucket diverges stays begun and
        leads the next group. Returns (group, T); a solo group routes
        through the per-request path."""
        e = self.ecfg
        group: list[_Request] = []
        width = 0
        cap = budget
        for r in self._waiting:
            if len(group) >= cap:
                break
            if self._needs_solo_prefill(r):
                break
            if r.prefill_pos < 0:
                if self._free_slot() is None:
                    break
                self._prefill_begin(r)
            t = self._chunk_width(len(r.tokens) - r.prefill_pos)
            if not group:
                width = t
                cap = min(budget, e.prefill_lanes(t))
            elif t != width:
                break
            group.append(r)
        if not group and self._waiting:
            head = self._waiting[0]
            if self._needs_solo_prefill(head) and (
                head.prefill_pos >= 0 or self._free_slot() is not None
            ):
                return [head], 0
        return group, width

    def _batch_prefill_group(
        self, group: list[_Request], width: int
    ) -> list[_Request]:
        """Dispatch one batched prefill for the group's next chunks and
        finish the requests whose prompts complete. The compiled batch
        width follows the group (EngineConfig.prefill_lanes, which also
        capped it): a group of two runs two lanes, and only a group of 3
        or 5-7 pads with scratch-lane dummies."""
        e = self.ecfg
        self.prof.enter(_SEG_ADMIT_PACK)
        K = e.prefill_lanes(width, len(group))
        toks = np.zeros((K, width), np.int32)
        slots = np.full(K, self._B, np.int32)   # dummies -> scratch lane
        q_starts = np.zeros(K, np.int32)
        seq_lens = np.zeros(K, np.int32)        # dummy seq_len 0: all
        chunk_lens = []                         # tokens masked out
        adapter_ids = np.zeros(K, np.int32)     # dummies -> identity row
        for i, r in enumerate(group):
            start = r.prefill_pos
            chunk = r.tokens[start : start + width]
            toks[i, : len(chunk)] = chunk
            slots[i] = r.slot
            q_starts[i] = start
            seq_lens[i] = start + len(chunk)
            chunk_lens.append(len(chunk))
            adapter_ids[i] = r.adapter_id
        # ctx_span is binary — 0 (fresh: no region read compiled) or the
        # FULL region: each distinct value is its own XLA compile of the
        # whole prefill program. The span only BOUNDS the read: the
        # attention scans each lane's region blocks below its q_start
        ctx_span = e.max_context if int(q_starts.max()) > 0 else 0
        self.batch_prefills += 1
        if self.on_dispatch is not None:
            self.on_dispatch("prefill_batch", {
                "tokens": toks.tolist(), "slots": slots.tolist(),
                "q_starts": q_starts.tolist(),
                "seq_lens": seq_lens.tolist(), "ctx_span": ctx_span,
                "adapter_ids": adapter_ids.tolist(),
            })
        t_disp = time.monotonic()
        self.dispatch_counts["prefill_batch"] += 1
        self._note_prefill_dispatch(
            sum(chunk_lens), llama.prefill_positions_run(
                self.config, width, q_starts, seq_lens))
        self._observe_attn_pairs(width, q_starts, seq_lens, ctx_span)
        sorted_rows = llama.moe_prefill_rows_sorted(self.config, K * width)
        self.prof.enter(_SEG_ADMIT_LAUNCH)
        self.ctx, logits, *moved = llama.batch_prefill(
            self.config, self.params, self.ctx, jnp.asarray(toks),
            jnp.asarray(slots), jnp.asarray(q_starts),
            jnp.asarray(seq_lens), ctx_span, jnp.asarray(adapter_ids),
            counted=sorted_rows > 0, attn=self.decode_attn,
        )
        self._newest = logits
        self._note_moe_rows(moved, sorted_rows)
        self.flight.record(
            "prefill_batch", slots=[r.slot for r in group], width=width,
            dispatch_ms=round((time.monotonic() - t_disp) * 1e3, 3),
        )
        self.prof.enter(_SEG_ADMIT_FIRST)
        done: list[tuple[int, _Request]] = []
        for i, r in enumerate(group):
            r.prefill_chunks += 1
            r.prefill_pos = int(q_starts[i]) + chunk_lens[i]
            if r.prefill_pos < len(r.tokens):
                self._seal_prefilled(r)  # mid-prompt blocks seal per chunk
                continue  # multi-chunk: next chunk in a later round
            done.append((i, r))
        if done:
            self._finish_prefill(logits, done, K)
        return [r for _, r in done]

    def _note_prefill_dispatch(self, real: int, padded: int) -> None:
        """The books of one prefill program about to be dispatched: the
        prompt tokens it computes and the positions it runs (a dense
        chunk's, from ``llama.prefill_positions_run``: its live row
        blocks where the program loops over them), which then stand
        ahead of the next fused round on the device's one queue."""
        self._poll_dry()
        self._h_pf_tokens.observe(real)
        self._h_pf_padded.observe(padded)
        self._ahead[0] += 1
        self._ahead[1] += padded

    def _poll_dry(self) -> None:
        """Before a model program is dispatched: has the newest one
        dispatched before it already finished (or was there none)? Then
        the device stood dry when this dispatch found it. The count is
        exact; the time is not, for the device ran dry somewhere between
        two polls (RoundProf.poll charges the whole stretch). The same
        moment reads what finished prefill programs counted."""
        newest = self._newest
        dry = newest is None or newest.is_ready()
        self._h_dry.observe(float(dry))
        self.prof.poll(dry)
        self._observe_moe_rows()

    def _note_moe_rows(self, moved: list, sorted_rows: int) -> None:
        """A prefill program whose expert layers loop over their live row
        blocks was dispatched: its count of the rows they moved starts
        for the host now and is read once it has landed
        (``_observe_moe_rows``), never waited for."""
        if moved:
            moved[0].copy_to_host_async()
            self._moe_rows.append((moved[0], sorted_rows))

    def _observe_moe_rows(self) -> None:
        """The counts of the prefill programs that have finished, in
        dispatch order (the device's): rows its expert layers sorted,
        rows their gathers ran."""
        while self._moe_rows and self._moe_rows[0][0].is_ready():
            moved, sorted_rows = self._moe_rows.popleft()
            self._h_moe_pf_sorted.observe(sorted_rows)
            self._h_moe_pf_moved.observe(int(moved))

    def _observe_attn_pairs(self, width, q_starts, seq_lens,
                            ctx_span) -> None:
        """One observation per prefill dispatch of the (query, key) pairs
        its attention had to score and the pairs it did score (whole
        blocks) — the host's mirror of prefill_attention's loop bounds."""
        live, scored = prefill_attention_pairs(
            width, q_starts, seq_lens, ctx_span)
        self._h_pf_live.observe(live)
        self._h_pf_scored.observe(scored)
        self._observe(self._prefill_mirror(
            width, q_starts, seq_lens, scored, ctx_span))
        # and the prompt positions it computed in CONTINUING chunks
        self._h_pf_continued.observe(sum(
            min(max(int(n) - int(q), 0), width)
            for q, n in zip(q_starts, seq_lens) if int(q) > 0))

    def _observe(self, pairs) -> None:
        """What a block's host mirror returned, (metric, value) pairs,
        each into the histogram it names (llama.decode_mirror,
        llama.prefill_mirror)."""
        for metric, value in pairs:
            self.telemetry.get(metric).observe(value)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None and i not in self._prefilling:
                return i
        return None

    def _abort_prefill(self, r: _Request) -> None:
        """Release a half-prefilled request's lane reservation."""
        self._uncount_waiting(r)
        if r.slot >= 0 and self._prefilling.get(r.slot) is r:
            del self._prefilling[r.slot]
        r.slot = -1
        r.prefill_pos = -1

    def _note_queue_wait(self, r: _Request) -> None:
        """Account the admission queue wait once, when the request first
        gets a lane (multi-chunk continuations keep the original mark).
        The request also leaves the waiting-token backlog here — it is
        active prefill work now, not queued work."""
        self._uncount_waiting(r)
        # SFQ: service starting advances the global virtual clock to
        # this request's stamp, so later light-tenant arrivals start
        # from here rather than from zero
        self._vclock = max(self._vclock, r.vft)
        if r.t_prefill_start is not None:
            return
        now = time.monotonic()
        wait = now - r.enqueue_time
        self._h_queue.observe(wait, exemplar_id=r.req.request_id or None)
        t = r.tenant
        self.tenant_quotas.note_queue_wait(t, wait)
        TENANT.observe("dynamo_tenant_request_queue_seconds", t, wait,
                       exemplar_id=r.req.request_id or None)
        r.trace_spans.append(_span_dict("queue", r.enqueue_time))
        r.t_prefill_start = now

    def _prefill_begin(self, r: _Request) -> None:
        """Start a request's prefill: reserve a lane, prefix-match (HBM,
        then host tiers) and copy the matched run pool -> ctx. Seals
        queued by other requests must be flushed first — their pool pages
        are matchable but the copy may not be dispatched yet."""
        ps = self.ecfg.page_size
        prompt = r.tokens
        self._flush_seals()
        slot = self._free_slot()
        assert slot is not None, "caller checks slot availability"
        r.slot = slot
        self._prefilling[slot] = r
        self._note_queue_wait(r)
        hashes = r.seq.block_hashes()
        matchable = hashes[: max(0, (len(prompt) - 1) // ps)]
        matched_pages = self.allocator.match_prefix(matchable)
        t_onboard = time.monotonic()
        g1_matched = len(matched_pages)
        matched_pages = self._onboard_from_host(matchable, matched_pages)
        if len(matched_pages) > g1_matched:
            r.trace_spans.append(_span_dict(
                "g2_onboard", t_onboard,
                blocks=len(matched_pages) - g1_matched,
            ))
        # a matched/onboarded run longer than the ctx region cannot be
        # loaded (and the pow2 PADDING below can overflow the region even
        # when the real run fits — load_ctx_pages clamps that statically:
        # 46 matched pages padded to 64 against a 52-page region once
        # crashed a run).
        # Drop overflow pages rather than failing the engine round; their
        # refs are released with the rest after the load dispatch.
        max_blocks = self.ecfg.max_context // ps
        usable_pages = matched_pages[:max_blocks]
        if len(matched_pages) > max_blocks:
            log.warning(
                "matched prefix run (%d pages) exceeds the ctx region "
                "(%d pages); dropping overflow",
                len(matched_pages), max_blocks,
            )
        r.matched_blocks = len(usable_pages)
        self._h_pf_matched.observe(len(usable_pages) * ps)
        if usable_pages:
            w = pow2_cover(len(usable_pages))
            padded = np.zeros(w, np.int32)  # padding -> scratch page 0
            padded[: len(usable_pages)] = usable_pages
            if self.on_dispatch is not None:
                self.on_dispatch("load_ctx", {
                    "slot": slot, "pages": padded.tolist(),
                })
            self.dispatch_counts["load_ctx"] += 1
            self.ctx = llama.load_ctx_pages(
                self.ctx, self.cache, jnp.int32(slot),
                jnp.asarray(padded),
            )
            if self.kv_quant and self.ctx_quant:
                # admission moved raw int8 pages + scales; the kernel
                # dequantizes them in VMEM per chunk (no fused dequant)
                KV_QUANT.inc("dynamo_kv_quant_ctx_admit_raw_pages_total",
                             len(usable_pages))
        if matched_pages:
            # copy dispatched (if any) — device order lets us drop the
            # refs now (all matched refs, including dropped overflow)
            self.allocator.free(matched_pages)
        r.prefill_pos = len(usable_pages) * ps
        r.sealed_prefix = len(usable_pages)  # matched blocks: already cached

    def _prefill_step(self, r: _Request) -> str:
        """Advance one prefill chunk; on the final chunk, sample the first
        token on device and activate the slot. Returns progress | done |
        failed. Long prompts route through the sequence-parallel ring
        prefill when the mesh has an sp axis (EngineConfig
        sp_prefill_threshold)."""
        e = self.ecfg
        ps = e.page_size
        prompt = r.tokens

        if (r.prefill_pos < 0
                and e.sp_prefill_threshold is not None
                and not (r.req.multimodal or {}).get("embeddings")
                and r.adapter_id == 0  # sp ring path serves the base model
                and self.mesh.shape.get("sp", 1) > 1):
            # threshold applies to the UNCACHED suffix: a mostly-cached
            # long prompt is cheaper on the chunked local path (which
            # reuses the prefix) than on a full ring recompute
            hashes = r.seq.block_hashes()
            matchable = hashes[: max(0, (len(prompt) - 1) // ps)]
            cached = self.allocator.cached_prefix_len(matchable)
            if len(prompt) - cached * ps >= e.sp_prefill_threshold:
                return self._sp_prefill_full(r)

        if r.prefill_pos < 0:
            self._prefill_begin(r)

        # one page-aligned continuation chunk (q_start advances); only the
        # final chunk's logits matter
        self.prof.enter(_SEG_ADMIT_PACK)
        max_chunk = ((e.prefill_buckets[-1] + ps - 1) // ps) * ps
        start = r.prefill_pos
        chunk = prompt[start : start + max_chunk]
        pad_t = e.bucket_for(len(chunk)) or max_chunk
        pad_t = ((pad_t + ps - 1) // ps) * ps
        toks = np.zeros(pad_t, np.int32)
        toks[: len(chunk)] = chunk
        embeds = embeds_mask = None
        mm = r.req.multimodal or {}
        if mm.get("embeddings"):
            # override rows for image-token positions in this chunk
            # (vision-encoder outputs injected in place of the token
            # embedding — reference examples/multimodal E/P/D flow)
            ov = np.zeros((pad_t, self.config.hidden_size), np.float32)
            msk = np.zeros(pad_t, bool)
            for ent in mm["embeddings"]:
                data = np.asarray(ent["data"], np.float32)
                p0 = int(ent["pos"])
                lo = max(p0, start)
                hi = min(p0 + len(data), start + len(chunk))
                if lo < hi:
                    ov[lo - start: hi - start] = data[lo - p0: hi - p0]
                    msk[lo - start: hi - start] = True
            if msk.any():
                embeds = jnp.asarray(ov)
                embeds_mask = jnp.asarray(msk)
        if self.on_dispatch is not None:
            if embeds is not None:
                r.emit(ValueError(
                    "multimodal requests are single-host only"))
                self._abort_prefill(r)
                return "failed"
            self.on_dispatch("prefill", {
                "tokens": toks.tolist(), "slot": r.slot,
                "start": start, "end": start + len(chunk),
                "adapter": r.adapter_id,
            })
        t_disp = time.monotonic()
        self.dispatch_counts["prefill"] += 1
        self._note_prefill_dispatch(
            len(chunk), llama.prefill_positions_run(
                self.config, pad_t, [start], [start + len(chunk)]))
        self._observe_attn_pairs(
            pad_t, [start], [start + len(chunk)],
            e.max_context if start else 0)
        r.prefill_chunks += 1
        sorted_rows = llama.moe_prefill_rows_sorted(self.config, pad_t)
        self.prof.enter(_SEG_ADMIT_LAUNCH)
        # a fresh prompt runs the program with no read of the region
        self.ctx, logits, *moved = llama.prefill(
            self.config, self.params, self.ctx,
            jnp.asarray(toks), jnp.int32(r.slot),
            jnp.int32(start), jnp.int32(start + len(chunk)),
            embeds, embeds_mask, jnp.int32(r.adapter_id),
            fresh=start == 0, counted=sorted_rows > 0,
            attn=self.decode_attn,
        )
        self._newest = logits
        self._note_moe_rows(moved, sorted_rows)
        self.flight.record(
            "prefill", slots=[r.slot], tokens=len(chunk), start=start,
            dispatch_ms=round((time.monotonic() - t_disp) * 1e3, 3),
        )
        self.prof.enter(_SEG_ADMIT_FIRST)
        r.prefill_pos = start + len(chunk)
        if r.prefill_pos < len(prompt):
            # commit the chunk's complete blocks now (prefix-hittable /
            # streamable while the next chunks compute)
            self._seal_prefilled(r)
            return "progress"  # decode rounds run before the next chunk

        return self._finish_prefill(logits, [(0, r)])

    def _sp_prefill_full(self, r: _Request) -> str:
        """Whole-prompt sequence-parallel ring prefill (ops/
        ring_attention.py): ONE pass with the prompt sharded over the sp
        mesh axis — per-device KV is O(T/sp), KV blocks rotate over ICI.
        The computed span enters the slot's ctx region via write_ctx_span;
        block sealing/commit then proceeds exactly like local prefill.
        (Recomputes the full prompt — no prefix-match integration; the sp
        path exists for prompts too long to prefill locally at all.)"""
        from dynamo_tpu.ops.ring_attention import sp_shard

        e = self.ecfg
        prompt = r.tokens
        self._flush_seals()
        slot = self._free_slot()
        assert slot is not None, "caller checks slot availability"
        r.slot = slot
        self._prefilling[slot] = r
        self._note_queue_wait(r)
        self.prof.enter(_SEG_ADMIT_PACK)
        sp_n = self.mesh.shape["sp"]
        pad = -len(prompt) % sp_n
        toks = np.zeros(len(prompt) + pad, np.int32)
        toks[: len(prompt)] = prompt
        if self.on_dispatch is not None:
            self.on_dispatch("sp_prefill", {
                "tokens": toks.tolist(), "slot": slot, "n": len(prompt),
            })
        t_disp = time.monotonic()
        self.dispatch_counts["sp_prefill"] += 1
        self._note_prefill_dispatch(len(prompt), len(toks))
        # the ring path scores every pair of its padded prompt
        n = len(prompt)
        self._h_pf_live.observe(n * (n + 1) // 2)
        self._h_pf_scored.observe(len(toks) ** 2)
        r.prefill_chunks += 1
        self.prof.enter(_SEG_ADMIT_LAUNCH)
        kv, logits = llama.sp_prefill(
            self.config, self.params,
            sp_shard(jnp.asarray(toks), self.mesh),
            jnp.int32(len(prompt)), self.mesh,
        )
        self.flight.record(
            "sp_prefill", slots=[slot], tokens=len(prompt),
            dispatch_ms=round((time.monotonic() - t_disp) * 1e3, 3),
        )
        self.ctx = llama.write_ctx_span(self.ctx, jnp.int32(slot), kv)
        self.prof.enter(_SEG_ADMIT_FIRST)
        r.prefill_pos = len(prompt)
        r.matched_blocks = 0
        self.sp_prefills += 1
        return self._finish_prefill(logits, [(0, r)])

    def _finish_prefill(self, logits, lanes: list[tuple[int, _Request]],
                        K: int = 1) -> str:
        """Shared prefill tail of ONE prefill dispatch: `lanes` are the
        (row of `logits`, request) pairs whose prompts it completed, `K`
        the dispatch's lanes. Commits their prompt blocks, activates
        their slots, then ONE program and ONE upload sample every first
        token and admit every slot (``admit_first``); a follower replays
        the one event on its own logits (same keys, same tokens)."""
        rows = np.zeros((K, _ROW_W), np.uint32)
        rows[:, _ROW_SLOT] = self._B  # not finishing here: nothing admitted
        for i, r in lanes:
            rows[i] = self._first_row(r)
        want_lp = any(r.req.output_options.logprobs is not None
                      for _, r in lanes)
        if self.on_dispatch is not None:
            self.on_dispatch("admit_first", {
                "rows": rows.tolist(), "want_lp": want_lp,
            })
        self.dispatch_counts["admit_first"] += 1
        self._dev, first_toks, first_lps = self._admit_first(
            self._dev, logits, jnp.asarray(rows), want_lp)
        # first tokens reach their clients via the async fetch pipeline:
        # one fetch an output a dispatch, shared by the lanes' entries
        first_toks.copy_to_host_async()
        self.dispatch_counts["fetch"] += 1
        if want_lp:
            first_lps.copy_to_host_async()  # packed: one fetch
            self.dispatch_counts["fetch"] += 1
        for i, r in lanes:
            self._track(_Entry(
                kind="first", handle=first_toks, request=r, row=i,
                lp_handle=(first_lps
                           if r.req.output_options.logprobs is not None
                           else None),
            ))
        return "done"

    def _first_row(self, r: _Request) -> np.ndarray:
        """Everything the HOST does for a request whose prompt is
        complete (commit its blocks, draw its keys, activate its slot)
        and its row of ``admit_first``'s upload. A speculative admission's
        row names slot B: its first token is sampled, its lane stays
        parked.

        The ``prefill`` span recorded here ends when the last prefill
        program was DISPATCHED: it is host dispatch time, not device
        work. ``first_token`` (_note_first_token), whose child it
        becomes, ends where the work does."""
        prompt = r.tokens
        r.rounds_at_dispatch = sum(
            1 for en in self._entries if en.kind == "round")
        if r.t_prefill_start is not None:
            r.trace_spans.append(_span_dict(
                "prefill", r.t_prefill_start,
                prompt_tokens=len(prompt), matched_blocks=r.matched_blocks,
                slot=r.slot,
            ))
        # copy-commit the remaining complete prompt blocks into the
        # prefix cache (earlier chunks sealed theirs incrementally)
        self._seal_prefilled(r, limit=len(r.seq.blocks))

        so = r.req.sampling_options
        if so.seed is not None:
            # seeded: fully reproducible keys derived from the seed alone
            first_key = np.array([_FIRST_TOKEN_KEY_TAG, so.seed], np.uint32)
            step_keys = np.array([0, so.seed], np.uint32)
        else:
            # unseeded: fresh entropy per request — two identical prompts
            # must NOT produce identical outputs (landing on the same slot
            # previously reused the [0, slot+1] key stream)
            nonce = np.frombuffer(os.urandom(8), np.uint32).copy()
            first_key = np.array(
                [_FIRST_TOKEN_KEY_TAG ^ int(nonce[0]), int(nonce[1])], np.uint32
            )
            step_keys = nonce

        slot = r.slot
        del self._prefilling[slot]
        self._slots[slot] = r
        self._ctx_disp[slot] = len(prompt) + 1
        # speculation is confined to the base model (adapter 0): the
        # draft/verify programs have no adapter plumbing, and a draft
        # proposing from base-model logits against a variant's target
        # distribution would crater acceptance anyway
        if (self.spec is not None and r.adapter_id == 0
                and self.spec.eligible(r.req)):
            # speculative admission: the device lane stays PARKED on the
            # scratch lane (exactly like a freed slot) — the slot's real
            # state lives host-side and it advances through verify
            # dispatches once the first token's fetch lands
            # (_process_first marks it spec-ready)
            r.spec = True
            self._slot_off(slot, spec=True)
            r.spec_keys = np.asarray(step_keys, np.uint32)
            if self.spec.penalized(r.req):
                # penalized slots carry the sampler's output-token
                # histogram host-side; the verifier's penalized accept
                # path advances it per accepted token
                r.spec_counts = np.zeros(
                    self.config.vocab_size, np.int32
                )
            slot = self._B
        else:
            self._slot_on(slot, r)
        row = np.empty(_ROW_W, np.uint32)
        # ints and floats as their bits: no scalar is converted by a
        # device program, admit_first bitcasts them back
        row[_ROW_SLOT:_ROW_TOP_K + 1] = np.array([
            slot, len(prompt) + 1, r.adapter_id, so.top_k or 0,
        ], np.int32).view(np.uint32)
        row[_ROW_TEMP:_ROW_REP + 1] = np.array([
            so.temperature or 0.0,
            so.top_p if so.top_p is not None else 1.0,
            so.frequency_penalty or 0.0,
            so.presence_penalty or 0.0,
            so.repetition_penalty or 1.0,
        ], np.float32).view(np.uint32)
        row[_ROW_KEY:_ROW_KEY + 2] = first_key
        row[_ROW_STEP_KEY:_ROW_STEP_KEY + 2] = step_keys
        return row

    # ---- processing side (lagged results) ----

    def _track(self, entry: _Entry) -> None:
        """Every in-flight fetch is tracked here; its handle is an output
        of the newest program dispatched."""
        self._entries.append(entry)
        self._newest = entry.handle

    def _process_entries(self, block: bool = False) -> None:
        # first-token / offload entries are independent of round ordering
        # (a round dispatched before an admission doesn't contain the
        # request; one dispatched after is behind it in the queue) —
        # process them as soon as their fetch lands instead of behind up
        # to max_inflight_rounds stacked round fetches. This is the TTFT
        # lever: the first token no longer waits out the decode pipeline.
        if not self._entries:
            return
        remaining = []
        for entry in self._entries:
            if entry.kind != "round" and entry.handle.is_ready():
                self._consume_entry(entry)
            else:
                remaining.append(entry)
        self._entries = remaining
        while self._entries:
            entry = self._entries[0]
            ready = entry.handle.is_ready()
            if not ready and not block:
                self.prof.poll(False)   # the device is busy right now
                return
            self._entries.pop(0)
            self._consume_entry(entry)
            if not ready:
                # the host waited this program out: the device was busy
                # up to here, whatever the next dispatch finds
                self.prof.poll(False)
            block = False  # only force at most one blocking wait

    def _unpack_lp(self, packed: np.ndarray):
        """Split one packed logprob row/stack [..., 1+2K] back into
        (chosen, top_ids, top_lps) — inverse of the jit-side pack_lp."""
        K = self.ecfg.max_logprobs
        return (packed[..., 0], packed[..., 1:1 + K].astype(np.int32),
                packed[..., 1 + K:])

    def _consume_entry(self, entry: _Entry) -> None:
        if entry.kind in ("round", "spec", "spec_tree") and entry.t_dispatch:
            self._h_round.observe(time.monotonic() - entry.t_dispatch)
        data = np.asarray(entry.handle)
        if entry.kind == "first":
            lp = None
            if entry.lp_handle is not None:
                chosen, ids, lps = self._unpack_lp(
                    np.asarray(entry.lp_handle)[entry.row]
                )
                lp = (float(chosen), ids, lps)
            self._process_first(entry.request, int(data[entry.row]), lp)
        elif entry.kind == "offload":
            scales = (
                np.asarray(entry.aux)[:, :, : entry.n_steps]
                if entry.aux is not None else None
            )
            self.offload.put_batch(
                entry.hashes, entry.parents,
                data[:, :, :, : entry.n_steps], scales,
            )
        elif entry.kind == "spec":
            self._process_spec(entry)
        elif entry.kind == "spec_tree":
            self._process_spec_tree(entry)
        else:
            self._process_round(entry, data)

    def _lp_payload(self, r: _Request, lp) -> dict:
        """LLMEngineOutput logprob fields for one emitted token."""
        n_req = r.req.output_options.logprobs
        if lp is None or n_req is None:
            return {}
        chosen, ids, lps = lp
        n = min(int(n_req), self.ecfg.max_logprobs)
        pairs = [[int(i), float(v)] for i, v in zip(ids[:n], lps[:n])]
        return {"log_probs": [float(chosen)], "top_logprobs": [pairs]}

    def _process_first(self, r: _Request, tok: int, lp=None) -> None:
        if r.cancelled or r.finished:
            self._finish(r, None)
            return
        if r.first_token_time is None:
            r.first_token_time = time.monotonic()
            r.t_last_emit = r.first_token_time
            ttft = r.first_token_time - r.enqueue_time
            self._h_ttft.observe(ttft,
                                 exemplar_id=r.req.request_id or None)
            if r.t_prefill_start is not None:
                self._note_first_token(r)
            TENANT.observe("dynamo_tenant_request_ttft_seconds",
                           r.tenant, ttft,
                           exemplar_id=r.req.request_id or None)
        sc = r.req.stop_conditions
        if not sc.ignore_eos and tok in (sc.stop_token_ids or []) and (
            sc.min_tokens is None or r.produced >= sc.min_tokens
        ):
            self._finish(r, FinishReason.EOS)
            return
        r.last_token = tok
        r.produced += 1  # may continue a preempted request's count
        r.emit(LLMEngineOutput(token_ids=[tok], **self._lp_payload(r, lp)))
        if r.produced >= r.max_new_tokens(self.ecfg.max_context):
            self._finish(r, FinishReason.LENGTH, emit_empty=True)
        elif r.spec:
            # the host now knows the pending token — speculation can start
            r.spec_tokens = list(r.tokens) + [tok]
            r.spec_ready = True

    def _note_first_token(self, r: _Request) -> None:
        """The ``first_token`` phase: ``queue`` ended when the request got
        its lane (t_prefill_start), this one ends HERE, with the fetched
        token on the host -- so engine TTFT = queue + first_token exactly.
        The dispatch-only ``prefill`` span becomes its child."""
        rid = r.req.request_id or None
        dur = r.first_token_time - r.t_prefill_start
        self._h_first_token.observe(dur, exemplar_id=rid)
        sp = Span("first_token", time.time() - dur, dur, attrs=dict(
            request_id=rid, prompt_tokens=len(r.tokens),
            chunks=r.prefill_chunks,
            rounds_in_flight_at_dispatch=r.rounds_at_dispatch,
        )).to_dict()
        for i in range(len(r.trace_spans) - 1, -1, -1):
            if r.trace_spans[i]["name"] == "prefill":
                sp["children"] = [r.trace_spans.pop(i)]
                break
        r.trace_spans.append(sp)

    def _process_round(self, entry: _Entry, toks: np.ndarray) -> None:
        """Consume one round's stacked tokens. Emission is BATCHED per
        request per round (tokens of a round arrive together in one fetch;
        per-token emits through the asyncio machinery are pure host
        overhead — on a 1-core box they, not the device, capped
        throughput)."""
        t = time.monotonic()
        wall = t - max(self._t_round_consumed, entry.t_dispatch)
        self._t_round_consumed = t
        gap = wall / entry.n_steps
        ordinal, programs, padded = entry.ahead
        self._h_step_gap.observe(gap)
        if programs:
            self._h_pf_ahead.observe(padded)
        else:
            self._h_step_gap_clean.observe(gap)
        mark = dict(consumed=ordinal, wall_us=int(wall * 1e6),
                    steps=entry.n_steps)
        # against the last clean rounds' mean wall: did it come late, and
        # where did its excess go (RoundProf.judge_round)
        late = self.prof.judge_round(wall, entry.n_steps, bool(programs))
        if late is not None:
            cause, entry.late, lead, expected = late
            mark["late"] = cause
            self.flight.record(
                "late_round", round=ordinal, wall_ms=round(wall * 1e3, 3),
                expected_ms=round(expected * 1e3, 3), cause=cause,
                excess_ms={c: round(v * 1e3, 3)
                           for c, v in entry.late.items()},
                host_segment=lead,
            )
        self.prof.mark_round(**mark)
        lp_arrs = None
        if entry.lp_handle is not None:
            lp_arrs = self._unpack_lp(np.asarray(entry.lp_handle))
        for col, hist, f32_bits in self._stats_table:
            # the block's declared counter row (llama.stats_layout): one
            # more row of the fetch, behind the steps' tokens
            v = toks[entry.n_steps:].reshape(-1)[col:col + 1]
            hist.observe(float(v.astype(np.int32).view(np.float32)[0])
                         if f32_bits else int(v[0]))
        delivered = 0
        for slot, r in enumerate(entry.slots):
            # identity check doubles as the epoch: a recycled slot holds
            # a different _Request object than the snapshot
            if r is None or r.finished or self._slots[slot] is not r:
                continue
            if r.cancelled:
                self._finish(r, None)
                continue
            batch: list[int] = []
            lp_chosen: list[float] = []
            lp_top: list[list] = []
            n_lp = r.req.output_options.logprobs
            finish: Optional[FinishReason] = None
            for step in range(entry.n_steps):
                tok = int(toks[step, slot])
                finish = self._advance_token(r, tok)
                if finish is FinishReason.EOS:
                    break  # stop token itself is not emitted
                batch.append(tok)
                if lp_arrs is not None and n_lp is not None:
                    k = min(int(n_lp), self.ecfg.max_logprobs)
                    lp_chosen.append(float(lp_arrs[0][step, slot]))
                    lp_top.append(
                        [[int(i), float(v)] for i, v in zip(
                            lp_arrs[1][step, slot][:k],
                            lp_arrs[2][step, slot][:k])]
                    )
                if finish is not None:
                    break
            if batch:
                delivered += len(batch)
                self._note_emit(r, len(batch), entry, "decode_round")
            if batch or finish is not None:
                extra = {}
                if lp_chosen:
                    extra = {"log_probs": lp_chosen, "top_logprobs": lp_top}
                if finish is not None:
                    extra["annotations"] = self._final_annotations(r)
                r.emit(LLMEngineOutput(
                    token_ids=batch, finish_reason=finish, **extra
                ))
            if finish is not None:
                self._finish(r, None)
                continue
            if r.spec_gated or r.spec_rearm_wait > 0:
                self._spec_gated_advance(slot, r, batch)
        self._h_round_tokens.observe(delivered)
        self.tokens_generated += int(
            sum(1 for s in entry.slots if s is not None) * entry.n_steps
        )

    def _spec_gated_advance(
        self, slot: int, r: _Request, batch: list[int]
    ) -> None:
        """Fused-round bookkeeping for a gated (or re-arming) stream:
        keep the host sequence/penalty mirrors current so speculation
        can resume exactly where the fused round leaves off — the
        proposers' lookup corpus and the despec/re-arm patches all read
        ``spec_tokens``."""
        if batch:
            r.spec_tokens.extend(batch)
            if r.spec_counts is not None:
                for t in batch:
                    r.spec_counts[t] += 1
        if r.spec_rearm_wait > 0:
            # phase 2 of the re-arm drain: one in-flight round entry
            # whose snapshot still stepped this lane has been consumed
            # (its tokens were real — mirrored above); once the last one
            # lands, the clear patch has taken effect in program order
            # and the first verify can dispatch
            r.spec_rearm_wait -= 1
            if r.spec_rearm_wait == 0:
                r.spec_ready = True
            return
        if self.ecfg.spec_rearm_tokens <= 0:
            return  # gate is permanent: no re-arm budget configured
        r.spec_rearm_left -= len(batch)
        if r.spec_rearm_left <= 0:
            self._rearm_spec(slot, r)

    def _rearm_spec(self, slot: int, r: _Request) -> None:
        """Re-arm speculation on a gated stream (two-phase drain).

        Phase 1 (here): flip the request back to spec mode and PARK the
        device lane. Unlike spec admission (_process_first), the lane is
        LIVE mid-stream — rounds already dispatched keep stepping it
        until the clear patch lands in program order — so count the
        in-flight round entries whose snapshot still contains this lane.
        Phase 2 (_spec_gated_advance): each such entry's consumption
        decrements the counter while still mirroring its emitted tokens;
        at zero the device has drained and spec_ready arms the first
        verify. Without the drain, that verify's commit would race the
        in-flight rounds' writes over the same ctx rows.
        """
        r.spec = True
        r.spec_gated = False
        r.spec_ready = False
        self.spec.on_rearm(slot)
        self._slot_off(slot, spec=True)
        self._dispatch_patch(clear_slots=[slot])
        self._ctx_disp[slot] = len(r.spec_tokens)
        # the device PRNG key advanced privately while the stream ran on
        # the fused round — the host cannot recover it without an extra
        # fetch. Reseed deterministically from the stale key and the
        # produced count: greedy streams are unaffected (keys unused),
        # sampled streams keep seeded reproducibility (same request +
        # schedule -> same fold) though the draw sequence diverges from
        # an ungated run's.
        stale = np.asarray(r.spec_keys, np.uint32)
        fold = (r.produced * 2654435761 + 0x9E3779B9) & 0xFFFFFFFF
        r.spec_keys = np.asarray(
            [int(stale[0]) ^ fold, int(stale[1]) ^ (fold >> 1)],
            np.uint32,
        )
        r.spec_rearm_wait = sum(
            1 for en in self._entries
            if en.kind == "round" and slot < len(en.slots)
            and en.slots[slot] is r
        )
        if r.spec_rearm_wait == 0:
            r.spec_ready = True

    def _advance_token(
        self, r: _Request, tok: int
    ) -> Optional[FinishReason]:
        """Per-token state advance (sealing, stop detection, budget).
        Returns the finish reason when this token ENDS the request (EOS:
        token not emitted; LENGTH: token emitted as the last one)."""
        sc = r.req.stop_conditions
        # copy-commit the block completed by the previous token into the
        # prefix cache (device order: those positions were written by
        # already-dispatched steps)
        if r.last_token >= 0:
            for blk in r.seq.extend([r.last_token]):
                self._queue_seal(
                    r, blk.position, blk.block_hash, blk.parent_hash
                )
        if not sc.ignore_eos and tok in (sc.stop_token_ids or []) and (
            sc.min_tokens is None or r.produced >= sc.min_tokens
        ):
            return FinishReason.EOS
        r.last_token = tok
        r.produced += 1
        if r.produced >= r.max_new_tokens(self.ecfg.max_context):
            return FinishReason.LENGTH
        return None

    def _finish(
        self,
        r: _Request,
        reason: Optional[FinishReason],
        emit_empty: bool = False,
    ) -> None:
        """Mark finished on host; the slot is reclaimed via a release patch
        at the next round boundary (in-flight garbage steps are redirected
        to the scratch lane by the patch's dest update)."""
        if r.finished:
            return
        r.finished = True
        if r.slot >= 0 and self._slots[r.slot] is r:
            self._slot_off(r.slot)  # out of the dispatch set immediately
        if reason is not None:
            r.emit(LLMEngineOutput(
                token_ids=[], finish_reason=reason,
                annotations=self._final_annotations(r),
            ))
        self._to_release.append(r)

    def _apply_releases(self) -> None:
        # also sweep cancelled requests that never got a finish event
        for slot, r in enumerate(self._slots):
            if r is not None and r.cancelled and not r.finished:
                r.finished = True
                self._slot_off(slot)
                self._to_release.append(r)
        if not self._to_release:
            return
        clear_slots = []
        for r in self._to_release:
            if r.slot >= 0 and self._slots[r.slot] is r:
                clear_slots.append(r.slot)
                self._slots[r.slot] = None
                self._slot_off(r.slot)
                self._ctx_disp[r.slot] = 1
                if self.spec is not None and r.spec:
                    self.spec.release(r.slot)  # drop stale draft KV state
            r.slot = -1
        self._to_release = []
        if clear_slots:
            self._dispatch_patch(clear_slots=clear_slots)

    def _fail_all(self, err: Exception) -> None:
        for r in list(self._slots):
            if r is not None:
                r.emit(err)
                r.finished = True
        self._slots = [None] * self._B
        self._slot_active[:] = False
        self._slot_spec[:] = False
        self._slot_lp[:] = False
        self._slot_sampler[:] = False
        self._active_cache = None
        if self.spec is not None:
            for i in range(self._B):
                self.spec.release(i)
        for r in self._waiting:
            r.emit(err)
            self._abort_prefill(r)  # also drops its waiting-token count
        self._waiting = []
        self._prefilling = {}
        self._entries = []
        self._newest = None
        self._moe_rows.clear()
        self._seal_queue = []

