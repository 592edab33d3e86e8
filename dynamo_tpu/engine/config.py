"""Engine runtime configuration (the vLLM-engine-args equivalent —
reference MockEngineArgs mocker/protocols.rs:72-94 and vllm_inc.py flags)."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


def _default_buckets() -> tuple[int, ...]:
    return (128, 256, 512, 1024, 2048, 4096)


def pow2_cover(n: int, lo: int = 1) -> int:
    """Smallest power of two >= max(n, lo) — the compile-cache bucketing
    used for page-table widths, transfer sizes, and the speculative round
    width (padding always targets scratch page 0 / the scratch lane).
    Lives here (not engine.py) so spec/ can use it without a
    module-scope import of the engine."""
    w = lo
    while w < n:
        w *= 2
    return w


@dataclass
class EngineConfig:
    """Knobs of the continuous-batching TPU engine."""

    # prefix-cache pool (round-4 layout: the paged pool is prefix-cache
    # STORAGE; the serving context is a contiguous per-slot region —
    # models/llama.py module doc)
    num_pages: int = 512          # pool capacity incl. reserved page 0
    page_size: int = 64           # tokens per page (also the router block size)
    # per-slot context capacity in pages: max_context = this * page_size
    # (sizes the contiguous ctx region, (slots+1) * max_context * kv)
    max_pages_per_seq: int = 64

    # batching
    max_decode_slots: int = 8     # fixed decode batch width
    prefill_buckets: tuple[int, ...] = field(default_factory=_default_buckets)

    # pipelining: steps per dispatched round (one fused jit + one stacked
    # token fetch + one ring->ctx flush per round) and rounds allowed in
    # flight before the loop blocks on results.
    # Effective host lag = flush_every * (max_inflight_rounds + 1) steps —
    # finished requests garbage-decode for up to that many steps, so raise
    # these only when D2H latency is high relative to step time. Both
    # defaults are untested choices: neither has a chip measurement
    # behind it (ROADMAP S3).
    flush_every: int = 4
    max_inflight_rounds: int = 2
    # double-buffered round pipelining: dispatch round N+1's fused
    # program BEFORE consuming round N's packed fetch, so round N's
    # host-side bookkeeping (emit, releases, transfers, offload) runs
    # while round N+1 executes on device and steady-state wall-clock
    # approaches max(host, device) instead of host + device. The
    # pipeline flushes (falls back to the strict process-then-dispatch
    # order) whenever slot state is about to change under it:
    # admissions/prefills, pending release patches, seal-queue overflow
    # past the fused width, speculating slots, and drain. False is the
    # differential tests' reference order and nothing else: it runs
    # every round in that fallback order, and no launcher, flag or
    # runtime setting reaches it.
    round_pipeline: bool = True
    # prefill chunks dispatched per scheduling round: bounds how long a
    # round can stall decode behind prompt processing (the ITL-interference
    # problem disagg solves globally; this bounds it locally)
    prefill_chunks_per_round: int = 2
    # batched multi-request prefill (models/llama.py batch_prefill — the
    # vLLM max_num_batched_tokens analogue): concurrent same-bucket chunks
    # run as ONE [K, T] program. K follows the group it carries
    # (prefill_lanes below: the power of two that covers the group, at
    # most min(prefill_batch_max, prefill_token_budget // T)), so a
    # dummy lane pays the block's matmuls only where a group of 3 or
    # 5-7 leaves one. A group holds at most prefill_chunks_per_round
    # requests: at its default of 2 the only batched K is 2, one
    # whole-model compile per (T, ctx_span); raised, K is 2, 4 or 8 and
    # up to three widths per (T, ctx_span) compile lazily, each when a
    # group of its size first forms. prefill_batch_max 1 disables
    # batching.
    prefill_batch_max: int = 8
    prefill_token_budget: int = 8192

    # sampling
    max_top_k: int = 64           # static top-k width for top-p/top-k sampling
    # static top-N width for logprobs (OpenAI caps top_logprobs at 20);
    # requests asking for logprobs compile the lp variant of the step
    max_logprobs: int = 20

    # speculative decoding (dynamo_tpu/spec/): "off" | "ngram" | "draft".
    # ngram needs no extra model (prompt-lookup against the request's own
    # history); draft needs a draft_config/draft_params pair passed to
    # TpuEngine (a small model sharing the target tokenizer). Eligible
    # slots (no penalties/logprobs) verify K proposed tokens per target
    # forward instead of taking the fused decode round.
    speculative: str = "off"
    num_speculative_tokens: int = 4   # K proposals per verify step (the CAP
                                      # when spec_adaptive is on)
    # acceptance-adaptive K (spec/decoder.py AdaptiveKController, where
    # its thresholds live): each slot's effective K walks within
    # [spec_min_k, num_speculative_tokens] on its acceptance rate, and a
    # slot whose acceptance collapses de-speculates back to the fused
    # decode round
    spec_adaptive: bool = True
    spec_min_k: int = 1
    # tree speculation (spec/verifier.py spec_verify_tree): proposals
    # form a packed token tree — up to spec_branches candidates per
    # divergence point — verified in ONE forward under a tree-causal
    # ancestor mask; acceptance walks the deepest surviving root-to-leaf
    # path and commits only that path's KV rows. spec_tree_budget bounds
    # the packed node count (root included) so one compiled verify shape
    # serves every tree; 0 = auto (1 + K * branches, the full comb).
    spec_tree: bool = False
    spec_branches: int = 4
    spec_tree_budget: int = 0
    # acceptance gating: a stream whose live acceptance EWMA stays below
    # spec_gate_acceptance for spec_gate_window consecutive verify steps
    # de-speculates back to the fused round (0.0 disables the gate —
    # adaptive-K despec still applies); it may re-arm after
    # spec_rearm_tokens emitted tokens (doubling each time it re-gates),
    # so chat-shaped traffic stops paying draft overhead while a stream
    # that turns repetitive mid-flight gets another chance
    spec_gate_acceptance: float = 0.0
    spec_gate_window: int = 4
    spec_rearm_tokens: int = 256

    # overload plane (dynamo_tpu/overload/): bounded admission. Intake
    # past either budget raises the retriable EngineOverloadedError
    # (surfacing as HTTP 429 + Retry-After at the frontend) instead of
    # growing the waiting queue — and every admitted request's TTFT —
    # without limit. 0 = unbounded (the pre-overload-plane behavior).
    max_waiting_requests: int = 0
    # prompt-token budget over the same backlog: ten 10k-token prompts
    # are a different storm than ten 10-token ones
    max_waiting_prefill_tokens: int = 0
    # priority preemption, running half: allow a waiting HIGH-priority
    # request to force-evict the lowest-priority RUNNING stream when no
    # lane is free — the victim's stream fails with the retriable
    # PreemptedError, which the router turns into a live migration
    # (replay prompt+emitted on a peer, exactly-once, greedy
    # token-identical). Waiting-entry preemption is always on once
    # budgets are set; this flag gates only the running case.
    preempt_running: bool = False

    # prefix cache
    enable_prefix_caching: bool = True

    # sequence-parallel ring prefill (ops/ring_attention.py): prompts of at
    # least this many tokens run as ONE whole-prompt ring-attention pass
    # over the mesh's `sp` axis instead of chunked local prefill. None
    # disables. Requires the engine mesh to have sp > 1; the long-context
    # path the reference lacks (SURVEY §2.5 SP row).
    sp_prefill_threshold: Optional[int] = None

    # host-DRAM offload tier (KVBM G2): 0 disables. Pages parked in the
    # LRU are asynchronously copied to a host pool of this many pages;
    # prefix misses in HBM onboard from it instead of recomputing.
    host_offload_pages: int = 0
    # mmap-backed disk tier (KVBM G3, reference storage/disk.rs:25): 0
    # disables. G2's LRU evictions spill into it; requires G2 enabled
    # (the tier hierarchy is strict: G1 -> G2 -> G3).
    disk_offload_pages: int = 0
    # backing file for the G3 pool (None = fresh tempfile per engine).
    # With a path the tier is restart-survivable: a sidecar manifest
    # (<path>.manifest) journals slot->(hash, crc) and is replayed at
    # attach (kv_integrity plane).
    disk_offload_path: Optional[str] = None
    # eager G3 startup scrub: re-checksum every manifest entry against
    # the backing file at attach, dropping mismatches (torn writes come
    # back as misses). Off = lazy verify at onboard gather — same
    # safety, the scrub cost is paid per hit instead of up front.
    scrub_on_start: bool = False
    # offload dispatch cap per scheduling round (bounds the per-round
    # gather size; pow2-bucketed for compile-cache reuse)
    offload_batch: int = 8

    # chunk-pipelined KV-transfer plane (kv_transfer.py / disagg.py):
    # bulk KV moves (remote-prefill pushes, G4 peer fetches, G2/G3
    # onboard scatters) run as a pipeline of this many pages per chunk
    # instead of one monolithic blob — transfer overlaps compute and
    # peak host staging drops from O(transfer) to O(chunk). 0 restores
    # the monolithic path.
    kv_transfer_chunk_pages: int = 8
    # chunk gathers/D2H copies allowed in flight per export stream (the
    # double-buffer depth: chunk i's D2H overlaps chunk i+1's gather)
    kv_transfer_inflight_chunks: int = 2
    # deadline for one queued page export/import op (engine._xfer_op).
    # A multi-GiB chunked import on a slow host link can legitimately
    # exceed the old hard-coded 120 s.
    xfer_op_timeout_s: float = 120.0
    # idle-timeout on a chunked export STREAM's backpressure: a receiver
    # that stalls mid-pull (dead peer connection, wedged link) parks the
    # stream with a full chunk queue; after this long without progress
    # the engine reclaims its pinned gather handles/page refs and errors
    # the consumer queue. Separate from xfer_op_timeout_s — a healthy
    # multi-GiB import may take minutes, but a stream that moved NOTHING
    # for 15 s is abandoned.
    kv_transfer_stream_idle_timeout_s: float = 15.0

    # SLO targets backing the dynamo_slo_{ttft,itl}_burn_rate gauges
    # (performance-attribution plane, telemetry/prof.py):
    # burn rate = frac-of-observations-over-target / (1 - objective),
    # recomputed from the live histograms at the metrics-publish cadence
    slo_ttft_target_s: float = 0.5
    slo_itl_target_s: float = 0.05
    slo_objective: float = 0.99
    # tail-latency forensics (telemetry/forensics.py): fraction of
    # NON-breaching finishes that still get a dossier captured worker-side
    # when no in-process frontend owns the request's trace. SLO breaches
    # are always captured; this adds a healthy-baseline sample for
    # comparison. 0 disables sampling (breach capture stays on).
    forensics_sample_rate: float = 0.0

    # model memory
    cache_dtype: str = "bfloat16"
    # KV quantization: "none" (everything stores cache_dtype, the
    # legacy A/B path) | "int8" (pool AND serving ctx store int8 with
    # per-block-per-layer absmax scales — the ctx scale grid uses
    # group == page_size, so seal/admission pool<->ctx copies are RAW
    # int8 page moves with no quant/dequant pass at all). Prefill/span
    # writes quantize on store, the once-per-round ring flush
    # requantizes the touched scale groups, and the flash-decode kernel
    # dequantizes each KV chunk in VMEM right after the DMA — live-
    # context HBM traffic per step is ~halved while the QK/PV dots stay
    # in the compute precision. Also halves pool HBM residency, G2/G3
    # tier footprint, and the payload bytes of every disagg/G4/offload
    # transfer; greedy outputs stay >=99% decisive-token-identical on
    # the differential harness (tests/test_kv_quant).
    kv_quant: str = "none"

    # multi-tenant serving plane (dynamo_tpu/tenancy/).
    # Resident LoRA adapter bank: >0 allocates a bank of this many
    # adapter slots (row 0 is the all-zeros identity = the base model)
    # at rank lora_rank, riding inside the params pytree so every jitted
    # program (fused round, prefill, batched prefill) serves mixed
    # adapter ids with zero extra dispatches. 0 = no bank: the engine
    # traces the identical pre-tenancy programs.
    lora_adapters: int = 0
    lora_rank: int = 8
    # per-tenant slices of the overload-plane backlog budgets (0 =
    # unbounded). One tenant's storm exhausts ITS slice — and bounces
    # with a Retry-After from that tenant's own observed queue waits —
    # before it can crowd the global queue.
    tenant_max_waiting_requests: int = 0
    tenant_max_waiting_prefill_tokens: int = 0
    # fair-share weights for the SFQ dequeue order (tenant -> weight,
    # default 1.0); weights bias ordering, not the budgets above
    tenant_weights: Optional[dict] = None

    # fleet prefix economy (kv_router/fleet.py): when the frontend's
    # hint digest is applied, dedup-by-hash admission consults it before
    # a G4 probe round — fleet-known holders are probed first, and a
    # demand miss whose blocks the fleet hot set doesn't know at all
    # skips the probe entirely. False restores hint-blind G4.
    kv_dedup_admission: bool = True

    # identity on the control plane
    worker_id: str = ""

    @property
    def max_context(self) -> int:
        return self.max_pages_per_seq * self.page_size

    def bucket_for(self, n_tokens: int) -> Optional[int]:
        """Smallest prefill bucket holding n_tokens (buckets are padded
        shapes; each distinct bucket is one XLA compilation)."""
        for b in self.prefill_buckets:
            if n_tokens <= b:
                return b
        return None

    def prefill_lanes(self, width: int, group: Optional[int] = None) -> int:
        """Lanes of a batched prefill at chunk width `width`: without
        `group` the most a group may hold (admission's cap), with it the
        compiled K of the dispatch that carries `group` requests. Both
        from here, so a group never outgrows its program and a program
        is never wider than a group could fill."""
        most = max(1, min(self.prefill_batch_max,
                          self.prefill_token_budget // width))
        return most if group is None else min(pow2_cover(group), most)
