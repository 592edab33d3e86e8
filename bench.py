"""Benchmark: prefill + steady-state decode of the TPU engine.

Runs the full continuous-batching engine (host scheduler + ONE fused
jit per round: flush_every decode+sample steps + ring flush) on
Llama-3.2-1B shapes, bf16, on whatever accelerator `jax.devices()` offers
(the driver runs this on one real v5e chip). Prints ONE JSON line.

Fields beyond the driver contract (metric/value/unit/vs_baseline):
  prefill_tok_s        prompt tokens consumed per second (batch prefill)
  ttft_p50/p95/p99_s   submit->first-token under full concurrency, read
                       from the engine's dynamo_request_ttft_seconds
                       histogram (telemetry plane, not ad-hoc timers)
  itl_p50/p95/p99_s    steady-state inter-token latency percentiles from
                       dynamo_request_itl_seconds
  decode_ms_per_step   wall per fused step at steady state
  device_ms_per_step   device-only time per step (blocking round / steps)
  mfu                  decode model-flops utilization vs chip peak
  roofline_frac        decode steps/s vs the weight-pass roofline
                       (HBM bandwidth / parameter bytes) — the honest
                       ceiling for small-batch decode
vs_baseline: ratio to the reference's published decode exemplar
(51.22 tok/s/GPU, TP=4 H100 profile_sla output, load_planner.md:56).
Model and hardware differ; it is a round-over-round tracking number,
not a head-to-head (see BASELINE.md).
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time

BASELINE_DECODE_TOK_S = 51.22

# chip peak table + per-step byte attribution live in dynamo_tpu.roofline
# (shared with tools/profile_round.py); re-exported for callers that
# import them from bench
from dynamo_tpu.roofline import (  # noqa: E402
    CHIP_PEAKS,
    decode_byte_accounting,
)
from dynamo_tpu.roofline import chip_info as _chip_info  # noqa: E402


def _count_params(params) -> int:
    import jax

    return sum(x.size for x in jax.tree.leaves(params))


async def run_bench() -> dict:
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshConfig
    from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions

    tiny = os.environ.get("DYNAMO_BENCH_TINY") == "1"
    if tiny:
        cfg = ModelConfig.tiny()
        ecfg = EngineConfig(
            num_pages=128, page_size=16, max_pages_per_seq=16,
            max_decode_slots=8, prefill_buckets=(64,), cache_dtype="float32",
        )
        prompt_len, max_tokens, n_requests = 48, 32, 8
    else:
        model = os.environ.get("DYNAMO_BENCH_MODEL", "llama3_1b")
        cfg = getattr(ModelConfig, model)()
        # Sizing notes: the fused round (one dispatch for flush_every
        # steps + flush) amortizes dispatch overhead; raising flush_every
        # deepens the pipeline at the cost of longer client token latency
        # granularity. flush_every=32 / max_inflight_rounds=4 below are
        # an untested choice on this hardware — picked for a development
        # setup that no longer exists, never swept on the v5e host
        # (ROADMAP S3).
        prompt_len = int(os.environ.get("DYNAMO_BENCH_ISL", 100))
        buckets = tuple(
            int(b) for b in
            os.environ.get("DYNAMO_BENCH_BUCKETS", "128").split(",")
        )
        ecfg = EngineConfig(
            num_pages=int(os.environ.get("DYNAMO_BENCH_PAGES", 416)),
            page_size=64,
            max_pages_per_seq=max(16, (prompt_len + 320) // 64 + 1),
            max_decode_slots=int(os.environ.get("DYNAMO_BENCH_SLOTS", 32)),
            prefill_buckets=buckets,
            flush_every=int(os.environ.get("DYNAMO_BENCH_FLUSH", 32)),
            max_inflight_rounds=int(os.environ.get("DYNAMO_BENCH_INFLIGHT", 4)),
            # serving default is 2 (ITL isolation); the bench is a batch
            # workload where admission ramp is throughput, not latency
            prefill_chunks_per_round=8,
        )
        # 256 keeps the whole run inside one page-table width bucket after
        # warmup (512 crosses into width 16 mid-measurement -> a recompile
        # lands inside the timed window)
        max_tokens = int(os.environ.get("DYNAMO_BENCH_MAX_TOKENS", 256))
        n_requests = int(os.environ.get("DYNAMO_BENCH_REQUESTS", 32))

    eng = TpuEngine(cfg, ecfg, mesh_config=MeshConfig(tp=1))
    n_params = _count_params(eng.params)
    # CPU harness: no peaks — every utilization field below stays None
    chip, peaks, on_accel = _chip_info()
    peak_flops, peak_bw = peaks if on_accel else (None, None)
    eng.start()

    rng = np.random.RandomState(0)

    def make_req(mt):
        return PreprocessedRequest(
            token_ids=rng.randint(1, cfg.vocab_size, size=prompt_len).tolist(),
            stop_conditions=StopConditions(max_tokens=mt, ignore_eos=True),
        )

    async def drive(req, t_submit):
        first = None
        n = 0
        async for out in eng.generate(req):
            if first is None and out.token_ids:
                first = time.monotonic() - t_submit
            n += len(out.token_ids)
        return first, n

    # warmup: trigger ALL compilations the measured phases will hit
    # (a mid-measurement whole-model compile poisons the numbers): the
    # solo prefill path, the BATCHED [K, T]
    # fresh-prefill program (concurrent burst), its ctx-continuation
    # variant (resubmitting the same prompts makes them prefix-hit
    # continuations), and the decode round
    await drive(make_req(max_tokens), time.monotonic())
    warm_burst = [make_req(1) for _ in range(min(n_requests, 8))]
    await asyncio.gather(*[drive(r, time.monotonic()) for r in warm_burst])
    await asyncio.gather(
        *[drive(PreprocessedRequest(
            token_ids=list(r.token_ids) + [7, 8, 9],
            stop_conditions=StopConditions(max_tokens=1, ignore_eos=True),
        ), time.monotonic()) for r in warm_burst]
    )

    # ---- phase 0: ISOLATED single-request TTFT (no load; includes one
    # device->host fetch — the loaded-vs-isolated ratio is the scheduling
    # cost).
    # Let the warmup's in-flight rounds drain first: a truly idle engine
    # has no queued device work ahead of the arrival. ----
    await asyncio.sleep(2.0)
    iso = [await drive(make_req(1), time.monotonic()) for _ in range(3)]
    iso_ok = sorted(f for f, _ in iso if f is not None)
    ttft_isolated = iso_ok[len(iso_ok) // 2] if iso_ok else None

    # ---- phase A: prefill throughput + TTFT under full concurrency.
    # TTFT percentiles come from the engine's telemetry histograms
    # (dynamo_request_ttft_seconds — the same series /metrics exports)
    # instead of ad-hoc timers; reset first so warmup/iso observations
    # don't pollute the phase. ----
    eng.telemetry.reset()
    t0 = time.monotonic()
    pre = await asyncio.gather(
        *[drive(make_req(1), t0) for _ in range(n_requests)]
    )
    prefill_wall = time.monotonic() - t0
    h_ttft = eng.telemetry.get("dynamo_request_ttft_seconds")
    ttft_p50 = h_ttft.percentile(0.50)
    ttft_p95 = h_ttft.percentile(0.95)
    ttft_p99 = h_ttft.percentile(0.99)
    prefill_tok_s = n_requests * prompt_len / prefill_wall
    # prefill is compute-bound: MFU against chip peak
    prefill_mfu = (
        n_requests * prompt_len * 2 * n_params / prefill_wall / peak_flops
        if on_accel else None
    )

    # ---- phase B: steady-state decode (ITL distribution from
    # dynamo_request_itl_seconds, this phase's observations only).
    # Dispatch-budget accounting rides the same window: deltas of the
    # engine's dispatch_counts over the phase pin how many host->device
    # program launches + fetch initiations one decode round costs. ----
    eng.telemetry.reset()
    steps0 = eng.step_count
    disp0 = dict(eng.dispatch_counts)
    prof0 = eng.prof.totals()
    t0 = time.monotonic()
    results = await asyncio.gather(
        *[drive(make_req(max_tokens), t0) for _ in range(n_requests)]
    )
    decode_wall = time.monotonic() - t0
    steps = eng.step_count - steps0
    disp_delta = {
        k: v - disp0.get(k, 0) for k, v in eng.dispatch_counts.items()
    }
    rounds = disp_delta.get("round", 0) + disp_delta.get("round_seal", 0)
    dispatches_per_round = (
        sum(disp_delta.values()) / rounds if rounds else None
    )
    h_itl = eng.telemetry.get("dynamo_request_itl_seconds")
    itl_p50 = h_itl.percentile(0.50)
    itl_p95 = h_itl.percentile(0.95)
    itl_p99 = h_itl.percentile(0.99)
    # performance attribution: where phase B's host milliseconds went
    # (per-segment prof delta over the measured window, ms per step)
    # plus the SLO burn-rate gauges over this phase's TTFT/ITL
    from dynamo_tpu.telemetry.prof import PROF

    proft = eng.prof.totals()
    host_breakdown = None
    if steps and proft["rounds"] > prof0["rounds"]:
        host_breakdown = {
            s: round(
                (proft["segments"][s] - prof0["segments"].get(s, 0.0))
                / steps * 1e3, 5)
            for s in proft["segments"]
        }
    PROF.fold_burn_rates(h_ttft.snapshot(), h_itl.snapshot())
    slo_burn = PROF.burn_rates()

    # ---- steady-window host tax (the tests/test_host_budget.py
    # definition): every slot decoding, no admissions/releases/compiles
    # inside the window — wall/step minus device/step is the per-round
    # host bookkeeping the round pipeline must hide. The whole-phase
    # host_ms_per_step below stays for continuity, but it amortizes
    # prefill dispatch + one-off XLA compiles (the `admit` segment)
    # over decode steps, so it cannot go under device on a workload
    # with admissions. ----
    s_osl = 64
    ns = min(n_requests, ecfg.max_decode_slots)
    s_progress = [0] * ns

    async def steady_one(i, req):
        async for out in eng.generate(req):
            s_progress[i] += len(out.token_ids)

    s_tasks = [asyncio.ensure_future(steady_one(i, make_req(s_osl)))
               for i in range(ns)]
    while not all(p >= 4 for p in s_progress):
        await asyncio.sleep(0.005)
    sw0 = time.monotonic()
    ss0 = eng.step_count
    # close before any stream can finish: the dispatch front leads
    # emitted tokens by the pipeline lag, so 20 tokens of headroom
    # keeps release patches out of the window
    while not any(p >= s_osl - 20 for p in s_progress):
        await asyncio.sleep(0.005)
    steady_wall = time.monotonic() - sw0
    steady_steps = eng.step_count - ss0
    await asyncio.gather(*s_tasks)

    pipe = eng.pipeline_stats()
    await eng.stop()

    total_tokens = sum(n for _, n in results)
    decode_tok_s = total_tokens / decode_wall
    steps_per_s = steps / decode_wall if steps else 0.0

    # ---- roofline/MFU ----
    import jax as _jax

    # actual bytes of the parameter tree (int8 weights halve the
    # weight-pass floor — the roofline must tighten with them)
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in _jax.tree.leaves(eng.params)
    )
    roofline_frac = mfu = None
    if on_accel:
        weight_pass_ceiling = peak_bw / param_bytes  # steps/s if BW-bound
        roofline_frac = steps_per_s / weight_pass_ceiling
        mfu = decode_tok_s * 2 * n_params / peak_flops
    # per-step byte attribution (dynamo_tpu/roofline.py): derived from
    # the steady-state geometry every lane reaches by the end of the run
    # — the same shape the device timing block below measures
    byte_acct = decode_byte_accounting(
        cfg, ecfg,
        [min(prompt_len + max_tokens, ecfg.max_context)]
        * ecfg.max_decode_slots,
        param_bytes, steps_per_s=steps_per_s, peak_bw=peak_bw,
    )
    # None on the CPU harness (no peak_bw): the BYTE fields stay — they
    # are derived geometry, real on any host
    attn_roofline_frac = byte_acct["attn_roofline_frac"]

    # ---- device-only time per fused round (dispatch + block). A failure
    # here fails the phase: a bench line whose device timing silently
    # went missing reads as "not measured" when it was "broken". ----
    import jax.numpy as jnp

    e = ecfg
    B = e.max_decode_slots
    # steady-state-shaped device state: all lanes live at the workload's
    # final context length (the released post-run dev would measure
    # ctx=1 scratch-lane decode — not the serving regime)
    dev = dict(
        eng._dev,
        ctx=jnp.full((B,), prompt_len + max_tokens, jnp.int32),
        dest=jnp.arange(B, dtype=jnp.int32),
        tokens=jnp.ones((B,), jnp.int32),
    )

    # time the FUSED round (round + flush + dummy seal) — the
    # program the serving loop actually dispatches, already hot
    # from phase B. Two warmups: the first call's outputs carry
    # jit-output shardings that key one more compilation.
    def one_round(dev):
        out = eng._engine_round_seal(
            eng.params, eng.ctx, eng.ring, dev, eng.cache,
            *eng._zero_seal, e.flush_every, False, False,
        )
        eng.ctx, eng.ring, eng.cache = out[0], out[1], out[3]
        _jax.block_until_ready(out)  # block each rep: no overlap illusion
        return out[2]

    dev = one_round(one_round(dev))
    t0 = time.monotonic()
    reps = 5
    for _ in range(reps):
        dev = one_round(dev)
    device_ms_per_step = (
        (time.monotonic() - t0) / (reps * e.flush_every) * 1e3
    )

    decode_ms_per_step = 1e3 / steps_per_s if steps_per_s else None
    host_ms_per_step = (
        decode_ms_per_step - device_ms_per_step
        if decode_ms_per_step is not None and device_ms_per_step is not None
        else None
    )
    host_ms_per_step_steady = (
        steady_wall / steady_steps * 1e3 - device_ms_per_step
        if steady_steps and device_ms_per_step is not None else None
    )
    return {
        "decode_tok_s": decode_tok_s,
        "prefill_tok_s": prefill_tok_s,
        "ttft_p50_s": ttft_p50,
        "ttft_p95_s": ttft_p95,
        "ttft_p99_s": ttft_p99,
        "itl_p50_s": itl_p50,
        "itl_p95_s": itl_p95,
        "itl_p99_s": itl_p99,
        "decode_ms_per_step": decode_ms_per_step,
        "ttft_isolated_s": ttft_isolated,
        "prefill_mfu": prefill_mfu,
        "device_ms_per_step": device_ms_per_step,
        "host_ms_per_step": host_ms_per_step,
        "host_ms_per_step_steady": host_ms_per_step_steady,
        "dispatches_per_round": dispatches_per_round,
        "host_breakdown": host_breakdown,
        "pipelined_dispatches": pipe["pipelined_dispatches"],
        "pipeline_depth": pipe["pipeline_depth"],
        "pipeline_overlap_ratio": pipe["overlap_ratio"],
        "slo_ttft_burn_rate": slo_burn.get("ttft"),
        "slo_itl_burn_rate": slo_burn.get("itl"),
        "mfu": mfu,
        "roofline_frac": roofline_frac,
        # per-step byte attribution (derived, real even on CPU; the
        # utilization FRACTION follows the on-accel honesty rule)
        "kv_bytes_per_step": byte_acct["kv_bytes_per_step"],
        "total_bytes_per_step": byte_acct["total_bytes_per_step"],
        "bytes_per_step_breakdown": byte_acct["bytes_per_step_breakdown"],
        "kv_ctx_bytes_vs_bf16": byte_acct["kv_ctx_bytes_vs_bf16"],
        "attn_roofline_frac": attn_roofline_frac,
        "chip": chip,
        "params_m": n_params / 1e6,
        "batch": ecfg.max_decode_slots,
        "total_tokens": total_tokens,
        "wall_s": decode_wall,
    }


def _routing_mode_fields() -> dict:
    """BASELINE config-3 tracking (KV-aware routing TTFT, the reference's
    3x headline) plus the resilience fault phase and the disagg
    chunk-pipeline phase (transfer_overlap_ratio, chunked-vs-monolithic
    remote-prefill TTFT): run the CPU mocker/tiny-engine experiments in a
    subprocess so they never touch the TPU run. A failure surfaces as
    routing_error + a failed_phases entry (and a non-zero exit), never a
    lost bench line.

    One process per chip: this parent has touched JAX and HOLDS the chip,
    so the child is forced onto the CPU (JAX_PLATFORMS=cpu) — fine for
    these mocker/tiny-engine experiments, whose numbers are counts and
    CPU timings, never device metrics. A child that needs the chip cannot
    be started from here: it would fail or hang at TPU init. Chip work
    belongs in this process, or in children of a parent that stays off
    JAX (chip_smoke.py)."""
    import subprocess

    if os.environ.get("DYNAMO_BENCH_ROUTING", "1") == "0":
        return {}
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("PYTHONWARNINGS", None)
        # fleet_sim (1k-worker storm + 3 autoscaling arms) roughly
        # doubles the subprocess runtime vs the pre-fleetsim phase set
        out = subprocess.run(
            [sys.executable, "-m", "dynamo_tpu.bench_modes"],
            capture_output=True, text=True, timeout=840, env=env,
        )
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 — secondary metric only
        return {"routing_error": str(e)[:200]}


def _run_8b_int8_phase() -> dict:
    """BASELINE config 1's model class (8B) on one 16 GB chip — only
    possible w8a16 (bf16 weights alone exceed HBM). A short measured
    decode+prefill pass, reported as int8_8b_* fields. A crash propagates
    to _extra_phase, which records it in failed_phases."""
    import gc

    overrides = {
        "DYNAMO_BENCH_MODEL": "llama3_8b_int8",
        "DYNAMO_BENCH_SLOTS": "16",
        "DYNAMO_BENCH_PAGES": "128",
        "DYNAMO_BENCH_REQUESTS": "16",
        "DYNAMO_BENCH_MAX_TOKENS": "64",
        "DYNAMO_BENCH_FLUSH": "16",
    }
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        gc.collect()
        s = asyncio.run(run_bench())
        return {
            "int8_8b_decode_tok_s": round(s["decode_tok_s"], 2),
            "int8_8b_prefill_tok_s": round(s["prefill_tok_s"], 2),
            "int8_8b_ttft_p50_s": round(s["ttft_p50_s"], 4)
            if s.get("ttft_p50_s") else None,
            "int8_8b_device_ms_per_step": round(s["device_ms_per_step"], 4)
            if s.get("device_ms_per_step") else None,
            "int8_8b_roofline_frac": round(s["roofline_frac"], 4),
            "int8_8b_params_m": round(s["params_m"], 1),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


async def _run_reuse_phase() -> dict:
    """Multi-turn prefix reuse through the offload tiers (BASELINE
    "40% TTFT from KV offload to CPU RAM", architecture.md:95): wave 1
    computes + seals long prompts into a deliberately small HBM pool so
    they spill to the G2 host tier; wave 2 resubmits the same prompts and
    onboards from G2 instead of recomputing. Reported speedup is wave-1
    TTFT / wave-2 TTFT."""
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshConfig
    from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions

    cfg = ModelConfig.llama3_1b()
    n_req, isl = 8, 1024
    ecfg = EngineConfig(
        # pool ~ half the wave's sealed pages: wave 1 MUST spill to G2
        num_pages=int(n_req * (isl / 64) / 2),
        page_size=64, max_pages_per_seq=20, max_decode_slots=8,
        prefill_buckets=(1024,), flush_every=16, max_inflight_rounds=2,
        prefill_chunks_per_round=8,
        host_offload_pages=n_req * (isl // 64) + 32,
    )
    eng = TpuEngine(cfg, ecfg, mesh_config=MeshConfig(tp=1))
    eng.start()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, isl).tolist()
               for _ in range(n_req)]

    async def drive(p, t0):
        first = None
        async for out in eng.generate(PreprocessedRequest(
            token_ids=list(p),
            stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
        )):
            if first is None and out.token_ids:
                first = time.monotonic() - t0
        return first

    # warmup: solo, batched-fresh, and continuation compiles on
    # throwaway prompts (wave timings must measure compute/onboard, not
    # XLA)
    await drive(rng.randint(1, cfg.vocab_size, isl).tolist(),
                time.monotonic())
    warm = [rng.randint(1, cfg.vocab_size, isl).tolist()
            for _ in range(n_req)]
    await asyncio.gather(*[drive(p, time.monotonic()) for p in warm])
    await asyncio.gather(*[drive(p + [5, 6, 7], time.monotonic())
                           for p in warm])
    w1 = await asyncio.gather(*[drive(p, time.monotonic())
                                for p in prompts])
    # let parked pages offload to G2 (piggybacks on rounds; poke with a
    # tiny request until the tier holds the corpus)
    for _ in range(60):
        if eng.offload is not None and len(eng.offload) >= n_req * 8:
            break
        await drive(rng.randint(1, cfg.vocab_size, 64).tolist(),
                    time.monotonic())
        await asyncio.sleep(0.2)
    # first G2->pool onboard compiles the scatter/load jits: a
    # warm prompt whose pages were evicted to G2 pays that bill here,
    # outside the timed wave
    await drive(warm[0] + [5, 6, 7], time.monotonic())
    hits0 = eng.offload.onboard_hits if eng.offload else 0
    w2 = await asyncio.gather(*[drive(p, time.monotonic())
                                for p in prompts])
    onboarded = (eng.offload.onboard_hits - hits0) if eng.offload else 0
    await eng.stop()
    w1m = sorted(x for x in w1 if x)[len(w1) // 2]
    w2m = sorted(x for x in w2 if x)[len(w2) // 2]
    return {
        "reuse_cold_ttft_p50_s": round(w1m, 4),
        "reuse_warm_ttft_p50_s": round(w2m, 4),
        "reuse_ttft_speedup": round(w1m / w2m, 3) if w2m else None,
        "reuse_onboarded_blocks": onboarded,
    }


async def _run_spec_phase() -> dict:
    """Speculative decoding on a repetitive/structured workload (where
    prompt-lookup shines: code, extraction, long copies — here a cycled
    token pattern). Runs the SAME prompts through an n-gram-speculating
    engine and a plain one and reports accepted-tokens-per-verify-step
    plus the tok/s ratio. Greedy speculation is output-identical by
    construction (tests/test_spec.py), so the speedup is free quality-
    wise whenever acceptance pays for the verify forwards.

    Also A/Bs DRAFT-model speculation with batched cross-slot drafting
    (one llama.batch_draft program per round) against the legacy
    per-slot dispatch loop (O(slots*K) programs per round): the tok/s
    ratio and draft-dispatches-per-emitted-token for both land in the
    bench JSON, so host-dispatch-overhead regressions on the drafting
    path are visible round over round."""
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        StopConditions,
    )

    tiny = os.environ.get("DYNAMO_BENCH_TINY") == "1"
    if tiny:
        cfg = ModelConfig.tiny()
        ecfg_kw = dict(
            num_pages=128, page_size=16, max_pages_per_seq=16,
            max_decode_slots=8, prefill_buckets=(128,),
            cache_dtype="float32",
        )
        n_req, isl, osl = 8, 96, 48
        draft_cfg = cfg  # draft == target: near-total acceptance
    else:
        cfg = ModelConfig.llama3_1b()
        ecfg_kw = dict(
            num_pages=256, page_size=64, max_pages_per_seq=16,
            max_decode_slots=8, prefill_buckets=(256,),
            flush_every=16, max_inflight_rounds=2,
            prefill_chunks_per_round=8,
        )
        n_req, isl, osl = 8, 192, 128
        # a toy draft sharing the target vocab: acceptance is noise
        # (random weights), but the batched-vs-per-slot DISPATCH cost
        # comparison is exactly what this phase tracks
        draft_cfg = ModelConfig.tiny(vocab_size=cfg.vocab_size)
    k = int(os.environ.get("DYNAMO_BENCH_SPEC_K", 4))
    rng = np.random.RandomState(0)
    # repetitive prompts: a short random cycle repeated to ISL — the
    # generated continuation re-enters the cycle and n-gram lookup
    # predicts it
    prompts = []
    for _ in range(n_req):
        pat = rng.randint(1, cfg.vocab_size, 16).tolist()
        prompts.append((pat * (isl // 16 + 1))[:isl])

    async def measure(speculative: str, *, draft=False, batch_draft=True,
                      out_len=osl, work=None, **spec_kw):
        work = prompts if work is None else work
        ekw = {}
        if draft:
            from dynamo_tpu.models import llama as _llama

            ekw = dict(
                draft_config=draft_cfg,
                draft_params=_llama.init_params(draft_cfg, 0),
            )
        eng = TpuEngine(
            cfg,
            EngineConfig(**ecfg_kw, speculative=speculative,
                         num_speculative_tokens=k,
                         spec_batch_draft=batch_draft, **spec_kw),
            mesh_config=MeshConfig(tp=1), **ekw,
        )
        eng.start()

        async def one(p, mt):
            n = 0
            async for out in eng.generate(PreprocessedRequest(
                token_ids=list(p),
                stop_conditions=StopConditions(
                    max_tokens=mt, ignore_eos=True
                ),
            )):
                n += len(out.token_ids)
            return n

        # warmup compiles (prefill buckets, decode round / draft / verify)
        await asyncio.gather(*[one(p, 8) for p in work[:2]])
        t0 = time.monotonic()
        tokens = sum(await asyncio.gather(
            *[one(p, out_len) for p in work]
        ))
        wall = time.monotonic() - t0
        stats = eng.spec.stats() if eng.spec else None
        await eng.stop()
        return tokens / wall, stats, tokens

    base_tok_s, _, _ = await measure("off")
    spec_tok_s, st, sp_toks = await measure("ngram")
    steps = max(st["spec_verify_steps"], 1)
    out = {
        "spec_decode_tok_s": round(spec_tok_s, 2),
        "spec_baseline_tok_s": round(base_tok_s, 2),
        "spec_speedup": round(spec_tok_s / base_tok_s, 3),
        # emitted tokens per verify step = accepted drafts + the bonus
        "spec_tokens_per_step": round(
            (st["spec_accepted_total"] + steps) / steps, 3
        ),
        "spec_acceptance_rate": round(st["spec_acceptance_rate"], 4),
        "spec_k": k,
        "spec_adaptive": st.get("spec_adaptive", False),
        "spec_verify_dispatches_per_token": round(
            st["spec_verify_dispatch_total"] / max(sp_toks, 1), 4
        ),
    }
    # draft-model drafting: batched (one program/round) vs per-slot
    # (O(slots*K) programs/round) — shorter outputs, this is a dispatch-
    # overhead A/B, not a quality phase
    d_osl = max(osl // 2, 16)
    bat_tok_s, bst, b_toks = await measure(
        "draft", draft=True, batch_draft=True, out_len=d_osl)
    per_tok_s, pst, p_toks = await measure(
        "draft", draft=True, batch_draft=False, out_len=d_osl)
    out.update({
        "spec_draft_batched_tok_s": round(bat_tok_s, 2),
        "spec_draft_per_slot_tok_s": round(per_tok_s, 2),
        "spec_draft_batch_speedup": round(
            bat_tok_s / per_tok_s, 3) if per_tok_s else None,
        "spec_draft_dispatches_per_token": round(
            bst["spec_draft_dispatch_total"] / max(b_toks, 1), 4
        ),
        "spec_draft_per_slot_dispatches_per_token": round(
            pst["spec_draft_dispatch_total"] / max(p_toks, 1), 4
        ),
    })
    # tree vs linear vs off at the same repetitive workload: the tree
    # hedges divergence points with sibling branches and fetches ONE
    # packed result per verify — same dispatch budget, longer accepted
    # paths whenever the top-1 chain isn't the whole story
    tree_tok_s, tst, t_toks = await measure(
        "ngram", spec_tree=True, spec_branches=4)
    out.update({
        "spec_tree_tok_s": round(tree_tok_s, 2),
        "spec_tree_speedup": round(tree_tok_s / base_tok_s, 3),
        "spec_tree_vs_linear": round(
            tree_tok_s / spec_tok_s, 3) if spec_tok_s else None,
        "spec_accept_rate": round(tst["spec_acceptance_rate"], 4),
        "spec_tree_mean_path_len": round(
            tst["spec_tree_mean_path_len"], 3
        ),
        "spec_tree_nodes_total": tst["spec_tree_nodes_total"],
        "spec_branch_accept_hist": tst["spec_branch_accept_hist"],
        "spec_tree_verify_dispatches_per_token": round(
            tst["spec_verify_dispatch_total"] / max(t_toks, 1), 4
        ),
    })
    # chat-shaped arm: incompressible random prompts — n-gram acceptance
    # collapses, the gate must hand every stream back to the fused round
    # and throughput must hold ~baseline (the de-speculated floor)
    chat = [rng.randint(1, cfg.vocab_size, isl).tolist()
            for _ in range(n_req)]
    c_osl = max(osl // 2, 16)
    chat_base_tok_s, _, _ = await measure("off", work=chat, out_len=c_osl)
    chat_tok_s, cst, _ = await measure(
        "ngram", work=chat, out_len=c_osl, spec_tree=True,
        spec_branches=4, spec_gate_acceptance=0.35, spec_gate_window=2,
        spec_rearm_tokens=256,
    )
    out.update({
        "spec_chat_gated_tok_s": round(chat_tok_s, 2),
        "spec_chat_baseline_tok_s": round(chat_base_tok_s, 2),
        "spec_chat_gated_speedup": round(
            chat_tok_s / chat_base_tok_s, 3) if chat_base_tok_s else None,
        "spec_gated_streams": cst["spec_gated_despec_total"],
        "spec_rearm_total": cst["spec_rearm_total"],
        "spec_chat_accept_rate": round(cst["spec_acceptance_rate"], 4),
    })
    return out


def _extra_phase(fields_prefix: str, fn, out: dict,
                 budget_left_s: float,
                 failed_phases: list = None) -> float:
    """Run one optional bench phase unless the wall budget is spent. A
    crash records {prefix}_error AND a failed_phases entry — the final
    JSON line always emits (a bench run that can't be parsed is silent
    data loss)."""
    import gc

    if budget_left_s <= 0:
        out[f"{fields_prefix}_skipped"] = "bench time budget exhausted"
        return 0.0
    # the previous phase's engine (params + ctx + pool, GBs of HBM) must
    # actually be freed before the next one allocates — an un-collected
    # engine OOMs the 8B/ISL-3000 phases
    gc.collect()
    t0 = time.monotonic()
    try:
        out.update(fn())
    except Exception as e:  # noqa: BLE001 — secondary metrics only
        out[f"{fields_prefix}_error"] = str(e)[:200]
        if failed_phases is not None:
            failed_phases.append(fields_prefix)
    return time.monotonic() - t0


def _run_isl3000_phase() -> dict:
    """BASELINE recipe shape (ISL 3000 / OSL 150,
    examples/llm/benchmarks/README.md:28) — not the ISL-100 tracking
    config."""
    overrides = {
        "DYNAMO_BENCH_ISL": "3000", "DYNAMO_BENCH_BUCKETS": "3072",
        "DYNAMO_BENCH_MAX_TOKENS": "150", "DYNAMO_BENCH_REQUESTS": "8",
        "DYNAMO_BENCH_SLOTS": "8", "DYNAMO_BENCH_FLUSH": "16",
    }
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        s = asyncio.run(run_bench())
        return {
            "isl3000_prefill_tok_s": round(s["prefill_tok_s"], 2),
            "isl3000_prefill_mfu": round(s["prefill_mfu"], 4),
            "isl3000_ttft_p50_s": round(s["ttft_p50_s"], 4)
            if s.get("ttft_p50_s") else None,
            "isl3000_decode_tok_s": round(s["decode_tok_s"], 2),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main() -> int:
    """Run every phase and ALWAYS emit the single-line JSON summary —
    a phase crash lands in ``failed_phases`` (plus a per-phase _error
    field) instead of killing the process before the print (BENCH_r05:
    rc=0 with no parseable line after an engine crash). The line is for
    the record; the EXIT CODE is for the caller: non-zero whenever any
    phase failed."""
    from dynamo_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()  # before the first jit of any phase
    failed_phases: list = []
    stats: dict = {}
    try:
        stats = run_bench()
        if asyncio.iscoroutine(stats):
            stats = asyncio.run(stats)
    except BaseException as e:  # noqa: BLE001 — the JSON line must emit
        failed_phases.append("core")
        stats = {"core_error": str(e)[:300]}
    rm = _routing_mode_fields()
    # phases that crash INSIDE the bench_modes subprocess (it exits 0
    # with a {phase}_error field) must land in failed_phases too, not
    # only a whole-subprocess failure
    for k in sorted(rm):
        if k.endswith("_error"):
            failed_phases.append(k[: -len("_error")])
    stats.update(rm)
    model = os.environ.get("DYNAMO_BENCH_MODEL", "llama3_1b")
    if os.environ.get("DYNAMO_BENCH_TINY") == "1":
        model = "tiny_cpu"   # the metric name must not claim a 1B run
    metric = {
        "llama3_1b": "decode_throughput_llama3.2-1b_bf16_agg",
    }.get(model, f"decode_throughput_{model}_agg")
    decode_tok_s = stats.get("decode_tok_s")
    # `is not None`, not truthiness: a measured 0.0 must emit as 0.0 —
    # value=null is reserved for "the phase did not produce a number"
    out = {
        "metric": metric,
        "value": round(decode_tok_s, 2) if decode_tok_s is not None else None,
        "unit": "tok/s/chip",
        "vs_baseline": (round(decode_tok_s / BASELINE_DECODE_TOK_S, 3)
                        if decode_tok_s is not None else None),
    }
    for k in ("prefill_tok_s", "prefill_mfu", "ttft_p50_s", "ttft_p95_s",
              "ttft_p99_s", "itl_p50_s", "itl_p95_s", "itl_p99_s",
              "ttft_isolated_s", "decode_ms_per_step",
              "device_ms_per_step", "host_ms_per_step",
              "host_ms_per_step_steady",
              "dispatches_per_round", "host_breakdown",
              "pipelined_dispatches", "pipeline_depth",
              "pipeline_overlap_ratio",
              "slo_ttft_burn_rate", "slo_itl_burn_rate", "mfu",
              "roofline_frac",
              # per-step byte attribution (dynamo_tpu/roofline.py):
              # derived from geometry, so the byte fields are real even
              # on CPU harnesses; attn_roofline_frac stays null there
              "kv_bytes_per_step", "total_bytes_per_step",
              "bytes_per_step_breakdown", "kv_ctx_bytes_vs_bf16",
              "attn_roofline_frac",
              "chip", "params_m", "batch",
              "core_error", "routing_error",
              "routing_kv_ttft_ms", "routing_random_ttft_ms",
              "routing_ttft_speedup",
              # fault phase (bench_modes.fault_experiment): mid-stream
              # worker-death recovery latency + exactly-once accounting
              "fault_requests", "fault_kills", "fault_migrations",
              "fault_tokens_lost", "fault_recovery_p50_ms",
              "fault_recovery_p95_ms",
              # overload phase (bench_modes.overload_experiment):
              # bounded admission A/B under a bursty storm — admitted
              # TTFT p99 shed-on vs shed-off, counted sheds, honored
              # Retry-After retries, token-identity of admitted streams
              "overload_on_ttft_p99_ms", "overload_off_ttft_p99_ms",
              "overload_sheds", "overload_retries_ok",
              "overload_gave_up", "overload_admitted_on",
              "overload_admitted_off", "overload_token_equal",
              "overload_error",
              # multi_tenant phase (bench_modes.
              # multi_tenant_experiment): tenant-A storm vs tenant-B
              # interactive TTFT isolation (< 20% move enforced in the
              # phase itself), per-tenant quota bounces with
              # tenant-derived Retry-After, token-identity
              "tenant_b_ttft_p99_alone_ms", "tenant_b_ttft_p99_storm_ms",
              "tenant_b_ttft_move_pct", "tenant_a_bounces",
              "tenant_a_storm_done", "tenant_retry_after_mean_s",
              "tenant_token_equal", "multi_tenant_error",
              # forensics phase (bench_modes.forensics_experiment):
              # SLO-breach dossier capture under the storm — every
              # breaching request joins spans+KV path under its id,
              # capture overhead A/B'd, fleet-merged p99s from the
              # summed worker histograms
              "forensics_dossiers", "forensics_breaches",
              "forensics_join_ok", "forensics_overhead_frac",
              "forensics_fleet_ttft_p99_ms",
              "forensics_fleet_queue_p99_ms", "forensics_error",
              # disagg chunk-pipeline phase (bench_modes.
              # disagg_experiment): how much transfer the overlap hides
              "disagg_chunked_ttft_ms", "disagg_mono_ttft_ms",
              "disagg_ttft_speedup", "transfer_overlap_ratio",
              "disagg_chunks_streamed", "disagg_token_equal",
              "disagg_chunked_ttfts_ms", "disagg_mono_ttfts_ms",
              "disagg_commit_wakeups", "disagg_timeout_wakeups",
              "disagg_poll_wakeups_saved",
              "disagg_timeline_events", "disagg_timeline_stream_events",
              "disagg_error",
              # kv_quant phase (bench_modes.kv_quant_experiment):
              # int8-vs-bf16 pool A/B through the disagg relay —
              # transfer bytes ~0.5x, pool capacity ~2x, prefix-hit
              # TTFT parity, token-match/logprob-delta parity
              "kv_quant_tx_bytes_int8", "kv_quant_tx_bytes_bf16",
              "kv_quant_bytes_ratio", "kv_quant_pool_blocks_int8",
              "kv_quant_pool_blocks_bf16", "kv_quant_capacity_ratio",
              "kv_quant_hit_ttft_int8_ms", "kv_quant_hit_ttft_bf16_ms",
              "kv_quant_token_match_pct", "kv_quant_logprob_delta_max",
              "kv_quant_remote_prefills", "kv_quant_error",
              # integrity phase (bench_modes.integrity_experiment):
              # clean vs corrupted prefix-hit TTFT under a flip_kv_bits
              # storm — quarantine/recompute counters fire and token
              # divergence must be 0
              "integrity_clean_hit_ttft_ms", "integrity_corrupt_ttft_ms",
              "integrity_flips_injected", "integrity_quarantined",
              "integrity_recomputed", "integrity_token_divergence",
              "integrity_error",
              # prefix_economy phase (bench_modes
              # .prefix_economy_experiment): cold worker joins mid-storm
              # — warm-start prefetch must beat the prefetch-off arm's
              # cold-start TTFT p99 with zero token divergence
              "prefix_economy_on_ttft_p99_ms",
              "prefix_economy_off_ttft_p99_ms",
              "prefix_economy_prefetched_blocks",
              "prefix_economy_recompute_avoided",
              "prefix_economy_warm_starts",
              "prefix_economy_token_divergence",
              "prefix_economy_error",
              # store_outage phase (bench_modes.store_outage_experiment):
              # store killed + WAL-restarted mid-storm — zero failed
              # requests, sessions resync, leases reclaimed from replay
              "store_outage_requests", "store_outage_failed",
              "store_outage_token_equal", "store_outage_ms",
              "store_outage_degraded_ms", "store_outage_resync_ms",
              "store_outage_resyncs", "store_outage_reconnects",
              "store_outage_replayed_keys",
              "store_outage_replayed_queue_items",
              "store_outage_workers_after", "store_outage_error",
              # fleet_sim phase (bench_modes.fleet_sim_experiment):
              # 1k-worker registration storm + bursty replay through the
              # real control plane, then the autoscaling differential
              # (SLA-violation minutes: predictive < static required)
              "fleet_sim_workers", "fleet_sim_register_s",
              "fleet_sim_discover_s", "fleet_sim_store_mutations_per_s",
              "fleet_sim_wal_batched_syncs",
              "fleet_sim_decision_p50_ms", "fleet_sim_decision_p99_ms",
              "fleet_sim_storm_requests", "fleet_sim_storm_failed",
              "fleet_sim_workers_after",
              "fleet_sim_static_sla_violation_minutes",
              "fleet_sim_static_ttft_p50_s", "fleet_sim_static_ttft_p99_s",
              "fleet_sim_static_peak_replicas",
              "fleet_sim_static_scale_events", "fleet_sim_static_failed",
              "fleet_sim_reactive_sla_violation_minutes",
              "fleet_sim_reactive_ttft_p50_s",
              "fleet_sim_reactive_ttft_p99_s",
              "fleet_sim_reactive_peak_replicas",
              "fleet_sim_reactive_scale_events",
              "fleet_sim_reactive_failed",
              "fleet_sim_predictive_sla_violation_minutes",
              "fleet_sim_predictive_ttft_p50_s",
              "fleet_sim_predictive_ttft_p99_s",
              "fleet_sim_predictive_peak_replicas",
              "fleet_sim_predictive_scale_events",
              "fleet_sim_predictive_failed", "fleet_sim_error"):
        v = stats.get(k)
        if v is None and k.endswith("_error"):
            continue
        out[k] = round(v, 4) if isinstance(v, float) else v
    if (os.environ.get("DYNAMO_BENCH_EXTRA", "1") != "0"
            and os.environ.get("DYNAMO_BENCH_TINY") != "1"
            and model == "llama3_1b" and "core" not in failed_phases):
        # extra measured phases, most important first, under a wall
        # budget so a slow run still emits the JSON line
        budget = float(os.environ.get("DYNAMO_BENCH_BUDGET_S", 900))
        budget -= _extra_phase("int8_8b", _run_8b_int8_phase, out, budget,
                               failed_phases)
        budget -= _extra_phase(
            "spec", lambda: asyncio.run(_run_spec_phase()), out, budget,
            failed_phases)
        budget -= _extra_phase(
            "reuse", lambda: asyncio.run(_run_reuse_phase()), out, budget,
            failed_phases)
        budget -= _extra_phase("isl3000", _run_isl3000_phase, out, budget,
                               failed_phases)
    elif (os.environ.get("DYNAMO_BENCH_EXTRA", "1") != "0"
            and os.environ.get("DYNAMO_BENCH_TINY") == "1"
            and "core" not in failed_phases):
        # the spec phase has a tiny mode: keep it observable in CI runs
        _extra_phase(
            "spec", lambda: asyncio.run(_run_spec_phase()), out,
            float(os.environ.get("DYNAMO_BENCH_BUDGET_S", 900)),
            failed_phases)
    out["failed_phases"] = failed_phases
    print(json.dumps(out, default=str))
    return 1 if failed_phases else 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException as e:  # noqa: BLE001 — last-ditch JSON line
        print(json.dumps({
            "metric": "decode_throughput", "value": None,
            "unit": "tok/s/chip", "vs_baseline": None,
            "failed_phases": ["bench"], "error": str(e)[:300],
        }))
        rc = 1
    sys.exit(rc)
