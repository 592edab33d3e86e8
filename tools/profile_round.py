"""Decompose the fused engine round at bench shapes on the real chip.

Times engine_round at (B=32, S_max=1024) with: the serving chunk config,
a bigger chunk, no-flush, and flush-only — to attribute device ms/step.
Run (through the chip tool — this box has no accelerator):
  PYTHONPATH=. python tools/profile_round.py

Spec mode (--spec): count DEVICE DISPATCHES per emitted token for the
speculative paths instead of timing kernels — the regression guard for
host dispatch overhead. Runs a tiny engine (CPU-friendly:
JAX_PLATFORMS=cpu works) through off / ngram / draft-batched /
draft-per-slot and prints one JSON line per mode with the per-token
dispatch breakdown (rounds, patches, draft programs, verify programs).
Batched drafting must show O(1) draft dispatches per round regardless of
the speculating slot count; the per-slot path shows the O(slots*K) cost
it replaced. Run: python tools/profile_round.py --spec all

Tree modes: ``--spec tree`` (n-gram trie) / ``--spec tree-draft`` (comb
batch_draft) add accepted-per-emitted, mean accepted path length, and
the per-branch acceptance histogram; ``--spec tree-vs-linear`` runs
off / linear ngram / tree at the same workload and prints a comparison
line — the tree must hold the linear path's dispatch budget (and one
FEWER fetch per verify: the packed result).
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import sampling
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig

N = 16
B, S = 32, 1024
CTX = 356


def timeit(name, fn, state, reps=5):
    out = fn(*state)
    jax.block_until_ready(out)
    state = (out[0], out[1], *state[2:])
    t0 = time.monotonic()
    for _ in range(reps):
        out = fn(*state)
        state = (out[0], out[1], *state[2:])
        jax.block_until_ready(out)
    dt = (time.monotonic() - t0) / reps
    print(f"{name:34s} {dt * 1e3 / N:8.3f} ms/step  ({dt * 1e3:8.2f} ms/round)")


def main():
    from dynamo_tpu.ops.attention import DecodeAttention, decode_attention_for
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    c = ModelConfig.llama3_1b()
    params = jax.device_put(llama.init_params(c, 0))
    # the implementation an engine on this device runs (the compiled
    # kernel on a chip; a CPU run times the reference and says so)
    base_attn = decode_attention_for(
        make_mesh(MeshConfig(tp=1), jax.devices()[:1])
    )
    print(f"decode_attention={base_attn.impl} "
          f"device_kind={jax.devices()[0].device_kind}")

    def make_state():
        ctx_kv = jax.device_put(llama.init_ctx(c, B, S, jnp.bfloat16))
        ring = jax.device_put(llama.init_ring(c, B, N, jnp.bfloat16))
        return ctx_kv, ring

    tokens = jnp.ones(B, jnp.int32)
    ctx0 = jnp.full((B,), CTX, jnp.int32)
    dest = jnp.arange(B, dtype=jnp.int32)

    def make_round(chunk, with_flush=True):
        # the kernel's chunk is a field of the explicit selection
        attn = DecodeAttention(base_attn.impl, base_attn.mesh, chunk=chunk)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def rnd(ctx_kv, ring, tokens, ctx, dest):
            ring_base = jnp.maximum(ctx - 1, 0)

            def body(s, carry):
                ring, toks, cl = carry
                ring, logits = llama.decode_step_impl(
                    c, params, ctx_kv, ring, toks, cl, ring_base, s,
                    attn=attn)
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return ring, toks, cl + 1

            ring, toks, cl = jax.lax.fori_loop(
                0, N, body, (ring, tokens, ctx))
            if with_flush:
                new_ctx = llama.flush_ctx_impl(
                    ctx_kv, ring, dest, ring_base,
                    jnp.full((B,), N, jnp.int32))
            else:
                new_ctx = ctx_kv
            return new_ctx, ring, toks

        return rnd

    for chunk in (256, 512, 1024):
        st = make_state()
        timeit(f"round chunk={chunk} +flush", make_round(chunk),
               (st[0], st[1], tokens, ctx0, dest))

    st = make_state()
    timeit("round chunk=512 NO flush", make_round(512, with_flush=False),
           (st[0], st[1], tokens, ctx0, dest))

    # flush alone
    st = make_state()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def flush_only(ctx_kv, ring, dest, base):
        return llama.flush_ctx_impl(ctx_kv, ring, dest, base,
                                    jnp.full((B,), N, jnp.int32)), ring

    timeit("flush only", flush_only,
           (st[0], st[1], dest, ctx0 - 1))


def _spec_dispatch_mode(modes: list[str], n_req: int, osl: int) -> int:
    """Count device dispatches per emitted token for each speculative
    path. Dispatch sources on the decode path: fused rounds
    (engine_round), state patches, first-token samples, draft programs
    (SpecDecoder.draft_dispatch_total — 1/round batched, ~K/slot/round
    per-slot), and verify programs (verify_dispatch_total)."""
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.parallel.mesh import MeshConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        StopConditions,
    )

    cfg = ModelConfig.tiny(dtype="float32")
    params = llama.init_params(cfg, 0)
    rng = np.random.RandomState(0)
    # repetitive prompts so the ngram path actually accepts drafts
    pat = rng.randint(1, cfg.vocab_size, 8).tolist()
    prompts = [pat * 6 for _ in range(n_req)]

    async def run_mode(mode: str) -> dict:
        speculative, batch_draft, tree = {
            "off": ("off", True, False),
            "ngram": ("ngram", True, False),
            "draft": ("draft", True, False),
            "draft-perslot": ("draft", False, False),
            # tree speculation: multi-branch trie drafts, tree-masked
            # verify, ONE packed fetch per verify round
            "tree": ("ngram", True, True),
            "tree-draft": ("draft", True, True),
        }[mode]
        ekw = {}
        if speculative == "draft":
            ekw = dict(draft_config=cfg, draft_params=params)
        eng = TpuEngine(
            cfg,
            EngineConfig(
                num_pages=64, page_size=16, max_pages_per_seq=8,
                max_decode_slots=max(n_req, 2), prefill_buckets=(64,),
                cache_dtype="float32", speculative=speculative,
                num_speculative_tokens=4, spec_batch_draft=batch_draft,
                spec_tree=tree, spec_branches=4,
            ),
            mesh_config=MeshConfig(tp=1), **ekw,
        )
        counts = {"round": 0, "patch": 0, "first": 0}

        def wrap(name, fn):
            def w(*a, **k):
                counts[name] += 1
                return fn(*a, **k)
            return w

        eng._engine_round = wrap("round", eng._engine_round)
        eng._engine_round_seal = wrap("round", eng._engine_round_seal)
        eng._patch = wrap("patch", eng._patch)
        eng._sample_first = wrap("first", eng._sample_first)
        eng.start()

        async def one(p):
            n = 0
            async for out in eng.generate(PreprocessedRequest(
                token_ids=list(p),
                stop_conditions=StopConditions(
                    max_tokens=osl, ignore_eos=True
                ),
            )):
                n += len(out.token_ids)
            return n

        tokens = sum(await asyncio.gather(*[one(p) for p in prompts]))
        st = eng.spec.stats() if eng.spec else {}
        await eng.stop()
        draft_d = st.get("spec_draft_dispatch_total", 0)
        verify_d = st.get("spec_verify_dispatch_total", 0)
        total = sum(counts.values()) + draft_d + verify_d
        out = {
            "mode": mode,
            "slots": n_req,
            "tokens": tokens,
            "round_dispatches": counts["round"],
            "patch_dispatches": counts["patch"],
            "first_dispatches": counts["first"],
            "draft_dispatches": draft_d,
            "verify_dispatches": verify_d,
            "draft_dispatches_per_verify": round(
                draft_d / max(verify_d, 1), 3
            ),
            "dispatches_per_token": round(total / max(tokens, 1), 4),
            "spec_acceptance_rate": round(
                st.get("spec_acceptance_rate", 0.0), 4
            ),
            # accepted draft tokens per emitted token: the speculation
            # payoff — 0 when off, -> 1 as every emission comes from an
            # accepted draft (the bonus token keeps it < 1)
            "accepted_per_emitted": round(
                st.get("spec_accepted_total", 0) / max(tokens, 1), 4
            ),
        }
        if st.get("spec_tree"):
            out["tree_nodes_per_verify"] = round(
                st["spec_tree_nodes_total"]
                / max(st["spec_tree_verify_steps"], 1), 3
            )
            out["tree_mean_path_len"] = round(
                st["spec_tree_mean_path_len"], 4
            )
            # accepted nodes by branch ordinal (0 = spine / best
            # candidate) — how much the sibling hedging actually buys
            out["branch_accept_hist"] = st["spec_branch_accept_hist"]
            out["gated_despecs"] = st["spec_gated_despec_total"]
        return out

    if "tree-vs-linear" in modes:
        # A/B at the same workload: linear chain vs tree at equal depth,
        # plus off as the floor — one JSON line each, then a comparison
        results = {}
        for mode in ("off", "ngram", "tree"):
            results[mode] = asyncio.run(run_mode(mode))
            print(json.dumps(results[mode]))
        lin, tr = results["ngram"], results["tree"]
        print(json.dumps({
            "mode": "tree-vs-linear",
            "linear_dispatches_per_token": lin["dispatches_per_token"],
            "tree_dispatches_per_token": tr["dispatches_per_token"],
            "linear_accepted_per_emitted": lin["accepted_per_emitted"],
            "tree_accepted_per_emitted": tr["accepted_per_emitted"],
            "tree_mean_path_len": tr.get("tree_mean_path_len", 0.0),
            "branch_accept_hist": tr.get("branch_accept_hist", []),
        }))
        return 0
    for mode in modes:
        print(json.dumps(asyncio.run(run_mode(mode))))
    return 0


def _dispatch_budget_mode(
    n_req: int, osl: int, kv_quant: str,
    round_pipeline: bool = True, baseline: str | None = None,
) -> int:
    """Profile the PLAIN (non-spec) decode path's host tax: run a tiny
    engine through a steady-decode workload and report (one JSON line)
    the engine's dispatch_counts broken down per source, the
    dispatches-per-decode-round number the tier-1 regression test pins
    (tests/test_dispatch_budget.py), and host ms/step = wall − device —
    the exact gap BENCH_r06 showed as 6.53 ms wall vs 1.04 ms device.
    Also reports the round-pipelining view (pipeline_depth,
    overlap_ratio, flush counters) and, with --baseline <json of a
    prior run>, the per-segment host_breakdown deltas against it.
    Run: python tools/profile_round.py --dispatch-budget"""
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.parallel.mesh import MeshConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        StopConditions,
    )

    cfg = ModelConfig.tiny(dtype="float32")
    ecfg = EngineConfig(
        num_pages=128, page_size=16, max_pages_per_seq=16,
        max_decode_slots=max(n_req, 2), prefill_buckets=(64,),
        cache_dtype="float32", kv_quant=kv_quant,
        round_pipeline=round_pipeline,
    )
    eng = TpuEngine(cfg, ecfg, mesh_config=MeshConfig(tp=1))
    eng.start()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, 48).tolist()
               for _ in range(n_req)]

    async def one(p, mt):
        n = 0
        async for out in eng.generate(PreprocessedRequest(
            token_ids=list(p),
            stop_conditions=StopConditions(max_tokens=mt, ignore_eos=True),
        )):
            n += len(out.token_ids)
        return n

    async def run() -> dict:
        # warmup: compile prefill/round/seal/patch before the window
        await asyncio.gather(*[one(p, 8) for p in prompts])
        d0 = dict(eng.dispatch_counts)
        p0 = eng.prof.totals()
        steps0 = eng.step_count
        t0 = time.monotonic()
        tokens = sum(await asyncio.gather(*[one(p, osl) for p in prompts]))
        wall = time.monotonic() - t0
        steps = eng.step_count - steps0
        delta = {k: v - d0.get(k, 0) for k, v in eng.dispatch_counts.items()}
        p1 = eng.prof.totals()
        prof = {
            "rounds": p1["rounds"] - p0["rounds"],
            "wall_s": p1["wall_s"] - p0["wall_s"],
            "segments": {
                s: p1["segments"][s] - p0["segments"][s]
                for s in p1["segments"]
            },
        }
        return {"tokens": tokens, "wall_s": wall, "steps": steps,
                "delta": delta, "prof": prof}

    stats = asyncio.run(run())
    pipe = eng.pipeline_stats()
    asyncio.run(eng.stop())  # quiesce: the loop must not patch _dev
                             # while the blocking reps donate it

    # device-only ms/step: blocking reps of the FUSED round (round +
    # flush + dummy seal — what the serving loop actually dispatches,
    # already hot) at the engine's own state, same methodology as
    # bench.py. Two warmups: the first call's outputs carry jit-output
    # shardings that key one more compilation.
    B = ecfg.max_decode_slots
    dev = dict(
        eng._dev,
        ctx=jnp.full((B,), 48 + osl, jnp.int32),
        dest=jnp.arange(B, dtype=jnp.int32),
        tokens=jnp.ones((B,), jnp.int32),
    )

    def one_round(dev):
        out = eng._engine_round_seal(
            eng.params, eng.ctx, eng.ring, dev, eng.cache,
            *eng._zero_seal, ecfg.flush_every, False, False,
        )
        eng.ctx, eng.ring, eng.cache = out[0], out[1], out[3]
        jax.block_until_ready(out)
        return out[2]

    dev = one_round(one_round(dev))
    t0 = time.monotonic()
    reps = 10
    for _ in range(reps):
        dev = one_round(dev)
    device_ms_per_step = (
        (time.monotonic() - t0) / (reps * ecfg.flush_every) * 1e3
    )

    delta = stats["delta"]
    rounds = delta.get("round", 0) + delta.get("round_seal", 0)
    wall_ms_per_step = stats["wall_s"] / max(stats["steps"], 1) * 1e3
    steps_per_s = (
        stats["steps"] / stats["wall_s"] if stats["wall_s"] > 0 else None
    )
    # per-step byte attribution (dynamo_tpu/roofline.py): derived from
    # the workload's steady geometry. attn_roofline_frac only attributes
    # against a real accelerator's bandwidth (the CPU has no peaks).
    from dynamo_tpu.roofline import chip_info, decode_byte_accounting

    _, peaks, on_accel = chip_info()
    peak_bw = peaks[1] if on_accel else None
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.params)
    )
    byte_acct = decode_byte_accounting(
        cfg, ecfg,
        [min(48 + osl, ecfg.max_context)] * ecfg.max_decode_slots,
        param_bytes, steps_per_s=steps_per_s, peak_bw=peak_bw,
    )
    # performance-attribution view (telemetry/prof.py): ms/step of each
    # host-round segment over the same window — names the slices inside
    # host_ms_per_step so the next perf PR attacks segments, not a blob
    prof = stats["prof"]
    steps = max(stats["steps"], 1)
    host_breakdown = {
        s: round(v / steps * 1e3, 5) for s, v in prof["segments"].items()
    }
    attributed = sum(prof["segments"].values())
    extra: dict = {}
    if baseline:
        # per-segment deltas vs a prior --dispatch-budget JSON: negative
        # = this run is cheaper. The diet's before/after in one field.
        with open(baseline) as f:
            base = json.load(f)
        base_bd = base.get("host_breakdown") or {}
        base_bytes = base.get("bytes_per_step_breakdown") or {}
        extra["baseline_deltas"] = {
            "host_ms_per_step": round(
                (wall_ms_per_step - device_ms_per_step)
                - base.get("host_ms_per_step", 0.0), 4),
            "device_ms_per_step": round(
                device_ms_per_step - base.get("device_ms_per_step", 0.0),
                4),
            "host_breakdown": {
                s: round(v - base_bd.get(s, 0.0), 5)
                for s, v in host_breakdown.items()
            },
            # byte deltas vs the prior run — the kv_quant=int8
            # before/after (live-KV bytes halving) in one diffable field
            "kv_bytes_per_step": (
                byte_acct["kv_bytes_per_step"]
                - base.get("kv_bytes_per_step", 0)),
            "bytes_per_step_breakdown": {
                s: v - base_bytes.get(s, 0)
                for s, v in byte_acct["bytes_per_step_breakdown"].items()
            },
        }
    print(json.dumps({
        "mode": "dispatch-budget",
        "kv_quant": kv_quant,
        "slots": n_req,
        "tokens": stats["tokens"],
        "steps": stats["steps"],
        "rounds": rounds,
        "dispatch_breakdown": delta,
        "dispatches_per_round": round(
            sum(delta.values()) / max(rounds, 1), 3),
        "standalone_seal_dispatches": delta.get("seal", 0),
        "wall_ms_per_step": round(wall_ms_per_step, 4),
        "device_ms_per_step": round(device_ms_per_step, 4),
        "host_ms_per_step": round(
            wall_ms_per_step - device_ms_per_step, 4),
        "host_breakdown": host_breakdown,
        "host_prof_rounds": prof["rounds"],
        "host_prof_coverage": round(
            attributed / prof["wall_s"], 4) if prof["wall_s"] > 0 else 1.0,
        "round_pipeline": pipe["round_pipeline"],
        "pipelined_dispatches": pipe["pipelined_dispatches"],
        "pipeline_depth": round(pipe["pipeline_depth"], 4),
        "overlap_ratio": round(pipe["overlap_ratio"], 4),
        "pipe_flushes": pipe["pipe_flushes"],
        "kv_bytes_per_step": byte_acct["kv_bytes_per_step"],
        "total_bytes_per_step": byte_acct["total_bytes_per_step"],
        "bytes_per_step_breakdown": byte_acct["bytes_per_step_breakdown"],
        "kv_ctx_bytes_vs_bf16": byte_acct["kv_ctx_bytes_vs_bf16"],
        "attn_roofline_frac": byte_acct["attn_roofline_frac"],
        **extra,
    }))
    return 0


if __name__ == "__main__":
    from dynamo_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()  # before the first jit
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--spec", default=None, nargs="?", const="all",
        choices=["off", "ngram", "draft", "draft-perslot", "tree",
                 "tree-draft", "tree-vs-linear", "all"],
        help="dispatch-count mode instead of kernel timing",
    )
    ap.add_argument(
        "--dispatch-budget", action="store_true",
        help="plain-round dispatch budget + host-ms/step JSON mode "
             "(the regression-pinned numbers)",
    )
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="pool quantization for --dispatch-budget")
    ap.add_argument("--round-pipeline", default="on",
                    choices=["on", "off"],
                    help="double-buffered round pipelining for "
                         "--dispatch-budget (off = the serialized "
                         "baseline to diff against)")
    ap.add_argument("--baseline", default=None, metavar="JSON",
                    help="a prior --dispatch-budget output file; adds "
                         "per-segment host_breakdown deltas vs it")
    ap.add_argument("--requests", type=int, default=4,
                    help="concurrent requests (= speculating slots)")
    ap.add_argument("--osl", type=int, default=32,
                    help="output tokens per request in --spec/"
                         "--dispatch-budget mode")
    args = ap.parse_args()
    if args.dispatch_budget:
        raise SystemExit(
            _dispatch_budget_mode(
                args.requests, args.osl, args.kv_quant,
                round_pipeline=args.round_pipeline == "on",
                baseline=args.baseline,
            )
        )
    if args.spec:
        modes = (["off", "ngram", "draft", "draft-perslot", "tree",
                  "tree-draft"]
                 if args.spec == "all" else [args.spec])
        raise SystemExit(_spec_dispatch_mode(modes, args.requests, args.osl))
    main()
