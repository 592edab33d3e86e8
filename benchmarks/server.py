#!/usr/bin/env python3
"""The benchmark's launcher for the system under test: the CHILD process
that holds the chip(s). It does what ``dynamo_tpu/launch/run.py``
``build_chain`` + ``_serve_http`` do for ``run in=http out=tpu`` — the same
front door (``HttpService``), preprocessor, backend, engine, scheduler and
cache — for a configuration FILE, which the CLI cannot take (it serves
canned ``ModelConfig`` classmethods or a directory of safetensors).

What is the benchmark's own here, and why:
  * weights are drawn on the device, sharded at birth, in ONE jitted call
    from the seed (``TpuEngine(params=None)`` draws them eagerly on
    device 0, which a model larger than one chip cannot survive);
  * while the engine is constructed, its ctx region and prefix pool are
    born sharded too (``born_sharded``): built eagerly on device 0, as
    the program does, the tp=4 configuration's ctx does not fit a chip;
  * a correctness check against the configuration's plain reference
    before serving (below);
  * a stdin command loop for the parent: ``snapshot <file>`` (the
    engine's counters), ``trace <dir> <delay_s> <seconds>`` (a
    ``jax.profiler`` trace of the device), ``stop``.

What this launcher knows about a block it finds through the configuration
file; a configuration without these keys gets the defaults in brackets:

  ``"reference": "<name>"``  the plain reference of its block,
      ``benchmarks/references/<name>.py`` (no key: ``benchmarks/
      reference.py``), loaded by path. The contract: ``logprobs(hf,
      params, tokens, positions) -> float array [len(positions),
      vocab_size]``, the log-softmax of the next token after each of
      ``positions`` of ``tokens``; float32 under
      ``jax.default_matmul_precision("highest")``; it imports nothing of
      the program and takes the engine's own weight pytree (``params``),
      so both sides compute the same model. The module may also state
      ``CHECK_PROMPTS`` (pairs of prompt tokens and decode steps >= 8),
      ``CHECK_TOL_MAX`` and ``CHECK_TOL_MEAN``, each with its reason
      written beside it; what it does not state is this file's. A named
      file that is missing, or has no ``logprobs``, is an error, never a
      fall back to another block's reference (``byname.py``).
  ``"bytes": "<name>"``  read by ``layer_metrics/step.decode_roofline.py``,
      not here.

The program module that builds the block is still named here
(``dynamo_tpu.models.llama``): ``TpuEngine`` calls it at some 25 sites, so
a key that named another would change what this file patches and not what
the engine runs. That seam is the program's to open (ROADMAP D2).
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import byname  # noqa: E402

# How far the served model (bf16 activations, f32 accumulation, KV cache in
# bf16, int8 weights dequantized inside the matmul) may stand from the
# float32 reference, as max |log-prob difference| over the engine's top-20
# tokens at every checked position. bf16 keeps 8 bits: each of the ~6
# roundings a layer makes perturbs the residual stream by ~2^-9 of its
# scale, independent roundings over 32-40 layers add up to ~1-2 % of the
# logits' scale (std ~1.3 here), i.e. a few hundredths of a nat: measured
# on the chip, mean 0.018 and max 0.07-0.08 over 480 comparisons, the same
# to two digits for two seeds (PERF.md). A dropped layer, a wrong rope
# pairing or head mapping, a mis-scaled int8 channel or int8 activations
# (7 bits: errors ~4x bf16's at every matmul input) move log-probs by
# tenths of a nat to nats. MEAN (1.7x what was measured) is the sharp
# test: it catches a uniform loss of precision; MAX (2.5x, the extreme of
# 480 draws moves more from seed to seed) catches a local fault.
CHECK_TOL_MAX = 0.2
CHECK_TOL_MEAN = 0.03
CHECK_PROMPTS = ((96, 12), (180, 12))   # (prompt tokens, decode steps >= 8)
CHECK_TOP = 20


T_START = time.monotonic()


def say(kind: str, obj: dict) -> None:
    obj = dict(obj, t=round(time.monotonic() - T_START, 3))
    print(f"{kind}: " + json.dumps(obj), flush=True)


def load_config(path: str, dry_run: bool) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    if dry_run:
        cfg.update(cfg["dry_run"])   # tiny widths, same file, same code
    return cfg


def reference_for(cfg: dict) -> dict:
    """The configuration's plain reference, loaded by path, and what the
    check holds the engine to: ``logprobs``, ``file`` (from the checkout's
    root), ``prompts``, ``tol_max``, ``tol_mean``; the last three are the
    module's own where it states them, else this file's."""
    if "reference" in cfg:
        directory, name = os.path.join(HERE, "references"), cfg["reference"]
    else:
        directory, name = HERE, "reference"
    mod = byname.module_with(directory, name, "logprobs")
    prompts = [[int(p), int(n)] for p, n in
               getattr(mod, "CHECK_PROMPTS", CHECK_PROMPTS)]
    if not prompts:
        raise ValueError(f"{mod.__file__}: CHECK_PROMPTS is empty")
    return {"logprobs": mod.logprobs,
            "file": os.path.relpath(mod.__file__, REPO), "prompts": prompts,
            "tol_max": float(getattr(mod, "CHECK_TOL_MAX", CHECK_TOL_MAX)),
            "tol_mean": float(getattr(mod, "CHECK_TOL_MEAN", CHECK_TOL_MEAN))}


class CompileWatch:
    """Counts programs JAX lowers (cache hit or miss): the window must add
    none."""

    def __init__(self):
        import jax.monitoring

        self.lowered = 0
        self.backend_compiles = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1
            self.names.append(str(kw.get("fun_name", "?")))
            del self.names[:-64]
        elif event.endswith("backend_compile_duration"):
            self.backend_compiles += 1


@contextlib.contextmanager
def born_sharded(llama, mesh):
    """While the engine is constructed, the program's ``init_ctx`` and
    ``init_cache`` give their zeros sharded at birth (one jitted call with
    the program's own shardings as ``out_shardings``) instead of eagerly
    on device 0: the tp=4 configuration's unsharded ctx region (11.4 GB)
    plus its weight shard does not fit one chip. Same arrays, same
    shardings; the engine's own ``device_put`` then finds them in place.
    A stand-in for a repair the program needs (PERF.md section 7)."""
    import jax

    def at_birth(make, shardings):
        def wrapped(config, *args, kv_quant="none", **kw):
            return jax.jit(
                lambda: make(config, *args, kv_quant=kv_quant, **kw),
                out_shardings=shardings(config, mesh, kv_quant=kv_quant))()
        return wrapped

    originals = llama.init_ctx, llama.init_cache
    llama.init_ctx = at_birth(llama.init_ctx, llama.ctx_shardings)
    llama.init_cache = at_birth(llama.init_cache, llama.cache_shardings)
    try:
        yield
    finally:
        llama.init_ctx, llama.init_cache = originals


def build_engine(cfg: dict, seed: int, dry_run: bool):
    import jax

    from dynamo_tpu.compile_cache import ensure_compile_cache
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
    from dataclasses import replace

    cache_dir = ensure_compile_cache()
    tp = int(cfg["tp"])
    devices = jax.devices()
    if len(devices) < tp:
        raise SystemExit(f"configuration needs {tp} devices, JAX has "
                         f"{len(devices)}")
    mcfg = ModelConfig.from_hf_dict(cfg)
    if cfg.get("quant"):
        mcfg = replace(mcfg, quant=cfg["quant"])
    engine_kw = dict(cfg["engine"])
    if dry_run:
        engine_kw["cache_dtype"] = "float32"
        mcfg = replace(mcfg, dtype="float32")
    ecfg = EngineConfig(**engine_kw)
    mesh = make_mesh(MeshConfig(tp=tp), devices[:tp])
    # weights: on the device(s), sharded at birth, one program
    t0 = time.monotonic()
    # the key is an argument, not a constant: one program for every seed
    params = jax.jit(
        lambda key: llama.init_params(mcfg, key),
        out_shardings=llama.param_shardings(mcfg, mesh),
    )(jax.random.PRNGKey(seed % (2 ** 31)))
    jax.block_until_ready(params)
    t_weights = time.monotonic() - t0
    with born_sharded(llama, mesh):
        engine = TpuEngine(mcfg, ecfg, params=params, mesh=mesh)
    dev0 = devices[0]
    # the largest leaf of the ctx region, whatever kinds of state it holds
    ctx_k = max(jax.tree.leaves(engine.ctx), key=lambda a: a.nbytes)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    say("engine up", {
        "platform": dev0.platform, "device_kind": dev0.device_kind,
        "devices": len(devices), "tp": tp,
        "decode_attention": engine.decode_attn.impl,
        "ctx_shape": list(ctx_k.shape),
        "ctx_shard": list(ctx_k.addressable_shards[0].data.shape),
        "hbm_gb": [round((d.memory_stats() or {}).get("bytes_in_use", 0)
                         / 1e9, 3) for d in mesh.devices.flat],
        "param_bytes": param_bytes,
        "weights_s": round(t_weights, 3),
        "flush_every": ecfg.flush_every,
        "compile_cache": cache_dir,
    })
    return engine


def warm_seal_widths(engine, rows: int) -> dict:
    """The standalone ctx->pool seal program is compiled per power-of-two
    batch width, and which widths a run meets depends on how admissions
    interleave — not something HTTP warm-up traffic can force. Compile
    them all here, before the engine loop starts, with all-padding
    batches (every row targets scratch page 0, garbage by contract), up to
    ``rows`` = slots x the pages of the mix's longest prompt: the program's
    temporaries grow with the width (8.4 MB a row at 7B sizes), so wider
    ones than the traffic can reach are not built.
    Reaches into the program; if its internals moved, say so and go on —
    the in-window compile counter still decides ``correct``."""
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    e = engine.ecfg
    widths, w = [], 1
    try:
        while w < 2 * rows:     # every power of two up to pow2_cover(rows)
            z = jnp.zeros(w, jnp.int32)
            engine.cache = llama.seal_blocks(
                engine.cache, engine.ctx, z, z, z, page_size=e.page_size)
            widths.append(w)
            w *= 2
    except (AttributeError, TypeError) as err:
        return {"widths": widths, "skipped": repr(err)}
    return {"widths": widths}


async def check_against_reference(engine, cfg: dict, seed: int,
                                  reference: dict) -> dict:
    """Prefill + decode through the engine's normal path with logprobs,
    then the same tokens teacher-forced through the configuration's plain
    reference, at its prompts and under its tolerances."""
    import random

    import numpy as np

    from dynamo_tpu.protocols.common import (
        OutputOptions, PreprocessedRequest, SamplingOptions, StopConditions)

    tol_max, tol_mean = reference["tol_max"], reference["tol_mean"]
    rng = random.Random(seed)
    vocab = cfg["vocab_size"]
    worst, total, n = 0.0, 0.0, 0
    mismatched_argmax = 0
    phases: list[float] = []    # per prompt: engine seconds, reference seconds
    for prompt_len, n_out in reference["prompts"]:
        prompt = [rng.randrange(10, vocab) for _ in range(prompt_len)]
        req = PreprocessedRequest(
            token_ids=prompt, model="bench",
            stop_conditions=StopConditions(max_tokens=n_out, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            output_options=OutputOptions(logprobs=CHECK_TOP),
        )
        toks, tops = [], []
        t_gen = time.monotonic()
        async for out in engine.generate(req):
            toks += out.token_ids
            tops += out.top_logprobs or []
        if len(toks) != n_out or len(tops) != n_out:
            raise SystemExit(f"check: engine gave {len(toks)} tokens, "
                             f"{len(tops)} logprob rows, asked {n_out}")
        t_ref = time.monotonic()
        phases.append(round(t_ref - t_gen, 2))
        seq = prompt + toks
        positions = [prompt_len - 1 + i for i in range(n_out)]
        ref = reference["logprobs"](cfg, engine.params, seq, positions)
        phases.append(round(time.monotonic() - t_ref, 2))
        for i, row in enumerate(tops):
            ids = np.asarray([p[0] for p in row], np.int64)
            got = np.asarray([p[1] for p in row], np.float64)
            diff = np.abs(got - ref[i, ids])
            worst = max(worst, float(diff.max()))
            total += float(diff.sum())
            n += len(ids)
            # informational: with random weights the top two logits can
            # sit within the rounding, so argmax is not held to agree
            mismatched_argmax += int(int(np.argmax(ref[i])) != toks[i])
    mean = total / n
    return {"ok": bool(worst <= tol_max and mean <= tol_mean),
            "max_abs_logprob_diff": worst, "mean_abs_logprob_diff": mean,
            "tol_max": tol_max, "tol_mean": tol_mean,
            "reference": reference["file"], "prompts": reference["prompts"],
            "compared": n, "argmax_differs": mismatched_argmax,
            "phases_s": phases}


def snapshot(engine, watch: CompileWatch) -> dict:
    import jax

    hists = {name: {"sum": h["sum"], "count": h["count"]}
             for name, h in engine.telemetry.snapshot().items()}
    mem = [(d.memory_stats() or {}) for d in engine.mesh.devices.flat]
    return {
        "t_wall": time.time(),
        "histograms": hists,
        "prof": engine.prof.totals(),
        "dispatch_counts": dict(engine.dispatch_counts),
        "pipe_flushes": dict(engine.pipe_flushes),
        "step_count": engine.step_count,
        "tokens_generated": engine.tokens_generated,
        "batch_prefills": engine.batch_prefills,
        "lowered": watch.lowered,
        "backend_compiles": watch.backend_compiles,
        "lowered_names": list(watch.names),
        "memory_peak_bytes": max(
            (m.get("peak_bytes_in_use", 0) for m in mem), default=0),
        "memory_in_use_bytes": [m.get("bytes_in_use", 0) for m in mem],
        "platform": jax.devices()[0].platform,
    }


def take_trace(path: str, delay_s: float, seconds: float) -> dict:
    """A profiler trace of ``seconds``, ``delay_s`` from now, left as
    ``<path>/plugins/profile/span/server.xplane.pb`` (where
    ``trace_reduce.find_xplane`` looks). The device planes are what is
    read: no Python tracer (it slows the engine's host loop and bloats the
    file), least host tracing.

    ``jax.profiler.start_trace`` / ``stop_trace`` are this session and
    ``stop_and_export``, which besides the ``.xplane.pb`` converts every
    event into a ``trace.json.gz`` that nothing here reads: on the chip a
    fifth of the collection of cell 1's span and two thirds of the four-chip
    cell's (95 of 146 s; PERF.md section 3), growing with the events, i.e.
    with the program's SPEED. So the session is held here and its bytes are
    written as they come. What is left, ``stop()``, is one CPU-bound thread
    inside the profiler (22-24 s on one chip, 52-61 s on four), and no
    ``ProfileOptions`` setting of JAX 0.9.0 shortens it: ``host_tracer_level``
    0, ``enable_hlo_proto`` off and ``tpu_trace_mode`` were tried."""
    import jax
    from jax._src.lib import _profiler   # what jax.profiler itself drives

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    time.sleep(delay_s)
    session = _profiler.ProfilerSession(opts)
    t0 = time.time()
    time.sleep(seconds)
    t1 = time.time()
    xspace = session.stop()             # collecting takes minutes
    out_dir = os.path.join(path, "plugins", "profile", "span")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "server.xplane.pb"), "wb") as f:
        f.write(xspace)
    return {"dir": path, "t_start_wall": t0, "t_stop_wall": t1,
            "collect_s": round(time.time() - t1, 2),
            "xspace_bytes": len(xspace)}


def command_loop(engine, watch, stop_evt: threading.Event, loop) -> None:
    """Reads the parent's commands from stdin (a thread of its own)."""

    def trace(path: str, delay_s: float, seconds: float) -> None:
        try:
            say("trace done", take_trace(path, delay_s, seconds))
        except Exception:
            # no trace, no result line: die where the parent sees it at
            # once, not after its whole wait for `trace done`
            traceback.print_exc()
            os._exit(1)

    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "snapshot":
            tmp = words[1] + ".tmp"
            with open(tmp, "w") as f:
                json.dump(snapshot(engine, watch), f)
            os.replace(tmp, words[1])
        elif words[0] == "trace":
            threading.Thread(
                target=trace, daemon=True,
                args=(words[1], float(words[2]), float(words[3]))).start()
        elif words[0] == "stop":
            break
    # stdin closed = the parent is gone: stop either way
    loop.call_soon_threadsafe(stop_evt.set)


class EveryTokenSpeaks:
    """The offline test tokenizer with its three special ids decoded as
    text like every other id; ``decode`` is all the backend reads.
    ``backend.py`` yields nothing for an engine output that decodes to no
    text and the frontend's usage then never counts its tokens; random
    weights decoding greedily can settle on a special id, whole rounds of
    it. Seen on the chip (PR 27, seed 2700270011, twice):
    ``usage.completion_tokens`` 54 of 58, 14 chunks of 16, ``correct``
    false. With this every emission is a chunk with text, so the client's
    clock sees each one and usage counts each token. The program's fault
    is not hidden by it: ``loadgen.send`` fails a request whose usage is
    short by even one token, so a launcher without this wrapper reads
    ``correct`` false on such a seed again."""

    def __init__(self, tok):
        self._tok = tok

    def decode(self, ids, skip_special_tokens: bool = True) -> str:
        return self._tok.decode(ids, skip_special_tokens=False)


async def serve(args) -> int:
    from dynamo_tpu.backend import Backend
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.model_manager import ModelChain
    from dynamo_tpu.preprocessor import OpenAIPreprocessor, PromptFormatter
    from dynamo_tpu.tokenizer import make_test_tokenizer

    cfg = load_config(args.config, args.dry_run)
    # before anything is built: a wrong name costs no compile
    reference = reference_for(cfg)
    watch = CompileWatch()
    engine = build_engine(cfg, args.seed, args.dry_run)
    t0 = time.monotonic()
    say("seal warm-up", warm_seal_widths(engine, args.seal_rows))
    verdict = await check_against_reference(engine, cfg, args.seed, reference)
    verdict["seconds"] = round(time.monotonic() - t0, 3)
    say("check", verdict)

    tok = make_test_tokenizer()
    chain = ModelChain(
        name="bench", engine=engine, backend=Backend(EveryTokenSpeaks(tok)),
        preprocessor=OpenAIPreprocessor(
            tokenizer=tok, formatter=PromptFormatter(), model_name="bench"))
    manager = ModelManager()
    manager.register(chain)
    svc = HttpService(manager, host="127.0.0.1", port=args.port,
                      trace_sample_rate=0.0)
    await svc.start()
    say("serving", {"port": args.port})
    stop_evt = asyncio.Event()
    threading.Thread(
        target=command_loop, daemon=True,
        args=(engine, watch, stop_evt, asyncio.get_running_loop())).start()
    try:
        await stop_evt.wait()
    finally:
        await svc.stop()
        await engine.stop()
    say("stopped", {})
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seal-rows", type=int, default=1,
                    help="most blocks one standalone seal can carry")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny widths on the CPU (the parent sets the "
                         "platform): a rehearsal, never a measurement")
    args = ap.parse_args()
    return asyncio.run(serve(args))


if __name__ == "__main__":
    sys.exit(main())
