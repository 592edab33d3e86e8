#!/usr/bin/env python
"""Time a decode attention kernel on the chip at its cells' shapes.

``--kind latent`` (the default): the latent decode attention at the two latent cells'
shapes (``B`` 16 x ``S`` 16384 x 7 layers, ``B`` 64 x ``S`` 4096 x 5
layers; 32 heads, rows stored at 640, value 512), on lane lengths like
the cells': the XLA loop as the parent ran it (every lane to the longest
device length, idle lanes' stale lengths included), the XLA loop told
which lanes are live, and the Pallas kernel at each chunk. Does the time
follow the live lanes' own rows, what does a chunk cost, and does the
kernel agree with the loop?  One JSON line per (case, implementation).

``--kind dense``: ``ops/flash_decode.py``'s kernel over K and V rows
through ``ctx_decode_attention`` at the dense cells' shapes (cell 1's 8
lanes x 8 K/V heads, a tp = 4 shard of cell 2's 16 lanes x 2, cell 9's 96
lanes x 1, cell 7's 16 lanes of 32768 rows), fewer and fewer lanes live
and the live ones scattered; a lane that is not live holds a stale length
like the live ones'. With ``--old-tree <checkout>`` the kernel of that
tree's ``ops/flash_decode.py`` (the grid over every lane's chunks, which
knows no ``live``) is timed in the same harness on the same arrays, and
the live lanes' outputs are compared BIT FOR BIT. One JSON line a case:
``new_us`` / ``old_us`` a layer-call, ``items`` on the work list,
``live_bit_equal``, ``max_abs_diff``, ``dead_rows_zero``.

``--kind prefill`` (PR 61): ONE layer's expanded latent PREFILL attention
as the latent programs hand it over (``[K, T, 32, 192]`` q / k, ``[K, T,
32, 128]`` v; a continuing chunk over a workspace of its prior rows
expanded per head), at the long-context cell's buckets (T 2048 / 4096,
fresh and continuing over 2048 / 4096 / 8192 prior rows of a 16384-row
workspace, ragged live lengths) and the chat cell's (``[1..2, 128..1024]``
over a 4096-row one): the XLA loops (``prefill_attention``; fresh chunks
with V zero-padded to the key's width, as the parent's fresh programs
passed it) against the fused kernel (``fused_prefill_attention``) at
``--heads`` heads a grid step. One JSON line a (case, form): ``ms`` a
layer-call, ``steps`` (query block, key block) pairs scored, ``us_per_step``,
``mxu_share`` of benchmarks/peaks.py's 197 TFLOP/s that the scored pairs'
two products make, and the kernel's distance from the loops on live rows.
With ``--kv-heads`` (PR 63) the DENSE decoder's GQA layer instead:
``--q-heads`` query heads over ``--kv-heads`` K/V heads of 128 (32 / 8: a
Mistral-7B layer; 8 / 2: a tp = 4 shard of Nemo-12B's), the prior context
read from a region ``[1, kvh, lanes, 4096, 128]`` in place as the engine
holds it (keys as rows), at the dense cells' buckets (``PREFILL_GQA_CASES``:
a fresh 4096 with 2560 live rows, a fresh 2048, the chat buckets, a 2048-row
chunk over 2048 prior rows).

  python tools/latent_decode_bench.py            # on the chip (chiprun)
  python tools/latent_decode_bench.py --dry-run  # tiny, interpreted, here
  python tools/latent_decode_bench.py --kind dense --old-tree .scratch/parent
  python tools/latent_decode_bench.py --kind prefill --heads 4,8,16
  python tools/latent_decode_bench.py --kind prefill --kv-heads 8 --heads 16,32
  python tools/latent_decode_bench.py --kind prefill --q-heads 8 --kv-heads 2
  python tools/latent_decode_bench.py --kind prefill --schedule --heads 4

``--schedule`` (HERE, no chip, ~20 s): the fused prefill kernel compiled
for a described v5e with libtpu's own dump of its FINAL instruction
bundles (``--xla_jf_dump_to``; a bundle issues in a cycle), reduced to one
JSON line a straight-line stretch of the kernel: bundles, and operations by
unit. The two long stretches are a step's prior-block and chunk-block
branches: bundles / ``--heads`` against the 384 a head that its MXU
passes take alone is what the chip then shows (PR 61: 658 -> 402 bundles a
head was 13.6 -> 10.7 us a 32-head step).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.ops.attention import (  # noqa: E402
    PALLAS,
    PALLAS_INTERPRET,
    REFERENCE_IMPL,
    DecodeAttention,
    PriorContext,
    ctx_decode_attention,
    fused_prefill_attention,
    prefill_attention,
    prefill_attention_pairs,
    prefill_steps,
    region_trips,
)
from dynamo_tpu.ops.flash_decode import DEFAULT_CHUNK  # noqa: E402
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh  # noqa: E402
from dynamo_tpu.ops.latent_decode import (  # noqa: E402
    chunk_rows,
    latent_decode_attention,
    region_rows_read,
)

NH, ROW, V, R = 32, 640, 512, 4


def cases(name: str, B: int, S: int, rng) -> dict:
    """Lane lengths (region rows) and liveness like the cell's traffic."""
    if name == "longdoc":
        # ~2.5 of 16 lanes live at 2k-14k rows; an idle lane's device
        # length creeps up 4 a round from 1
        typical = np.full(B, 900)
        typical[[0, 1, 2]] = [9000, 4100, 12000]
        live_t = np.zeros(B, bool)
        live_t[:3] = True
        burst = rng.integers(2048, 14336, B)
        live_b = np.arange(B) < 12
        burst[~live_b] = 300
        return {"typical_3_live": (typical, live_t),
                "burst_12_live": (burst, live_b)}
    # chat-decode: ~46 of 64 lanes live at 32-1500 rows; the highest
    # lanes idle for long
    lens = np.clip(rng.lognormal(np.log(400), 0.7, B), 32, 1500).astype(int)
    live = np.arange(B) < 46
    lens[~live] = rng.integers(500, S, (~live).sum())
    return {"chat_46_live": (lens, live)}


# name -> ((layers, K/V heads, lanes, region rows, query heads), cases of
# (live lanes, region rows of every lane))
DENSE_SHAPES = {
    "cell1_mistral7b": ((32, 8, 8, 4096, 32), [
        (n, rows) for rows in (300, 3000) for n in (0, 1, 2, 4, 8)]),
    "cell2_nemo12b_shard": ((40, 2, 16, 4096, 8), [(13, 400), (16, 400)]),
    "cell9_jamba2": ((2, 1, 96, 4096, 20), [(48, 500), (96, 500)]),
    "cell7_minicpm_sala": ((4, 2, 16, 32768, 32), [(5, 6000), (16, 6000)]),
}
DENSE_TOY = {"toy": ((2, 2, 4, 64, 4), [(0, 20), (2, 20), (4, 40)])}
HD = 128


def dense_main(args, dev) -> int:
    """``--kind dense``: the module doc."""
    shapes, hd, dtype, impl, cb = DENSE_SHAPES, HD, jnp.bfloat16, PALLAS, 0
    iters = args.iters
    if args.dry_run:
        shapes, hd, dtype, impl, cb = (DENSE_TOY, 16, jnp.float32,
                                       PALLAS_INTERPRET, 16)
        iters = 1
    # as the engine calls it: mapped over a mesh's ``tp`` axis (of one)
    attn = DecodeAttention(impl, make_mesh(MeshConfig(tp=1), [dev]),
                           chunk=cb)
    old = None
    if args.old_tree:
        spec = importlib.util.spec_from_file_location(
            "old_flash_decode", os.path.join(
                args.old_tree, "dynamo_tpu", "ops", "flash_decode.py"))
        old = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old)

    for name, ((L, nkv, B, S, nh), cases) in shapes.items():
        if args.shapes and name not in args.shapes.split(","):
            continue
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        ck, cv = (jax.random.normal(k, (L, nkv, B + 1, S, hd), dtype)
                  for k in ks[:2])
        rk, rv = (jax.random.normal(k, (L, nkv, B, R, hd), dtype)
                  for k in ks[2:4])
        q0 = jax.random.normal(ks[4], (B, nh, hd), dtype)
        order = np.random.RandomState(0).permutation(B)
        for n_live, rows in cases:
            live = np.zeros(B, bool)
            live[order[:n_live]] = True
            # lengths around ``rows``, one ring row written
            below = np.clip(np.random.RandomState(n_live).randint(
                rows - rows // 4, rows + rows // 4 + 1, B), 0, S - R)
            base = jnp.asarray(below, jnp.int32)
            lens = base + 1
            live_j = jnp.asarray(live)

            def new_call(q, l, lens, base, live, *kv):
                return ctx_decode_attention(attn, q, *kv, l, lens, base,
                                            live=live)

            def old_call(q, l, lens, base, live, *kv):
                return old.flash_decode_attention(
                    q, *kv, l, lens, base, chunk=cb, interpret=args.dry_run)

            def run(call, n):
                # lengths and liveness are values of the program, as in
                # the engine's round: nothing of the list folds away
                @jax.jit
                def f(q, *rest):
                    def body(_, c):
                        q, outs = c
                        for l in range(L):
                            o = call(q, jnp.int32(l), *rest)
                            outs = outs.at[l].set(o)
                            q = q + (o * 1e-3).astype(q.dtype)
                        return q, outs
                    return jax.lax.fori_loop(
                        0, n, body, (q, jnp.zeros((L,) + q.shape, q.dtype)))
                operands = (q0, lens, base, live_j, ck, cv, rk, rv)
                jax.block_until_ready(f(*operands))            # compiles
                t0 = time.perf_counter()
                out = jax.block_until_ready(f(*operands))
                return (time.perf_counter() - t0) / (n * L), np.asarray(
                    out[1], np.float32)

            new_s, _ = run(new_call, iters)
            # one pass for the comparison: the timed loop feeds its own
            # output back, and a dead lane's old output is not 0
            _, new_o = run(new_call, 1)
            line = {
                "device": dev.device_kind, "shape": name, "lanes": B,
                "S": S, "layers": L, "kv_heads": nkv, "live": n_live,
                "rows": rows,
                "items": int((region_trips(
                    below, live, cb or DEFAULT_CHUNK) + live).sum()),
                "new_us": round(new_s * 1e6, 2),
                "dead_rows_zero": not new_o[:, ~live].any(),
            }
            if old is not None:
                old_s, _ = run(old_call, iters)
                _, old_o = run(old_call, 1)
                line.update({
                    "old_us": round(old_s * 1e6, 2),
                    "live_bit_equal": bool(np.array_equal(
                        new_o[:, live], old_o[:, live])),
                    "max_abs_diff": float(np.abs(
                        new_o[:, live] - old_o[:, live]).max(initial=0.0)),
                })
            print(json.dumps(line), flush=True)
    return 0


# (name, lanes K, bucket T, workspace rows (0: a fresh program), q_starts,
# live rows of each chunk)
PREFILL_CASES = [
    ("longdoc_fresh_2048", 1, 2048, 0, [0], [2048]),
    ("longdoc_fresh_4096", 1, 4096, 0, [0], [4096]),
    ("longdoc_fresh_4096_ragged", 1, 4096, 0, [0], [3000]),
    ("longdoc_cont_2048_over_4096", 1, 2048, 16384, [4096], [2048]),
    ("longdoc_cont_4096_over_2048", 1, 4096, 16384, [2048], [4096]),
    ("longdoc_cont_4096_over_4096", 1, 4096, 16384, [4096], [4096]),
    ("longdoc_cont_4096_over_8192", 1, 4096, 16384, [8192], [4096]),
    ("longdoc_cont_4096_over_8192_ragged", 1, 4096, 16384, [8192], [1700]),
    ("chat_fresh_1x128", 1, 128, 0, [0], [100]),
    ("chat_fresh_2x256", 2, 256, 0, [0, 0], [256, 150]),
    ("chat_fresh_2x512", 2, 512, 0, [0, 0], [512, 300]),
    ("chat_fresh_2x1024", 2, 1024, 0, [0, 0], [1024, 600]),
    ("chat_fresh_1x1024", 1, 1024, 0, [0], [1024]),
    ("chat_cont_2x256_over_1024", 2, 256, 4096, [1024, 512], [256, 100]),
    ("chat_cont_1x1024_over_1024", 1, 1024, 4096, [1024], [1024]),
]
# the dense cells' (1, 2 and 5): 4096-row regions, prompts of ~2.5 k rows in
# the 4096 bucket or chunked at 2048, and the chat buckets
PREFILL_GQA_CASES = [
    ("longprompt_fresh_4096_2560_live", 1, 4096, 0, [0], [2560]),
    ("longprompt_fresh_2048", 1, 2048, 0, [0], [2048]),
    ("longprompt_cont_2048_over_2048", 1, 2048, 4096, [2048], [2048]),
    ("longprompt_cont_2048_over_2048_512_live", 1, 2048, 4096, [2048],
     [512]),
    ("chat_fresh_2x256", 2, 256, 0, [0, 0], [256, 150]),
    ("chat_fresh_2x512", 2, 512, 0, [0, 0], [512, 300]),
    ("chat_fresh_1x1024", 1, 1024, 0, [0], [1024]),
    ("chat_cont_2x256_over_1024", 2, 256, 4096, [1024, 512], [256, 100]),
]
PREFILL_TOY = [
    ("toy_fresh", 2, 32, 0, [0, 0], [32, 19]),
    ("toy_cont", 2, 32, 64, [24, 0], [32, 9]),
]


def prefill_main(args, dev) -> int:
    """``--kind prefill``: the module doc."""
    cases, nh, hd, hd_v, dtype, block = (PREFILL_CASES, NH, 192, 128,
                                         jnp.bfloat16, 256)
    kvh = args.kv_heads       # 0: K and V per head, the latent form
    if kvh:
        cases, nh, hd = PREFILL_GQA_CASES, args.q_heads, 128
    iters, interpret = args.iters, False
    if args.dry_run:
        cases, nh, hd, hd_v, dtype, block = (PREFILL_TOY, 4, 24, 16,
                                             jnp.float32, 8)
        kvh, hd = (2, 16) if kvh else (0, hd)
        iters, interpret = 1, True
    heads = [int(h) for h in args.heads.split(",")]
    for name, K, T, span, q_starts, n_live in cases:
        if args.shapes and not any(s in name for s in args.shapes.split(",")):
            continue
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        q = jax.random.normal(ks[0], (K, T, nh, hd), dtype)
        k = jax.random.normal(ks[1], (K, T, kvh or nh, hd), dtype)
        v = jax.random.normal(ks[2], (K, T, kvh or nh, hd_v), dtype)
        work = () if not span else (
            jax.random.normal(ks[3], (1, kvh or nh, K, span, hd), dtype),
            jax.random.normal(ks[4], (1, kvh or nh, K, span, hd_v), dtype))
        qs = jnp.asarray(q_starts, jnp.int32)
        sl = qs + jnp.asarray(n_live, jnp.int32)
        live = np.arange(T)[None, :] < np.asarray(n_live)[:, None]
        _, scored = prefill_attention_pairs(T, q_starts, np.asarray(sl),
                                            span, block=block)
        steps = int(prefill_steps(
            jnp.asarray(n_live), jnp.minimum(qs, span), T, span,
            block)[0][4])

        def loops(q, k, v, qs, sl, *work):
            ctx = PriorContext(*work, jnp.int32(0), jnp.arange(
                K, dtype=jnp.int32)) if work else None
            if not work and not kvh:   # the latent parent's fresh
                # programs: V at K's width
                v = jnp.pad(v, ((0, 0),) * 3 + ((0, hd - hd_v),))
            return prefill_attention(q, k, v, qs, sl, ctx, block=block,
                                     ctx_span=span)[..., :hd_v]

        def fused(h):
            def call(q, k, v, qs, sl, *work):
                ctx = PriorContext(*work, jnp.int32(0), jnp.arange(
                    K, dtype=jnp.int32)) if work else None
                return fused_prefill_attention(
                    q, k, v, qs, sl, ctx, block=block, ctx_span=span,
                    interpret=interpret, heads=h, key_rows=bool(kvh))
            return call

        def run(call, n):
            # the next call's lengths hang on this one's result (by a
            # zero): nothing of the loop is the same at every trip
            @jax.jit
            def f(q, k, v, qs, sl, *work):
                def body(_, c):
                    qs, _ = c
                    o = call(q, k, v, qs, sl, *work)
                    return qs + (o[0, 0, 0, 0] > 1e30).astype(jnp.int32), o
                return jax.lax.fori_loop(
                    0, n, body, (qs, jnp.zeros((K, T, nh, hd_v), dtype)))[1]
            operands = (q, k, v, qs, sl, *work)
            jax.block_until_ready(f(*operands))            # compiles
            t0 = time.perf_counter()
            out = jax.block_until_ready(f(*operands))
            return (time.perf_counter() - t0) / n, np.asarray(
                out, np.float32)

        want = None
        for label, call in [("xla", loops)] + [
                (f"kernel_h{h}", fused(h)) for h in heads]:
            sec, out = run(call, iters)
            if want is None:
                want = out
            flop = scored * nh * 2 * (hd + hd_v)
            print(json.dumps({
                "device": dev.device_kind, "case": name, "form": label,
                "q_heads": nh, "kv_heads": kvh or nh,
                "K": K, "T": T, "span": span, "q_starts": q_starts,
                "live": n_live, "steps": steps,
                "ms": round(sec * 1e3, 4),
                "us_per_step": round(sec * 1e6 / max(steps, 1), 2),
                "mxu_share": round(flop / sec / 197e12, 4),
                "max_abs_diff_vs_xla": float(
                    np.abs(out - want)[live].max(initial=0.0)),
                "dead_blocks_zero": not out[
                    ~(np.repeat(live[:, ::min(block, T)], min(block, T),
                                axis=1))].any(),
            }), flush=True)
    return 0


def _unit(op: str) -> str:
    """The unit of a bundle that an LLO operation occupies."""
    if op.startswith(("vmatmul", "vmatpush", "vpop.f32.mrf")):
        return "mxu_" + op.split(".")[0]
    if "xlane" in op or op.startswith(("vrot", "vxpose", "vperm")):
        return "xlu"
    if op.startswith(("vpow2", "vrcp", "vpop.eup")):
        return "eup"
    if op.startswith(("vld", "vst")):
        return op[:3]
    return "valu" if op.startswith("v") else "scalar"


def schedule_main(args) -> int:
    """``--schedule``: the module doc. The compile runs in a child: the
    dumper ends the process (a report template the wheel does not ship)
    AFTER the bundles are on disk."""
    import collections
    import glob
    import re
    import subprocess
    import tempfile

    heads = int(args.heads.split(",")[0])
    # the latent layer's 4096-row chunk over a 16384-row workspace, or
    # (--kv-heads) the dense GQA layer's over the engine's 4096-row region
    nh, kvh, hd, span, rows = (
        (args.q_heads, args.kv_heads, 128, 4096, True) if args.kv_heads
        else (NH, NH, 192, 16384, False))
    with tempfile.TemporaryDirectory() as out:
        child = (
            "import jax, jax.numpy as jnp\n"
            "from jax.experimental import topologies\n"
            "from jax.sharding import SingleDeviceSharding\n"
            "from dynamo_tpu.ops.attention import (PriorContext,\n"
            "    fused_prefill_attention)\n"
            "dev = SingleDeviceSharding(topologies.get_topology_desc(\n"
            "    platform='tpu', topology_name='v5e:2x2').devices[0])\n"
            "S = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(\n"
            "    s, dt, sharding=dev)\n"
            "def f(q, k, v, qs, sl, pk, pv):\n"
            "    ctx = PriorContext(pk, pv, jnp.int32(0),\n"
            "                       jnp.zeros(1, jnp.int32))\n"
            "    return fused_prefill_attention(\n"
            f"        q, k, v, qs, sl, ctx, ctx_span={span},\n"
            f"        interpret=False, heads={heads}, key_rows={rows})\n"
            f"i = S(1, dt=jnp.int32)\n"
            f"jax.jit(f).lower(S(1, 4096, {nh}, {hd}),\n"
            f"    S(1, 4096, {kvh}, {hd}), S(1, 4096, {kvh}, 128), i, i,\n"
            f"    S(1, {kvh}, 1, {span}, {hd}), S(1, {kvh}, 1, {span}, 128)"
            ").compile()\n")
        ran = subprocess.run(
            [sys.executable, "-c", child], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                     LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={out} "
                                      "--xla_jf_dump_llo_text=true"))
        found = glob.glob(os.path.join(
            out, "*flash_prefill_attention*-final_bundles.txt"))
        if not found:
            print("the compiler left no bundles (one process at a time "
                  "holds libtpu); the child's last words:\n"
                  + ran.stderr[-1500:], file=sys.stderr)
            return 1
        with open(found[0]) as f:
            text = f.read()
    stretch, stretches = [], []
    for line in text.splitlines():
        m = re.match(r"\s*0x[0-9a-f]+\s+(?:[A-Z]+)?\s*:?\s*>?\s*\{(.*)\}", line)
        if not m:
            continue
        if "PF:" in line[:24] or "sbr.rel" in line:   # a branch ends it
            stretches.append(stretch)
            stretch = []
            continue
        stretch.append([
            (re.search(r"=\s*([a-z_0-9.]+)", op) or re.match(
                r"\s*%?\w*\s*([a-z_0-9.]+)", op)).group(1)
            for op in m.group(1).split(";;") if op.strip()])
    for bundles in stretches + [stretch]:
        if len(bundles) >= 100:
            print(json.dumps({
                "heads": heads, "bundles": len(bundles),
                "bundles_per_head": round(len(bundles) / heads, 1),
                **collections.Counter(
                    _unit(op) for ops in bundles for op in ops)}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kind", choices=("latent", "dense", "prefill"),
                    default="latent")
    ap.add_argument("--old-tree", default="",
                    help="dense: a checkout whose flash kernel to time "
                         "and compare beside this tree's")
    ap.add_argument("--shapes", default="",
                    help="dense, prefill: the shapes to run (all by "
                         "default; prefill: substrings of the cases' names)")
    ap.add_argument("--heads", default="16",
                    help="prefill: query heads a grid step of the fused "
                         "kernel, one run each")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="prefill: the dense GQA layer's cases, so many "
                         "K/V heads of 128 (0: the expanded latent "
                         "layer's, K and V per head)")
    ap.add_argument("--q-heads", type=int, default=NH,
                    help="prefill, with --kv-heads: the query heads")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--chunks", default="256,512,1024")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--schedule", action="store_true",
                    help="prefill: the kernel's compiled bundles by unit, "
                         "here, without the chip")
    args = ap.parse_args(argv)
    if args.schedule:
        return schedule_main(args)
    chunks = [int(c) for c in args.chunks.split(",")]
    dev = jax.devices()[0]
    if not args.dry_run and dev.platform != "tpu":
        print(f"no chip here ({dev.platform}); --dry-run rehearses",
              file=sys.stderr)
        return 2
    if args.kind == "dense":
        return dense_main(args, dev)
    if args.kind == "prefill":
        return prefill_main(args, dev)
    shapes = {"longdoc": (7, 16, 16384), "chat-decode": (5, 64, 4096)}
    dtype, kernel, iters = jnp.bfloat16, PALLAS, args.iters
    if args.dry_run:
        shapes = {"longdoc": (2, 4, 2048), "chat-decode": (2, 6, 1024)}
        dtype, kernel, iters = jnp.float32, PALLAS_INTERPRET, 1

    for name, (L, B, S) in shapes.items():
        rng = np.random.default_rng(42)
        key = jax.random.PRNGKey(1)
        ctx = jax.random.normal(key, (L, 1, B + 1, S, ROW), dtype)
        ring = jax.random.normal(key, (L, 1, B, R, ROW), dtype)
        q = (jax.random.normal(key, (B, NH, ROW), jnp.float32)
             * 0.05).astype(dtype)
        for case, (below, live) in cases(name, B, S, rng).items():
            below = np.minimum(below, S - 1)
            base = jnp.asarray(below, jnp.int32)
            lens = base + 1
            live_j = jnp.asarray(live)
            own = int(below[live].sum())

            def run(attn, live_arg, n):
                @jax.jit
                def f(q, ctx, ring):
                    def body(_, q):
                        for l in range(L):
                            o = latent_decode_attention(
                                attn, q, ctx, ring, jnp.int32(l), lens,
                                base, V, live_arg)
                            q = q.at[..., :V].add(o * 1e-3)
                        return q
                    return jax.lax.fori_loop(0, n, body, q)
                jax.block_until_ready(f(q, ctx, ring))      # compiles
                t0 = time.perf_counter()
                out = jax.block_until_ready(f(q, ctx, ring))
                return (time.perf_counter() - t0) / (n * L), out

            want = None
            impls = [("xla_parent", DecodeAttention(REFERENCE_IMPL,
                                                    chunk=256), None),
                     ("xla_live", DecodeAttention(REFERENCE_IMPL), live_j)]
            impls += [(f"kernel_{c}", DecodeAttention(kernel, chunk=c),
                       live_j) for c in chunks]
            for label, attn, live_arg in impls:
                sec, _ = run(attn, live_arg, iters)
                # one pass for the comparison: the timed loop feeds its
                # own output back
                _, out = run(attn, live_arg, 1)
                out = np.asarray(out, np.float32)[np.asarray(live)]
                if label == "xla_live":
                    want = out
                cb = chunk_rows(S, attn.chunk)
                trips = region_trips(
                    below, live if live_arg is not None else True, cb)
                read = region_rows_read(attn.impl, trips, cb)
                print(json.dumps({
                    "device": dev.device_kind, "cell": name, "case": case,
                    "impl": label, "B": B, "S": S, "layers": L,
                    "live_lanes": int(live.sum()), "rows_own": own,
                    "rows_read": read,
                    "us_per_layer": round(sec * 1e6, 2),
                    "us_per_chunk": (round(sec * 1e6 / max(
                        int(trips.sum()), 1), 3)
                        if attn.impl != REFERENCE_IMPL else None),
                    "gbps_read": round(read * ROW * 2 / sec / 1e9, 1),
                    "max_abs_diff_vs_xla_live": (
                        None if want is None
                        else float(np.abs(out - want).max())),
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
