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

  python tools/latent_decode_bench.py            # on the chip (chiprun)
  python tools/latent_decode_bench.py --dry-run  # tiny, interpreted, here
  python tools/latent_decode_bench.py --kind dense --old-tree .scratch/parent
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
    ctx_decode_attention,
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kind", choices=("latent", "dense"), default="latent")
    ap.add_argument("--old-tree", default="",
                    help="dense: a checkout whose flash kernel to time "
                         "and compare beside this tree's")
    ap.add_argument("--shapes", default="",
                    help="dense: the shapes to run (all by default)")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--chunks", default="256,512,1024")
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args(argv)
    chunks = [int(c) for c in args.chunks.split(",")]
    dev = jax.devices()[0]
    if not args.dry_run and dev.platform != "tpu":
        print(f"no chip here ({dev.platform}); --dry-run rehearses",
              file=sys.stderr)
        return 2
    if args.kind == "dense":
        return dense_main(args, dev)
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
