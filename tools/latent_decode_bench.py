#!/usr/bin/env python
"""Time the latent decode attention on the chip at the two latent cells'
shapes (``B`` 16 x ``S`` 16384 x 7 layers, ``B`` 64 x ``S`` 4096 x 5
layers; 32 heads, rows stored at 640, value 512), on lane lengths like
the cells': the XLA loop as the parent ran it (every lane to the longest
device length, idle lanes' stale lengths included), the XLA loop told
which lanes are live, and the Pallas kernel at each chunk. Does the time
follow the live lanes' own rows, what does a chunk cost, and does the
kernel agree with the loop?  One JSON line per (case, implementation).

  python tools/latent_decode_bench.py            # on the chip (chiprun)
  python tools/latent_decode_bench.py --dry-run  # tiny, interpreted, here
"""
from __future__ import annotations

import argparse
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
)
from dynamo_tpu.ops.latent_decode import (  # noqa: E402
    chunk_rows,
    latent_decode_attention,
    region_rows_read,
    region_trips,
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--chunks", default="256,512,1024")
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args(argv)
    chunks = [int(c) for c in args.chunks.split(",")]
    dev = jax.devices()[0]
    shapes = {"longdoc": (7, 16, 16384), "chat-decode": (5, 64, 4096)}
    dtype, kernel, iters = jnp.bfloat16, PALLAS, args.iters
    if args.dry_run:
        shapes = {"longdoc": (2, 4, 2048), "chat-decode": (2, 6, 1024)}
        dtype, kernel, iters = jnp.float32, PALLAS_INTERPRET, 1
    elif dev.platform != "tpu":
        print(f"no chip here ({dev.platform}); --dry-run rehearses",
              file=sys.stderr)
        return 2

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
