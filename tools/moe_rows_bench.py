#!/usr/bin/env python
"""Time ONE routed-expert layer (``models/moe.py: grouped_experts``: sort,
gather into sorted order, three grouped products, un-sort and combine) on
the chip with its two row movements straight-line over all T x K sorted
rows, as the parent ran them, against the looped form over the row blocks
that hold a live row, at the three routed-expert cells' prefill shapes:
the state-space hybrid's share of an expert-parallel layer (36 of 72
experts held, 10 picks, 4096 x 768), the chat cell's (256 experts, 8
picks, 2048 x 768) and the long-document cell's (4 picks, 3584 x 1024).
Tokens x share of the bucket that holds a prompt token x block height:
does the time follow the live rows, what does the loop cost when every
row is live, from how many rows does it pay, with and without a share,
and is the result the straight-line form's bit for bit on this device?
The two movements are also timed alone (there XLA fuses neither with a
neighbour, as it does in a whole program: PERF.md section 6, PR 46). The
rule, ``moe.move_block``, is replaced for each trace. One JSON line per
(shape, tokens, live share, form).

  python tools/moe_rows_bench.py            # on the chip (chiprun)
  python tools/moe_rows_bench.py --shapes granite4h-ep2 --heights 128,256
  python tools/moe_rows_bench.py --dry-run  # toy widths, here
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

from dynamo_tpu.models import moe  # noqa: E402

# name: (hidden, expert width, experts held, experts routed over, first
# held | None, picks, tokens of a program)
SHAPES = {
    "granite4h-ep2": (4096, 768, 36, 72, 0, 10,
                      (256, 512, 1024, 2048, 4096)),
    "joyai": (2048, 768, 256, 256, None, 8, (512, 1024, 2048)),
    "xing4": (3584, 1024, 64, 64, None, 4, (2048, 4096)),
}
TOY = {"toy-share": (64, 32, 6, 12, 0, 4, (64, 128)),
       "toy-whole": (64, 32, 8, 8, None, 2, (64,))}
LIVE = (0.25, 0.5, 0.77, 1.0)


def timed(fn, args, reps: int) -> float:
    """Median wall ms of ``fn(*args)`` after one warm-up call."""
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(out))


def forms(heights):
    """(label, the block height ``moe.move_block`` gives) to trace
    under."""
    return [("straight", 0)] + [(f"looped{R}", R) for R in heights]


def layer(first):
    return jax.jit(lambda x, sel, w, wg, wu, wd, valid: moe.grouped_experts(
        x, sel, w, wg, wu, wd, valid, first=first))


def sort_ids(K: int, E: int, first):
    """``grouped_experts``'s group ids, sorted order and its inverse."""
    @jax.jit
    def ids(sel, valid):
        flat = sel.reshape(-1).astype(jnp.int32)
        if first is not None:
            flat = flat - first
            flat = jnp.where((flat >= 0) & (flat < E), flat, E)
        flat = jnp.where(jnp.repeat(valid, K), flat, E)
        order = jnp.argsort(flat, stable=True)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        return flat, order, back
    return ids


def movements(K: int, E: int, held: bool, R: int):
    """The gather and the combine ALONE, as ``grouped_experts`` stages
    them (R 0: straight-line), the sort done."""
    def gather(x, flat, order):
        if R:
            return moe._gather_live(x, order, K, jnp.sum(flat < E), R)
        return x[order // K]

    def combine(y, back, w, flat, valid):
        if R:
            return moe._combine_live(y, back, w, flat, valid, E, held, R)
        return moe._combine(y[back], w, flat, valid, E, held)

    return jax.jit(gather), jax.jit(combine)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--shapes", default="", help="comma-separated subset")
    ap.add_argument("--heights", default="256,512,1024")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if not args.dry_run and dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): --dry-run rehearses",
              file=sys.stderr)
        return 2
    shapes = TOY if args.dry_run else {
        n: v for n, v in SHAPES.items()
        if not args.shapes or n in args.shapes.split(",")}
    heights = (8, 16) if args.dry_run else tuple(
        int(h) for h in args.heights.split(","))
    dtype = jnp.float32 if args.dry_run else jnp.bfloat16
    rule = moe.move_block
    rng = np.random.default_rng(46)
    try:
        for name, (H, I, E, E_all, first, K, tokens) in shapes.items():
            ws = [jnp.asarray(rng.standard_normal(s) / np.sqrt(s[1]), dtype)
                  for s in ((E, H, I), (E, H, I), (E, I, H))]
            for T in tokens:
                x = jnp.asarray(rng.standard_normal((T, H)), dtype)
                sel = jnp.asarray(np.argsort(
                    rng.random((T, E_all)), axis=1)[:, :K], jnp.int32)
                w = jnp.asarray(rng.random((T, K)), jnp.float32)
                y = jnp.asarray(rng.standard_normal((T * K, H)), dtype)
                for share in LIVE:
                    valid = jnp.arange(T) < int(T * share)
                    flat, order, back = sort_ids(K, E, first)(sel, valid)
                    ref = None
                    for label, R in forms(heights):
                        if R and T % R:
                            continue
                        moe.move_block = lambda *a, R=R: R
                        run = layer(first)
                        out, load = run(x, sel, w, *ws, valid)
                        ref = out if ref is None else ref
                        g, c = movements(K, E, first is not None, R)
                        print(json.dumps({
                            "shape": name, "tokens": T, "picks": K,
                            "rows": T * K, "live_share": share,
                            "held_rows": int(load.sum()), "form": label,
                            "layer_ms": round(timed(
                                run, (x, sel, w, *ws, valid), args.reps), 3),
                            "gather_ms": round(timed(
                                g, (x, flat, order), args.reps), 3),
                            "combine_ms": round(timed(
                                c, (y, back, w, flat, valid), args.reps), 3),
                            "equal_to_straight": bool(
                                jnp.array_equal(out, ref)),
                            "device": dev.device_kind}), flush=True)
    finally:
        moe.move_block = rule
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
