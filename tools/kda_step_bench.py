#!/usr/bin/env python
"""Time the delta-rule step kernel (``ops/kda.py: step_pallas``) on the
chip at the delta-rule cell's shape (48 lanes, 32 heads of 128, a [49, 32,
128, 128] float32 state a layer) over work lists of fewer and fewer live
lanes, the live ones scattered. Does a call's time follow the lanes its
list holds, what does a lane past the list cost, and does the kernel agree
with the XLA step on the chip? One JSON line a live count: ``call_us``,
the state's bytes the call moved over the HBM's peak (``roofline``),
``dead_lane_us``, what each lane past the list added beyond the live
share of the all-live call, ``o_diff`` / ``state_diff``, the largest
distance from ``kda.step`` on the live lanes, and ``others_untouched``:
every other lane's state bit for bit, its ``o`` 0.

  python tools/kda_step_bench.py            # on the chip (chiprun)
  python tools/kda_step_bench.py --dry-run  # tiny, interpreted, here
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.ops import kda  # noqa: E402

HBM_BYTES_PER_S = 819e9   # one TPU v5e chip (Google Cloud, "TPU v5e")


def inputs(B: int, H: int, D: int, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda i, *s: jax.random.normal(ks[i], s)  # noqa: E731
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            for x in (n(0, B, H, D), n(1, B, H, D)))
    g = -5.0 * jax.nn.sigmoid(3 * n(3, B, H, D) - 6)
    return (q / np.sqrt(D), k, n(2, B, H, D), g, jax.nn.sigmoid(n(4, B, H)),
            0.1 * n(5, B + 1, H, D, D))


def timed(step, vectors, state, work, reps: int, iters: int):
    """Seconds a call: ``reps`` calls in one program (the state carried in
    place), the best of ``iters`` programs."""
    @functools.partial(jax.jit, donate_argnums=(0,))
    def many(state):
        def one(_, c):
            o, s = step(*vectors, c[1], *work)
            return c[0] + o, s
        return jax.lax.fori_loop(
            0, reps, one, (jnp.zeros(vectors[0].shape, jnp.float32), state))

    acc, state = many(state)
    jax.block_until_ready(state)
    best = float("inf")
    for _ in range(iters):
        t = time.perf_counter()
        acc, state = many(state)
        jax.block_until_ready((acc, state))
        best = min(best, time.perf_counter() - t)
    return best / reps, state


def agreement(step, vectors, state, live, work) -> dict:
    q, k, v, g, b = vectors
    B = q.shape[0]
    o_ref, S_ref = kda.step(q, k, v, g, b, state[:B])
    o, S = step(q, k, v, g, b, state, *work)
    on = np.asarray(live)
    S, S_ref, o, o_ref = (np.asarray(x) for x in (S, S_ref, o, o_ref))
    return {
        "o_diff": float(np.abs(o[on] - o_ref[on]).max(initial=0.0)),
        "state_diff": float(np.abs(S[:B][on] - S_ref[on]).max(initial=0.0)),
        "others_untouched": bool(
            np.array_equal(S[:B][~on], np.asarray(state)[:B][~on])
            and np.array_equal(S[B:], np.asarray(state)[B:])
            and not o[~on].any()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--live", default="48,40,35,29,20,10,1,0")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    B, H, D, reps, iters = 48, 32, 128, args.reps, args.iters
    step = kda.step_pallas
    if args.dry_run:
        B, H, D, reps, iters = 6, 4, 16, 2, 1
        step = functools.partial(kda.step_pallas, interpret=True)
    elif dev.platform != "tpu":
        print(f"no chip here ({dev.platform}); --dry-run rehearses",
              file=sys.stderr)
        return 2
    *vectors, state = inputs(B, H, D)
    order = np.random.RandomState(0).permutation(B)
    full = None
    for n_live in sorted({B, *(min(int(x), B) for x in args.live.split(","))},
                         reverse=True):
        live = np.zeros(B, bool)
        live[order[:n_live]] = True
        work = kda.work_list(jnp.asarray(live))
        agree = agreement(step, vectors, state, live, work)
        s, state = timed(step, vectors, state, work, reps, iters)
        full = s if full is None else full
        dead = B - n_live
        print(json.dumps({
            "device": dev.device_kind, "lanes": B, "live": n_live,
            "call_us": round(s * 1e6, 2),
            "roofline": round(
                2 * n_live * H * D * D * 4 / HBM_BYTES_PER_S / s, 4),
            "dead_lane_us": round(
                (s - full * n_live / B) / dead * 1e6, 4) if dead else None,
            **agree}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
