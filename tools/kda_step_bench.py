#!/usr/bin/env python
"""Time a recurrent layer's decode-step kernel on the chip at its cell's
shape over work lists of fewer and fewer live lanes, the live ones
scattered, beside the XLA form that steps every lane (a dead one masked
to a step that moves nothing). ``--kind``:

  kda     ``ops/kda.py: step_pallas``, the delta-rule cell: 48 lanes, 32
          heads of 128, a [49, 32, 128, 128] float32 state a layer
  mamba2  ``ops/mamba2.py: scan_step_pallas``, the Mamba-2 + experts cell:
          32 lanes, 128 heads of 64, a [33, 128, 64, 128] float32 state

Does a call's time follow the lanes its list holds, what does a lane past
the list cost, from how many live lanes on does the every-lane XLA form
win, and does the kernel agree with it on the chip? One JSON line a live
count: ``call_us``, the state's bytes the call moved over the HBM's peak
(``roofline``), ``dead_lane_us``, what each lane past the list added
beyond the live share of the all-live call, ``xla_all_lanes_us``, the XLA
form's call whatever is live, ``o_diff`` / ``state_diff``, the largest
distance from it on the live lanes, and ``others_untouched``: every other
lane's state bit for bit, its output 0.

  python tools/kda_step_bench.py [--kind mamba2]            # chiprun
  python tools/kda_step_bench.py [--kind mamba2] --dry-run  # tiny, here
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

from dynamo_tpu.ops import kda, mamba2  # noqa: E402

HBM_BYTES_PER_S = 819e9   # one TPU v5e chip (Google Cloud, "TPU v5e")


def _pad(a):
    """The scratch lane rides along as one more row (the model's ``pad``)."""
    return jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))


def kda_inputs(B: int, H: int, D: int, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda i, *s: jax.random.normal(ks[i], s)  # noqa: E731
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            for x in (n(0, B, H, D), n(1, B, H, D)))
    g = -5.0 * jax.nn.sigmoid(3 * n(3, B, H, D) - 6)
    return (q / np.sqrt(D), k, n(2, B, H, D), g, jax.nn.sigmoid(n(4, B, H)),
            0.1 * n(5, B + 1, H, D, D))


def kda_every_lane(vectors, state, live):
    q, k, v, g, b = vectors
    on = live[:, None, None]
    return kda.step(_pad(q), _pad(jnp.where(on, k, 0.0)), _pad(v),
                    _pad(jnp.where(on, g, 0.0)),
                    _pad(jnp.where(live[:, None], b, 0.0)), state)


def m2_inputs(B: int, H: int, P: int, N: int, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda i, *s: jax.random.normal(ks[i], s)  # noqa: E731
    return (n(0, B, H, P), 0.3 * jax.nn.softplus(n(1, B, H)),
            -jnp.exp(0.5 * n(2, H)), n(3, B, N), n(4, B, N),
            0.1 * n(5, B + 1, H, P, N))


def m2_every_lane(vectors, state, live):
    x, dt, A, Bm, Cm = vectors
    return mamba2.scan_step(_pad(x), _pad(jnp.where(live[:, None], dt, 0.0)),
                            A, _pad(Bm), _pad(Cm), state)


# kind -> (inputs, the kernel, the XLA form over every lane, the cell's
# shape, a toy shape, the live counts to time)
KINDS = {
    "kda": (kda_inputs, kda.step_pallas, kda_every_lane, (48, 32, 128),
            (6, 4, 16), "48,40,35,29,20,10,1,0"),
    "mamba2": (m2_inputs, mamba2.scan_step_pallas, m2_every_lane,
               (32, 128, 64, 128), (6, 4, 8, 16), "32,28,24,16,10,4,1,0"),
}


def timed(step, state, reps: int, iters: int):
    """Seconds a call of ``step`` (state -> (o, state)): ``reps`` calls in
    one program (the state carried in place), the best of ``iters``
    programs."""
    o = jax.eval_shape(step, state)[0]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def many(state):
        def one(_, c):
            o, s = step(c[1])
            return c[0] + o, s
        return jax.lax.fori_loop(
            0, reps, one, (jnp.zeros(o.shape, jnp.float32), state))

    acc, state = many(state)
    jax.block_until_ready(state)
    best = float("inf")
    for _ in range(iters):
        t = time.perf_counter()
        acc, state = many(state)
        jax.block_until_ready((acc, state))
        best = min(best, time.perf_counter() - t)
    return best / reps, state


def agreement(kernel, every_lane, vectors, state, live, work) -> dict:
    B = live.shape[0]
    o_ref, S_ref = every_lane(vectors, state, jnp.asarray(live))
    o, S = kernel(*vectors, state, *work)
    S, S_ref, o, o_ref, S0 = (np.asarray(x) for x in (S, S_ref, o, o_ref,
                                                      state))
    return {
        "o_diff": float(np.abs(o[live] - o_ref[:B][live]).max(initial=0.0)),
        "state_diff": float(
            np.abs(S[:B][live] - S_ref[:B][live]).max(initial=0.0)),
        "others_untouched": bool(
            np.array_equal(S[:B][~live], S0[:B][~live])
            and np.array_equal(S[B:], S0[B:])
            and np.array_equal(S_ref[:B][~live], S0[:B][~live])
            and not o[~live].any()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kind", choices=sorted(KINDS), default="kda")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--live", default="",
                    help="live counts to time (the kind's own by default)")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    inputs, kernel, every_lane, shape, toy, counts = KINDS[args.kind]
    reps, iters = args.reps, args.iters
    if args.dry_run:
        shape, reps, iters = toy, 2, 1
        kernel = functools.partial(kernel, interpret=True)
    elif dev.platform != "tpu":
        print(f"no chip here ({dev.platform}); --dry-run rehearses",
              file=sys.stderr)
        return 2
    B = shape[0]
    *vectors, state = inputs(*shape)
    lane_bytes = 2 * 4 * int(np.prod(state.shape[1:]))   # read + written
    order = np.random.RandomState(0).permutation(B)
    half = jnp.asarray(np.isin(np.arange(B), order[:B // 2]))
    xla_s, state = timed(lambda s: every_lane(vectors, s, half), state, reps,
                         iters)
    full = None
    for n_live in sorted({B, *(min(int(x), B) for x in
                               (args.live or counts).split(","))},
                         reverse=True):
        live = np.zeros(B, bool)
        live[order[:n_live]] = True
        work = kda.work_list(jnp.asarray(live))
        agree = agreement(kernel, every_lane, vectors, state, live, work)
        s, state = timed(lambda s: kernel(*vectors, s, *work), state, reps,
                         iters)
        full = s if full is None else full
        dead = B - n_live
        print(json.dumps({
            "device": dev.device_kind, "kind": args.kind, "lanes": B,
            "live": n_live, "call_us": round(s * 1e6, 2),
            "roofline": round(n_live * lane_bytes / HBM_BYTES_PER_S / s, 4),
            "dead_lane_us": round(
                (s - full * n_live / B) / dead * 1e6, 4) if dead else None,
            "xla_all_lanes_us": round(xla_s * 1e6, 2),
            **agree}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
