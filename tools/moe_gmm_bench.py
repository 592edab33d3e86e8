#!/usr/bin/env python
"""Time the grouped expert product on the chip at the served shapes, both
ways: XLA's own ``jax.lax.ragged_dot`` and the megablox Pallas kernel at
whole-matrix tiles as the program calls it (``models/moe.py: _gmm_tpu``).
Does the time follow the experts TOUCHED (weights read) or all of them,
and at what share of the HBM peak?  One JSON line per case.

  python tools/moe_gmm_bench.py            # on the chip (chiprun)
"""
from __future__ import annotations

import json
import sys
import time

import os

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.models.moe import _gmm_tpu  # noqa: E402


def bench(f, *args, n=20):
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> int:
    dev = jax.devices()[0]
    H, I, E = 2048, 768, 256
    key = jax.random.PRNGKey(0)
    wg = jax.random.normal(key, (E, H, I), jnp.bfloat16)
    wd = jax.random.normal(key, (E, I, H), jnp.bfloat16)

    ragged = jax.jit(jax.lax.ragged_dot)
    kernel = jax.jit(_gmm_tpu)

    for M, touched in ((512, 256), (512, 222), (512, 64), (512, 8),
                       (4096, 256), (16384, 256)):
        rng = np.random.default_rng(M + touched)
        experts = rng.choice(E, touched, replace=False)
        counts = np.zeros(E, np.int64)
        counts[experts] = 1
        extra = rng.choice(experts, M - touched)
        np.add.at(counts, extra, 1)
        gs = jnp.asarray(counts, jnp.int32)
        x = jax.random.normal(key, (M, H), jnp.bfloat16)
        y = jax.random.normal(key, (M, I), jnp.bfloat16)
        nbytes = touched * H * I * 2
        for name, f in (("ragged_dot", ragged), ("gmm", kernel)):
            t_up = bench(f, x, wg, gs)
            t_dn = bench(f, y, wd, gs)
            print(json.dumps({
                "platform": dev.platform, "kind": dev.device_kind,
                "product": name, "rows": M, "touched": touched,
                "up_ms": t_up * 1e3, "down_ms": t_dn * 1e3,
                "up_GBps_touched": nbytes / t_up / 1e9,
                "down_GBps_touched": nbytes / t_dn / 1e9,
                "up_TFLOPs": 2 * M * H * I / t_up / 1e12,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
