#!/usr/bin/env python
"""Time the two row-wise halves of ONE dense decoder layer (``_layer_qkv``:
norm, Q/K/V, RoPE; ``_layer_out``: output projection, residual, norm,
SwiGLU) on the chip, straight-line over all ``T`` bucket rows as the parent
ran them against ``llama._live_rows`` over the row blocks that hold a live
row, at Mistral-7B-v0.3 widths with int8 weights and at one chip's share
of Mistral-Nemo-12B under tp = 4 (8 of 32 heads, 2 of 8 kv heads, 3584 of
14336 ffn columns, bf16; the two all-reduces of a layer are NOT here: one
chip). ``T`` in {1024, 2048, 4096} x live rows in {T/4, T/2, T-1, T} x
block height ``R`` in {256, 512}: does the time follow the live rows, what
does the loop cost at full length, and which ``R``?  The attention between
the halves is left out (it already stops at the live length). Before
them, per widths, the WHOLE solo prefill program of a 4-layer cut, looped
against straight-line: do logits and region rows agree on this device? One
JSON line per (widths, T, live, form). The hybrid block's halves and scans
(models/ssm_moe.py) have a tool of their own beside this one:
tools/hybrid_rows_bench.py.

  python tools/prefill_rows_bench.py            # on the chip (chiprun)
  python tools/prefill_rows_bench.py --dry-run  # toy widths, here
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import ModelConfig  # noqa: E402
from dynamo_tpu.ops.rope import rope_cos_sin, rope_inv_freq  # noqa: E402

LAYERS = 2  # a stack, so that the loop's layer index selects something


def widths(dry_run: bool) -> dict[str, ModelConfig]:
    if dry_run:
        tiny = ModelConfig.tiny(num_layers=LAYERS, dtype="float32")
        return {"tiny": tiny,
                "tiny-w8": dataclasses.replace(tiny, quant="int8")}
    out = {}
    for name, cut in (("mistral7b-w8", {}),
                      ("nemo12b-tp4", {"num_heads": 8, "num_kv_heads": 2,
                                       "intermediate_size": 3584})):
        path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                            "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        c = ModelConfig.from_hf_dict(cfg)
        # the vocabulary is no part of a layer's halves
        out[name] = dataclasses.replace(
            c, quant=cfg.get("quant"), num_layers=LAYERS, vocab_size=256,
            **cut)
    return out


def program_check(name: str, c: ModelConfig, T: int, block: int, dev) -> dict:
    """The whole solo prefill program (all of ``c``'s layers, attention
    and region write included) on a prompt that ends inside a row block
    of the ``T`` bucket, traced once with the halves looped over blocks
    ``block`` high and once straight-line (the height set past the bucket
    for that trace): do the last token's logits and the region's live
    rows agree on this device?"""
    live = T // 2 + block // 8
    kept = llama.LIVE_ROW_BLOCK
    params = llama.init_params(c, 0)
    toks = np.zeros(T, np.int32)
    toks[:live] = np.random.default_rng(live).integers(1, c.vocab_size, live)
    out = {}
    for form, height in (("looped", block), ("straight", 1 << 30)):
        llama.LIVE_ROW_BLOCK = height
        try:
            ctx = llama.init_ctx(c, 1, T, jnp.dtype(c.dtype))
            ctx, logits = jax.jit(
                lambda *a: llama.prefill_impl(*a, fresh=True),
                static_argnums=(0,))(
                c, params, ctx, jnp.asarray(toks), jnp.int32(0),
                jnp.int32(0), jnp.int32(live))
        finally:
            llama.LIVE_ROW_BLOCK = kept
        out[form] = (np.asarray(logits, np.float32),
                     np.asarray(ctx["k"][:, :, 0, :live], np.float32),
                     np.asarray(ctx["v"][:, :, 0, :live], np.float32))
    (la, ka, va), (lb, kb, vb) = out["looped"], out["straight"]
    return {"device": dev.device_kind, "widths": name, "T": T,
            "form": "whole_program", "live": live, "layers": c.num_layers,
            "greedy_equal": bool(la.argmax() == lb.argmax()),
            "logits_max_abs_diff": float(np.abs(la - lb).max()),
            "logits_max_abs": float(np.abs(lb).max()),
            "region_k_max_abs_diff": float(np.abs(ka - kb).max()),
            "region_v_max_abs_diff": float(np.abs(va - vb).max()),
            "region_max_abs": float(max(np.abs(kb).max(), np.abs(vb).max()))}


def timed(f, args, iters: int) -> float:
    jax.block_until_ready(f(*args))                  # compiles
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--blocks", default="256,512")
    ap.add_argument("--widths", default="1024,2048,4096")
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args(argv)
    blocks = [int(x) for x in args.blocks.split(",")]
    Ts = [int(x) for x in args.widths.split(",")]
    iters = args.iters
    dev = jax.devices()[0]
    if args.dry_run:
        blocks, Ts, iters = [8, 16], [32, 64], 1
    elif dev.platform != "tpu":
        print(f"no chip here ({dev.platform}); --dry-run rehearses",
              file=sys.stderr)
        return 2

    for name, c in widths(args.dry_run).items():
        print(json.dumps(program_check(
            name, dataclasses.replace(c, num_layers=4), Ts[-1],
            blocks[0] if args.dry_run else llama.LIVE_ROW_BLOCK, dev)),
            flush=True)
        layers = llama.init_params(c, 0)["layers"]
        dtype = jnp.dtype(c.dtype)
        inv_freq = jnp.asarray(
            rope_inv_freq(c.head_dim, c.rope_theta, c.rope_scaling_dict))
        for T in Ts:
            h = jax.random.normal(jax.random.PRNGKey(T), (1, T, c.hidden_size),
                                  jnp.float32).astype(dtype)
            cos, sin = rope_cos_sin(jnp.arange(T, dtype=jnp.int32), inv_freq)
            cos, sin = cos[None], sin[None]

            @jax.jit
            def straight(layers, h, cos, sin):
                lp = jax.tree.map(lambda x: x[1], layers)
                q, k, v = llama._layer_qkv(c, lp, h[0], cos[0], sin[0])
                return llama._layer_out(c, lp, h[0], q)[None], k[None], v[None]

            def looped(R):
                @jax.jit
                def f(layers, h, cos, sin, trips):
                    q, k, v = llama._live_rows(
                        llama._layer_qkv, c, layers, jnp.int32(1), None,
                        trips, (h, cos, sin), R)
                    out, = llama._live_rows(
                        llama._layer_out, c, layers, jnp.int32(1), None,
                        trips, (h, q), R)
                    return out, k, v
                return f

            base = timed(straight, (layers, h, cos, sin), iters)
            want = [np.asarray(x, np.float32)
                    for x in straight(layers, h, cos, sin)]
            line = {"device": dev.device_kind, "widths": name, "T": T}
            print(json.dumps({**line, "form": "straight", "rows_run": T,
                              "ms": round(base * 1e3, 4)}), flush=True)
            for R in blocks:
                if T % R:
                    continue
                f = looped(R)
                for live in sorted({T // 4, T // 2, T - 1, T}):
                    trips = jnp.asarray(llama.live_row_trips(
                        np.zeros(1, np.int64), np.full(1, live), T, R),
                        jnp.int32)
                    sec = timed(f, (layers, h, cos, sin, trips), iters)
                    got = f(layers, h, cos, sin, trips)
                    diff = max(float(np.abs(
                        np.asarray(g, np.float32)[:, :live]
                        - w[:, :live]).max()) for g, w in zip(got, want))
                    rows = int(trips.sum()) * R
                    print(json.dumps({
                        **line, "form": f"looped_R{R}", "live": live,
                        "rows_run": rows, "ms": round(sec * 1e3, 4),
                        "vs_straight": round(sec / base, 4),
                        "ms_per_block": round(sec * 1e3 * R / rows, 4),
                        "max_abs_diff_live_rows": diff,
                    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
