#!/usr/bin/env python
"""Time what ONE layer of each kind of the hybrid block does per row in a
prefill chunk, on the chip, straight-line over all ``T`` bucket rows (as
the parent ran it) against the live-row loops of ``models/ssm_moe.py``
(``_live_half`` over the two row-wise halves ``_mix_in`` / ``_mix_out``,
``_live_scan`` over the recurrent kinds' chunked scans), at the three
hybrid cells' published widths: a Mamba-2 and an attention layer of
granite-4.0-h-small with 36 held experts, a lightning and a sparse layer
of MiniCPM-SALA with its dense MLP, and a dense and a routed delta-rule
layer and a latent layer of Ling-3.0-flash with 64 held experts. ``T`` in
{1024, 2048, 4096} x live rows in {T/4, T/2, 3T/4, T} x block height ``R``
in {256, 512}; halves and scan timed apart (the attention between the
halves, the expert sort and the grouped products already follow the live
rows and are left out), the short convolution alone beside them (it stays
straight-line: what would joining the loop give back?). Does the time
follow the live rows, what does a loop cost at full length, which ``R``,
and does a two-block bucket pay? Each looped line carries the largest
difference from the straight-line form on the live rows (and, where the
half routes, the share of the router's picks that differ: near-ties that
another rounding flips). One JSON line
per (widths, kind, T, part, form, live).

  python tools/hybrid_rows_bench.py            # on the chip (chiprun)
  python tools/hybrid_rows_bench.py --dry-run  # toy widths, here
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

from dynamo_tpu.models import llama, ssm_moe  # noqa: E402
from dynamo_tpu.models.config import ModelConfig  # noqa: E402
from dynamo_tpu.models.live_rows import live_row_trips  # noqa: E402
from dynamo_tpu.ops import mamba2  # noqa: E402

# each cell's configuration cut to one layer of each of its kinds
CUTS = {
    "granite4h-ep2-d10": {"num_hidden_layers": 2,
                          "layer_types": ["mamba", "attention"]},
    "minicpm-sala-d16": {"num_hidden_layers": 2,
                         "mixer_types": ["lightning-attn", "minicpm4"]},
    "ling3-flash-ep8-d12": {"num_hidden_layers": 3, "layer_group_size": 3,
                            "first_k_dense_replace": 1,
                            "expert_swiglu_limit_list": [0, 0, 0],
                            "share_expert_swiglu_limit_list": [0, 0, 0]},
}
RECURRENT = ("mamba", "linear_attention", "kda")


def widths(dry_run: bool) -> dict[str, ModelConfig]:
    if dry_run:
        return {name: getattr(ModelConfig, name)(dtype="float32")
                for name in ("tiny_ssm_moe", "tiny_linear_sparse",
                             "tiny_kda_latent")}
    out = {}
    for name, cut in CUTS.items():
        path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                            "configs", name + ".json")
        with open(path) as f:
            out[name] = ModelConfig.from_hf_dict({**json.load(f), **cut})
    return out


def timed(f, args, iters: int) -> float:
    jax.block_until_ready(f(*args))                  # compiles
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def layer_parts(c: ModelConfig, kind: str, lp, T: int):
    """The layer's per-row parts as (name, straight, looped(R), operands)
    with operands [1, T, ...] made by one straight-line pass:
    ``straight(lp, *operands, real)`` and ``looped(R)(lp, *operands, real,
    trips)`` return the same tuple of [1, T, ...] arrays (the scan its
    state too)."""
    d = ssm_moe.dims(c)
    cdt = jnp.dtype(c.dtype)
    h = jax.random.normal(jax.random.PRNGKey(T), (1, T, c.hidden_size),
                          jnp.float32).astype(cdt)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    everything = jnp.ones((1, T), bool)
    ins = jax.jit(lambda lp, h, pos: tuple(
        a[None] for a in ssm_moe._mix_in(c, kind, lp, h[0], pos[0])))(
        lp, h, pos)
    parts = []
    if kind in RECURRENT:
        if kind == "linear_attention":
            rows, rest = ins[:3], ins[3:]
            S0 = jnp.zeros((1, d["lin_heads"], d["lin_dim"], d["lin_dim"]),
                           jnp.float32)
        else:
            if kind == "kda":
                a, rows, rest = ins[0], ins[1:3], ins[3:]
                S0 = jnp.zeros((1, d["kda_heads"], d["kda_dim"],
                                d["kda_dim"]), jnp.float32)
            else:
                a, rows, rest = ins[1], ins[2:3], ins[:1]
                S0 = jnp.zeros((1, d["nh"], d["P"], d["N"]), jnp.float32)
            win0 = jnp.zeros((lp["conv_w"].shape[0] - 1, a.shape[2]), cdt)

            def conv(lp, a, real):
                bias = lp["conv_b"] if "conv_b" in lp else ssm_moe._no_bias(lp)
                return (mamba2.causal_conv(a[0], win0, lp["conv_w"], bias,
                                           real.sum())[0][None],)

            parts.append(("conv", jax.jit(conv), None, (a,)))
            rows = conv(lp, a, everything) + rows

        def scan(lp, *rows_real):
            (o,), S = jax.vmap(
                lambda S, *blk: ssm_moe._scan_block(c, kind, lp, S, *blk)
            )(S0, *rows_real)
            return o, S

        def scan_looped(R):
            return lambda lp, *a: ssm_moe._live_scan(
                kind, c, lp, a[-1], a[:-1], S0, R)

        parts.append(("scan", jax.jit(scan), scan_looped, rows))
        o = jax.jit(scan)(lp, *rows, everything)[0]
        seq = (o,) + rest if kind != "mamba" else (o, rows[0]) + rest
    elif kind == "latent_attention":
        seq = (ins[0][..., :ins[2].shape[-1]],)      # o: [1, T, heads, v]
    else:
        seq = (ins[0],) + ins[3:]                    # o shaped as q

    def halves(lp, h, pos, *seq_real):
        seq = [s[0] for s in seq_real[:-1]]
        return tuple(a[None] for a in (
            *ssm_moe._mix_in(c, kind, lp, h[0], pos[0]),
            *ssm_moe._mix_out(c, kind, lp, h[0], *seq)))

    def halves_looped(R):
        def f(lp, h, pos, *rest):
            *seq, _, trips = rest
            return (*ssm_moe._live_half(ssm_moe._mix_in, kind, c, lp, trips,
                                        (h, pos), R),
                    *ssm_moe._live_half(ssm_moe._mix_out, kind, c, lp, trips,
                                        (h, *seq), R))
        return f

    parts.append(("halves", jax.jit(halves), halves_looped, (h, pos) + seq))
    return parts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--blocks", default="256,512")
    ap.add_argument("--widths", default="1024,2048,4096")
    ap.add_argument("--configs", default="")
    ap.add_argument("--parts", default="conv,scan,halves")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args(argv)
    blocks = [int(x) for x in args.blocks.split(",")]
    Ts = [int(x) for x in args.widths.split(",")]
    iters = args.iters
    dev = jax.devices()[0]
    if args.dry_run:
        blocks, Ts, iters = [64, 128], [256], 1
        ssm_moe.LIN_CHUNK = 64     # a toy block holds whole chunks
    elif dev.platform != "tpu":
        print(f"no chip here ({dev.platform}); --dry-run rehearses",
              file=sys.stderr)
        return 2

    for name, c in widths(args.dry_run).items():
        if args.configs and name not in args.configs.split(","):
            continue
        layers = llama.serving_params(
            c, llama.init_params(c, 0))["layers"]
        seen = set()
        for kind, lp in zip(ssm_moe.dims(c)["kinds"], layers):
            if (kind, "wr" in lp) in seen:
                continue
            seen.add((kind, "wr" in lp))
            for T in Ts:
                line = {"device": dev.device_kind, "widths": name,
                        "kind": kind, "routes": "wr" in lp, "T": T}
                for part, straight, looped, operands in layer_parts(
                        c, kind, lp, T):
                    if part not in args.parts.split(","):
                        continue
                    everything = jnp.ones((1, T), bool)
                    base = timed(straight, (lp, *operands, everything), iters)
                    print(json.dumps({**line, "part": part,
                                      "form": "straight", "rows_run": T,
                                      "ms": round(base * 1e3, 4)}),
                          flush=True)
                    for R in blocks if looped else ():
                        if T % R:
                            continue
                        f = jax.jit(looped(R))
                        for live in sorted({T // 4, T // 2, 3 * T // 4, T}):
                            real = (jnp.arange(T) < live)[None]
                            trips = jnp.asarray(live_row_trips(
                                np.zeros(1, np.int64), np.full(1, live), T,
                                R), jnp.int32)
                            sec = timed(f, (lp, *operands, real, trips), iters)
                            got = f(lp, *operands, real, trips)
                            want = straight(lp, *operands, real)
                            pairs = [(np.asarray(g)[:, :live],
                                      np.asarray(w)[:, :live])
                                     for g, w in zip(jax.tree.leaves(got),
                                                     jax.tree.leaves(want))]
                            diff = max(float(np.abs(
                                g.astype(np.float32) - w.astype(np.float32)
                            ).max()) for g, w in pairs
                                if g.dtype.kind not in "iub")
                            # the router's picks: flips of near-ties
                            flips = [float((g != w).mean()) for g, w in pairs
                                     if g.dtype.kind == "i"]
                            rows = int(trips.sum()) * R
                            print(json.dumps({
                                **line, "part": part, "form": f"looped_R{R}",
                                "live": live, "rows_run": rows,
                                "ms": round(sec * 1e3, 4),
                                "vs_straight": round(sec / base, 4),
                                "max_abs_diff_live_rows": diff,
                                **({"picks_flipped_share": flips[0]}
                                   if flips else {}),
                            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
