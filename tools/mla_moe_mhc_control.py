#!/usr/bin/env python
"""The reference check of the four-stream latent-attention cell, and its
CONTROLS, in one process on the chip.

Builds the engine exactly as the benchmark's launcher does
(``benchmarks/server.py``: ``configs/xing4-mhc-d7.json``, weights from the
seed) and runs the launcher's own ``check_against_reference`` against
``benchmarks/references/mla_moe_mhc.py``: once as it stands (sound: has to
pass), then with the reference computing what a faulty program would:

  hc_iters_1   one Sinkhorn iteration             } part of the mathematics
  hc_static    the token-dependent term dropped   } left out: each MUST
  yarn_off     plain rotary and softmax scale     } fail a tolerance
  mix_bf16     mixing coefficients in bfloat16    } lower precision: at
  fp8          every matmul operand in float8     } least one must fail

The engine generates ONCE (a fresh 4096-token chunk, a continuing chunk,
decode over both: the check's prompts); every check after the first
replays its outputs (``tools/mla_moe_control.py: Replay``), so all
controls are held against the same tokens and log-probs. One JSON line
per check. Exit code 1 if the sound check fails, one of the first three
controls passes or both of the last two do; else 0.

  chiprun -- python3 tools/mla_moe_mhc_control.py --seed 3700370037
  python3 tools/mla_moe_mhc_control.py --seed 1 --dry-run     # tiny, CPU
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.join(REPO, "tools"))

from mla_moe_control import Replay  # noqa: E402

MATHEMATICS = ("hc_iters_1", "hc_static", "yarn_off")
PRECISION = ("mix_bf16", "fp8")


async def main(args) -> int:
    import server  # benchmarks/server.py

    cfg = server.load_config(
        os.path.join(REPO, "benchmarks", "configs", args.config + ".json"),
        args.dry_run)
    reference = server.reference_for(cfg)
    engine = server.build_engine(cfg, args.seed, args.dry_run)
    replay = Replay(engine)
    passed = {}
    for control in (None,) + MATHEMATICS + PRECISION:
        replay.rewind()
        ref = reference if control is None else dict(
            reference, logprobs=functools.partial(
                reference["logprobs"], control=control))
        verdict = await server.check_against_reference(
            replay, cfg, args.seed, ref)
        passed[control] = verdict["ok"]
        print(json.dumps({
            "control": control, "seed": args.seed, "failed_by": [
                n for n, got, tol in (
                    ("max", verdict["max_abs_logprob_diff"],
                     verdict["tol_max"]),
                    ("mean", verdict["mean_abs_logprob_diff"],
                     verdict["tol_mean"])) if got > tol],
            **verdict}), flush=True)
    await engine.stop()
    if args.dry_run:
        return 0
    ok = (passed[None] and not any(passed[c] for c in MATHEMATICS)
          and not all(passed[c] for c in PRECISION))
    return 0 if ok else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="xing4-mhc-d7")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dry-run", action="store_true")
    sys.exit(asyncio.run(main(ap.parse_args())))
