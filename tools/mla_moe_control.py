#!/usr/bin/env python
"""The reference check of a routed-expert cell, and its CONTROLS, in one
process on the chip: ``--config`` names the configuration (the latent
block's by default; ``granite4h-ep2-d10`` for the state-space hybrid).

Builds the engine exactly as the benchmark's launcher does
(``benchmarks/server.py``: same configuration file, weights from the
seed) and runs the launcher's own ``check_against_reference`` against the
configuration's reference: once as it stands (sound: has to pass), then
with the reference computing what a faulty program would (``control=``;
a control has to FAIL the check, by a limit or by the reference's refusal,
or the check does not catch a program that computes that way). Which
controls: the reference's own ``CONTROLS_REQUIRED`` (each has to fail) and
``CONTROLS_NAMED`` (reported whichever way they read) where it states
them, as ``references/ssm_moe.py`` does beside what each computes; for
``references/mla_moe.py``, which states none, this file's:

  fp8           every matmul operand in float8_e4m3fn (MEAN)
  lane_swap     one compared position answers with its neighbour's
                state: a local fault (MAX)
  experts_int8_stored   the engine HOLDS a routed expert matrix in 8
                bits (refused: the reference computes the stated model)
  router_bf16   router scores in bfloat16     } the two ISSUE 31 named;
  experts_int8  routed experts ROUNDED to 8   } both read inside the
                bits, held in bfloat16        } band sound seeds span

The engine is built once and generates ONCE a seed; every check after the
first replays its outputs, so all controls are held against the same
tokens and log-probs. With several ``--seeds`` the weights are drawn anew
for each (the old ones dropped first: two copies do not fit the chip) and
the first ``--controls-on`` of them also run the controls. One JSON line a
(seed, control), also appended to ``chiprun_out/<reference>_control.jsonl``.
Exit code 1 if a sound check fails or a required control passes; else 4
while a named control still passes (a hole the records describe: PERF.md
section 7 item 2 for the latent block's two, the hybrid configuration's
``assumed`` for ``state_bf16``); 0 only when every control fails.

  chiprun -- python3 tools/mla_moe_control.py --seeds 3100310031
  chiprun --timeout 3000 -- python3 tools/mla_moe_control.py \
      --config granite4h-ep2-d10 --seeds 4100410001 ... --controls-on 4
  python3 tools/mla_moe_control.py --seeds 1 --dry-run     # tiny, CPU
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import gc
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

REQUIRED = ("fp8", "lane_swap", "experts_int8_stored")
NAMED = ("router_bf16", "experts_int8")


class Replay:
    """The engine for ``check_against_reference``: the first pass through
    the check's prompts records what the engine streamed, later passes
    replay it."""

    def __init__(self, engine):
        self.engine, self.params = engine, engine.params
        self.kept: list[list] = []
        self.at = 0

    def rewind(self, params=None):
        self.at = 0
        self.params = self.engine.params if params is None else params

    async def generate(self, req):
        if self.at == len(self.kept):
            self.kept.append([out async for out in self.engine.generate(req)])
        outs = self.kept[self.at]
        self.at += 1
        for out in outs:
            yield out


def experts_held_in_8_bits(params: dict) -> dict:
    """The engine's pytree with ONE routed-expert matrix held as int8
    values (the rest shared, nothing copied: a second copy of the experts
    does not fit the chip, which is the point)."""
    import jax.numpy as jnp

    first = dict(params["experts"][0])
    w = first["we_g"][:1].astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    first["we_g"] = jnp.round(w / s).astype(jnp.int8)
    return dict(params, experts=[first] + list(params["experts"][1:]))


async def main(args) -> int:
    import jax

    import server  # benchmarks/server.py
    from dynamo_tpu.models import llama

    cfg = server.load_config(
        os.path.join(REPO, "benchmarks", "configs", args.config + ".json"),
        args.dry_run)
    reference = server.reference_for(cfg)
    stated = reference["logprobs"].__globals__
    required = tuple(stated.get("CONTROLS_REQUIRED", REQUIRED))
    named = tuple(stated.get("CONTROLS_NAMED", NAMED))
    if args.only:   # a part of them (a control is minutes of reference)
        unknown = sorted(set(args.only) - set(required + named))
        if unknown:
            raise SystemExit(f"--only {unknown}: the reference states "
                             f"{required + named}")
        required, named = (tuple(c for c in held if c in args.only)
                           for held in (required, named))
    engine = server.build_engine(cfg, args.seeds[0], args.dry_run)
    draw = jax.jit(
        lambda key: llama.init_params(engine.config, key),
        out_shardings=llama.param_shardings(engine.config, engine.mesh))
    out_path = os.path.join(
        REPO, "chiprun_out", os.path.basename(reference["file"])[:-3]
        + "_control.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    ok, unseen, replay = True, False, None
    for n, seed in enumerate(args.seeds):
        if n:
            # nothing may still hold the old weights when the new are drawn
            engine.params = replay = None
            gc.collect()
            engine.params = llama.serving_params(
                engine.config, draw(jax.random.PRNGKey(seed % (2 ** 31))))
        replay = Replay(engine)
        controls = (None,) + (required + named if n < args.controls_on
                              else ())
        for control in controls:
            stored = control == "experts_int8_stored"
            replay.rewind(experts_held_in_8_bits(engine.params) if stored
                          else None)
            ref = reference if control is None or stored else dict(
                reference, logprobs=functools.partial(
                    reference["logprobs"], control=control))
            rec = {"control": control, "seed": seed}
            try:
                verdict = await server.check_against_reference(
                    replay, cfg, seed, ref)
            except ValueError as e:
                verdict = {"ok": False, "refused": str(e)}
            else:
                rec["failed_by"] = [name for name, got, tol in (
                    ("max", verdict["max_abs_logprob_diff"],
                     verdict["tol_max"]),
                    ("mean", verdict["mean_abs_logprob_diff"],
                     verdict["tol_mean"])) if got > tol]
            line = json.dumps({**rec, **verdict})
            print(line, flush=True)
            with open(out_path, "a") as f:
                f.write(line + "\n")
            if control is None:
                ok &= verdict["ok"]
            elif control in required:
                ok &= not verdict["ok"]
            else:
                unseen |= verdict["ok"]
    await engine.stop()
    if args.dry_run:
        return 0
    return 1 if not ok else 4 if unseen else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mla-moe-joyai-d5")
    ap.add_argument("--seeds", "--seed", type=int, nargs="+", required=True)
    ap.add_argument("--controls-on", type=int, default=1,
                    help="how many of the first seeds also run the controls")
    ap.add_argument("--only", nargs="*", default=[],
                    help="run these of the reference's controls only")
    ap.add_argument("--dry-run", action="store_true")
    sys.exit(asyncio.run(main(ap.parse_args())))
