#!/usr/bin/env python
"""Why a decode step of the routed-expert cell touches fewer experts than
independent lanes would: are the lanes' tokens alike?

Builds the engine exactly as the benchmark's launcher does
(``benchmarks/server.py``: same configuration file, weights from the
seed), fills every decode slot with a request of its own random prompt
and lets them all decode ``--steps`` tokens, twice: GREEDY, as the
benchmark's traffic asks (a lane's next token is the argmax of seeded
random weights at its last one), then SAMPLED at temperature 1 with a
seed per lane (random weights give near-flat logits, so every lane draws
tokens from the whole vocabulary: the token -> routing -> token feedback
is broken). One JSON line per phase:

  distinct_tokens_per_step   distinct tokens over the lanes at one output
                             index (median and quartiles over indices past
                             the first 32); lanes = all different
  lanes_in_a_short_cycle     share of lanes whose last 48 tokens repeat
                             with a period of at most 8
  touched_share              routed experts read per step per layer, of
                             those held (the engine's own counters)
  expected_touched_share     1 - (1 - k/E)^lanes: what independent lanes
                             at the measured live count would touch
  load_max_over_mean         most tokens on one expert over the mean

  chiprun -- python3 tools/moe_lane_diversity.py --seed 3100310501
  python3 tools/moe_lane_diversity.py --seed 1 --dry-run --steps 24
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

TOUCHED = "dynamo_moe_experts_touched"
ROUTED = "dynamo_moe_tokens_routed"
LOAD_MAX = "dynamo_moe_expert_load_max"


def short_cycle(toks: list[int], tail: int = 48, longest: int = 8) -> bool:
    t = toks[-tail:]
    return any(all(t[i] == t[i - p] for i in range(p, len(t)))
               for p in range(1, longest + 1))


async def phase(engine, cfg, name, lanes, prompt_len, steps, seed, sampled):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)

    rng = random.Random(seed)
    before = engine.telemetry.snapshot()

    async def one(i):
        req = PreprocessedRequest(
            token_ids=[rng.randrange(10, cfg["vocab_size"])
                       for _ in range(prompt_len)],
            model="bench",
            stop_conditions=StopConditions(max_tokens=steps, ignore_eos=True),
            sampling_options=(
                SamplingOptions(temperature=1.0, seed=seed + i) if sampled
                else SamplingOptions(temperature=0.0)))
        return [t async for out in engine.generate(req)
                for t in out.token_ids]

    outs = await asyncio.gather(*(one(i) for i in range(lanes)))
    after = engine.telemetry.snapshot()
    d = {k: after[k]["sum"] - before.get(k, {"sum": 0})["sum"]
         for k in (TOUCHED, ROUTED, LOAD_MAX)}
    rounds = after[ROUTED]["count"] - before.get(ROUTED, {"count": 0})["count"]
    n_exp = cfg["n_routed_experts"]
    k = cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    layer_steps = rounds * engine.ecfg.flush_every * layers
    live = d[ROUTED] / (layer_steps * k)
    distinct = [len({o[i] for o in outs}) for i in range(32, steps)]
    q = statistics.quantiles(distinct, n=4) if len(distinct) > 1 else distinct
    print(json.dumps({
        "phase": name, "seed": seed, "lanes": lanes, "steps": steps,
        "distinct_tokens_per_step": {"q1": q[0], "median": q[1], "q3": q[-1]},
        "lanes_in_a_short_cycle": sum(map(short_cycle, outs)) / lanes,
        "distinct_tokens_overall": len({t for o in outs for t in o}),
        "live_lanes_per_step": live,
        "touched_share": d[TOUCHED] / (layer_steps * n_exp),
        "expected_touched_share": 1 - (1 - k / n_exp) ** live,
        # LOAD_MAX is a maximum over a round's steps and layers
        "load_max_over_mean": (d[LOAD_MAX] / rounds) / (live * k / n_exp),
    }), flush=True)


async def main(args) -> int:
    import server  # benchmarks/server.py

    cfg = server.load_config(
        os.path.join(REPO, "benchmarks", "configs", args.config + ".json"),
        args.dry_run)
    engine = server.build_engine(cfg, args.seed, args.dry_run)
    lanes = cfg["engine"]["max_decode_slots"]
    for name, sampled in (("greedy", False), ("sampled", True)):
        await phase(engine, cfg, name, lanes, args.prompt_len, args.steps,
                    args.seed, sampled)
    await engine.stop()
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mla-moe-joyai-d5")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=160)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--dry-run", action="store_true")
    sys.exit(asyncio.run(main(ap.parse_args())))
