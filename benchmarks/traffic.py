"""The one general traffic generator: a mix file's parameters + the
cell's rate or client count + ``--seed`` -> the requests of one run.

The arithmetic (exponential gaps for a Poisson process, clipped
log-normal lengths) follows ``dynamo_tpu/fleetsim/traces.py:_arrivals``
and ``dynamo_tpu/data_generator.py:lognorm``; it is written out here,
with ``random.Random`` only, so that later PRs may change the originals.

The SET of gaps and of prompt and output lengths is fixed by the mix and
the cell's rate alone: the n values of a distribution are its quantiles at
(i + 0.5) / n, so the set has the distribution's shape with no sampling
noise, and n = rate x seconds arrivals always fall inside the window. Their
ORDER is fixed too, by the mix's ``schedule_seed``: measured on the chip
(PERF.md, PR 24), the same set in another order moved ``ttft_ms_p90`` by
44 % and ``tok_s`` by 13 % (quartile distance over median, six orders),
while two runs of one order agree to 0.4 % — a queue near its knee is that
sensitive to which long prompt meets which burst, and no bound of 10 %
could be held across orders. ``--seed`` draws what the timing does not
depend on: the token ids (and, in the server, the weights).

Named kinds (a new mix of these kinds is a data file only):
  arrivals:  poisson | closed
  lengths:   lognormal | uniform | fixed
  tokens:    uniform
"""
from __future__ import annotations

import math
import random
import statistics
from dataclasses import dataclass


@dataclass
class Req:
    due_s: float            # relative to the window's start; < 0 = pre-roll
    prompt_len: int
    output_len: int
    token_seed: int         # the prompt's ids are drawn from this


def length_at(spec: dict, u: float) -> int:
    """The u-quantile of a length distribution, clipped."""
    kind = spec["kind"]
    if kind == "lognormal":
        x = math.exp(math.log(spec["median"])
                     + spec["sigma"] * statistics.NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "fixed":
        return int(spec["value"])
    else:
        raise ValueError(f"unknown length kind {kind!r}")
    return int(min(max(round(x), spec["min"]), spec["max"]))


def lengths(spec: dict, n: int) -> list[int]:
    return [length_at(spec, (i + 0.5) / n) for i in range(n)]


def poisson_gaps(rate_rps: float, span_s: float) -> list[float]:
    """round(rate x span) exponential gaps (the quantiles of the
    exponential law), scaled so that the last arrival falls half a mean
    gap before the end of the span."""
    n = round(rate_rps * span_s)
    if n <= 0:
        return []
    q = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (span_s - 0.5 / rate_rps) / sum(q)
    return [x * scale for x in q]


def prompt_tokens(req: Req, spec: dict, vocab: int) -> list[int]:
    if spec["kind"] != "uniform":
        raise ValueError(f"unknown tokens kind {spec['kind']!r}")
    rng = random.Random(req.token_seed)
    low = min(int(spec.get("low", 0)), vocab - 1)
    return [rng.randrange(low, vocab) for _ in range(req.prompt_len)]


def _segment(mix: dict, rate_rps: float, start_s: float, span_s: float,
             rng: random.Random) -> list[Req]:
    gaps = poisson_gaps(rate_rps, span_s)
    prompts = lengths(mix["prompt_len"], len(gaps))
    outputs = lengths(mix["output_len"], len(gaps))
    for values in (gaps, prompts, outputs):
        rng.shuffle(values)
    out, t = [], start_s
    for g, p, o in zip(gaps, prompts, outputs):
        t += g
        out.append(Req(t, p, o, 0))
    return out


def _with_token_seeds(reqs: list[Req], seed: int) -> list[Req]:
    rng = random.Random(seed)
    for r in reqs:
        r.token_seed = rng.getrandbits(48)
    return reqs


def open_schedule(mix: dict, rate_rps: float, seconds: float, seed: int
                  ) -> list[Req]:
    """Pre-roll arrivals in [-preroll_s, 0) and the window's in
    [0, seconds): two fixed sets in the mix's fixed order."""
    if mix["arrivals"]["kind"] != "poisson":
        raise ValueError(
            f"unknown open-loop arrivals {mix['arrivals']['kind']!r}")
    order = random.Random(mix["schedule_seed"])
    pre = float(mix.get("preroll_s", 0))
    reqs = _segment(mix, rate_rps, -pre, pre, order) if pre else []
    reqs += _segment(mix, rate_rps, 0.0, seconds, order)
    return _with_token_seeds(reqs, seed)


def closed_stream(mix: dict, seed: int) -> list[Req]:
    """The fixed population of a closed loop in the mix's fixed order;
    clients take the next entry whenever their last request ends."""
    order = random.Random(mix["schedule_seed"])
    n = int(mix["population"])
    prompts = lengths(mix["prompt_len"], n)
    outputs = lengths(mix["output_len"], n)
    order.shuffle(prompts)
    order.shuffle(outputs)
    return _with_token_seeds(
        [Req(0.0, p, o, 0) for p, o in zip(prompts, outputs)], seed)
