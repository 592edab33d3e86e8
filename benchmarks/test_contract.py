"""Which end-to-end metric is judged in which cell, shown on the CPU with no
JAX and no server: ``python3 -m pytest benchmarks/test_contract.py -q``.
NOT part of tier-1 (``pyproject.toml``: ``testpaths = ["tests"]``) and run
by no check: a benchmark PR may add no file under ``tests/``. It is the
README's table as something that can be run by hand; a PR that may touch
``tests/`` gives it one tier-1 case (PERF.md section 7).

1. WHY ``tok_s`` is judged only in saturated cells: through
   ``traffic.open_schedule`` and ``stats.reduce_log`` themselves, an open
   loop under its knee reads the schedule plus the backlog the pre-roll
   carries into the window, so the FASTER server reads the LOWER ``tok_s``;
   the same two servers in a saturated closed loop read the other way.
2. What ``BENCHMARK.json`` may say, from data alone: readers and entries
   pair up; a per-layer entry moves a metric that is judged in EVERY cell
   the entry runs in (else the driver drops it from the others); ``tok_s``
   is judged exactly in the saturated cells, which are the closed loops
   and the open loops whose cell file gives a ``knee_rps`` at or under its
   ``rate_rps``; every cell judges what its kind of load needs, and
   records the TTFT tail per layer where it does not judge it.
3. The two arithmetic self-checks exit 0.
"""
from __future__ import annotations

import heapq
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


traffic = _load("bench_traffic", "traffic.py")
stats = _load("bench_stats", "stats.py")


def _json(*parts: str) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
MIX = {w["name"]: _json("benchmarks", "traffic", w["traffic"] + ".json")
       for w in BENCH["workloads"]}
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}
PER_LAYER = {m["name"]: m for m in BENCH["per_layer"]}
READER_FILES = sorted(f[:-3] for f in os.listdir(
    os.path.join(HERE, "layer_metrics")) if f.endswith(".py"))

SECONDS = float(BENCH["run_seconds"])
FLUSH_EVERY = 4        # tokens to a chunk after the first (stats.py)
SLOW = (1.2, 0.045)    # first token after due, s; then s per token:
FAST = (0.29, 0.020)   # the parent's and PR 26's medians (ledger, PR 26)


def judged(metric: str, cell: str) -> bool:
    m = END_TO_END.get(metric)
    return m is not None and cell in m.get("workloads", CELLS)


def record(due: float, asked: int, server: tuple[float, float]) -> dict:
    """One request served with no queue: the first chunk ``ttft`` after it
    was due, then a chunk every FLUSH_EVERY tokens at ``tpot`` a token."""
    ttft, tpot = server
    steps = list(range(FLUSH_EVERY, asked - 1, FLUSH_EVERY)) + [asked - 1]
    chunks = [due + ttft] + [due + ttft + k * tpot for k in steps]
    return {"due": due, "sent": due, "chunks": chunks, "tokens": asked,
            "asked": asked, "ok": True, "error": None}


def cell_file(cell: str) -> dict:
    return _json("benchmarks", "cells", cell + ".json")


def saturated(cell: str) -> bool:
    """The offered work is not fixed by a schedule the system keeps up with:
    a closed loop, or an open loop at or above the knee its file names."""
    if MIX[cell]["loop"] == "closed":
        return True
    own = cell_file(cell)
    return "knee_rps" in own and own["rate_rps"] >= own["knee_rps"]


OPEN_UNDER_KNEE = next(c for c in sorted(CELLS) if not saturated(c))


def open_log(server, seed: int = 27) -> list[dict]:
    mix = MIX[OPEN_UNDER_KNEE]
    rate = cell_file(OPEN_UNDER_KNEE)["rate_rps"]
    return [record(r.due_s, r.output_len, server)
            for r in traffic.open_schedule(mix, rate, SECONDS, seed)]


def closed_log(server, clients: int = 8, seed: int = 27) -> list[dict]:
    """``clients`` callers from the pre-roll's start, each sending its next
    request the moment the last one ends: the load of ``run_closed``."""
    mix = next(MIX[c] for c in sorted(CELLS) if MIX[c]["loop"] == "closed")
    stream = iter(traffic.closed_stream(mix, seed))
    free = [(-float(mix["preroll_s"]), c) for c in range(clients)]
    log = []
    while free[0][0] < SECONDS:
        t, c = heapq.heappop(free)
        rec = record(t, next(stream).output_len, server)
        log.append(rec)
        heapq.heappush(free, (rec["chunks"][-1], c))
    return log


def offered_tok_s(log: list[dict]) -> float:
    return sum(r["asked"] for r in log
               if stats.of_window(r, SECONDS)) / SECONDS


def preroll_tok_s(log: list[dict]) -> float:
    return sum(r["asked"] for r in log if r["due"] < 0) / SECONDS


# ---- 1. why ---------------------------------------------------------


def test_the_open_window_offers_the_same_tokens_whatever_the_seed():
    """121.5 tokens/s for cell 1 at 1.6 req/s over 50 s (PERF.md section 2):
    ``schedule_seed`` fixes lengths, gaps and order; ``--seed`` none of it."""
    logs = [open_log(FAST, seed) for seed in (1, 27, 2**31 + 5)]
    assert len({(offered_tok_s(g), preroll_tok_s(g)) for g in logs}) == 1
    assert offered_tok_s(logs[0]) > 0 and preroll_tok_s(logs[0]) > 0


def test_open_loop_under_the_knee_the_faster_server_reads_the_lower_tok_s():
    slow = stats.reduce_log(open_log(SLOW), SECONDS)
    fast = stats.reduce_log(open_log(FAST), SECONDS)
    assert fast["ttft_ms_p90"] < slow["ttft_ms_p90"]
    assert fast["tpot_ms_p90"] < slow["tpot_ms_p90"]
    # both deliver more than the window offered, pre-roll backlog, and the
    # ceiling (every pre-roll token landing inside) is reached by being slow
    offered = offered_tok_s(open_log(FAST))
    ceiling = offered + preroll_tok_s(open_log(FAST))
    assert offered < fast["tok_s"] < slow["tok_s"] < ceiling


def test_saturated_closed_loop_the_faster_server_reads_the_higher_tok_s():
    slow = stats.reduce_log(closed_log(SLOW), SECONDS)
    fast = stats.reduce_log(closed_log(FAST), SECONDS)
    assert fast["tok_s"] > 1.5 * slow["tok_s"]
    # and there tok_s is the work done, not the schedule
    assert fast["attempted"] > 1.5 * slow["attempted"]


def test_carried_tok_s_reader_is_tok_s_less_the_windows_own_tokens():
    read = _load("reader_carried", "layer_metrics", "gen.carried_tok_s.py").read
    carried = {}
    for name, server in (("slow", SLOW), ("fast", FAST)):
        log = open_log(server)
        gen = stats.reduce_log(log, SECONDS)
        carried[name] = read({"gen": gen, "log": log, "seconds": SECONDS})
        assert carried[name] == pytest.approx(
            gen["tok_s"] - offered_tok_s(log))
    assert 0 < carried["fast"] < carried["slow"]
    # a failure leaves tok_s short of its tokens: nothing to read then
    log[-1]["ok"] = False
    assert read({"gen": stats.reduce_log(log, SECONDS), "log": log,
                 "seconds": SECONDS}) is None


# ---- 2. the contract, one case per entry ----------------------------


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_a_per_layer_entry_moves_a_metric_judged_in_every_cell_it_runs_in(
        name):
    """The driver records an entry in the cells that report what it moves:
    one judged in only some of the entry's cells loses it the others."""
    metric = PER_LAYER[name]
    runs_in = metric.get("workloads", list(CELLS))
    assert runs_in and set(runs_in) <= set(CELLS)
    assert all(judged(metric["moves"], c) for c in runs_in)


@pytest.mark.parametrize("name", sorted(set(READER_FILES) | set(PER_LAYER)))
def test_every_reader_has_an_entry_and_every_entry_a_reader(name):
    assert name in PER_LAYER, f"layer_metrics/{name}.py has no entry"
    assert name in READER_FILES, f"per_layer {name} has no reader file"
    assert callable(_load("reader_" + name, "layer_metrics",
                          name + ".py").read)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_tok_s_is_judged_exactly_where_the_cell_is_saturated(cell):
    assert judged("tok_s", cell) == saturated(cell)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_judges_what_its_kind_of_load_needs(cell):
    assert judged("setup_s", cell) and judged("tpot_ms_p90", cell)
    # TTFT's tail: judged where its runs are steady enough for a bound
    # (nowhere since PR 30: README.md), else recorded per layer under the
    # name of the cell's traffic mix
    if not judged("ttft_ms_p90", cell):
        recorded = PER_LAYER["ttft_ms_p90." + CELLS[cell]["traffic"]]
        assert cell in recorded["workloads"]
    files = (("configs", CELLS[cell]["config"]),
             ("traffic", CELLS[cell]["traffic"]), ("cells", cell))
    for folder, stem in files:
        assert os.path.exists(os.path.join(HERE, folder, stem + ".json"))


# ---- 3. the self-checks ---------------------------------------------


@pytest.mark.parametrize("cmd", [["benchmarks/stats.py", "--selftest"],
                                 ["-m", "benchmarks.trace_reduce",
                                  "--selftest"]], ids=["stats", "trace"])
def test_the_arithmetic_selfchecks_pass(cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable] + cmd, cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
