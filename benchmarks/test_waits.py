"""The harness's fixed waits, with a stand-in child (no JAX, no chip, no real
sleep): ``python3 -m pytest benchmarks/test_waits.py -q``; in tier-1 through
``tests/test_benchmark_rehearsal.py``, which runs ``pytest benchmarks``.

PR 32 was refused because ``run.py`` gave the profiler 120 s to collect a
trace whose collection takes longer the FASTER the program is (README.md,
"Every fixed wait"). What is held here: a limit ends a run whose child hangs
or has died, never one whose child is alive and still working; every limit
is a named constant of ``run.py`` that the README's table states; a wait
that does run out says what ran out, as a ``BenchFailure`` and no traceback.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str, file: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, file))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


run = _load("bench_run", "run.py")
procs = run.procs                 # the module whose clock the waits read
OLD_TRACE_LIMIT_S = 120.0         # what refused PR 32
DONE = ('trace done: {"dir": "t", "t_start_wall": 1.0, "t_stop_wall": 4.0, '
        '"collect_s": %s, "t": 9.0}\n')


class Clock:
    """``procs.wait_for`` sleeps 0.1 s a poll: here a poll costs no time and
    moves this clock by what it asked to sleep."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(procs, "time", c)    # procs' name for the module
    return c


class StandInChild:
    """What ``wait_for`` and ``said`` touch of a ``procs.Child``: a log that
    grows and an exit code, both scripted on the stand-in clock."""

    name = "server"

    def __init__(self, clock: Clock, done_after_s: float | None = None,
                 dies_after_s: float | None = None, collect_s: float = 0.0):
        self._clock, self._t0 = clock, clock.now
        self._done, self._dies = done_after_s, dies_after_s
        self._collect_s = collect_s
        self.proc = self
        self.returncode = None

    def _age(self) -> float:
        return self._clock.now - self._t0

    def poll(self):
        if self._dies is not None and self._age() >= self._dies:
            self.returncode = -9
        return self.returncode

    def log_text(self) -> str:
        text = "serving: {}\nRuntimeError: a line near the log's end\n"
        if self._done is not None and self._age() >= self._done:
            text += DONE % self._collect_s
        return text


# How long the collection took after the window: the accepted tree on four
# chips (114.6 s, under the old limit by 5 s), PR 32's faster program (182.7
# s: refused), a program twice as fast again, and the longest the limit
# itself still waits out.
@pytest.mark.parametrize("wait_s", [114.6, 182.7, 365.4,
                                    run.TRACE_COLLECT_TIMEOUT_S - 1.0])
def test_a_live_child_that_is_still_collecting_is_waited_out(clock, wait_s):
    child = StandInChild(clock, done_after_s=wait_s, collect_s=wait_s + 45.0)
    traced = run.collected_trace(child)
    assert traced["collect_s"] == wait_s + 45.0
    assert clock.now - 1000.0 == pytest.approx(wait_s, abs=0.2)


def test_a_child_that_dies_while_collecting_fails_at_once_with_its_log(clock):
    child = StandInChild(clock, done_after_s=300.0, dies_after_s=50.0)
    with pytest.raises(run.BenchFailure) as e:
        run.collected_trace(child)
    assert clock.now - 1000.0 == pytest.approx(50.0, abs=0.2)   # not the limit
    assert "exited with code -9" in str(e.value)
    assert "the profiler to collect the trace" in str(e.value)
    assert "a line near the log's end" in str(e.value)


def test_a_child_that_hangs_is_given_up_at_the_limit_and_the_reason_says_so(
        clock):
    child = StandInChild(clock)         # alive, never done
    with pytest.raises(run.BenchFailure) as e:
        run.collected_trace(child)
    assert clock.now - 1000.0 == pytest.approx(run.TRACE_COLLECT_TIMEOUT_S,
                                               abs=0.2)
    assert f"timed out after {run.TRACE_COLLECT_TIMEOUT_S:.0f}s" in str(e.value)
    assert "the profiler to collect the trace" in str(e.value)


def test_the_call_site_reads_the_named_constant(clock, monkeypatch):
    """Not a number written where the wait is made: the constant moved,
    the wait moves with it."""
    monkeypatch.setattr(run, "TRACE_COLLECT_TIMEOUT_S", 30.0)
    with pytest.raises(run.BenchFailure, match="timed out after 30s"):
        run.collected_trace(StandInChild(clock, done_after_s=40.0))
    body = open(os.path.join(HERE, "run.py")).read().split("def run(args)")[1]
    assert "collected_trace(server)" in body
    assert not re.search(r"wait(_for)?\([^)]*\b\d+(\.\d+)?\)", body, re.S), \
        "a wait in run() with its limit written as a number"


# 3x the longest collection seen on the chip (228.2 s: PR 32's program, four
# chips) is the least a limit of a wait that grows with SPEED may be.
@pytest.mark.parametrize("name", ["TRACE_COLLECT_TIMEOUT_S",
                                  "TRACE_REDUCE_TIMEOUT_S"])
def test_a_limit_that_grows_with_the_programs_speed_is_not_under_700_s(name):
    assert getattr(run, name) >= 700.0 > 3 * 228.2 > OLD_TRACE_LIMIT_S


WAITS = ["START_TIMEOUT_S", "HTTP_UP_TIMEOUT_S", "SNAPSHOT_TIMEOUT_S",
         "TRACE_COLLECT_TIMEOUT_S", "SERVER_EXIT_TIMEOUT_S",
         "TRACE_REDUCE_TIMEOUT_S"]


@pytest.mark.parametrize("name", WAITS)
def test_the_readmes_table_states_every_wait_with_its_limit(name):
    with open(os.path.join(HERE, "README.md")) as f:
        table = f.read().split("## Every fixed wait", 1)[1].split("\n## ")[0]
    row = next((r for r in table.splitlines() if f"`{name}`" in r), None)
    assert row, f"no row for {name} in README.md's table of waits"
    cells = [c.strip() for c in row.strip("|").split("|")]
    assert float(cells[1].split()[0]) == getattr(run, name), row
    assert re.match(r"(SPEED|SIZE|neither)\b", cells[3]), row


def test_a_reduction_that_runs_out_of_time_is_a_failure_that_names_it(
        tmp_path, monkeypatch):
    """``subprocess.run`` raises ``TimeoutExpired``, which is no
    ``BenchFailure``: ``main`` would leave a traceback and no reason."""
    monkeypatch.setattr(run, "TRACE_REDUCE_TIMEOUT_S", 0.01)  # < an import
    with pytest.raises(run.BenchFailure) as e:
        run.reduce_trace(str(tmp_path / "trace"), str(tmp_path))
    assert "trace reduction" in str(e.value) and "0.01s" in str(e.value)


def test_a_reduction_that_fails_still_says_why(tmp_path):
    with pytest.raises(run.BenchFailure, match="no .xplane.pb under"):
        run.reduce_trace(str(tmp_path / "no-trace-here"), str(tmp_path))


def test_a_traced_cpu_dry_run_is_still_refused():
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    args = argparse.Namespace(workload=cell, seed=1, seconds=6.0, trace=1,
                              cpu_dry_run=True)
    with pytest.raises(run.BenchFailure, match="--trace 1 needs the chip"):
        run.run(args)


# ---- the server's side of the traced run ----------------------------

_DYING_TRACE = """
import io, sys, time
sys.path.insert(0, {here!r})
import server

def no_profiler(path, delay_s, seconds):
    raise RuntimeError("the profiler would not start")

class Loop:
    def call_soon_threadsafe(self, fn):
        pass

server.take_trace = no_profiler
sys.stdin = io.StringIO("trace /nowhere 0 0\\n")
server.command_loop(None, None, server.threading.Event(), Loop())
time.sleep(20)          # a server goes on serving: the trace thread ends it
"""


def test_a_trace_that_fails_ends_the_server_at_once_with_the_reason():
    """Else the parent waits its whole limit for a `trace done` that cannot
    come; dead, the server fails the run on the next poll (above)."""
    r = subprocess.run([sys.executable, "-c", _DYING_TRACE.format(here=HERE)],
                       capture_output=True, text=True, timeout=15)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "the profiler would not start" in r.stderr
    assert "trace done" not in r.stdout


def test_the_trace_the_server_leaves_is_one_the_reduction_finds_and_reads(
        tmp_path):
    """``take_trace`` holds the profiler's session itself (no
    ``trace.json.gz``): the file must lie where ``trace_reduce`` looks and
    parse. On the CPU there is no device plane, so nothing to reduce."""
    pytest.importorskip("jax")
    server = _load("bench_server", "server.py")
    reduce_ = _load("bench_trace_reduce", "trace_reduce.py")
    said = server.take_trace(str(tmp_path), 0.0, 0.2)
    assert said["t_stop_wall"] - said["t_start_wall"] >= 0.2
    assert said["collect_s"] >= 0 and said["xspace_bytes"] > 0
    found = reduce_.find_xplane(str(tmp_path))
    assert os.path.getsize(found) == said["xspace_bytes"]
    assert os.listdir(os.path.dirname(found)) == [os.path.basename(found)]
    assert reduce_.reduce_events(reduce_.read_xplane(found))["chips"] == 0
