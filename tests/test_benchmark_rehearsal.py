"""The benchmark's own checks as tier-1 cases (PERF.md section 7 (a),
owed since PR 27): ``benchmarks/`` is outside ``testpaths``, so nothing
ran its contract tests, its arithmetic self-tests or a rehearsal of a
cell unless a builder did by hand. Each runs as the command a builder
would type, in a child process (the rehearsal's server child takes the
CPU "chip"; nothing here imports JAX), under a time limit of its own.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_CELL = "mla-moe-joyai-d5.chat-decode"


def run(cmd, limit_s):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # the rehearsal sets its own device count
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=limit_s)


@pytest.mark.parametrize("cmd,limit_s", [
    ([sys.executable, "-m", "pytest", "benchmarks", "-q",
      "-p", "no:cacheprovider", "-p", "no:xdist"], 120),
    ([sys.executable, "-m", "benchmarks.trace_reduce", "--selftest"], 120),
    ([sys.executable, "benchmarks/stats.py", "--selftest"], 60),
], ids=["pytest-benchmarks", "trace_reduce-selftest", "stats-selftest"])
def test_the_benchmarks_own_checks_pass(cmd, limit_s):
    r = run(cmd, limit_s)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]


def test_the_new_cell_rehearses_on_the_cpu():
    """The cell's files end to end at the dry-run widths: configuration,
    reference by name, traffic mix, warm-up, window, result line."""
    r = run([sys.executable, "benchmarks/run.py", "--workload", NEW_CELL,
             "--seed", "3100310031", "--seconds", "6", "--cpu-dry-run"], 420)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["dry_run"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0, r.stdout[-3000:]
    assert {"tpot_ms_p90", "setup_s"} <= set(line["metrics"])
    notes = next(json.loads(l[len("notes: "):])
                 for l in r.stdout.splitlines() if l.startswith("notes: "))
    assert notes["check"]["reference"] == "benchmarks/references/mla_moe.py"
