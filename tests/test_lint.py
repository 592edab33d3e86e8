"""dynlint self-test: every rule fires on a known-bad fixture, the
suppression pragma works, the CLI exit/JSON contract holds, and — the
actual gate — the whole tree lints clean with zero unsuppressed
findings."""
import functools
import json
import os
import subprocess
import sys
from pathlib import Path

from dynamo_tpu.lint import all_rules, lint_paths, lint_source

REPO_ROOT = Path(__file__).resolve().parents[1]
DYNLINT = REPO_ROOT / "tools" / "dynlint.py"


def rules_fired(source: str, path: str) -> set:
    return {f.rule for f in lint_source(source, path, root=str(REPO_ROOT))
            if not f.suppressed}


# ---------------------------------------------------------------------------
# one known-bad fixture per rule

def test_dtl001_fires_on_host_effect_in_jitted_fn():
    bad = (
        "import time\n"
        "import jax\n"
        "\n"
        "def _step_impl(x):\n"
        "    return x * time.time()\n"
        "\n"
        "step = jax.jit(_step_impl)\n"
    )
    assert "DTL001" in rules_fired(bad, "dynamo_tpu/models/bad_model.py")


def test_dtl001_ignores_untraced_code():
    ok = (
        "import time\n"
        "\n"
        "def host_side(x):\n"
        "    return x * time.time()\n"
    )
    assert "DTL001" not in rules_fired(ok, "dynamo_tpu/models/ok_model.py")


def test_dtl002_fires_on_blocking_call_in_async_def():
    bad = (
        "import time\n"
        "\n"
        "async def tick():\n"
        "    time.sleep(0.1)\n"
    )
    assert "DTL002" in rules_fired(bad, "dynamo_tpu/runtime/bad_loop.py")


def test_dtl003_fires_on_unguarded_field_access():
    bad = (
        "import threading\n"
        "\n"
        "class TpuEngine:\n"
        "    def __init__(self):\n"
        "        self._wt_lock = threading.Lock()\n"
        "        self._waiting_tokens = {}\n"
        "\n"
        "    def peek(self):\n"
        "        return len(self._waiting_tokens)\n"
    )
    assert "DTL003" in rules_fired(bad, "dynamo_tpu/engine/engine.py")


def test_dtl003_accepts_guarded_access():
    ok = (
        "import threading\n"
        "\n"
        "class TpuEngine:\n"
        "    def __init__(self):\n"
        "        self._wt_lock = threading.Lock()\n"
        "        self._waiting_tokens = {}\n"
        "\n"
        "    def peek(self):\n"
        "        with self._wt_lock:\n"
        "            return len(self._waiting_tokens)\n"
    )
    assert "DTL003" not in rules_fired(ok, "dynamo_tpu/engine/engine.py")


def test_dtl004_fires_on_unaccounted_device_put():
    bad = (
        "import jax\n"
        "\n"
        "class Engine:\n"
        "    def push(self, x):\n"
        "        return jax.device_put(x)\n"
    )
    assert "DTL004" in rules_fired(bad, "dynamo_tpu/engine/bad_engine.py")


def test_dtl004_accepts_accounted_device_put():
    ok = (
        "import jax\n"
        "\n"
        "class Engine:\n"
        "    def push(self, x):\n"
        "        self.dispatch_counts['fetch'] += 1\n"
        "        return jax.device_put(x)\n"
    )
    assert "DTL004" not in rules_fired(ok, "dynamo_tpu/engine/ok_engine.py")


def test_dtl005_fires_on_invalid_family_type():
    bad = (
        "from dynamo_tpu.telemetry.metrics import CounterRegistry\n"
        "\n"
        "FAMILIES = (\n"
        "    ('dynamo_bogus_total', 'kounter', 'bogus things'),\n"
        ")\n"
        "BOGUS = CounterRegistry(FAMILIES, label='bogus')\n"
    )
    assert "DTL005" in rules_fired(bad, "dynamo_tpu/bogus/metrics.py")


def test_dtl005_fires_on_a_registry_render_planes_does_not_name():
    """The one list of metric planes (telemetry/metrics.py:
    render_planes) and the three scrape surfaces that call it, plus one
    module whose registry the list leaves out."""
    from dynamo_tpu.lint.core import ProjectIndex, _run
    from dynamo_tpu.lint.metrics_contract import (
        _PLANES,
        _SURFACES,
        MetricsContractRule,
    )

    index = ProjectIndex(str(REPO_ROOT))
    for rel in (_PLANES, *_SURFACES):
        index.add_file("dynamo_tpu/" + rel)
    rule = [MetricsContractRule()]
    assert [f for f in _run(index, rule) if "render_planes" in f.message] == []
    index.add_source(
        "dynamo_tpu/bogus/metrics.py",
        "from dynamo_tpu.telemetry.metrics import CounterRegistry\n"
        "BOGUS = CounterRegistry((), label='bogus')\n")
    missed = [f for f in _run(index, rule) if "render_planes" in f.message]
    assert [f.path for f in missed] == ["dynamo_tpu/bogus/metrics.py"]
    assert "BOGUS" in missed[0].message


def test_dtl006_fires_on_unregistered_wire_exception():
    bad = (
        "class FlakyLinkError(ConnectionError):\n"
        "    pass\n"
    )
    assert "DTL006" in rules_fired(bad, "dynamo_tpu/runtime/bad_errors.py")


def test_dtl006_fires_on_unregistered_nack_kind():
    bad = (
        "def nack(writer):\n"
        "    frame = {'ok': False, 'kind': 'mystery'}\n"
        "    return frame\n"
    )
    assert "DTL006" in rules_fired(bad, "dynamo_tpu/engine/kv_transfer.py")


def test_dtl007_fires_on_silent_broad_except():
    bad = (
        "def f(g):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert "DTL007" in rules_fired(bad, "dynamo_tpu/runtime/bad_except.py")


def test_dtl007_accepts_logged_broad_except():
    ok = (
        "import logging\n"
        "log = logging.getLogger(__name__)\n"
        "\n"
        "def f(g):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        log.debug('probe failed', exc_info=True)\n"
    )
    assert "DTL007" not in rules_fired(ok, "dynamo_tpu/runtime/ok_except.py")


# ---------------------------------------------------------------------------
# suppression pragma

BAD_EXCEPT = (
    "def f(g):\n"
    "    try:\n"
    "        g()\n"
    "    except Exception:{pragma}\n"
    "        pass\n"
)


def test_trailing_pragma_suppresses_and_captures_justification():
    src = BAD_EXCEPT.format(
        pragma="  # dynlint: disable=DTL007 — test probe is best-effort")
    fs = [f for f in lint_source(src, "dynamo_tpu/runtime/x.py",
                                 root=str(REPO_ROOT))
          if f.rule == "DTL007"]
    assert len(fs) == 1
    assert fs[0].suppressed
    assert fs[0].justification == "test probe is best-effort"


def test_standalone_pragma_guards_next_line():
    src = (
        "def f(g):\n"
        "    try:\n"
        "        g()\n"
        "    # dynlint: disable=DTL007 — fixture\n"
        "    except Exception:\n"
        "        pass\n"
    )
    fs = [f for f in lint_source(src, "dynamo_tpu/runtime/x.py",
                                 root=str(REPO_ROOT))
          if f.rule == "DTL007"]
    assert len(fs) == 1 and fs[0].suppressed


def test_file_pragma_suppresses_whole_file():
    src = ("# dynlint: disable-file=DTL007 — fixture file\n"
           + BAD_EXCEPT.format(pragma=""))
    fs = [f for f in lint_source(src, "dynamo_tpu/runtime/x.py",
                                 root=str(REPO_ROOT))
          if f.rule == "DTL007"]
    assert len(fs) == 1 and fs[0].suppressed


def test_pragma_only_suppresses_named_rule():
    src = BAD_EXCEPT.format(pragma="  # dynlint: disable=DTL001")
    fs = [f for f in lint_source(src, "dynamo_tpu/runtime/x.py",
                                 root=str(REPO_ROOT))
          if f.rule == "DTL007"]
    assert len(fs) == 1 and not fs[0].suppressed


# ---------------------------------------------------------------------------
# the gate: the tree lints clean

# what the gate covers: the package, the tools, and the chip smoke
LINTED = ["dynamo_tpu", "tools", "chip_smoke.py"]


@functools.cache
def _tree_findings():
    """ONE pass over the tree (~20 s) for the cases that read it."""
    return lint_paths(LINTED, root=str(REPO_ROOT))


def test_tree_has_zero_unsuppressed_findings():
    active = [f for f in _tree_findings() if not f.suppressed]
    assert not active, "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in active)


def test_every_suppression_carries_a_justification():
    bare = [f for f in _tree_findings()
            if f.suppressed and not f.justification]
    assert not bare, "\n".join(
        f"{f.path}:{f.line}: {f.rule} suppressed without justification"
        for f in bare)


def test_all_seven_rules_are_registered():
    assert {r.ID for r in all_rules()} == {
        "DTL001", "DTL002", "DTL003", "DTL004", "DTL005", "DTL006",
        "DTL007",
    }


# ---------------------------------------------------------------------------
# CLI exit-status + JSON contract

def run_cli(*args, cwd=None):
    return subprocess.run(
        [sys.executable, str(DYNLINT), *args],
        capture_output=True, text=True, cwd=cwd or str(REPO_ROOT),
    )


def test_cli_clean_tree_exits_zero_with_json():
    p = run_cli("--format", "json", "dynamo_tpu", "tools")
    assert p.returncode == 0, p.stdout + p.stderr
    data = json.loads(p.stdout)
    assert data["exit_code"] == 0
    assert data["counts"]["active"] == 0
    # suppressed findings still appear in JSON, with justifications
    for f in data["findings"]:
        assert f["suppressed"] and f.get("justification")


def test_cli_findings_exit_one_with_locations(tmp_path):
    (tmp_path / "bad.py").write_text(
        "def f(g):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    p = run_cli("--root", str(tmp_path), "--format", "json", "bad.py")
    assert p.returncode == 1, p.stdout + p.stderr
    data = json.loads(p.stdout)
    assert data["exit_code"] == 1
    assert data["counts"]["by_rule"] == {"DTL007": 1}
    f = data["findings"][0]
    assert (f["rule"], f["path"], f["line"]) == ("DTL007", "bad.py", 4)


def test_cli_usage_errors_exit_two(tmp_path):
    assert run_cli("--rules", "DTL999", "dynamo_tpu").returncode == 2
    assert run_cli("no/such/path.py").returncode == 2


def test_cli_syntax_error_is_a_finding(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    p = run_cli("--root", str(tmp_path), "--format", "json", "broken.py")
    assert p.returncode == 1
    data = json.loads(p.stdout)
    assert any(f["rule"] == "DTL000" for f in data["findings"])


def test_cli_rules_filter_restricts_output(tmp_path):
    (tmp_path / "bad.py").write_text(
        "import time\n"
        "async def tick():\n"
        "    time.sleep(0.1)\n"
        "    try:\n"
        "        tick\n"
        "    except Exception:\n"
        "        pass\n"
    )
    rel = os.path.join("runtime", "bad.py")
    (tmp_path / "runtime").mkdir()
    (tmp_path / rel).write_text((tmp_path / "bad.py").read_text())
    p = run_cli("--root", str(tmp_path), "--format", "json",
                "--rules", "DTL002", rel)
    data = json.loads(p.stdout)
    assert {f["rule"] for f in data["findings"]} == {"DTL002"}


# ---------------------------------------------------------------------------
# the test tier's own convention: no file is the run's pole

def test_no_test_file_holds_more_than_a_third_of_the_budgeted_wall():
    """The driver's run hands a FILE to one worker, so the longest file
    bounds the wall whatever the order: a file whose recorded seconds
    (``tests/seconds.json``, from a whole run's junit file by
    ``tools/test_seconds.py``) pass the ceiling is split by what it holds,
    as ``tests/test_lowering_<config>.py`` and
    ``tests/test_rehearsal_<id>.py`` are, before a run is cut by it. The
    ceiling is 400 s, about a third of the budgeted wall (ROADMAP D9: 1100
    s) and not a round 300: the longest file is ONE case that cannot be
    split, the ``thinklong`` rehearsal, 216 s alone and 232-370 s beside
    five other files (my four whole runs, PR 62), 126 s of it the cell's
    own check. Reads the table; times nothing. The table names files that exist (a renamed
    file would sort as new)."""
    with open(REPO_ROOT / "tests" / "seconds.json") as f:
        seconds = json.load(f)
    assert seconds, "an empty table orders nothing"
    over = {name: s for name, s in seconds.items() if s > 400}
    assert not over, f"split these by what they hold: {over}"
    gone = [name for name in seconds
            if not (REPO_ROOT / "tests" / name).exists()]
    assert not gone, f"re-make the table (tools/test_seconds.py): {gone}"
