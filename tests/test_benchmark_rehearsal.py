"""The benchmark's own checks as tier-1 cases (PERF.md section 7 (a),
owed since PR 27): ``benchmarks/`` is outside ``testpaths``, so nothing
ran its contract tests, its arithmetic self-tests or a rehearsal of a
cell unless a builder did by hand. Each runs as the command a builder
would type, in a child process (the rehearsal's server child takes the
CPU "chip"; nothing here imports JAX), under a time limit of its own.

The rehearsals of whole cells (``test_the_new_cell_rehearses_on_the_cpu``,
one case a cell) live in ``tests/test_rehearsal_<id>.py``, one cell a
file, over ``tests/rehearsal.py``: the driver's run gives a file to one
worker, and six rehearsals in this file were 835 of its 863 s (PR 50).
"""
import json
import os
import sys

import pytest

from tests.rehearsal import REPO, run


def _benchmark_tests():
    """(file, function) of every test under ``benchmarks/``, read from the
    text (importing them here would import the harness)."""
    import ast

    found = []
    for name in sorted(os.listdir(os.path.join(REPO, "benchmarks"))):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(REPO, "benchmarks", name)) as f:
                found += [(name[:-3], node.name)
                          for node in ast.parse(f.read()).body
                          if isinstance(node, ast.FunctionDef)
                          and node.name.startswith("test_")]
    return found


@pytest.fixture(scope="module")
def benchmarks_own_run(tmp_path_factory):
    """``pytest benchmarks`` as a builder types it, ONCE, nothing left
    out: the outcome of every case by (file, function)."""
    import xml.etree.ElementTree as ET

    xml = str(tmp_path_factory.mktemp("bench") / "run.xml")
    r = run([sys.executable, "-m", "pytest", "benchmarks", "-q",
             "-p", "no:cacheprovider", "-p", "no:xdist",
             "--junitxml", xml], 180)
    assert os.path.exists(xml), (r.stdout + r.stderr)[-3000:]
    ran: dict = {}
    for case in ET.parse(xml).getroot().iter("testcase"):
        key = (case.get("classname").rsplit(".", 1)[-1],
               case.get("name").split("[", 1)[0])
        bad = [c.get("message", "")[:600] for c in case
               if c.tag in ("failure", "error")]
        ran.setdefault(key, []).append((case.get("name"), bad))
    return ran, (r.stdout + r.stderr)[-3000:]


@pytest.mark.parametrize("file,function", _benchmark_tests(),
                         ids=lambda v: v)
def test_the_benchmarks_own_tests_pass(benchmarks_own_run, file, function):
    """One tier-1 case a test function of ``benchmarks/``, so that one that
    fails stands red under its own name and hides no other. RED SINCE PR 41,
    for the next ``benchmark`` issue (PERF.md section 7 (a)):
    ``test_contract``'s two cases that draw their example from
    ``OPEN_UNDER_KNEE``, the alphabetically first open-loop cell, which is
    ``granite4h-ep2-d10.ragdoc`` now and not the cell whose two servers the
    example is (``mistral7b-w8.chat``); a ``model_config`` PR may edit no
    file of the benchmark. What they show is held below, by the cell's name."""
    ran, tail = benchmarks_own_run
    cases = ran.get((file, function))
    assert cases, f"{file}::{function} did not run\n{tail}"
    failed = {name: bad for name, bad in cases if bad}
    assert not failed, failed


@pytest.mark.parametrize("cmd,limit_s", [
    ([sys.executable, "-m", "benchmarks.trace_reduce", "--selftest"], 120),
    ([sys.executable, "benchmarks/stats.py", "--selftest"], 60),
], ids=["trace_reduce-selftest", "stats-selftest"])
def test_the_benchmarks_own_checks_pass(cmd, limit_s):
    r = run(cmd, limit_s)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]


@pytest.mark.parametrize("cell,entry,shown,registered_agrees", [
    ("mistral7b-w8.chat", "gen.carried_tok_s", "lower", True),
    ("mistral7b-w8.longprompt", "gen.carried_tok_s.longprompt-open",
     "lower", True),
    # accepted entries that say ``lower`` where the two servers show
    # ``higher``: theirs to repair is a ``benchmark`` issue's (PERF.md 7 (a))
    ("mla-moe-joyai-d5.chat-decode", "gen.carried_tok_s.chat-decode-open",
     "higher", False),
    ("xing4-mhc-d7.longdoc", "gen.carried_tok_s.longdoc-open", "higher",
     False),
    ("granite4h-ep2-d10.ragdoc", "gen.carried_tok_s.ragdoc-open", "higher",
     True),
    ("ling3-flash-ep8-d12.reasoning", "gen.carried_tok_s.reasoning-open",
     "higher", True),
    # answers of up to 768 tokens behind a 10 s pre-roll: what is carried
    # OUT decides (-134.3 slow / +8.6 fast at the cell's 12.8 req/s: the fast
    # server drains the pre-roll's backlog inside the window)
    ("jamba2-3b.chat-rate", "gen.carried_tok_s.chat-rate-open", "higher",
     True),
    # answers of 256-1536 tokens behind a 20 s pre-roll, as the reasoning
    # cell's: carried in as well as out
    ("phi4-mini-flash.thinklong", "gen.carried_tok_s.thinklong-open",
     "higher", True),
], ids=["chat", "longprompt", "chat-decode", "longdoc", "ragdoc",
        "reasoning", "chat-rate", "thinklong"])
def test_carried_tok_s_reads_the_way_its_entry_says(monkeypatch, cell, entry,
                                                     shown, registered_agrees):
    """``benchmarks/test_contract.py``'s two servers (cell 1's pace before
    and after PR 26) on each open-loop cell's OWN schedule, the cell given
    by name. Where the answers are short beside the pre-roll, the backlog
    carried INTO the window decides: both read above zero and the faster
    server the LOWER (the contract's example, asserted on cell 1 as that
    file writes it). Where the longest answers outlast the pre-roll
    (``ragdoc-open``: 384 tokens are 17 s at the slow pace, the pre-roll 6),
    what is carried OUT past the window's end decides: both read below
    zero and the faster server the HIGHER."""
    import importlib

    contract = importlib.import_module("benchmarks.test_contract")
    monkeypatch.setattr(contract, "OPEN_UNDER_KNEE", cell)
    read = contract._load("reader_" + entry, "layer_metrics",
                          entry + ".py").read
    byname = contract._load("bench_byname", "byname.py")
    carried = {}
    for name, server in (("slow", contract.SLOW), ("fast", contract.FAST)):
        log = contract.open_log(server)
        gen = contract.stats.reduce_log(log, contract.SECONDS)
        carried[name] = read({"gen": gen, "log": log, "byname": byname,
                              "seconds": contract.SECONDS})
        assert carried[name] == pytest.approx(
            gen["tok_s"] - contract.offered_tok_s(log))
    if shown == "lower":
        assert 0 < carried["fast"] < carried["slow"]
    elif cell in ("ling3-flash-ep8-d12.reasoning", "jamba2-3b.chat-rate",
                  "phi4-mini-flash.thinklong"):
        # a pre-roll of answers that outlast it carries IN as well as out:
        # the faster server still reads the higher, on either side of 0
        assert carried["slow"] < carried["fast"] and carried["slow"] < 0
    else:
        assert carried["slow"] < carried["fast"] < 0
    assert (contract.PER_LAYER[entry]["better"] == shown) is registered_agrees
    if cell == "mistral7b-w8.chat":
        contract.test_open_loop_under_the_knee_the_faster_server_reads_the_lower_tok_s()
        contract.test_carried_tok_s_reader_is_tok_s_less_the_windows_own_tokens()


def _sweep_line(tok_s, offered, p50, p90, failed=0):
    return {"failed": failed, "tok_s": tok_s, "offered_tok_s": offered,
            "ttft_ms_p50_first_half": p50[0], "ttft_ms_p50_second_half": p50[1],
            "ttft_ms_p90_first_half": p90[0], "ttft_ms_p90_second_half": p90[1]}


@pytest.mark.parametrize("line,bounded", [
    # lines of sweeps on the chip (PERF.md section 4, PR 37), rounded
    (_sweep_line(151.7, 152.0, (690, 516), (1246, 1202)), True),
    # level TTFT, completion 0.90: the window's last requests end after it
    (_sweep_line(153.4, 170.2, (647, 676), (1351, 1527)), True),
    (_sweep_line(168.0, 189.9, (592, 1118), (1026, 3382)), False),
    # a first half slower than the second is one trajectory, not a queue
    (_sweep_line(107.6, 110.9, (1724, 826), (2513, 1448)), True),
    # the median grows 1.67x while p90 grows 1.31x: a queue
    (_sweep_line(120.4, 138.7, (3937, 6568), (5662, 7393)), False),
    (_sweep_line(151.7, 152.0, (690, 516), (1246, 1202), failed=1), False),
    ({"failed": 0, "tok_s": 1.0, "offered_tok_s": 1.0}, False),
    # the long-context cell over 150 s (PERF.md section 6, PR 45): 0.6
    # req/s and, one step up, a queue by both TTFT arms
    (_sweep_line(98.8, 109.3, (1642, 2156), (2764, 3388)), True),
    (_sweep_line(100.7, 119.0, (1608, 3958), (3851, 8184)), False),
], ids=["keeps-up", "level-at-0.90", "tail-grows", "first-half-slower",
        "median-grows", "a-failure", "an-empty-half", "longctx-at-0.6",
        "longctx-at-0.65"])
def test_the_knee_rule_reads_the_recorded_sweeps(line, bounded):
    """ONE rule says whether a rate's backlog stayed bounded, for every
    open-loop cell (``tools/knee_sweep.py: bounded``)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import knee_sweep
    finally:
        sys.path.pop(0)
    assert knee_sweep.bounded(line) is bounded


def test_the_cause_tool_holds_picks_and_coefficients_at_tiny_widths():
    """``tools/mla_moe_mhc_cause.py`` (the emulation behind the check's
    limits: a rounded pass of the reference against its float32 pass)
    prints its four passes; with the picks held no pick differs and the
    distance is no larger, with the coefficients held none is apart."""
    r = run([sys.executable, "tools/mla_moe_mhc_cause.py", "--seed", "1",
             "--dry-run"], 600)
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(line) for line in r.stdout.splitlines()
            if line.startswith("{")]
    assert [tuple(row["held_to_float32"]) for row in rows] == [
        (), ("picks",), ("mix",), ("picks", "mix")]
    free, picks, mix, both = rows
    assert free["mean_abs_logprob_diff"] > 0
    assert picks["mean_abs_logprob_diff"] <= free["mean_abs_logprob_diff"]
    for row in (picks, both):
        assert not any(row["picks_differ_share_by_expert_layer"])
    for row in (mix, both):
        assert not any(row["h_res_apart_max_by_sublayer"])


def _stated_limits(path):
    """``CHECK_TOL_MAX`` / ``CHECK_TOL_MEAN`` as a reference's file states
    them, read from its text (importing it would take JAX)."""
    import ast

    with open(os.path.join(REPO, path)) as f:
        stated = {t.id: ast.literal_eval(node.value)
                  for node in ast.parse(f.read()).body
                  if isinstance(node, ast.Assign)
                  for t in node.targets if isinstance(t, ast.Name)
                  and t.id.startswith("CHECK_TOL_")}
    return stated["CHECK_TOL_MAX"], stated["CHECK_TOL_MEAN"]


@pytest.mark.parametrize("reading,worst,mean,passes", [
    # readings of the check on the chip (PERF.md section 6, PR 37): the
    # sound program's extremes over its seeds, then each control's
    # reading nearest the limits
    ("sound-largest-max", 5.2026, 0.3124, True),    # seed 1235265473
    ("sound-largest-mean", 3.9465, 0.3907, True),
    ("mix_bf16-largest", 3.9671, 0.3995, True),     # reported as passing
    ("hc_iters_1-smallest", 4.5400, 0.5736, False),
    ("hc_static-smallest", 5.2576, 1.7143, False),
    ("yarn_off-smallest", 7.6777, 3.6346, False),
    ("fp8-smallest", 4.9620, 1.5830, False),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_four_stream_checks_limits_stand_between_its_readings(
        reading, worst, mean, passes):
    """Whoever moves a limit of ``references/mla_moe_mhc.py`` is held to
    the record: every sound reading passes, every control that leaves out
    part of the mathematics, and the lower precision, fails."""
    tol_max, tol_mean = _stated_limits("benchmarks/references/mla_moe_mhc.py")
    assert (worst <= tol_max and mean <= tol_mean) is passes, reading


def _reader_sources(hists_after, kernels, config="ling3-flash-ep8-d12"):
    """What ``run.py`` hands a reader, as far as the delta-rule cell's own
    readers look (or another cell's, by its ``config``): the published
    configuration, counters that stood at zero when the window opened, a
    reduced trace of ten rounds."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import byname
    import peaks

    with open(os.path.join(REPO, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    zero = {name: {"sum": 0.0, "count": 0} for name in hists_after}
    return {
        "config": cfg, "byname": byname, "peaks": peaks,
        "before": {"histograms": zero}, "after": {"histograms": hists_after},
        "engine_up": {"flush_every": 4, "device_kind": "TPU v5 lite"},
        "trace": {"modules": {"jit_engine_round_seal": {"count": 10}},
                  "kernels": kernels},
    }


@pytest.mark.parametrize("kernels,stepped,share", [
    # 100 rounds of 4 steps x 48 lanes x 10 layers; the span holds ten of
    # them: 10 x 1920 states x 2 x 2 MiB over 0.5 s of the kernel
    ({"kda_step (f32[48,32,128], f32[49,32,128,128])": 0.5,
      "gmm bf16[384,768]": 9.0}, {"sum": 192000.0, "count": 100},
     10 * 1920 * 2 * 2097152 / 819e9 / 0.5 * 100),
    # a program without the kernel (the XLA step), or without the counter
    # (the parent): nothing to read, and no reader raises
    ({"gmm bf16[384,768]": 9.0}, {"sum": 192000.0, "count": 100}, None),
    ({"kda_step f32[48,32,128]": 0.5}, None, None),
], ids=["kernel-and-counter", "no-kernel", "no-counter"])
def test_the_step_kernels_roofline_reads_states_stepped_over_its_seconds(
        kernels, stepped, share):
    hists = {} if stepped is None else {
        "dynamo_kda_state_rows_stepped": stepped}
    sources = _reader_sources(hists, kernels)
    read = sources["byname"].module_with(
        os.path.join(REPO, "benchmarks", "layer_metrics"),
        "kernel.kda_step_roofline", "read").read
    got = read(sources)
    if share is None:
        assert got is None
    else:
        assert got == pytest.approx(share) and 0 < got < 100


@pytest.mark.parametrize("stepped,live,ratio", [
    # 100 rounds of 4 steps at 29 of 48 lanes live, ten delta-rule layers:
    # a program that steps every lane, then one that follows its work list
    (100 * 4 * 48 * 10.0, 100 * 4 * 29.0, 48 / 29),
    (100 * 4 * 29 * 10.0, 100 * 4 * 29.0, 1.0),
    # a program without the counter, a window without a round: nothing
    (None, 100 * 4 * 29.0, None),
    (100 * 4 * 29 * 10.0, None, None),
], ids=["every-lane", "the-work-list", "no-counter", "no-rounds"])
def test_the_states_stepped_are_read_over_the_live_lanes(stepped, live,
                                                         ratio):
    hists = {name: {"sum": total, "count": 100} for name, total in (
        ("dynamo_kda_state_rows_stepped", stepped),
        ("dynamo_engine_round_live_lane_steps", live)) if total is not None}
    sources = _reader_sources(hists, {})
    got = sources["byname"].module_with(
        os.path.join(REPO, "benchmarks", "layer_metrics"),
        "kda.states_stepped_over_live", "read").read(sources)
    if ratio is None:
        assert got is None
    else:
        assert got == pytest.approx(ratio)   # ~1.65, and 1.00


@pytest.mark.parametrize("config,stepped,live,ratio", [
    # 100 rounds of 4 steps at 10.3 of 32 lanes live, nine Mamba-2 layers:
    # a program that steps every lane (had it counted), then one that
    # follows its work list
    ("granite4h-ep2-d10", 100 * 4 * 32 * 9.0, 100 * 4 * 10.3, 32 / 10.3),
    ("granite4h-ep2-d10", 100 * 4 * 10.3 * 9, 100 * 4 * 10.3, 1.0),
    # a program without the counter (the parent), a window without a
    # round, a byte count without ``n_ssm`` (another stack's): nothing
    ("granite4h-ep2-d10", None, 100 * 4 * 10.3, None),
    ("granite4h-ep2-d10", 100 * 4 * 10.3 * 9, None, None),
    ("ling3-flash-ep8-d12", 100 * 4 * 29 * 10.0, 100 * 4 * 29.0, None),
], ids=["every-lane", "the-work-list", "no-counter", "no-rounds",
        "no-mamba2-layers"])
def test_the_mamba2_states_stepped_are_read_over_the_live_lanes(
        config, stepped, live, ratio):
    hists = {name: {"sum": total, "count": 100} for name, total in (
        ("dynamo_ssm_state_rows_stepped", stepped),
        ("dynamo_engine_round_live_lane_steps", live)) if total is not None}
    sources = _reader_sources(hists, {}, config)
    got = sources["byname"].module_with(
        os.path.join(REPO, "benchmarks", "layer_metrics"),
        "ssm.states_stepped_over_live.ragdoc-open", "read").read(sources)
    if ratio is None:
        assert got is None
    else:
        assert got == pytest.approx(ratio)   # ~3.1, and 1.00


@pytest.mark.parametrize("read,own,share", [
    # 100 rounds of 4 steps, 1.9 of 8 lanes live at ~330 rows each: a grid
    # over every lane's chunks (had it counted) reads every lane's first
    # 512-row chunk, the work list the live lanes' alone
    (100 * 4 * 8 * 512.0, 100 * 4 * 1.9 * 330, 1.9 * 330 / (8 * 512) * 100),
    (100 * 4 * 1.9 * 512, 100 * 4 * 1.9 * 330, 330 / 512 * 100),
    # a program without the counters (the parent), a window without a round
    (None, None, None),
    (0.0, 0.0, None),
], ids=["every-lane", "the-work-list", "no-counter", "no-rounds"])
def test_the_chat_cells_decode_attention_rows_are_read_over_the_live_lanes(
        read, own, share):
    hists = {name: {"sum": total, "count": 100} for name, total in (
        ("dynamo_decode_attn_rows_read", read),
        ("dynamo_decode_attn_rows_live", own)) if total is not None}
    sources = _reader_sources(hists, {}, "mistral7b-w8")
    got = sources["byname"].module_with(
        os.path.join(REPO, "benchmarks", "layer_metrics"),
        "step.decode_attn_live_share.chat-open", "read").read(sources)
    if share is None:
        assert got is None
    else:
        assert got == pytest.approx(share)   # ~15 %, and 64 %


def test_the_reasoning_cells_counter_readers_count_expert_layers_only():
    """Ten of the twelve held layers route: the held experts touched a
    step are read over 10 x 64, and the byte count's parts say what a step
    of 40 live lanes at 2000 rows moves."""
    hists = {"dynamo_moe_experts_touched": {"sum": 100 * 4 * 320.0,
                                            "count": 100},
             "dynamo_moe_tokens_routed": {"sum": 100 * 4 * 400.0,
                                          "count": 100},
             "dynamo_moe_picks_routed": {"sum": 100 * 4 * 3200.0,
                                         "count": 100}}
    sources = _reader_sources(hists, {})
    metrics = os.path.join(REPO, "benchmarks", "layer_metrics")
    reader = lambda name: sources["byname"].module_with(  # noqa: E731
        metrics, name, "read").read
    assert reader("moe.experts_touched_share.reasoning-open")(
        sources) == pytest.approx(320 / 640 * 100)
    assert reader("moe.held_pick_share.reasoning-open")(
        sources) == pytest.approx(12.5)
    count = sources["byname"].module_with(
        os.path.join(REPO, "benchmarks", "bytes"), "kda_mla_moe",
        "decode_parts")
    parts = count.decode_parts(sources, [2000.0] * 40)
    assert parts["state"] == 2 * 40 * 10 * (2097152 + 73728)
    assert parts["rows"] == 40 * 2000 * 640 * 2 * 2
    assert parts["experts"] == 320 * 3 * 2560 * 768 * 2
    # 10 x 52.6 M + 2 x 31.9 M + 2 x 47.2 M + 10 x 7.2 M + 50.3 M
    assert parts["weights"] == pytest.approx(2 * 807.0e6, rel=0.01)
    nbytes, ops, labels = count.gmm_decode(sources)
    assert labels == ("gmm bf16[384,768]", "gmm bf16[384,2560]")
    assert nbytes == parts["experts"] and ops == 400 * 3 * 2 * 2560 * 768


_ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


@pytest.mark.parametrize("section", sorted(_ENTRY_KEYS))
def test_benchmark_json_keeps_the_form_the_driver_refuses_without(section):
    """What the driver checks of ``BENCHMARK.json`` before any run, from
    the file alone (PR 47 was refused once for a configuration's ``why`` of
    220 characters): a name is at most 64 of ``[A-Za-z0-9_.-]``, a ``why``,
    ``layer`` or ``source`` 1 to 200 printable characters on one line, a
    unit 1 to 16 characters without a space, no key beyond the entry's own,
    no name twice, the whole file under 64 KiB."""
    import re

    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        entries = json.load(f)[section]
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert not set(e) - _ENTRY_KEYS[section], e["name"]
        words = [e["name"], *e.get("reduced", []), *e.get("workloads", []),
                 *(e[k] for k in ("config", "traffic", "moves") if k in e)]
        assert all(name.fullmatch(w) for w in words), e["name"]
        assert len(e.get("reduced", [])) <= 16, e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                text = e[key]
                assert 1 <= len(text) <= 200 and text.isprintable(), (
                    e["name"], key, len(text))
        if "unit" in e:
            assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", e["unit"]), e
            assert e["better"] in ("lower", "higher"), e["name"]
