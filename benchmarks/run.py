#!/usr/bin/env python3
"""The one command of the benchmark.

  python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
                            --trace <0|1> [--cpu-dry-run]

One run of one cell of ``BENCHMARK.json``: starts the system under test
(``server.py``, a child that holds the chip), warms up every program the
cell's traffic uses, offers the cell's load through the HTTP front door
for ``--seconds``, and prints as the LAST line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.

This process never imports JAX: it spawns children and speaks HTTP, so
the chip belongs to the server child alone. Everything belonging to one
configuration, traffic mix, cell or per-layer metric is a file found by
its name in ``BENCHMARK.json`` (see README.md); nothing here names one.

``--cpu-dry-run`` is a rehearsal switch: tiny widths of the same files on
the CPU. Its line says ``"platform": "cpu"`` and ``"dry_run": true`` and
carries no device metric; with ``--trace 1`` it fails (no chip, no trace).
"""
from __future__ import annotations

T_START = __import__("time").monotonic()

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import byname  # noqa: E402
import loadgen  # noqa: E402
import peaks  # noqa: E402
import procs  # noqa: E402
import stats  # noqa: E402
from procs import BenchFailure  # noqa: E402

# Every fixed wait of a run (README.md has the table). A limit is there to
# end a run whose child hangs; `procs.wait_for` fails at once when the child
# dies. None is sized to today's program: what grows with the model's SIZE
# (start-up) or with the program's SPEED (a faster program puts more events
# into the traced span, so collecting and reducing them takes longer) gets
# several times the longest wait seen on the chip (PERF.md section 3).
START_TIMEOUT_S = 1000.0      # a cold start compiles a dozen whole models
HTTP_UP_TIMEOUT_S = 120.0
SNAPSHOT_TIMEOUT_S = 60.0
TRACE_COLLECT_TIMEOUT_S = 1000.0   # after the window, for `trace done`
SERVER_EXIT_TIMEOUT_S = 60.0
TRACE_REDUCE_TIMEOUT_S = 1000.0
TRACE_DELAY_S = 4.0           # into the window
TRACE_SECONDS = 3.0
_LINE = {k: re.compile(rf"^{k}: (\{{.*\}})$", re.M)
         for k in ("engine up", "check", "serving", "trace done")}


def said(child: procs.Child, kind: str) -> dict | None:
    m = _LINE[kind].search(child.log_text())
    return json.loads(m.group(1)) if m else None


def load_json(*parts: str) -> dict:
    """A JSON file under the checkout's root."""
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def load_reader(name: str):
    return byname.module_with(
        os.path.join(HERE, "layer_metrics"), name, "read").read


def in_cell(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cache_entries(cache_dir: str | None) -> int:
    return len(glob.glob(os.path.join(cache_dir, "*"))) if cache_dir else 0


def snapshot(child: procs.Child, out_dir: str, tag: str) -> dict:
    path = os.path.join(out_dir, f"snapshot_{tag}.json")
    if os.path.exists(path):
        os.remove(path)
    child.tell(f"snapshot {path}")
    procs.wait_for(f"the {tag} snapshot", child,
                   lambda: os.path.exists(path), SNAPSHOT_TIMEOUT_S)
    with open(path) as f:
        return json.load(f)


def collected_trace(server: procs.Child) -> dict:
    """The server's `trace done` line: the profiler has handed over the
    span and the trace is on disk. A server that is alive is collecting and
    is waited for; one that died fails the run at once."""
    return procs.wait_for("the profiler to collect the trace", server,
                          lambda: said(server, "trace done"),
                          TRACE_COLLECT_TIMEOUT_S)


def reduce_trace(trace_dir: str, out_dir: str) -> dict:
    """In a child of its own, on the CPU, after the server has exited."""
    out = os.path.join(out_dir, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    cmd = [sys.executable, os.path.join(HERE, "trace_reduce.py"),
           "--reduce", trace_dir, "--out", out]
    try:
        r = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                           text=True, timeout=TRACE_REDUCE_TIMEOUT_S)
    except subprocess.TimeoutExpired as e:      # run() has killed the child
        tail = e.stderr or b""          # bytes here, whatever `text` says
        if isinstance(tail, bytes):
            tail = tail.decode(errors="replace")
        raise BenchFailure(
            f"the trace reduction (trace_reduce.py) did not finish within "
            f"{TRACE_REDUCE_TIMEOUT_S:g}s; the end of what it said:\n"
            f"{tail[-2000:]}") from None
    if r.returncode != 0:
        raise BenchFailure(f"trace reduction failed:\n{r.stderr[-2000:]}")
    with open(out) as f:
        return json.load(f)


def run(args) -> dict:
    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        raise BenchFailure(f"no workload {args.workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(conf_entry["file"])
    mix = load_json("benchmarks", "traffic", cell["traffic"] + ".json")
    knobs = load_json("benchmarks", "cells", cell["name"] + ".json")
    dry = args.cpu_dry_run
    if dry and args.trace:
        raise BenchFailure("--trace 1 needs the chip: a CPU run has no "
                           "device trace and never stands in for one")
    vocab = cfg["dry_run"]["vocab_size"] if dry else cfg["vocab_size"]
    out_dir = os.path.join(REPO, "chiprun_out", "bench", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["TPU_LOG_DIR"] = env.get("TPU_LOG_DIR", "disabled")
    if dry:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={cell['chips']}")
    port = procs.free_port()
    eng = cfg["engine"]
    longest = mix["prompt_len"].get("max") or mix["prompt_len"]["value"]
    seal_rows = eng["max_decode_slots"] * -(-longest // eng["page_size"])
    cmd = [sys.executable, os.path.join(HERE, "server.py"), "--config",
           os.path.join(REPO, conf_entry["file"]), "--port", str(port),
           "--seed", str(args.seed), "--seal-rows", str(seal_rows)
           ] + (["--dry-run"] if dry else [])
    server = procs.Child("server", cmd, env, REPO, out_dir)
    try:
        up = procs.wait_for("the engine", server,
                            lambda: said(server, "engine up"), START_TIMEOUT_S)
        want = "cpu" if dry else "tpu"
        if up["platform"] != want:
            raise BenchFailure(f"JAX gave the server {up['platform']!r} "
                               f"({up['device_kind']}), not {want!r}")
        if up["devices"] < cell["chips"] or up["tp"] != cfg["tp"]:
            raise BenchFailure(f"cell needs {cell['chips']} chips, server "
                               f"has {up['devices']} (tp={up['tp']})")
        if not dry:
            peaks.peaks_for(up["device_kind"])   # unknown kind: an error
        check = procs.wait_for("the reference check", server,
                               lambda: said(server, "check"), START_TIMEOUT_S)
        procs.wait_for("the HTTP service", server,
                       lambda: said(server, "serving") and procs.http_json(
                           port, "GET", "/health", timeout=5)[0] == 200,
                       HTTP_UP_TIMEOUT_S)
        warm = loadgen.warm_up(port, mix, vocab, args.seed)
        t_warm = time.monotonic()
        warm_failed = [r for r in warm if not r["ok"]]
        if warm_failed:
            raise BenchFailure(f"warm-up request failed: {warm_failed[0]}")

        before = {}
        entries0 = [0]

        def on_window_start():
            # the pre-roll is over: set-up ends here
            before["setup_s"] = time.monotonic() - T_START
            before["snap"] = snapshot(server, out_dir, "before")
            entries0[0] = cache_entries(up["compile_cache"])
            if args.trace:
                server.tell(f"trace {trace_dir} {TRACE_DELAY_S} "
                            f"{min(TRACE_SECONDS, args.seconds)}")

        if mix["loop"] == "open":
            log, t0 = loadgen.run_open(port, mix, vocab, knobs["rate_rps"],
                                       args.seconds, args.seed,
                                       on_window_start)
        elif mix["loop"] == "closed":
            log, t0 = loadgen.run_closed(port, mix, vocab, knobs["clients"],
                                         args.seconds, args.seed,
                                         on_window_start)
        else:
            raise BenchFailure(f"unknown loop kind {mix['loop']!r}")
        after = snapshot(server, out_dir, "after")
        entries1 = cache_entries(up["compile_cache"])
        traced = None
        if args.trace:
            t_close = time.monotonic()
            traced = collected_trace(server)
            trace_wait_s = time.monotonic() - t_close
        server.tell("stop")
        rc = server.wait(SERVER_EXIT_TIMEOUT_S)
        if rc != 0 or "engine round failed" in server.log_text():
            raise BenchFailure(f"server exited {rc} or logged a failed "
                               f"round; tail:\n{server.log_text()[-2000:]}")
    finally:
        server.kill()

    with open(os.path.join(out_dir, "requests.json"), "w") as f:
        json.dump(log, f)
    gen = stats.reduce_log(log, args.seconds)
    snap0 = before["snap"]
    lowered = after["lowered"] - snap0["lowered"]
    compiled = (after["backend_compiles"] - snap0["backend_compiles"]
                + entries1 - entries0[0])
    correct = bool(check["ok"] and gen["attempted"] > 0
                   and gen["failed"] == 0 and lowered == 0 and compiled == 0)
    notes = {
        "timeline_s": {"engine_up": up["t"], "check": check["t"],
                       "serving": said(server, "serving")["t"],
                       "warm_done": round(t_warm - T_START, 3),
                       "window_start": round(before["setup_s"], 3)},
        "check": check, "programs_lowered_in_window": lowered,
        "compiled_in_window": compiled,
        "lowered_names": after["lowered_names"][-lowered:] if lowered else [],
        "late_ms_p90": gen.get("late_ms_p90"),
        "usage_short": sum(1 for r in log if r.get("usage_short")),
        "tpot_ms_p50": gen.get("tpot_ms_p50"),
        "errors": sorted({r["error"] for r in log if r["error"]})[:5],
    }
    trace = reduce_failure = None
    if args.trace:
        t_reduce = time.monotonic()
        try:
            trace = reduce_trace(trace_dir, out_dir)
        except BenchFailure as e:
            reduce_failure = e      # after the notes, which say how long
        shutil.rmtree(trace_dir, ignore_errors=True)   # large; never kept
        # how near each wait of a traced run came to its limit
        notes.update(
            trace_collect_s=traced["collect_s"],
            trace_wait_s=round(trace_wait_s, 3),
            trace_wait_limit_s=TRACE_COLLECT_TIMEOUT_S,
            trace_reduce_s=round(time.monotonic() - t_reduce, 3),
            trace_reduce_limit_s=TRACE_REDUCE_TIMEOUT_S)
    notes["elapsed_s"] = round(time.monotonic() - T_START, 3)
    print("notes: " + json.dumps(notes), flush=True)
    if reduce_failure is not None:
        raise reduce_failure
    # every number `correct` compared, beside its limit, as the last lines
    # of stderr: what the driver's record keeps of a run that is not correct
    for name, got, rel, limit in (
            ("check.max_abs_logprob_diff", check["max_abs_logprob_diff"],
             "<=", check["tol_max"]),
            ("check.mean_abs_logprob_diff", check["mean_abs_logprob_diff"],
             "<=", check["tol_mean"]),
            ("attempted", gen["attempted"], ">", 0),
            ("failed", gen["failed"], "==", 0),
            ("programs_lowered_in_window", lowered, "==", 0),
            ("compiled_in_window", compiled, "==", 0)):
        print(f"compared: {name} {got} {rel} {limit}", file=sys.stderr)

    device = {"platform": up["platform"], "kind": up["device_kind"],
              "count": cell["chips"],
              "memory_peak_bytes": after["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": gen["attempted"],
              "failed": gen["failed"], "metrics": {}, "device": device}
    if dry:
        result["dry_run"] = True
    metrics = result["metrics"]
    if not args.trace:
        values = dict(gen, setup_s=before["setup_s"])
        for m in bench["end_to_end"]:
            if in_cell(m, cell["name"]) and values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        return result

    if not trace.get("busy_s"):
        raise BenchFailure("the trace shows no op on the device")
    # the traced span on the generator's clock (both processes read the
    # same wall clock; offsets of a millisecond do not matter here)
    wall_to_mono = time.monotonic() - time.time()
    span = (traced["t_start_wall"] + wall_to_mono - t0,
            traced["t_stop_wall"] + wall_to_mono - t0)

    def delta_hist_mean_ms(name: str):
        a, b = snap0["histograms"][name], after["histograms"][name]
        n = b["count"] - a["count"]
        return (b["sum"] - a["sum"]) / n * 1e3 if n > 0 else None

    sources = {
        "before": snap0, "after": after, "engine_up": up, "trace": trace,
        "trace_span": span, "gen": gen, "log": log, "config": cfg,
        "cell": cell, "knobs": knobs, "mix": mix, "seconds": args.seconds,
        "peaks": peaks, "byname": byname,
        "delta_hist_mean_ms": delta_hist_mean_ms,
    }
    for m in bench["per_layer"]:
        if not in_cell(m, cell["name"]):
            continue
        value = load_reader(m["name"])(sources)
        if value is not None:     # nothing to read: left out of the line
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["busy_s"] = trace["busy_s"]
    device["window_s"] = trace["window_s"]
    result["breakdown"] = {"device_ops": trace["device_ops"],
                           "idle_gaps": trace["idle_gaps"]}
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="TEST SWITCH: tiny widths on the CPU")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, "dynamo_tpu")):
        print("benchmarks/run.py: no dynamo_tpu/ beside benchmarks/: the "
              "benchmark drives the repo's program", file=sys.stderr)
        return 2
    try:
        result = run(args)
    except BenchFailure as e:
        print(f"benchmarks/run.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
