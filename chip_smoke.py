#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts,
serves and shuts down on a TPU chip. No network, no files outside the
checkout, random weights from the seed, the test tokenizer.

  python3 chip_smoke.py               one chip: llama3_1b, tp=1
  python3 chip_smoke.py --chips 4     the four-chip host: one tp=4 engine
  python3 chip_smoke.py --replicas 4  the four-chip host: `serve` graph
                                      of four one-chip workers + frontend
  python3 chip_smoke.py --cpu-dry-run (any of the above) the same script
                                      at --model-config tiny on the CPU —
                                      for typos, before chip time is spent

What it does, through the entry points a user would call:

 1. starts the real server — ``python -m dynamo_tpu.cli run in=http
    out=tpu --model-config llama3_1b`` (bf16, all 16 layers, vocab
    128256, the CLI's default engine sizes) — and holds it to its
    ``engine up:`` line: platform ``tpu``, the compiled Pallas decode
    kernel, and (``--chips 4``) weights/ctx/pool spread over four devices;
 2. drives it over HTTP as a client: a streaming short prompt (time to
    the first served token, cold), a prompt of several hundred words (a
    second prefill bucket), a burst of concurrent requests (the batched
    [K, T] prefill program), a resubmission extending the long prompt
    (prefix hit: pool->ctx page load + the continuation prefill), each
    with ``nvext.ignore_eos`` and enough ``max_tokens`` to span many fused
    rounds. Every response must be 200 with ``usage.completion_tokens``
    equal to what was asked; streamed chunks must arrive; the engine's
    flight recorder must show the batched and the continuation prefill;
    the log must show no failed engine round;
 3. stops the server (SIGINT) and requires exit code 0;
 4. in a second child, on the chip: the compiled Pallas decode kernel vs
    ``flash_decode_attention_reference`` on seeded inputs at the
    llama3_1b serving shape, dense and int8 ctx — a kernel that compiles
    and computes the wrong thing does not pass.

One process per chip: this parent never imports JAX — it only spawns
children and speaks HTTP — and its children hold the chip one at a time.
Exit code 0 and, as the last line of stdout, one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the device
as the server's JAX reported it. Any failure — including ``platform !=
"tpu"`` — exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import glob
import http.client
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
MODEL = "smoke"
DEADLINE_S = 1100.0          # the contract allows 1200 s, compiles included
SERVER_START_TIMEOUT_S = 600.0
REQUEST_TIMEOUT_S = 600.0    # the first request of each shape compiles
# decode lengths: multiples and non-multiples of flush_every=4, all many
# fused rounds long
STREAM_TOKENS, LONG_TOKENS, BURST_TOKENS, EXTEND_TOKENS = 48, 40, 24, 30
BURST = 6

# kernel-vs-reference tolerances, as max|got - want| / max|want|:
# both paths take bf16 q/k/v, accumulate in f32, round the softmax
# weights to bf16 and emit bf16 — but the kernel rounds p against each
# chunk's running max and rescales, the reference against the global max,
# so they differ by a few bf16 ulps (2^-8 relative) of the output scale.
KERNEL_TOL_BF16 = 2e-2
# int8 ctx adds one more difference: the reference rounds every
# dequantized K/V element to bf16 before the dots, the kernel keeps the
# int8 payload exact and applies the f32 scale to the score columns.
KERNEL_TOL_INT8 = 4e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# children


def child_env(dry_run: bool, devices: int = 1) -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if dry_run:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}")
    return env


class Child:
    """A process group this script started and will stop."""

    def __init__(self, name: str, cmd: list[str], env: dict[str, str]):
        self.name = name
        self.log_path = os.path.join(OUT_DIR, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def interrupt_and_wait(self, timeout_s: float) -> int:
        """SIGINT (the CLI's clean-shutdown path), wait, return the code."""
        self.proc.send_signal(signal.SIGINT)
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name} did not exit within {timeout_s:.0f}s of SIGINT"
            ) from None

    def kill(self) -> None:
        """Stop the child and everything under it. `serve` starts its own
        children in their own sessions, so the process group is not
        enough: walk /proc for descendants BEFORE killing the parent
        (orphans are re-parented and no longer traceable)."""
        if self.proc.poll() is None:
            victims = _descendants(self.proc.pid)
            for pid in [self.proc.pid] + victims:
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            self.proc.wait()
        self._log.close()


def _descendants(root: int) -> list[int]:
    children: dict[int, list[int]] = {}
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(stat) as f:
                # pid (comm) state ppid ...; comm may contain spaces
                pid_s, rest = f.read().split(" (", 1)
                ppid = int(rest.rsplit(") ", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue  # raced with an exiting process
        children.setdefault(ppid, []).append(int(pid_s))
    out, stack = [], [root]
    while stack:
        for pid in children.get(stack.pop(), []):
            out.append(pid)
            stack.append(pid)
    return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# HTTP client (stdlib only)


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = REQUEST_TIMEOUT_S) -> tuple[int, dict]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, path, payload,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, {"raw": raw[:500].decode(errors="replace")}
    finally:
        conn.close()


def http_sse(port: int, path: str, body: dict,
             timeout: float = REQUEST_TIMEOUT_S
             ) -> tuple[int, list[dict], float | None]:
    """POST a streaming request; returns (status, chunks, monotonic time
    the first content chunk arrived)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, [{"raw": resp.read()[:500].decode(
                errors="replace")}], None
        chunks: list[dict] = []
        t_first = None
        for line in resp:
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                break
            chunk = json.loads(data)
            chunks.append(chunk)
            if t_first is None and _delta_text(chunk):
                t_first = time.monotonic()
        return resp.status, chunks, t_first
    finally:
        conn.close()


def _delta_text(chunk: dict) -> str:
    choices = chunk.get("choices") or [{}]
    return (choices[0].get("delta") or {}).get("content") or ""


def chat_body(prompt: str, max_tokens: int, stream: bool = False) -> dict:
    body = {
        "model": MODEL,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
        "nvext": {"ignore_eos": True},
    }
    if stream:
        body["stream"] = True
        body["stream_options"] = {"include_usage": True}
    return body


def words(rng: random.Random, n: int) -> str:
    # the test tokenizer's vocabulary: one token per word
    return " ".join(f"w{rng.randrange(100)}" for _ in range(n))


def check_completion(what: str, status: int, body: dict, asked: int) -> None:
    check(status == 200, f"{what}: HTTP {status}: {str(body)[:300]}")
    got = (body.get("usage") or {}).get("completion_tokens")
    check(got == asked,
          f"{what}: usage.completion_tokens={got}, asked {asked}")


# ---------------------------------------------------------------------------
# what the server says about itself

_ENGINE_UP = re.compile(r"^engine up: (\{.*\})$", re.M)


def engine_reports(log_text: str) -> list[dict]:
    """The JSON each `run out=tpu` process prints once its engine is
    built (launch/run.py build_chain)."""
    return [json.loads(m.group(1)) for m in _ENGINE_UP.finditer(log_text)]


def check_engine_report(rep: dict, tp: int, dry_run: bool) -> None:
    want_platform, want_attn = ("cpu", "reference") if dry_run else (
        "tpu", "pallas")
    check(rep["platform"] == want_platform,
          f"JAX gave the server platform {rep['platform']!r} "
          f"({rep['device_kind']}), not {want_platform!r}: no accelerator")
    check(rep["decode_attention"] == want_attn,
          f"engine compiled decode attention {rep['decode_attention']!r}, "
          f"not {want_attn!r}")
    check(rep["tp"] == tp and rep["devices"] >= tp,
          f"server runs tp={rep['tp']} on {rep['devices']} device(s), "
          f"wanted tp={tp}")
    if tp > 1:
        # kv heads (axis 1) split over tp: every device holds 1/tp of ctx
        check(rep["ctx_shard"][1] * tp == rep["ctx_shape"][1],
              f"ctx is not sharded over tp={tp}: shard {rep['ctx_shard']} "
              f"of {rep['ctx_shape']}")
        if not dry_run:  # the CPU reports no memory statistics
            hbm = rep["hbm_gb"]
            check(len(hbm) == tp and min(hbm) > 0
                  and max(hbm) < 1.5 * min(hbm),
                  f"weights/ctx/pool are not spread over {tp} devices: "
                  f"HBM in use per device {hbm} GB")


def compile_cache_dir() -> str:
    sys.path.insert(0, REPO)
    from dynamo_tpu.compile_cache import cache_dir  # imports no JAX

    return cache_dir()


def cache_entries() -> int:
    return len(glob.glob(os.path.join(compile_cache_dir(), "*")))


# ---------------------------------------------------------------------------
# the legs


def wait_for(what: str, child: Child, ready, timeout_s: float):
    """Poll ``ready()`` until truthy; fail if the child dies first (or,
    for `serve`, gives up restarting one of ITS children)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise SmokeFailure(
                f"{child.name} exited with code {child.proc.returncode} "
                f"before {what}; tail of {child.log_path}:\n"
                + child.log_text()[-3000:]
            )
        if "restarts; giving up" in child.log_text():
            raise SmokeFailure(
                f"{child.name} gave up on a child before {what}; tail of "
                f"{child.log_path}:\n" + child.log_text()[-3000:]
            )
        try:
            got = ready()
        except (OSError, http.client.HTTPException):
            got = None
        if got:
            return got
        time.sleep(0.5)
    raise SmokeFailure(
        f"timed out after {timeout_s:.0f}s waiting for {what}; tail of "
        f"{child.log_path}:\n" + child.log_text()[-3000:]
    )


def drive_requests(port: int, server: Child, flight: bool) -> float:
    """The client's side of the smoke. Returns seconds from server spawn
    to the first streamed token."""
    rng = random.Random(21)
    seq_mark = -1

    def flight_kinds() -> list[dict]:
        nonlocal seq_mark
        status, body = http_json(port, "GET", "/debug/flight", timeout=30)
        check(status == 200, f"/debug/flight: HTTP {status}")
        events = [e for e in body["engines"][MODEL]["events"]
                  if e["seq"] > seq_mark]
        if events:
            seq_mark = events[-1]["seq"]
        return events

    # 1. streaming, short prompt: the first served token, cold
    status, chunks, t_first = http_sse(
        port, "/v1/chat/completions",
        chat_body(words(rng, 12), STREAM_TOKENS, stream=True))
    check(status == 200, f"stream: HTTP {status}: {str(chunks)[:300]}")
    content = [c for c in chunks if _delta_text(c)]
    check(len(content) >= 2 and t_first is not None,
          f"stream: {len(content)} content chunks arrived, expected many")
    usage = [c["usage"] for c in chunks if c.get("usage")]
    check(bool(usage) and usage[-1].get("completion_tokens") == STREAM_TOKENS,
          f"stream: usage chunk {usage[-1:]} != {STREAM_TOKENS} tokens")
    first_token_s = t_first - server.t_spawn
    say(f"stream ok: {len(content)} chunks, {STREAM_TOKENS} tokens, first "
        f"served token {first_token_s:.1f}s after server spawn")

    # 2. non-streaming, several hundred words: a second prefill bucket
    long_prompt = words(rng, 300)
    status, body = http_json(port, "POST", "/v1/chat/completions",
                             chat_body(long_prompt, LONG_TOKENS))
    check_completion("long prompt", status, body, LONG_TOKENS)
    prompt_tokens = body["usage"]["prompt_tokens"]
    check(prompt_tokens >= 300, f"long prompt tokenized to {prompt_tokens}")
    say(f"long prompt ok: {prompt_tokens} prompt tokens, "
        f"{LONG_TOKENS} completion tokens")
    if flight:
        flight_kinds()  # advance the mark past the solo prefills

    # 3. a burst of concurrent requests: the batched [K, T] prefill
    results: list = [None] * BURST
    prompts = [words(rng, 40) for _ in range(BURST)]

    def one(i: int) -> None:
        try:
            results[i] = http_json(port, "POST", "/v1/chat/completions",
                                   chat_body(prompts[i], BURST_TOKENS))
        except (OSError, http.client.HTTPException) as e:
            results[i] = (0, {"error": repr(e)})

    threads = [threading.Thread(target=one, args=(i,)) for i in range(BURST)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(REQUEST_TIMEOUT_S)
    for i, res in enumerate(results):
        check(res is not None, f"burst[{i}]: no response")
        check_completion(f"burst[{i}]", res[0], res[1], BURST_TOKENS)
    if flight:
        batched = [e for e in flight_kinds() if e["kind"] == "prefill_batch"]
        check(bool(batched), "burst: the engine never dispatched the "
              "batched prefill program (no prefill_batch flight event)")
        say(f"burst ok: {BURST} concurrent requests, batched prefill "
            f"groups {[len(e['slots']) for e in batched]}")
    else:
        say(f"burst ok: {BURST} concurrent requests")

    # 4. the long prompt again, extended: prefix hit -> load_ctx_pages +
    # the continuation prefill
    status, body = http_json(
        port, "POST", "/v1/chat/completions",
        chat_body(long_prompt + " " + words(rng, 24), EXTEND_TOKENS))
    check_completion("extended prompt", status, body, EXTEND_TOKENS)
    if flight:
        cont = [e for e in flight_kinds()
                if e["kind"] == "prefill" and e.get("start", 0) > 0]
        check(bool(cont), "extended prompt: no continuation prefill "
              "(prefix cache missed: no prefill flight event with start>0)")
        say(f"prefix hit ok: continuation prefill from position "
            f"{cont[0]['start']}")
    else:
        say("extended prompt ok")
    return first_token_s


def stop_server(server: Child) -> None:
    rc = server.interrupt_and_wait(90.0)
    log = server.log_text()
    check(rc == 0, f"{server.name} exited with code {rc} after SIGINT; "
          f"tail:\n{log[-2000:]}")
    check("engine round failed" not in log,
          f"{server.name} logged a failed engine round; see "
          f"{server.log_path}")
    say(f"{server.name} shut down cleanly (exit 0)")


def single_server_leg(tp: int, dry_run: bool) -> tuple[dict, float]:
    """One `run in=http out=tpu` process: tp=1 on one chip, or tp=4."""
    port = free_port()
    cmd = [sys.executable, "-m", "dynamo_tpu.cli", "run", "in=http",
           "out=tpu", "--model-name", MODEL, "--http-port", str(port),
           "--tensor-parallel-size", str(tp)]
    if dry_run:
        cmd += ["--model-config", "tiny_wide", "--cache-dtype", "float32"]
    else:
        cmd += ["--model-config", "llama3_1b"]
    entries0 = cache_entries()
    server = Child("server", cmd, child_env(dry_run, devices=tp))
    try:
        rep = wait_for("its engine-up line", server,
                       lambda: engine_reports(server.log_text()),
                       SERVER_START_TIMEOUT_S)[0]
        check_engine_report(rep, tp, dry_run)
        say(f"server: platform={rep['platform']} device_kind="
            f"{rep['device_kind']!r} devices={rep['devices']} "
            f"tp={rep['tp']} decode_attention={rep['decode_attention']} "
            f"ctx_shard={rep['ctx_shard']} hbm_gb={rep['hbm_gb']}")
        check(dry_run or rep["compile_cache"] == compile_cache_dir(),
              f"server caches compiles in {rep['compile_cache']}, expected "
              f"{compile_cache_dir()}")
        wait_for("/health", server,
                 lambda: http_json(port, "GET", "/health", timeout=5)[0]
                 == 200, SERVER_START_TIMEOUT_S)
        first_token_s = drive_requests(port, server, flight=True)
        stop_server(server)
        entries1 = cache_entries()
        say(f"compile cache {rep['compile_cache']}: {entries0} entries "
            f"before the server started, {entries1} after it exited: "
            f"it compiled "
            f"{entries1 - entries0} new program(s)")
        return rep, first_token_s
    finally:
        server.kill()


def replicas_leg(n: int, dry_run: bool) -> tuple[dict, float]:
    """`serve` graph: n one-chip workers + a frontend + the Python store
    (never a prebuilt native binary: that directory is not in git)."""
    cp_port, http_port = free_port(), free_port()
    worker_args = ["out=tpu", "--model-name", MODEL]
    worker_args += (["--model-config", "tiny", "--cache-dtype", "float32"]
                    if dry_run else ["--model-config", "llama3_1b"])
    graph = {
        "namespace": "smoke",
        "control_plane": {"external": f"127.0.0.1:{cp_port}"},
        "frontend": {"http_port": http_port},
        "workers": [{"name": "w", "replicas": n, "args": worker_args}],
    }
    graph_path = os.path.join(OUT_DIR, "graph.json")
    with open(graph_path, "w") as f:
        json.dump(graph, f)
    env = child_env(dry_run)
    store = Child("store", [sys.executable, "-m", "dynamo_tpu.cli", "cp",
                            "--python", "--port", str(cp_port)],
                  # the store needs no chip and must never take one
                  dict(env, JAX_PLATFORMS="cpu"))
    graph_proc = None
    try:
        wait_for("the store's port", store,
                 lambda: socket.create_connection(
                     ("127.0.0.1", cp_port), timeout=1).close() is None, 60)
        graph_proc = Child("serve", [sys.executable, "-m", "dynamo_tpu.cli",
                                     "serve", graph_path], env)
        reps = wait_for(
            f"{n} engine-up lines", graph_proc,
            lambda: (lambda r: r if len(r) >= n else None)(
                engine_reports(graph_proc.log_text())),
            SERVER_START_TIMEOUT_S)
        for rep in reps:
            check_engine_report(rep, 1, dry_run)
            if not dry_run:
                check(rep["devices"] == 1, "a replica sees "
                      f"{rep['devices']} chips, not its own one")
        say(f"{n} workers up, each on its own device: "
            + ", ".join(f"{r['platform']}/{r['devices']}" for r in reps))

        def served() -> bool:
            status, body = http_json(http_port, "GET", "/v1/models",
                                     timeout=5)
            return status == 200 and any(
                m.get("id") == MODEL for m in body.get("data", []))

        wait_for("the frontend to discover the model", graph_proc, served,
                 SERVER_START_TIMEOUT_S)
        # registration of the LAST worker can trail discovery of the first
        wait_for(f"{n} worker registrations", graph_proc,
                 lambda: graph_proc.log_text().count("serving smoke/") >= n,
                 SERVER_START_TIMEOUT_S)
        first_token_s = drive_requests(http_port, graph_proc, flight=False)
        rc = graph_proc.interrupt_and_wait(120.0)
        log = graph_proc.log_text()
        check(rc == 0, f"serve exited with code {rc}; tail:\n{log[-2000:]}")
        check("engine round failed" not in log,
              f"a worker logged a failed engine round; {graph_proc.log_path}")
        say("serve graph drained and exited 0")
        return reps[0], first_token_s
    finally:
        if graph_proc is not None:
            graph_proc.kill()
        store.kill()


def kernel_check_leg(tp: int, dry_run: bool) -> None:
    cmd = [sys.executable, os.path.abspath(__file__), "--kernel-check",
           "--chips", str(tp)] + (["--cpu-dry-run"] if dry_run else [])
    child = Child("kernel_check", cmd, child_env(dry_run, devices=tp))
    try:
        try:
            rc = child.proc.wait(timeout=600)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("kernel check did not finish in 600s") from None
        log = child.log_text()
        check(rc == 0, f"kernel check failed (exit {rc}); tail:\n"
              f"{log[-3000:]}")
        for line in log.splitlines():
            if line.startswith("kernel_check:"):
                say(line)
    finally:
        child.kill()


def kernel_check_main(tp: int, dry_run: bool) -> int:
    """CHILD process (holds the chip): compiled Pallas decode kernel vs
    flash_decode_attention_reference, seeded, at the serving shape."""
    from dynamo_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.attention import (
        PALLAS_INTERPRET,
        REFERENCE,
        DecodeAttention,
        ctx_decode_attention,
        decode_attention_for,
    )
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(tp=tp), jax.devices()[:tp])
    if dry_run:
        # the same code path at toy size, kernel in the interpreter
        c, S, B, R, group = ModelConfig.tiny_wide(), 256, 4, 4, 64
        attn = DecodeAttention(PALLAS_INTERPRET, mesh, chunk=128)
    else:
        e = EngineConfig()  # the CLI's default engine sizes
        c = ModelConfig.llama3_1b()
        S, B, R, group = (e.max_context, e.max_decode_slots, e.flush_every,
                          e.page_size)
        attn = decode_attention_for(mesh)
        if attn.impl != "pallas":
            print(f"kernel_check: no chip: engine would run {attn.impl!r} "
                  f"on {jax.devices()[0].platform}")
            return 1
    L, nkv, nh, hd = 2, c.num_kv_heads, c.num_heads, c.head_dim
    rng = np.random.default_rng(21)

    def normal(*shape, scale=1.0):
        return (rng.standard_normal(shape, np.float32) * scale)

    # q scaled up so the softmax is peaked: a masking or indexing error
    # then moves the output by its own magnitude, not by an average's
    q = normal(B, nh, hd, scale=3.0)
    ck, cv = normal(L, nkv, B + 1, S, hd), normal(L, nkv, B + 1, S, hd)
    rk, rv = normal(L, nkv, B, R, hd), normal(L, nkv, B, R, hd)
    # ring bases on, beside and far from the 512-position chunk edges,
    # from an empty context to a full one; 1..R live ring entries
    base = np.resize(
        np.array([0, 1, S // 8 - 1, S // 8, S // 8 + 1, S // 2, S - 96,
                  S - R - 1]), B).astype(np.int32)
    ctx_lens = base + 1 + (np.arange(B, dtype=np.int32) % R)

    def quantize(x):
        g = x.reshape(L, nkv, B + 1, S // group, group, hd)
        s = np.maximum(np.abs(g).max(axis=(1, 4, 5)) / 127.0, 1e-8)
        q8 = np.clip(np.rint(g / s[:, None, :, :, None, None]), -127, 127)
        return q8.astype(np.int8).reshape(x.shape), s.astype(np.float32)

    heads = NamedSharding(mesh, P(None, "tp", None))
    rep = NamedSharding(mesh, P())
    bf16 = jnp.bfloat16

    def place(kv_quant: str, k, v):
        sh = llama.ctx_shardings(c, mesh, kv_quant=kv_quant)
        return (jax.device_put(k, sh["k"]), jax.device_put(v, sh["v"]))

    ring_sh = llama.ring_shardings(c, mesh)
    common = dict(
        q=jax.device_put(jnp.asarray(q, bf16), heads),
        rk=jax.device_put(jnp.asarray(rk, bf16), ring_sh["k"]),
        rv=jax.device_put(jnp.asarray(rv, bf16), ring_sh["v"]),
        lens=jax.device_put(jnp.asarray(ctx_lens), rep),
        base=jax.device_put(jnp.asarray(base), rep),
    )
    (k8, ks), (v8, vs) = quantize(ck), quantize(cv)
    cases = {
        "bf16": (place("none", jnp.asarray(ck, bf16), jnp.asarray(cv, bf16)),
                 (), KERNEL_TOL_BF16),
        "int8": (place("int8", jnp.asarray(k8), jnp.asarray(v8)),
                 (jax.device_put(jnp.asarray(ks), rep),
                  jax.device_put(jnp.asarray(vs), rep)), KERNEL_TOL_INT8),
    }
    run = jax.jit(ctx_decode_attention, static_argnums=0)
    dev = jax.devices()[0]
    ok = True
    for name, ((k, v), scales, tol) in cases.items():
        args = (common["q"], k, v, common["rk"], common["rv"], jnp.int32(1),
                common["lens"], common["base"]) + scales
        got = np.asarray(run(attn, *args).astype(jnp.float32))
        want = np.asarray(run(REFERENCE, *args).astype(jnp.float32))
        scale = float(np.abs(want).max())
        err = float(np.abs(got - want).max()) / scale
        passed = bool(got.shape == (B, nh, hd) and np.isfinite(got).all()
                      and scale > 0.1 and err <= tol)
        ok = ok and passed
        print(f"kernel_check: {name} ctx, {attn.impl} on {tp} x "
              f"{dev.device_kind} vs reference at B={B} S={S} R={R} "
              f"kvh={nkv} hd={hd}: max|diff|/max|want| = {err:.2e} "
              f"(tolerance {tol:.0e}, max|want| {scale:.2f}) "
              f"{'ok' if passed else 'FAIL'}", flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: one tp=4 engine on the four-chip host")
    ap.add_argument("--replicas", type=int, default=0,
                    help="N: `serve` graph of N one-chip workers instead")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="TEST SWITCH: --model-config tiny on the CPU")
    ap.add_argument("--kernel-check", action="store_true",
                    help=argparse.SUPPRESS)  # the second child's entry
    args = ap.parse_args(argv)
    if args.kernel_check:
        return kernel_check_main(args.chips, args.cpu_dry_run)

    t0 = time.monotonic()
    if not os.path.isdir(os.path.join(REPO, "dynamo_tpu")):
        print("chip_smoke: FAIL: no dynamo_tpu/ beside chip_smoke.py — "
              "this script drives the repo's program", file=sys.stderr)
        return 1
    os.makedirs(OUT_DIR, exist_ok=True)
    # a watchdog for the contract's time limit: SIGALRM (or a caller's
    # SIGTERM) -> SmokeFailure from wherever the main thread is, and the
    # finally blocks stop the children

    def on_alarm(signum, frame):
        raise SmokeFailure(
            f"not done after {DEADLINE_S:.0f}s" if signum == signal.SIGALRM
            else "terminated")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.signal(signal.SIGTERM, on_alarm)  # a caller's timeout: clean up
    signal.alarm(int(DEADLINE_S))
    try:
        if args.replicas:
            rep, first_token_s = replicas_leg(args.replicas,
                                              args.cpu_dry_run)
        else:
            rep, first_token_s = single_server_leg(args.chips,
                                                   args.cpu_dry_run)
        # the server has exited: the chip is free for the second child
        kernel_check_leg(args.chips, args.cpu_dry_run)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
    say(f"all legs passed in {time.monotonic() - t0:.0f}s; first served "
        f"token {first_token_s:.1f}s after spawn; compile cache "
        f"{rep['compile_cache']}")
    result = {"ok": True, "device": {
        "platform": rep["platform"], "kind": rep["device_kind"],
        "count": rep["devices"]}}
    if args.cpu_dry_run:
        result["dry_run"] = True  # never mistaken for a chip result
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
