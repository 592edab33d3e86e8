"""Child processes and the HTTP/SSE client, stdlib only, no JAX.

Copied from ``chip_smoke.py`` (Child, _descendants, free_port, http_json,
http_sse) so that a later PR changing the smoke cannot change the
yardstick. Changes from the original: the log directory is an argument,
the child gets a stdin pipe (the benchmark steers its server through
it), and the SSE reader returns the arrival time of every chunk that
carries text, not only the first.
"""
from __future__ import annotations

import glob
import http.client
import json
import os
import signal
import socket
import subprocess
import time

REQUEST_TIMEOUT_S = 300.0


class BenchFailure(Exception):
    """The run cannot produce a result line (no chip, server died, ...)."""


class Child:
    """A process group the benchmark started and will stop."""

    def __init__(self, name: str, cmd: list[str], env: dict[str, str],
                 cwd: str, log_dir: str):
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=cwd, env=env, stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def tell(self, line: str) -> None:
        """One command line to the child's stdin."""
        try:
            self.proc.stdin.write(line.encode() + b"\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError) as e:
            raise BenchFailure(f"{self.name} is gone: {e}; tail of its log:\n"
                               + self.log_text()[-3000:]) from None

    def wait(self, timeout_s: float) -> int:
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise BenchFailure(
                f"{self.name} did not exit within {timeout_s:.0f}s") from None

    def kill(self) -> None:
        """Stop the child and everything under it: walk /proc for
        descendants BEFORE killing the parent (orphans are re-parented
        and no longer traceable)."""
        if self.proc.poll() is None:
            victims = _descendants(self.proc.pid)
            for pid in [self.proc.pid] + victims:
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            self.proc.wait()
        if self.proc.stdin is not None:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
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


def wait_for(what: str, child: Child, ready, timeout_s: float):
    """Poll ``ready()`` until truthy; fail if the child dies first."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise BenchFailure(
                f"{child.name} exited with code {child.proc.returncode} "
                f"before {what}; tail of its log:\n"
                + child.log_text()[-3000:])
        try:
            got = ready()
        except (OSError, http.client.HTTPException):
            got = None
        if got:
            return got
        time.sleep(0.1)
    raise BenchFailure(
        f"timed out after {timeout_s:.0f}s waiting for {what}; tail of "
        f"{child.name}'s log:\n" + child.log_text()[-3000:])


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


def http_sse(port: int, path: str, body: bytes,
             timeout: float = REQUEST_TIMEOUT_S
             ) -> tuple[int, list[float], dict | None, str, str]:
    """POST a streaming completion. Returns (status, the monotonic arrival
    time of every chunk that carries text, the ``usage`` object or None,
    the stream's finish_reason or "", an error string or "")."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return (resp.status, [], None, "",
                    resp.read()[:300].decode(errors="replace"))
        arrivals: list[float] = []
        usage = None
        finish = error = ""
        for line in resp:
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                break
            now = time.monotonic()
            chunk = json.loads(data)
            if chunk.get("usage"):
                usage = chunk["usage"]
            if "error" in chunk:
                error = str(chunk["error"])[:300]
            choices = chunk.get("choices") or [{}]
            finish = choices[0].get("finish_reason") or finish
            if choices[0].get("text") or (
                    choices[0].get("delta") or {}).get("content"):
                arrivals.append(now)
        return resp.status, arrivals, usage, finish, error
    finally:
        conn.close()
