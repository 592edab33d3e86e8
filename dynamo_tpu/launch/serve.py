"""``dynamo-tpu serve graph.yaml`` — one-command serving-graph supervisor.

Parity: reference ``dynamo serve`` (deploy/sdk/src/dynamo/sdk/cli/
serving.py:66-152): a circus arbiter running one watcher per component —
here a small asyncio supervisor that launches the control-plane store,
worker fleets, and the HTTP frontend as child processes, restarts
unexpected exits with capped backoff, and drains gracefully on SIGTERM
(workers first so leases revoke, then frontend, then the store).

Graph file (YAML or JSON):

    namespace: dynamo
    control_plane:
      port: 7111            # omit `external: HOST:PORT` to self-host
    frontend:
      http_port: 8080
      args: []              # extra `run` args
    workers:
      - name: decode
        replicas: 2
        args: [out=tpu, --model-config, tiny, --model-name, m,
               --role, decode]
      - name: prefill
        replicas: 1
        args: [out=tpu, --model-config, tiny, --role, prefill]

One process per chip (launch/chips.py): on a TPU host each ``out=tpu``
replica is started with its own chip visible, every other child is pinned
to the CPU, and a graph that wants more chips than the host has is
refused before anything starts. The supervisor itself never touches JAX.
Children inherit this process's stdout/stderr, so a worker that dies at
start-up says why where the operator is looking.
"""
from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from dynamo_tpu.launch.chips import ChipPlacement

log = logging.getLogger(__name__)

MAX_RESTARTS = 5          # per child, within RESTART_WINDOW_S
RESTART_WINDOW_S = 300.0
BACKOFF_BASE_S = 1.0


def load_graph(path: str) -> dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        text = f.read()
    try:
        import yaml

        return yaml.safe_load(text) or {}
    except ImportError:
        return json.loads(text)


@dataclass
class _Child:
    name: str
    cmd: list[str]
    # chip placement (launch/chips.py): kept across restarts, so a
    # restarted replica comes back on the chip it had
    env: dict[str, str] = field(default_factory=dict)
    proc: Optional[subprocess.Popen] = None
    restarts: list[float] = field(default_factory=list)
    give_up: bool = False
    # restart scheduled for this deadline (0 = none); the monitor never
    # sleeps per-child, so one crash-looping child can't stall the others
    next_restart_at: float = 0.0

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class Supervisor:
    """Launch + babysit the graph's processes."""

    def __init__(self, graph: dict[str, Any], *, python: str = sys.executable):
        self.graph = graph
        self.python = python
        self.children: list[_Child] = []
        self.namespace = graph.get("namespace", "dynamo")
        cp = graph.get("control_plane", {}) or {}
        self.external_cp: Optional[str] = cp.get("external")
        self.cp_port: int = int(cp.get("port", 7111))
        self._stop = asyncio.Event()

    @property
    def cp_addr(self) -> str:
        return self.external_cp or f"127.0.0.1:{self.cp_port}"

    def _build_children(self) -> None:
        base = [self.python, "-m", "dynamo_tpu.cli"]
        chips = ChipPlacement()
        off_chip, _ = chips.env_for([])  # pinned to the CPU on a TPU host
        if self.external_cp is None:
            self.children.append(_Child(
                name="control-plane",
                cmd=base + ["cp", "--port", str(self.cp_port)],
                env=off_chip,
            ))
        for spec in self.graph.get("workers", []) or []:
            name = spec.get("name", "worker")
            replicas = int(spec.get("replicas", 1))
            args = [str(a) for a in (spec.get("args") or [])]
            for i in range(replicas):
                try:
                    env, taken = chips.env_for(args)
                except ValueError as e:
                    raise SystemExit(f"serve: {name}-{i}: {e}") from e
                if taken:
                    log.info("serve: %s-%d gets chip(s) %s", name, i, taken)
                self.children.append(_Child(
                    name=f"{name}-{i}",
                    cmd=base + ["run", "in=endpoint",
                                "--control-plane", self.cp_addr,
                                "--namespace", self.namespace] + args,
                    env=env,
                ))
        if "frontend" in self.graph:
            # a bare `frontend:` key (YAML null) means defaults, not absent
            fe = self.graph.get("frontend") or {}
            args = [str(a) for a in (fe.get("args") or [])]
            self.children.append(_Child(
                name="frontend",
                cmd=base + ["run", "in=http",
                            "--control-plane", self.cp_addr,
                            "--namespace", self.namespace,
                            "--http-port",
                            str(fe.get("http_port", 8080))] + args,
                env=off_chip,
            ))

    def _spawn(self, child: _Child) -> None:
        log.info("serve: starting %s: %s", child.name, " ".join(child.cmd))
        # stdout/stderr inherited: a child that cannot start is visible
        child.proc = subprocess.Popen(
            child.cmd,
            start_new_session=True,
            env={**os.environ, **child.env},
        )

    async def start(self) -> "Supervisor":
        self._build_children()
        for child in self.children:
            self._spawn(child)
            if child.name == "control-plane":
                await asyncio.sleep(0.5)  # store up before dependents
        self._monitor_task = asyncio.get_running_loop().create_task(
            self._monitor()
        )
        return self

    async def _monitor(self) -> None:
        """Restart unexpected exits with capped per-child backoff (no
        inline sleeps: each child carries its own restart deadline)."""
        while not self._stop.is_set():
            await asyncio.sleep(0.5)
            now = time.monotonic()
            for child in self.children:
                if child.alive() or child.give_up:
                    continue
                if child.next_restart_at:
                    if now >= child.next_restart_at:
                        child.next_restart_at = 0.0
                        self._spawn(child)
                    continue
                child.restarts = [
                    t for t in child.restarts if now - t < RESTART_WINDOW_S
                ]
                if len(child.restarts) >= MAX_RESTARTS:
                    log.error("serve: %s exceeded %d restarts; giving up",
                              child.name, MAX_RESTARTS)
                    child.give_up = True
                    continue
                backoff = BACKOFF_BASE_S * (2 ** len(child.restarts))
                log.warning(
                    "serve: %s exited (rc=%s); restarting in %.1fs",
                    child.name,
                    child.proc.returncode if child.proc else "?",
                    backoff,
                )
                child.restarts.append(now)
                child.next_restart_at = now + backoff

    async def drain(self, timeout_s: float = 15.0) -> None:
        """Graceful stop: workers first (lease revocation deregisters
        them), then frontend, then the store."""
        self._stop.set()
        self._monitor_task.cancel()

        def group(pred):
            return [c for c in self.children if pred(c) and c.alive()]

        order = [
            group(lambda c: c.name not in ("frontend", "control-plane")),
            group(lambda c: c.name == "frontend"),
            group(lambda c: c.name == "control-plane"),
        ]
        for batch in order:
            for c in batch:
                c.proc.terminate()
            deadline = time.monotonic() + timeout_s
            for c in batch:
                while c.alive() and time.monotonic() < deadline:
                    await asyncio.sleep(0.1)
                if c.alive():
                    log.warning("serve: %s ignored SIGTERM; killing", c.name)
                    c.proc.kill()

    def status(self) -> dict[str, str]:
        return {
            c.name: ("up" if c.alive()
                     else "failed" if c.give_up else "down")
            for c in self.children
        }


async def serve_main(path: str) -> int:
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    sup = Supervisor(load_graph(path))
    await sup.start()
    names = ", ".join(c.name for c in sup.children)
    print(f"serving graph: {names} (control plane {sup.cp_addr})")

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    print("draining...")
    await sup.drain()
    return 0
