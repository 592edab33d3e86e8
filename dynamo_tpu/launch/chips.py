"""One process per chip: how a supervisor hands each worker its own TPU.

A TPU chip belongs to one process at a time, and a process that touches
JAX takes EVERY chip it can see. So on a four-chip host, ``replicas: 4``
workers started with an unchanged environment do not land one per chip:
the first takes all four and the other three die at TPU init. The
supervisors (launch/serve.py, planner.LocalConnector) therefore stay off
JAX themselves and give each chip-needing child its own chip through
libtpu's process-visibility variables; children that need no chip are
pinned to the CPU so they can never grab one.

Supported placements: one chip per worker, or one worker spanning the
whole host (tensor-parallel size == host chips). Anything else — a
2-chip worker, more workers than chips — is refused before anything is
spawned: a refusal the operator reads beats a restart loop nobody does.

Established on the 2x2 v5e host (libtpu 0.0.34; CHANGES.md PR 21): four
processes each hold their own chip under ``TPU_VISIBLE_CHIPS=<i>`` plus
the two ``*_BOUNDS=1,1,1`` variables. ``TPU_VISIBLE_CHIPS`` alone is not
enough — the second process dies with "Internal error when accessing
libtpu multi-process lockfile" — and per-process ports / task ids are not
needed: the processes never talk to each other.
"""
from __future__ import annotations

import glob
import os
from typing import Optional


def host_chip_count() -> int:
    """TPU chips on this host, counted WITHOUT touching JAX (the caller
    must not become the process that holds them): accelerator device
    nodes, /dev/accel* (v2-v4) or /dev/vfio/<n> (v5e and later). 0 on a
    host with no TPU."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return len([p for p in glob.glob("/dev/vfio/*")
                if os.path.basename(p).isdigit()])


def chips_needed(run_args: list[str]) -> int:
    """Chips a ``dynamo-tpu run`` worker asks for: its tensor-parallel
    size if it runs the TPU engine, else 0."""
    if "out=tpu" not in run_args:
        return 0
    tp = 1
    for i, a in enumerate(run_args):
        if a == "--tensor-parallel-size" and i + 1 < len(run_args):
            tp = int(run_args[i + 1])
        elif a.startswith("--tensor-parallel-size="):
            tp = int(a.split("=", 1)[1])
    return tp


class ChipPlacement:
    """Hands out this host's chips to worker processes. On a host with
    no TPU (``total == 0``) every request is answered with an empty
    environment: there is nothing to place and nothing to protect."""

    def __init__(self, total: Optional[int] = None):
        self.total = host_chip_count() if total is None else total
        self._free = list(range(self.total))

    def env_for(self, run_args: list[str]) -> tuple[dict[str, str], list[int]]:
        """(extra environment, chips taken) for one worker. Raises
        ValueError when the worker cannot be placed."""
        if self.total == 0:
            return {}, []
        n = chips_needed(run_args)
        if n == 0:
            # no chip wanted: make sure it cannot take one
            return {"JAX_PLATFORMS": "cpu"}, []
        if n > len(self._free):
            raise ValueError(
                f"worker needs {n} chip(s) but only {len(self._free)} of "
                f"this host's {self.total} are free: one process per chip"
            )
        if n == self.total:
            chips, self._free = self._free, []
            return {}, chips  # the whole host: libtpu's default
        if n != 1:
            raise ValueError(
                f"cannot place a {n}-chip worker on a {self.total}-chip "
                "host: supported are one chip per worker or the whole host"
            )
        chip = self._free.pop(0)
        return {
            "TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }, [chip]

    def release(self, chips: list[int]) -> None:
        self._free = sorted(set(self._free) | set(chips))
