"""Where the persistent XLA compilation cache lives.

A cold server compiles half a dozen whole-model programs (16 unrolled
layers each) before its first token, and every process of a chip command
would pay that again. JAX's persistent cache makes the second process —
and the second start — load instead of compile, but only if they agree
on ONE directory that does not move: a path from ``tempfile``, a pid or
the clock never hits.

Contract (one call, before the first jit, in every process that compiles
for the chip: ``launch/run.py`` out=tpu, ``benchmarks/server.py``,
``chip_smoke.py``'s children):

  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
    sets no path in code, so the directory can be placed from outside.
  - unset: a fixed directory inside the checkout (``<repo>/.jax_cache``,
    git-ignored).
  - a process JAX holds to the CPU (tests, dry runs) gets no cache from
    here: XLA:CPU compiles these programs in seconds, and loading its
    cached AOT results logs a machine-feature mismatch per entry.
"""
from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def cache_dir() -> str:
    """The directory the rule above names. Imports no JAX: a parent that
    must stay off the chip (chip_smoke.py) may ask too."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def ensure_compile_cache() -> Optional[str]:
    """Point this process's jits at the shared persistent cache; returns
    the directory in use (None on the CPU backend)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only the slow ones: "the second start
    # compiles nothing" is then checkable as "it added no cache entry"
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
