"""A file of the benchmark found by the name that ``BENCHMARK.json`` or a
configuration gives it: a per-layer reader (``layer_metrics/<name>.py``),
a plain reference (``references/<name>.py``), a byte count
(``bytes/<name>.py``). One rule for all three: the name is a name and no
path, the file is there, and it has the function asked for. Anything else
is an error, never a fall back to another file. Stdlib only, no JAX.
"""
from __future__ import annotations

import importlib.util
import os
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")   # no slash


def load(path: str):
    """The module in the file at ``path``, loaded by path (a name with a
    dot or a dash in it is no module name)."""
    name = "bench_" + re.sub(r"\W", "_", os.path.basename(path)[:-3])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module_with(directory: str, name, function: str):
    """``<directory>/<name>.py`` as a module that has ``function``."""
    path = os.path.join(directory, f"{name}.py")
    if not (isinstance(name, str) and NAME.fullmatch(name)
            and os.path.isfile(path)):
        raise FileNotFoundError(
            f"{name!r} names no file under {directory} ({path})")
    mod = load(path)
    if not callable(getattr(mod, function, None)):
        raise AttributeError(f"{path} has no function {function}")
    return mod
