"""DTL004 — dispatch accounting.

The dispatch diet (PR 7) pinned steady decode at 1 program + 1 fetch
per round, and ``tests/test_dispatch_budget.py`` pins the *count* — but
only on the paths the test drives. The invariant it depends on is that
``TpuEngine.dispatch_counts`` sees every host->device program launch
and every async D2H fetch initiation; an unaccounted dispatch added on
a cold path silently corrupts the counts that test compares (and that
every snapshot of the benchmark's server copies). This rule is the static companion: every
compiled-call site in ``engine/`` (a call to a ``jax.jit``-produced
callable, ``jax.device_put``, or ``.copy_to_host_async()``) must sit in
a function that increments ``dispatch_counts`` — or in a function all
of whose in-package callers do (accounted wrappers like
``_gather_padded`` count at the call site, into per-purpose buckets).

Exempt: ``__init__`` (the one-time startup weight/pool upload is not a
per-round dispatch) and ``_build_jits`` (builds programs, launches
nothing).
"""
from __future__ import annotations

import ast
from typing import Optional

from dynamo_tpu.lint.core import Finding, Module, ProjectIndex, dotted

_EXEMPT_FUNCTIONS = {"__init__", "_build_jits"}
_DEVICE_PUT = {"jax.device_put", "jax.device_put_sharded",
               "jax.device_put_replicated"}
_FETCH_METHODS = {"copy_to_host_async"}


def _is_jit_producer(call: ast.Call) -> bool:
    name = dotted(call.func)
    if name in ("jax.jit", "jit", "pjit", "jax.pjit"):
        return True
    if name in ("partial", "functools.partial") and call.args:
        return dotted(call.args[0]) in ("jax.jit", "jit", "pjit", "jax.pjit")
    return False


def _jit_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        if dotted(dec) in ("jax.jit", "jit", "pjit", "jax.pjit"):
            return True
        if isinstance(dec, ast.Call) and _is_jit_producer(dec):
            return True
    return False


def _collect_compiled_names(index: ProjectIndex) -> set[str]:
    """Names bound to jax.jit(...) products anywhere in the scanned tree
    (module-level ``x = jax.jit(fn)`` and jit-decorated defs)."""
    names: set[str] = set()
    for mod in index.modules.values():
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                if (isinstance(node.value, ast.Call)
                        and _is_jit_producer(node.value)):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            names.add(tgt.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _jit_decorated(node):
                    names.add(node.name)
    return names


def _compiled_self_attrs(mod: Module) -> set[str]:
    """``self.X = <jit-decorated local fn>`` bindings (the engine stores
    its per-instance programs this way in ``_build_jits``)."""
    local_jits = {
        n.name for n in ast.walk(mod.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and _jit_decorated(n)
    }
    # names rebound from a jit via functools.partial(jax.jit, ...)(fn)
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Call)
                and _is_jit_producer(node.value.func)):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    local_jits.add(tgt.id)
    attrs: set[str] = set()
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Name)
                and node.value.id in local_jits):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    attrs.add(tgt.attr)
    return attrs


class DispatchAccountingRule:
    ID = "DTL004"
    WHAT = ("every device_put / compiled call / async-fetch site in "
            "engine/ must flow through dispatch_counts accounting")

    def check(self, index: ProjectIndex) -> list[Finding]:
        findings: list[Finding] = []
        compiled = _collect_compiled_names(index)
        engine_mods = [
            m for p, m in index.modules.items()
            if "engine" in m.segments()[:-1]
        ]
        # function name -> accounts? across the engine package (caller
        # delegation is by name; engine methods are unique enough)
        accounts: dict[str, bool] = {}
        calls: dict[str, set[str]] = {}   # fn name -> names it calls
        fn_nodes: list[tuple[Module, ast.AST]] = []
        for mod in engine_mods:
            for fn in ast.walk(mod.tree):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    fn_nodes.append((mod, fn))
                    accounts[fn.name] = (accounts.get(fn.name, False)
                                         or self._accounts(fn))
                    calls.setdefault(fn.name, set()).update(
                        self._called_names(fn))
        for mod, fn in fn_nodes:
            sites = self._sites(mod, fn, compiled,
                                _compiled_self_attrs(mod))
            if not sites:
                continue
            if fn.name in _EXEMPT_FUNCTIONS:
                continue
            if accounts.get(fn.name):
                continue
            callers = [c for c, callees in calls.items()
                       if fn.name in callees and c != fn.name]
            if callers and all(accounts.get(c) for c in callers):
                continue  # accounted wrapper: every caller counts
            for line, col, what in sites:
                findings.append(Finding(
                    self.ID, mod.path, line, col,
                    f"{what} in '{fn.name}' is not dispatch-accounted — "
                    "increment self.dispatch_counts[...] here or in "
                    "every caller (the budget pin in "
                    "tests/test_dispatch_budget.py depends on it)",
                ))
        return findings

    def _accounts(self, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if (isinstance(node, ast.Attribute)
                    and node.attr == "dispatch_counts"):
                return True
        return False

    def _called_names(self, fn: ast.AST) -> set[str]:
        out = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = dotted(node.func)
                if name:
                    out.add(name.split(".")[-1])
        return out

    def _sites(self, mod: Module, fn: ast.AST, compiled: set[str],
               self_attrs: set[str]) -> list[tuple[int, int, str]]:
        sites: list[tuple[int, int, str]] = []
        for node in ast.walk(fn):
            if (node is not fn and isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef))):
                continue  # nested defs are checked as their own unit
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            what: Optional[str] = None
            if name in _DEVICE_PUT:
                what = f"{name}() call"
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _FETCH_METHODS):
                what = "async D2H fetch (.copy_to_host_async())"
            elif name:
                head, _, tail = name.partition(".")
                leaf = name.split(".")[-1]
                if head == "self" and "." not in tail \
                        and tail in self_attrs:
                    what = f"compiled call self.{tail}()"
                elif leaf in compiled and not leaf.endswith("_impl") \
                        and head != "self":
                    what = f"compiled call {name}()"
            if what is not None:
                sites.append((node.lineno, node.col_offset, what))
        return sites
