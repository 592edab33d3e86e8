"""DTL005 — metrics contract, the static half.

``tests/test_metrics_contract.py`` asserts at runtime that every
rendered ``dynamo_*`` family has HELP/TYPE and a README row — but only
for families that render in the test's stub setup. This rule checks the
*definitions*: every family tuple handed to a ``CounterRegistry`` (and
every canonical 2-tuple metric constant) must carry a valid type and a
non-empty help string; every ``dynamo_*`` metric-name literal anywhere
in the tree must have a README row; and every module-level registry
(``OVERLOAD``, ``KV_TRANSFER``, ... — anything assigned from
``CounterRegistry(...)`` or ``ProfRegistry(...)``) must be rendered by
``telemetry/metrics.py:render_planes``, the one list that all three
scrape surfaces (frontend ``/metrics``, per-worker system server,
aggregating exporter) call, so a new subsystem plane cannot ship
half-scraped.

The surface check only runs when that module and all three surface
modules are in the scanned set (i.e. whole-tree runs, not single-file
fixture runs).
"""
from __future__ import annotations

import ast
import re

from dynamo_tpu.lint.core import Finding, Module, ProjectIndex, dotted

_METRIC_NAME = re.compile(r"dynamo_[a-z0-9_]+")
_VALID_TYPES = {"counter", "gauge", "histogram", "summary"}
_REGISTRY_CTORS = {"CounterRegistry", "ProfRegistry", "FleetLatencyFeed",
                   "TenantRegistry"}
_PLANES = "telemetry/metrics.py"     # home of render_planes
_SURFACES = (
    "frontend/service.py",
    "runtime/system_server.py",
    "metrics_exporter.py",
)


def _tuple_elts(node: ast.AST) -> list[ast.Tuple]:
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e for e in node.elts if isinstance(e, ast.Tuple)]
    return []


def _const_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class MetricsContractRule:
    ID = "DTL005"
    WHAT = ("every dynamo_* family needs HELP text + a valid TYPE, a "
            "README row, and its registry in render_planes, which all "
            "three scrape surfaces call")

    def check(self, index: ProjectIndex) -> list[Finding]:
        findings: list[Finding] = []
        readme = index.readme_text()
        for mod in index.modules.values():
            if "/tests/" in mod.path or mod.path.startswith("tests/"):
                continue
            self._check_family_defs(mod, findings)
            if readme is not None:
                self._check_readme(mod, readme, findings)
        self._check_surfaces(index, findings)
        return findings

    # -- family tuples ----------------------------------------------------

    def _check_family_defs(self, mod: Module, findings) -> None:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign):
                continue
            for fam in _tuple_elts(node.value):
                elts = fam.elts
                name = _const_str(elts[0]) if elts else None
                if name is None or not _METRIC_NAME.fullmatch(name):
                    continue
                if len(elts) == 3:  # (name, type, help)
                    typ, help_ = _const_str(elts[1]), _const_str(elts[2])
                    if typ not in _VALID_TYPES:
                        findings.append(Finding(
                            self.ID, mod.path, fam.lineno, fam.col_offset,
                            f"family {name!r} has invalid metric type "
                            f"{typ!r} (one of {sorted(_VALID_TYPES)})",
                        ))
                    if not (help_ or "").strip():
                        findings.append(Finding(
                            self.ID, mod.path, fam.lineno, fam.col_offset,
                            f"family {name!r} has empty HELP text",
                        ))
                elif len(elts) == 2:  # (name, help) histogram/canonical
                    if not (_const_str(elts[1]) or "").strip():
                        findings.append(Finding(
                            self.ID, mod.path, fam.lineno, fam.col_offset,
                            f"family {name!r} has empty HELP text",
                        ))

    # -- README rows ------------------------------------------------------

    def _check_readme(self, mod: Module, readme: str, findings) -> None:
        seen: set[str] = set()
        for node in ast.walk(mod.tree):
            name = _const_str(node)
            if name is None or not _METRIC_NAME.fullmatch(name):
                continue
            if name in seen or name in readme:
                continue
            seen.add(name)
            findings.append(Finding(
                self.ID, mod.path, node.lineno, node.col_offset,
                f"metric family {name!r} is not documented in README "
                "(Observability section) — the scrape surfaces and the "
                "docs must not drift",
            ))

    # -- three-surface rendering ------------------------------------------

    def _check_surfaces(self, index: ProjectIndex, findings) -> None:
        planes = index.get(_PLANES)
        surfaces = [index.get(s) for s in _SURFACES]
        if planes is None or any(s is None for s in surfaces):
            return
        listed = next(
            (ast.get_source_segment(planes.source, n) or ""
             for n in planes.tree.body
             if isinstance(n, ast.FunctionDef) and n.name == "render_planes"),
            "")
        for sname, smod in zip(_SURFACES, surfaces):
            if "render_planes(" not in smod.source:
                findings.append(Finding(
                    self.ID, smod.path, 1, 0,
                    f"scrape surface {sname} does not call render_planes"
                    " — every metric plane must appear on all three "
                    "surfaces",
                ))
        for mod in index.modules.values():
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Assign):
                    continue
                if not (isinstance(node.value, ast.Call) and
                        dotted(node.value.func).split(".")[-1]
                        in _REGISTRY_CTORS):
                    continue
                for tgt in node.targets:
                    if not (isinstance(tgt, ast.Name)
                            and tgt.id.isupper()):
                        continue  # instance/local registries opt out
                    # open paren, not `render()`: the list may pass
                    # render(openmetrics=...) for exemplar-capable
                    # registries
                    if f"{tgt.id}.render(" not in listed:
                        findings.append(Finding(
                            self.ID, mod.path, node.lineno,
                            node.col_offset,
                            f"registry {tgt.id} is not rendered by "
                            "render_planes (telemetry/metrics.py) — "
                            "every metric plane must appear on all "
                            "three scrape surfaces",
                        ))
