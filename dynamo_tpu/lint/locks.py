"""DTL003 — lock discipline.

Fields shared between the engine thread and the asyncio serving thread
are documented as "guarded by" a specific lock; nothing enforced that
until now, and a single unguarded ``+=`` on ``_waiting_tokens`` is a
lost-update bug that only shows under load. The guarded-by table below
is the authority: every read/write of a listed field must sit lexically
inside a ``with <lock>:`` block in the same function. ``__init__`` is
exempt (fields are created before the object escapes the constructor),
as is the lock's own module-level declaration.

Known-unsynchronized *advisory* reads must carry an explicit
``# dynlint: disable=DTL003 — <why safe>`` pragma, which is the point:
the table plus the pragmas are a complete, greppable inventory of the
cross-thread field accesses.
"""
from __future__ import annotations

import ast

from dynamo_tpu.lint.core import Finding, ProjectIndex, dotted

# module-path suffix -> {field name: guarding lock attribute}
GUARDED_BY: dict[str, dict[str, str]] = {
    "engine/engine.py": {
        # waiting-queue token backlog: updated from the asyncio intake
        # AND the engine thread (overload admission budget)
        "_waiting_tokens": "_wt_lock",
        # commit-event subscribers: subscribe/unsubscribe on the disagg
        # thread, fired from the engine loop
        "_commit_cbs": "_commit_lock",
        # Intentionally NOT listed (cross-thread but lock-free by
        # design — keep this inventory honest when touching them):
        #   _wake_evt          threading.Event doorbell: producers set()
        #                      from serving/disagg threads, the engine
        #                      loop wait()/clear()s; Event is internally
        #                      synchronized.
        #   _pipe_dispatches / _pipe_depth_sum / _pipe_hidden_s /
        #   _pipe_host_s / pipe_flushes
        #                      round-pipeline counters: written ONLY by
        #                      the engine thread inside _round;
        #                      pipeline_stats() performs advisory
        #                      GIL-atomic reads for tests.
    },
    "disagg.py": {
        # pending remote-prefill jobs: serving tasks add/discard, the
        # engine-side poller reads
        "_pending_jobs": "_jobs_lock",
    },
    "telemetry/metrics.py": {
        # histogram/counter state: engine thread observes, asyncio
        # scrape handlers render
        "_counts": "_lock", "_sum": "_lock", "_count": "_lock",
        "_values": "_lock",
    },
    "telemetry/flight.py": {
        # flight-recorder ring: engine thread records, debug handlers
        # snapshot
        "_ring": "_lock", "_next": "_lock", "_seq": "_lock",
    },
    "runtime/session.py": {
        # session registration state: mutated by user-facing calls
        # (put/lease_grant/watch_prefix) AND the supervisor's resync —
        # concurrent asyncio tasks, so every access holds the session
        # mutex (an await between read and write is a lost update)
        "_session_leases": "_mu",
        "_session_watches": "_mu",
    },
    "fleetsim/sim.py": {
        # simulated fleet roster: resized by the planner's connector AND
        # a test's own scale calls — concurrent asyncio tasks, and scale_to
        # awaits mid-resize (spawn/drain), so an unguarded access reads
        # a half-resized fleet
        "_workers": "_mu",
    },
}

_EXEMPT_FUNCTIONS = ("__init__",)


class LockDisciplineRule:
    ID = "DTL003"
    WHAT = ("accesses to cross-thread fields (guarded-by table) must hold "
            "their lock: with self.<lock>: ...")

    def check(self, index: ProjectIndex) -> list[Finding]:
        findings: list[Finding] = []
        for mod in index.modules.values():
            table = None
            for suffix, fields in GUARDED_BY.items():
                if (mod.path == suffix
                        or mod.path.endswith("/" + suffix)):
                    table = fields
                    break
            if table is None:
                continue
            for fn in ast.walk(mod.tree):
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                if fn.name in _EXEMPT_FUNCTIONS:
                    continue
                self._check_fn(mod, fn, table, findings)
        return findings

    def _check_fn(self, mod, fn, table, findings) -> None:
        locks = set(table.values())

        def visit(node: ast.AST, held: frozenset) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                # a nested def runs later, outside this lock scope
                held = frozenset()
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    name = dotted(item.context_expr).split(".")[-1]
                    if name in locks:
                        held = held | {name}
            if isinstance(node, ast.Attribute):
                lock = table.get(node.attr)
                if lock is not None and lock not in held:
                    # the lock attribute itself (e.g. `self._lock`) and
                    # `with self._x_lock:` context exprs are not data
                    # accesses
                    findings.append(Finding(
                        self.ID, mod.path, node.lineno, node.col_offset,
                        f"access to '{node.attr}' outside 'with "
                        f"{lock}:' in '{fn.name}' — this field is "
                        "shared across threads (guarded-by table in "
                        "dynamo_tpu/lint/locks.py)",
                    ))
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for child in ast.iter_child_nodes(fn):
            visit(child, frozenset())
