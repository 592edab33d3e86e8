#!/usr/bin/env python3
"""Device trace -> numbers. Two pieces, kept apart:

  read_xplane(path)  the ``.xplane.pb`` a ``jax.profiler`` trace leaves ->
                     a flat list of ``(plane, line, name, start_ns,
                     dur_ns)``; needs JAX (``ProfileData``), nothing else.
  reduce_events(ev)  pure arithmetic on that list: union of intervals per
                     chip, sums by name, every named kernel (custom
                     call), collectives and their exposed part, idle gaps
                     labelled with the XLA modules on either side. No JAX.

Run by the harness as a child of its own with ``JAX_PLATFORMS=cpu`` after
the server has exited (the parent stays off JAX, nothing contends for the
chip):  ``python3 benchmarks/trace_reduce.py --reduce <dir> --out <json>``
and checked on a hand-written event list by
``python3 -m benchmarks.trace_reduce --selftest`` (exit 0/1).

What a v5e trace looks like (one looked at by hand, PR 24; ``--dump``
lists a trace's planes, lines and heaviest names): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per executed
program, named ``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event
per HLO op executed, named by its whole HLO text, ``%copy.12 = bf16[..]{..}
copy(..)``; a ``while`` and the ops of its body are both there, nested; a
Pallas call appears under its kernel function's name), ``Async XLA Ops``
and ``TC Overlay`` (not read); host threads are planes ``/host:CPU``.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import glob
import gzip
import itertools
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")
# ops that only contain other ops: their time is their children's
CONTAINER = re.compile(r"^(while|conditional|call)$")
# a Pallas/Mosaic kernel is a custom call named after its kernel function
KERNEL_OPCODE = "custom-call"

Event = tuple  # (plane, line, name, start_ns, dur_ns)


def read_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out: list[Event] = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb*")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# --------------------------------------------------------------------------
# interval arithmetic


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint cover of [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(cover: list[tuple[int, int]]) -> int:
    return sum(e - s for s, e in cover)


def overlap(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of the intersection of two disjoint sorted covers."""
    i = j = n = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            n += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return n


# A span holds a million events under some ten thousand names, and reducing
# it is part of every traced run: each function of a name is worked out once
# (`functools.cache` on op_kind, op_label, op_code).
@functools.cache
def op_kind(name: str) -> str:
    """An op event is named by its HLO text, ``%copy.12 = bf16[..]{..}
    copy(..)`` (or just ``copy.12``): -> ``copy``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head)


_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")


def _typed_rhs(name: str) -> str:
    """``%x.1 = bf16[8]{0} copy(..)`` -> ``bf16[8] copy(..)``: what
    follows `` = ``, without layouts; a bare name -> ''."""
    rhs = name.split(" = ", 1)[1] if " = " in name else ""
    return re.sub(r"\{[^}]*\}", "", rhs)


@functools.cache
def op_label(name: str) -> str:
    """Kind and output type without layouts: the 32 per-layer copies of
    one unrolled op get one label, ``fusion (f32[32,1024], f32[..])``."""
    rhs = _typed_rhs(name)
    m = _OPCODE.search(rhs)
    shape = (rhs[:m.start()] if m else rhs).strip()
    return (op_kind(name) + " " + shape).strip()[:120]


@functools.cache
def op_code(name: str) -> str:
    """The HLO opcode of an op event named by its HLO text: ``%x.1 =
    bf16[8]{0} custom-call(..)`` -> ``custom-call``; a bare name -> ''."""
    m = _OPCODE.search(_typed_rhs(name))
    return m.group(1) if m else ""


def module_base(name: str) -> str:
    """``jit_engine_round_seal(123456)`` -> ``jit_engine_round_seal``."""
    return name.split("(", 1)[0]


def label_gaps(mods: list[tuple], busy: list[tuple[int, int]], w0: int,
               w1: int) -> dict[str, int]:
    """One chip's idle nanoseconds inside [w0, w1), by the modules around
    each gap of its ``busy`` cover: ``inside <m>`` for the first module (by
    start; ``mods`` is sorted by it) that spans the gap, else ``<the last
    module that ended before it> -> <the first that starts after it>``
    (``start`` / ``end`` where there is none).

    A chip that is never idle for long still has a gap of nanoseconds after
    most of its ops, a million to a span, so each side is found by
    bisection and not by a pass over the modules: ``spans_to[i]`` is the
    latest end among modules 0..i (it first reaches a time at the first
    module that does), ``ended[j]`` the last module among the j that end
    earliest. ``test_trace_reduce.py`` holds it to the plain passes."""
    starts = [s for _, s, _ in mods]
    spans_to = list(itertools.accumulate((e for _, _, e in mods), max))
    by_end = sorted((e, i) for i, (_, _, e) in enumerate(mods))
    ends = [e for e, _ in by_end]
    ended = list(itertools.accumulate((i for _, i in by_end), max))
    gaps: dict[str, int] = {}
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        began = bisect.bisect_right(starts, g0)
        inside = bisect.bisect_left(spans_to, g1, 0, began)
        if inside < began:
            label = f"inside {module_base(mods[inside][0])}"
        else:
            before = bisect.bisect_right(ends, g0 + 1)
            after = bisect.bisect_left(starts, g1 - 1)
            left = mods[ended[before - 1]][0] if before else "start"
            right = mods[after][0] if after < len(mods) else "end"
            label = f"{module_base(left)} -> {module_base(right)}"
        gaps[label] = gaps.get(label, 0) + (g1 - g0)
    return gaps


def reduce_events(events: list[Event]) -> dict:
    """Everything the per-layer readers and the breakdown need."""
    chips: dict[str, dict] = {}
    for plane, line, name, start, dur in events:
        if not DEVICE_PLANE.match(plane):
            continue
        chip = chips.setdefault(plane, {"ops": [], "modules": []})
        (chip["ops"] if line == OPS_LINE else chip["modules"]).append(
            (name, start, start + dur))
    all_ops = [o for c in chips.values() for o in c["ops"]]
    if not all_ops:
        return {"chips": 0, "window_s": 0.0, "busy_s": 0.0}
    w0 = min(s for _, s, _ in all_ops)
    w1 = max(e for _, _, e in all_ops)
    window = w1 - w0
    per_chip = {}
    op_time: dict[str, int] = {}
    kernel_time: dict[str, int] = {}
    gaps: dict[str, int] = {}
    modules: dict[str, dict] = {}
    for plane, chip in sorted(chips.items()):
        busy = union([(s, e) for _, s, e in chip["ops"]])
        kinds = [op_kind(n) for n, _, _ in chip["ops"]]
        coll = union([(s, e) for (_, s, e), k in zip(chip["ops"], kinds)
                      if COLLECTIVE.match(k)])
        other = union([(s, e) for (_, s, e), k in zip(chip["ops"], kinds)
                       if not COLLECTIVE.match(k) and not CONTAINER.match(k)])
        per_chip[plane] = {
            "busy_s": total(busy) / 1e9,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": (total(coll) - overlap(coll, other)) / 1e9,
        }
        for (n, s, e), k in zip(chip["ops"], kinds):
            if not CONTAINER.match(k):
                label = op_label(n)
                op_time[label] = op_time.get(label, 0) + (e - s)
                if op_code(n) == KERNEL_OPCODE:
                    kernel_time[label] = kernel_time.get(label, 0) + (e - s)
        mods = sorted(chip["modules"], key=lambda m: m[1])
        for n, s, e in mods:
            m = modules.setdefault(module_base(n), {"count": 0, "ns": 0})
            m["count"] += 1
            m["ns"] += e - s
        for label, ns in label_gaps(mods, busy, w0, w1).items():
            gaps[label] = gaps.get(label, 0) + ns
    n = len(chips)
    busiest = max(c["busy_s"] for c in per_chip.values())
    idlest = min(c["busy_s"] for c in per_chip.values())
    return {
        "chips": n,
        "window_s": window / 1e9,
        "busy_s": sum(c["busy_s"] for c in per_chip.values()) / n,
        "busy_s_max": busiest,
        "busy_s_min": idlest,
        "per_chip": per_chip,
        # modules: per-chip means (every chip runs every module of a
        # sharded program, so counts and times are summed over chips)
        "modules": {k: {"count": v["count"] / n, "seconds": v["ns"] / 1e9 / n}
                    for k, v in modules.items()},
        "device_ops": [[k, v / 1e9 / n] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v / 1e9 / n] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:10]],
        # every named kernel, however light: a reader finds its own by the
        # label's first word (the ten device_ops keep only the heaviest)
        "kernels": {k: v / 1e9 / n for k, v in sorted(kernel_time.items())},
    }


# --------------------------------------------------------------------------


def selftest() -> int:
    bad: list[str] = []

    def expect(name, cond):
        if not cond:
            bad.append(name)

    def near(a, b):
        return abs(a - b) < 1e-12

    expect("union merges overlap and touch",
           union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)])
    expect("overlap", overlap([(0, 4), (6, 9)], [(3, 7)]) == 2)
    T0, T1 = "/device:TPU:0", "/device:TPU:1"
    ms = 1_000_000
    ev = [
        # chip 0: two overlapping ops (0-4 ms), a gap, a collective alone
        # (6-8 ms), a collective under compute (8-10 ms), a while that
        # only contains the first two ops
        (T0, MODULES_LINE, "jit_round(11)", 0, 4 * ms),
        (T0, MODULES_LINE, "jit_prefill(22)", 6 * ms, 4 * ms),
        (T0, OPS_LINE, "%while.1 = (s32[]{:T(128)}) while(s32[] %p)", 0, 4 * ms),
        (T0, OPS_LINE, "%fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(f32[8] %a)",
         0, 3 * ms),
        (T0, OPS_LINE, "fusion.2", 2 * ms, 2 * ms),
        (T0, OPS_LINE, "%all-reduce.1 = bf16[16,5120]{1,0} all-reduce(bf16[16,"
         "5120] %x)", 6 * ms, 2 * ms),
        (T0, OPS_LINE, "all-reduce.2", 8 * ms, 2 * ms),
        (T0, OPS_LINE, "fusion.3", 8 * ms, 2 * ms),
        # chip 1: busy only 0-2 ms, a kernel inside it twice
        (T1, MODULES_LINE, "jit_round(11)", 0, 2 * ms),
        (T1, OPS_LINE, "fusion.1", 0, 2 * ms),
        (T1, OPS_LINE, "%my_kernel.3 = bf16[8,4]{1,0:T(8,128)(2,1)} custom-call("
         "bf16[8,4]{1,0} %q), custom_call_target=\"tpu_custom_call\"",
         0, ms // 2),
        (T1, OPS_LINE, "%my_kernel.7 = bf16[8,4]{1,0} custom-call(bf16[8,4] %q)",
         ms, ms // 4),
        # a host plane is ignored
        ("/host:CPU", "python", "sleep", 0, 100 * ms),
    ]
    r = reduce_events(ev)
    expect("chips", r["chips"] == 2)
    expect("window", near(r["window_s"], 0.010))
    c0, c1 = r["per_chip"][T0], r["per_chip"][T1]
    expect("busy chip 0 (union, not sum)", near(c0["busy_s"], 0.008))
    expect("busy chip 1", near(c1["busy_s"], 0.002))
    expect("busy mean", near(r["busy_s"], 0.005))
    expect("worst chip", near(r["busy_s_min"], 0.002))
    expect("collective", near(c0["collective_s"], 0.004))
    expect("exposed collective: only the one with no compute beside it",
           near(c0["collective_exposed_s"], 0.002))
    expect("container op not in the op sums",
           all(not k.startswith("while") for k, _ in r["device_ops"]))
    expect("ops of one kind and shape share a label",
           ["fusion f32[8,4]", 0.003 / 2] in
           [[k, round(v, 12)] for k, v in r["device_ops"]])
    expect("label of a bare name", op_label("fusion.3") == "fusion")
    expect("opcode", op_code(ev[3][2]) == "fusion"
           and op_code("%t = (f32[8]{0}, bf16[8,4]{1,0}) fusion(f32[8] %a)")
           == "fusion" and op_code("fusion.3") == "")
    expect("kernels: every custom call by label, mean over chips, and "
           "nothing else", list(r["kernels"]) == ["my_kernel bf16[8,4]"]
           and near(r["kernels"]["my_kernel bf16[8,4]"], 0.00075 / 2))
    expect("module mean over chips",
           near(r["modules"]["jit_round"]["seconds"], 0.003)
           and near(r["modules"]["jit_round"]["count"], 1.0))
    expect("gap label", ["jit_round -> jit_prefill", 0.002 / 2]
           in [[k, round(v, 12)] for k, v in r["idle_gaps"]])
    expect("chip 1's tail is a gap", any(
        k == "jit_round -> end" and near(v, 0.008 / 2)
        for k, v in r["idle_gaps"]))
    empty = reduce_events([("/host:CPU", "python", "sleep", 0, ms)])
    expect("empty device", empty["chips"] == 0 and empty["busy_s"] == 0.0)
    for name in bad:
        print(f"trace_reduce selftest FAILED: {name}")
    print("trace_reduce selftest:", "FAILED" if bad else "ok")
    return 1 if bad else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--reduce", metavar="TRACE_DIR")
    ap.add_argument("--out", metavar="JSON")
    ap.add_argument("--dump", action="store_true",
                    help="also list planes, lines and the commonest names")
    args = ap.parse_args()
    if args.selftest:
        return selftest()
    if not args.reduce:
        ap.error("--selftest or --reduce")
    events = read_xplane(find_xplane(args.reduce))
    result = reduce_events(events)
    if args.dump:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(find_xplane(args.reduce))
        result["planes"] = [
            [pl.name, ln.name, sum(1 for _ in ln.events)]
            for pl in data.planes for ln in pl.lines]
        seen: dict = {}
        for plane, line, name, _, dur in events:
            k = (plane, line, name)
            c = seen.setdefault(k, [0, 0])
            c[0] += 1
            c[1] += dur
        result["dump"] = [[*k, c, d / 1e9] for k, (c, d) in sorted(
            seen.items(), key=lambda kv: -kv[1][1])[:80]]
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
