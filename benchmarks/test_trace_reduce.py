"""``trace_reduce.label_gaps`` finds each gap's neighbours by bisection (a
span has a million gaps: PERF.md section 3); the plain passes over the
modules that it replaced stay here as its reference, on drawn cases with
modules that overlap, nest, touch and are missing.
``python3 -m pytest benchmarks/test_trace_reduce.py -q``
"""
from __future__ import annotations

import importlib.util
import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
spec = importlib.util.spec_from_file_location(
    "bench_trace_reduce", os.path.join(HERE, "trace_reduce.py"))
tr = importlib.util.module_from_spec(spec)
sys.modules["bench_trace_reduce"] = tr
spec.loader.exec_module(tr)


def plain_label_gaps(mods, busy, w0, w1) -> dict[str, int]:
    """The labelling as PR 24 wrote it: three passes over the modules a gap."""
    gaps: dict[str, int] = {}
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        before = [n for n, s, e in mods if e <= g0 + 1]
        after = [n for n, s, e in mods if s >= g1 - 1]
        inside = [n for n, s, e in mods if s <= g0 and e >= g1]
        if inside:
            label = f"inside {tr.module_base(inside[0])}"
        else:
            label = (
                f"{tr.module_base(before[-1]) if before else 'start'}"
                f" -> {tr.module_base(after[0]) if after else 'end'}")
        gaps[label] = gaps.get(label, 0) + (g1 - g0)
    return gaps


@pytest.mark.parametrize("seed", range(8))
def test_gaps_found_by_bisection_are_the_plain_passes_gaps(seed):
    rng = random.Random(seed)
    for _ in range(60):
        mods = sorted(
            ((f"jit_m{rng.randrange(4)}({i})", s, s + rng.randrange(0, 300))
             for i in range(rng.randrange(0, 12))
             for s in [rng.randrange(0, 1000)]), key=lambda m: m[1])
        cuts = sorted(rng.sample(range(0, 1400), 2 * rng.randrange(1, 20)))
        busy = tr.union(list(zip(cuts[0::2], cuts[1::2])))
        w0, w1 = cuts[0] - rng.randrange(0, 5), cuts[-1] + rng.randrange(0, 5)
        assert (tr.label_gaps(mods, busy, w0, w1)
                == plain_label_gaps(mods, busy, w0, w1)), (mods, busy)


def test_a_name_is_worked_out_once_and_reads_the_same():
    name = ("%fusion.7 = bf16[16,3584]{1,0:T(8,128)(2,1)} fusion(bf16[16,5120]"
            "{1,0} %p.1), kind=kOutput")
    for _ in range(2):
        assert (tr.op_kind(name), tr.op_label(name), tr.op_code(name)) == (
            "fusion", "fusion bf16[16,3584]", "fusion")
    assert tr.op_label.cache_info().hits > 0
