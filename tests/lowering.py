"""What the ``tests/test_lowering_<config>.py`` files share: ONE record a
program compiled for compile-only v5e devices (``record``, memoised for the
process), the digests a program is pinned by, and a cell's count of
whole-model prefill programs.

THE RULE: a new configuration adds ``tests/test_lowering_<config>.py`` and
edits no other file. Its guards ask ``record`` for the programs they read,
at the depth and width the cell serves them (what XLA hoists depends on
depth: no guard is made shallower to save time); two guards that read one
program compile it once. The run's order needs no edit either
(``tests/conftest.py`` sorts files by ``tests/seconds.json``, and a file it
does not know starts first).

A digest (``lowered_sha256`` of tools/tpu_compile_check.py: the StableHLO
text before the compiler, Mosaic bodies masked) equal on two trees is the
same program. A PR that MEANS to move a program records the new digest in
its file's table and says so in ``CHANGES.md``.
"""
import functools
import json
import os
import re
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import tpu_compile_check  # noqa: E402

MOVERS = ("flush_ctx", "seal_blocks", "flush_seal")


def v5e_or_skip():
    """The compile-only v5e topology, described inside a test (never at
    import: one process at a time may load libtpu), or a skip."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name=tpu_compile_check.TOPOLOGY)
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no compile-only v5e topology here: {exc!r:.200}")


def one_v5e():
    """A sharding on one described v5e device, for a kernel compiled alone."""
    return jax.sharding.SingleDeviceSharding(v5e_or_skip().devices[0])


def serving_precision():
    """conftest pins matmul precision to "highest" for the CPU goldens;
    Mosaic refuses a bf16 dot at that precision ("Bad lhs type"), and no
    serving process sets it: compile what serving compiles."""
    return jax.default_matmul_precision("default")


@functools.lru_cache(maxsize=None)
def _record(config, program, width, layers):
    v5e_or_skip()
    with serving_precision():
        (rec,) = tpu_compile_check.compile_programs(
            config=config, programs=(program,), layers=layers,
            prefill_width=width, keep_text=True)
    return rec


def record(config, program, width=None, layers=None):
    """The tool's record of ONE program of ``benchmarks/configs/<config>``
    compiled by XLA:TPU and Mosaic for compile-only v5e devices, with its
    text (``rec["text"]``): ``program`` as the tool's table names it,
    ``width`` the prefill bucket (None: the cell's first), ``layers`` a cut
    depth (None: the cell's own). Compiled once a process whoever asks."""
    if not program.startswith(("prefill", "batch_prefill", "admit_first")):
        width = None          # no other program's shapes follow the bucket
    return _record(config, program, width or 0, layers or 0)


def program_of(label):
    """``seal_blocks_w64`` -> ``seal_blocks``: the tool's name of a program
    from the label its record carries."""
    return re.sub(r"(_[A-Za-z]?\d+)+$", "", label)


def pinned(table):
    """The parametrisation of a file's ``..._keep_their_lowering`` case over
    its table ``{(config, layers): {label: digest}}``."""
    return pytest.mark.parametrize(
        "key,program",
        [(key, label) for key in sorted(table) for label in table[key]],
        ids=lambda v: v if isinstance(v, str) else v[0])


def assert_pinned(table, key, label):
    config, layers = key
    rec = record(config, program_of(label), layers=layers)
    assert rec["program"] == label
    assert rec.get("lowered_sha256", rec.get("error")) == table[key][label]


def assert_prefill_programs(config, expected, slots=None, context=None,
                            continuing=(False, True)):
    """A cell's whole-model programs are a budget (the chip machine's
    compile cache holds ~190 MiB: six of them): a prefill program a
    (bucket, lanes, fresh | continuing) the cell's engine options reach,
    beside the round's two (with and without log-probs)."""
    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(REPO, "benchmarks", "configs",
                           config + ".json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    assert slots in (None, e.max_decode_slots)
    assert context in (None, e.max_context)
    programs = {(T, e.prefill_lanes(T, group), c)
                for T in e.prefill_buckets
                for group in range(1, e.prefill_chunks_per_round + 1)
                for c in continuing}
    assert sorted(programs) == expected
