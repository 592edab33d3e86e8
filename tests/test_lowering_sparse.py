"""The linear + sparse attention configuration's programs compiled for
compile-only v5e devices (cell 6: ``minicpm-sala-d16``; tests/lowering.py
has the rule for a new configuration)."""
import pytest

from tests.lowering import assert_prefill_programs, record

# the linear + sparse attention cell's programs (compiled, PR 45: the round
# 0.107 GB, a 4096-token chunk 0.364 GB fresh and 0.444 continuing) with a
# little room. 12.86 GB of weights, rows, compressed keys and state leave
# the chip ~3 GB
SPARSE_TEMP_CEILING = {"round_seal": 0.2e9, "batch_prefill": 0.5e9,
                       "batch_prefill_cont": 0.6e9}


# the full-depth round's digest
ROUND_LOWERING = "ebe6ccdc309a8d57"


@pytest.mark.parametrize("name", sorted(SPARSE_TEMP_CEILING))
def test_sparse_programs_hold_no_copy_of_the_region(name):
    """The fused round and the ``[1, 4096]`` prefills at the published
    widths (16 layers, region ``[4, 2, 17, 32768, 128]``, compressed keys
    ``[4, 2, 17, 2048, 128]``, twelve ``[17, 32, 128, 128]`` float32
    states; ~10-20 s of compile each). The decode step reads the region
    where it lies: the chosen blocks
    by a gather, the keys a step's compressed key averages by one slice a
    lane (a gather over lanes, or a rolled loop that carries the region,
    made XLA:TPU relayout all 1.14 GB of K in every step: PR 45), the
    dense read by the flash kernel (16 query heads a K/V head). XLA's
    temporaries say so: a materialised copy of K or V alone is 1.14 GB.
    (``region_copies`` also counts a layout change FUSED into the slices
    that read it and the state's asynchronous write-backs, which hold no
    buffer of their own: the ceiling on temporaries is the test.)"""
    rec = record("minicpm-sala-d16", name, width=4096)
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [4, 2, 17, 32768, 128]
    assert rec["temp_bytes"] < SPARSE_TEMP_CEILING[name], rec["temp_gb"]
    assert 12.8 < rec["argument_gb"] < 12.95
    if name == "round_seal":
        # the dense read of a lane below the switch: one Mosaic call a
        # sparse layer
        assert rec["mosaic_calls"] >= 4
        assert rec["lowered_sha256"] == ROUND_LOWERING


def test_sparse_cell_keeps_four_prefill_programs():
    """As the other long-prompt cells: 2 buckets x 1 lane x {fresh,
    continuing} whole-model prefill programs beside the round's two."""
    assert_prefill_programs(
        "minicpm-sala-d16", slots=16, context=32768, expected=[
            (2048, 1, False), (2048, 1, True),
            (4096, 1, False), (4096, 1, True)])
