"""The window + full rotary GQA configuration's programs compiled for
compile-only v5e devices (cell 11: ``laguna-s-ep8-d12``; tests/lowering.py
has the rule for a new configuration)."""
import pytest

from tests.lowering import assert_prefill_programs, record

# the window + full rotary GQA cell's programs at its depth (12 layers
# unrolled, 12.6 GB of arguments; compiled, PR 58: the flush 0 temporaries,
# the round 0.038 GB in ~20 s, the continuing ``[1, 4096]`` prefill 0.67 GB
# in ~55 s, the fresh one 0.65)
GQA_TEMP_CEILING = {"flush_ctx": 0.01e9, "round_seal": 0.08e9,
                    "batch_prefill_cont": 0.85e9}


@pytest.mark.parametrize("name", [
    "flush_ctx", "round_seal",
    # ~55 s of compile: by hand and under ``-m slow``
    pytest.param("batch_prefill_cont", marks=pytest.mark.slow)])
def test_window_gqa_programs_copy_neither_the_region_nor_a_weight(name):
    """The flush, the fused round and the continuing ``[1, 4096]`` prefill
    at the published widths (12 layers: full rows ``[3, 8, 17, 17408,
    128]``, window rows ``[9, 8, 17, 512, 128]``, 72 / 48 query heads over
    8 K/V heads, 32 held experts a layer). Rows of two lengths under two
    head counts: the flush wraps the
    window kind modulo its 512 rows and the round reads both kinds where
    they lie (q blocks of ``[8, 9, 128]`` and ``[8, 6, 128]`` a lane, each
    call under its own name); a continuing chunk un-rotates its lane's
    buffers into a workspace and reads its full layers' prior rows through
    a slice: no ``copy`` the size of the region's largest leaf (1.2 GB a
    kind), temporaries under their ceiling, and no layer's ``wq`` / ``wk``
    / ``wv`` laid out anew in front of its product (their products end
    ahead of the reshape to heads; what is left are same-layout prefetches
    of the gates' ``wg`` and, in the round, of one expert's matrices)."""
    rec = record("laguna-s-ep8-d12", name, width=4096)
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [3, 8, 17, 17408, 128]
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < GQA_TEMP_CEILING[name], rec["temp_gb"]
    assert {w.split(" ", 1)[1] for w in rec["weight_copies"]} <= {
        "prefetch loop", "prefetch entry"}, rec["weight_copies"]
    if name == "round_seal":
        # twelve decode attention calls and three grouped products an
        # expert layer
        assert rec["mosaic_calls"] >= 12 + 3 * 11
        for kernel in ("full_gqa_decode_attention",
                       "window_gqa_decode_attention"):
            assert kernel in rec["text"], kernel
    if name != "flush_ctx":
        assert 12.55 < rec["argument_gb"] < 12.7


def test_codeturn_cell_keeps_six_prefill_programs():
    """2 buckets x {one or two lanes at 1024, one at 4096} x {fresh,
    continuing} whole-model prefill programs beside the round."""
    assert_prefill_programs(
        "laguna-s-ep8-d12", slots=16, context=17408, expected=[
            (1024, 1, False), (1024, 1, True),
            (1024, 2, False), (1024, 2, True),
            (4096, 1, False), (4096, 1, True)])
