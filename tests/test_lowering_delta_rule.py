"""The delta-rule + latent attention configuration's programs compiled
for compile-only v5e devices (cell 8: ``ling3-flash-ep8-d12``;
tests/lowering.py has the rule for a new configuration)."""
import pytest

from tests.lowering import assert_prefill_programs, record

# the delta-rule + latent attention cell's programs (compiled, PR 47: the
# round 0.083 GB; a 4096-token chunk 0.74 GB fresh and 2.37 GB continuing,
# of which 0.42 GB is the workspace of the prior latent rows expanded per
# head over a 20480-row span). 12.9 GB of weights, latent rows and state
# leave the chip ~4 GB: the continuing chunk is what has to fit
# Since PR 49 the chunk's delta-rule scans run a 256-row block a trip of a
# loop over the live blocks, so the per-chunk products (``A``, ``T``, ``W``,
# the [C, C] query-key products) exist for four chunks at a time and not for
# all 64 of a 4096-row bucket: the continuing chunk holds 0.995 GB (fresh
# 0.374, parent 0.74), and the ceiling came down with it
KDA_TEMP_CEILING = {"round_seal": 0.15e9, "batch_prefill_cont": 1.2e9}

# the full-depth round's digest
ROUND_LOWERING = "2faf5c54da78563f"


@pytest.mark.parametrize("name", sorted(KDA_TEMP_CEILING))
def test_delta_rule_programs_copy_neither_the_state_nor_the_region(name):
    """The fused round and the continuing ``[1, 4096]`` prefill at the
    published widths (12 layers, latent rows ``[2, 1, 49, 20480, 640]``,
    ten ``[49, 32, 128, 128]`` float32 states and ``[49, 3, 12288]``
    windows, 64 held experts a layer; ~15-40 s of compile each). Every
    decode step rewrites ten 100 MB state leaves in place (the
    step kernel aliases its state operand; the leaves ride the round's
    carry) and reads the latent rows where they lie; a continuing chunk
    reads its lane's state and rows and writes them in one tail pass: no
    ``copy`` the size of the latent region (2.57 GB) or of a state leaf,
    temporaries under their ceiling, and the step kernel is there (ten
    KDA layers' and two latent layers' Mosaic calls beside the grouped
    products'). Since PR 48 the step kernel follows a scalar-prefetched
    list of the live lanes under a grid bound that is traced (0.068 GB
    of temporaries for the round): all of the above must hold of that
    form too, the state still aliased in place."""
    rec = record("ling3-flash-ep8-d12", name, width=4096)
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [2, 1, 49, 20480, 640]
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < KDA_TEMP_CEILING[name], rec["temp_gb"]
    assert 12.85 < rec["argument_gb"] < 12.95
    if name == "round_seal":
        assert rec["mosaic_calls"] >= 10 + 2 + 3 * 10
        assert rec["lowered_sha256"] == ROUND_LOWERING
        # its two latent layers read W_kvb where it lies (one transposed
        # ``bf16[512,8192]`` a layer a round until PR 57); what is left
        # are same-layout prefetches of the delta-rule layers' ``w_bg``
        assert {w.split(" ", 1)[1] for w in rec["weight_copies"]} <= {
            "prefetch loop", "prefetch entry"}, rec["weight_copies"]
    else:
        # a block of the looped first half: q | k | v of 512 rows
        assert "bf16[512,12288]" in rec["text"]


def test_delta_rule_cell_keeps_four_prefill_programs():
    """As the other long-prompt cells: 2 buckets x 1 lane x {fresh,
    continuing} whole-model prefill programs beside the round's two."""
    assert_prefill_programs(
        "ling3-flash-ep8-d12", slots=48, context=20480, expected=[
            (1024, 1, False), (1024, 1, True),
            (4096, 1, False), (4096, 1, True)])
