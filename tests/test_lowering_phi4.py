"""The differential-attention + Mamba-1 configuration's programs compiled
for compile-only v5e devices (cell 10: ``phi4-mini-flash``;
tests/lowering.py has the rule for a new configuration)."""
import re

from tests.lowering import record


def test_the_round_relayouts_neither_row_kind_at_the_published_widths():
    """The flush alone and the fused round as the engine builds it, at 40
    lanes of 18432: no synchronous copy of the full rows ([1, 10, 41,
    18432, 128]) nor of a window leaf ([8, 10, 41, 512, 128]: two
    read-modify-writes of one buffer in ONE loop body made XLA:TPU relayout
    it around the loop, 0.43 GB of temporaries a kind), the temporaries
    small, and the kernels there: sixteen differential decode calls (eight
    window layers, layer 17, seven cross layers) beside nine Mamba-1
    steps."""
    flush, round_ = (record("phi4-mini-flash", name)
                     for name in ("flush_ctx", "round_seal"))
    for rec in (flush, round_):
        assert rec["ok"], rec.get("error")
        assert rec["region_shard"] == [1, 10, 41, 18432, 128]
        assert not [l for l in rec["text"].splitlines() if re.search(
            r"= bf16\[(8,10,41,512|1,10,41,18432),128\]\S* copy\(", l)]
        assert rec["temp_bytes"] < 0.15e9, rec["temp_gb"]
    assert round_["mosaic_calls"] == 16 + 9
    assert round_["text"].count("diff_decode_attention") >= 16
