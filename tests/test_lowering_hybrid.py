"""The state-space hybrid configuration's programs compiled for
compile-only v5e devices (cell 5: ``granite4h-ep2-d10``; tests/lowering.py
has the rule for a new configuration)."""
import re

import pytest

import jax
import jax.numpy as jnp

from tests.lowering import (
    assert_pinned,
    assert_prefill_programs,
    one_v5e,
    pinned,
    record,
    serving_precision,
)

# the state-space hybrid cell's programs: what each may hold in XLA's
# temporaries, with a little room. Compiled, PR 41: the round 0.033 GB, a
# 4096-token chunk 1.014 GB, fresh or continuing. Since PR 46 an expert
# layer's two row movements loop over the live row blocks and a chunk holds
# 0.715 GB at 4096 tokens, 0.353 at 2048 (the straight-line gather's
# [40960, 4096] output and its un-sorted twin no longer live at once). A
# loop that copied the sorted-rows buffer it carries (335 MB at 4096
# tokens x 10 picks, 168 MB at 2048) would pass these ceilings, as
# tests/test_lowering_dense.py holds the dense loops. 12.3 GB of weights, rows
# and recurrent state leave the chip ~3 GB. Since PR 49 every layer's two
# row-wise halves and the Mamba scans loop over the live row blocks too
# (ssm_moe._live_half / _live_scan): 0.339 / 0.348 GB at 2048 and 0.736 /
# 0.753 at 4096, about where they stood (the halves' outputs are whole
# [T, ...] buffers either way); a copy of a layer's weights in front of its
# loops (0.9 GB a layer) would pass every ceiling
HYBRID_TEMP_CEILING = {
    ("round_seal", 4096): 0.1e9,
    ("batch_prefill", 2048): 0.42e9, ("batch_prefill_cont", 2048): 0.42e9,
    ("batch_prefill", 4096): 0.8e9, ("batch_prefill_cont", 4096): 0.8e9,
}


@pytest.mark.parametrize(
    "name,width", sorted(HYBRID_TEMP_CEILING),
    ids=[f"{name}_T{width}" for name, width in sorted(HYBRID_TEMP_CEILING)])
def test_hybrid_programs_copy_neither_the_state_nor_the_region(name, width):
    """The fused round and the four ``[1, T]`` prefills at the published
    widths (10 layers, region ``[1, 8, 33, 8192, 128]``, nine ``[33, 128,
    64, 128]`` float32 states; ~15-35 s of compile each). The recurrent
    state is rewritten in place by every decode step and
    written a lane at a prefill chunk's end; the region is read-only in
    the round and read through a sliced workspace by a continuing chunk:
    no ``copy`` the size of the region (553 MB a kind) or of one layer's
    float32 state (138 MB, 1.26 GB over nine), and temporaries that leave
    the chip its room: in a prefill, the buffer the expert layers' looped
    gather carries is updated in place."""
    rec = record("granite4h-ep2-d10", name, width=width)
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [1, 8, 33, 8192, 128]
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < HYBRID_TEMP_CEILING[name, width], (
        rec["temp_gb"])
    assert rec["argument_gb"] < 12.5
    if name != "round_seal":
        # a block of the looped first half: the mixers' in-projection over
        # 512 rows, where the parent ran the bucket's ``[T, 16768]``
        assert "bf16[512,16768]" in rec["text"]
        return
    # the round: a step's nine Mamba-2 layers step the live lanes' states
    # through ``m2_step`` (PR 52: 31 Mosaic calls -> 40), in place; no
    # every-lane recurrence is left, and neither a step operand (``dt x``
    # and ``y`` as ``[32, 64, 128]`` rows, ``exp(dt A)`` ``[32, 128]``)
    # nor a state leaf is relaid by a ``copy``
    assert rec["mosaic_calls"] == 31 + 9
    assert rec["text"].count("m2_step") >= 9
    assert "f32[33,128,64]" not in rec["text"]
    relayouts = [l for l in rec["text"].splitlines() if re.search(
        r"= f32\[(32,64,128|32,128,64|32,128|33,128,64,128)\]\S* copy\(", l)]
    assert not relayouts, relayouts[:3]


def test_mamba2_step_kernel_compiles_at_the_published_widths():
    """``ops/mamba2.py: scan_step_pallas`` alone, through Mosaic for the
    v5e (~3 s): a decode step over 32 lanes' ``[128, 64, 128]`` float32
    states in place, 1 MB state blocks of 32 heads."""
    from dynamo_tpu.ops import mamba2

    one = one_v5e()
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    L, H, P, N = 32, 128, 64, 128
    bf = jnp.bfloat16
    with serving_precision():
        step = jax.jit(mamba2.scan_step_pallas, donate_argnums=(5,)).lower(
            sd((L, H, P), bf), sd((L, H)), sd((H,)), sd((L, N), bf),
            sd((L, N), bf), sd((L + 1, H, P, N)), sd((L,), jnp.int32),
            sd((1,), jnp.int32)).compile()
    text = step.as_text()
    assert "m2_step" in text
    # the state goes in and comes out in one buffer: no copy of it
    assert not re.search(r"= f32\[33,128,64,128\]\S* copy\(", text)
    assert step.memory_analysis().temp_size_in_bytes < 8e6


def test_hybrid_cell_keeps_four_prefill_programs():
    """As the long-context latent cell: 2 buckets x 1 lane x {fresh,
    continuing} whole-model prefill programs beside the round's two."""
    assert_prefill_programs(
        "granite4h-ep2-d10", slots=32, context=8192, expected=[
            (2048, 1, False), (2048, 1, True),
            (4096, 1, False), (4096, 1, True)])


# the round at the cell's own depth: the guard above compiles it
UNMOVED = {
    ("granite4h-ep2-d10", 0): {"round_seal_n4_w32": "5aace7fa74a39250"},
}


@pinned(UNMOVED)
def test_programs_beside_the_continuing_latent_chunk_keep_their_lowering(
        key, program):
    assert_pinned(UNMOVED, key, program)
