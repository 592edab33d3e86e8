"""The one-part hybrid configuration's programs compiled for compile-only
v5e devices (cell 12: ``nemotron3-nano-ep8``, ALL 52 layers;
tests/lowering.py has the rule for a new configuration)."""
import re

import pytest

import jax
import jax.numpy as jnp

from tests.lowering import (
    assert_prefill_programs,
    one_v5e,
    record,
    serving_precision,
)

# the one-part hybrid cell's programs at its depth (52 layers unrolled,
# 13.42 GB of arguments at 24 lanes; compiled, PR 60: the flush 0
# temporaries, the round 0.078 GB in ~24 s, the ``[1, 4096]`` prefills 0.48 /
# 0.49 GB in ~70 s each)
ONE_PART_TEMP_CEILING = {"flush_ctx": 0.01e9, "round_seal": 0.15e9,
                         "batch_prefill_cont": 0.7e9}


@pytest.mark.parametrize("name", [
    "flush_ctx", "round_seal",
    # ~70 s of compile: by hand and under ``-m slow``
    pytest.param("batch_prefill_cont", marks=pytest.mark.slow)])
def test_one_part_programs_copy_neither_the_state_nor_the_region(name):
    """The flush, the fused round and the continuing ``[1, 4096]`` prefill
    at the published widths (ALL 52 layers: 23 Mamba-2 mixers of 64 heads
    in 8 B/C groups, 6 NoPE GQA layers of 32 query heads over 2 K/V heads,
    23 expert layers of 16 held two-matrix experts stored at 1920). 52
    one-part layers in one program: the round steps 23 grouped
    states in place through ``m2_step`` (a block of two heads reads its
    group's row of B and C), reads six attention layers' rows at a query
    group of 16, and runs TWO grouped products an expert layer at tiles of
    [2688, 640] and [1920, 896]: no ``copy`` the size of the region's
    largest leaf or of a layer's float32 state, temporaries under their
    ceiling, and in the round no weight laid out anew (what is left are
    same-layout prefetches of the routers)."""
    rec = record("nemotron3-nano-ep8", name, width=4096)
    assert rec["ok"], rec.get("error")
    assert rec["layers"] == 52
    assert rec["region_shard"] == [6, 2, 25, 9216, 128]
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < ONE_PART_TEMP_CEILING[name], rec["temp_gb"]
    if name == "round_seal":
        assert {w.split(" ", 1)[1] for w in rec["weight_copies"]} <= {
            "prefetch loop"}, rec["weight_copies"]
        # 23 state steps, 6 decode attention calls, 2 grouped products an
        # expert layer: no third product, no gate matrix
        assert rec["mosaic_calls"] == 23 + 6 + 2 * 23
        assert rec["text"].count("m2_step") >= 23
        assert not re.search(r"= f32\[25,64,64,128\]\S* copy\(", rec["text"])
    if name != "flush_ctx":
        assert 13.3 < rec["argument_gb"] < 13.5


@pytest.mark.parametrize("rows", [144, 24576], ids=["decode", "prefill"])
def test_grouped_products_compile_at_odd_widths(rows):
    """The megablox kernel through Mosaic for the v5e at hidden 2688 (21
    lane columns) and an expert width of 1856 stored at 1920 (15): tiles
    of [2688, 640] and [1920, 896] inside the 16 MiB a call gets, where
    the whole matrices (10.3 MB, double-buffered) are not."""
    from dynamo_tpu.models import moe

    one = one_v5e()
    bf = jnp.bfloat16
    sd = lambda shape, dt=bf: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    H, I = 2688, moe.stored_width(1856)
    assert (moe.gmm_tile_n(H, I, 2), moe.gmm_tile_n(I, H, 2)) == (640, 896)

    def two(x, wu, wd, sizes):
        return moe._gmm_tpu(moe.relu2(moe._gmm_tpu(x, wu, sizes)), wd, sizes)

    with serving_precision():
        text = jax.jit(two).lower(
            sd((rows, H)), sd((16, H, I)), sd((16, I, H)),
            sd((16,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 2


def test_mamba2_step_kernel_compiles_in_eight_groups():
    """``ops/mamba2.py: scan_step_pallas`` with B and C by GROUP, through
    Mosaic for the v5e: 24 lanes' ``[64, 64, 128]`` float32 states in
    place, B and C ``[24, 8, 128]``, a block of two heads reading its
    group's row; the kernel keeps its name."""
    from dynamo_tpu.ops import mamba2

    one = one_v5e()
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    L, H, P, G, N = 24, 64, 64, 8, 128
    bf = jnp.bfloat16
    with serving_precision():
        step = jax.jit(mamba2.scan_step_pallas, donate_argnums=(5,)).lower(
            sd((L, H, P), bf), sd((L, H)), sd((H,)), sd((L, G, N), bf),
            sd((L, G, N), bf), sd((L + 1, H, P, N)), sd((L,), jnp.int32),
            sd((1,), jnp.int32)).compile()
    text = step.as_text()
    assert "m2_step" in text
    assert not re.search(r"= f32\[25,64,64,128\]\S* copy\(", text)
    assert step.memory_analysis().temp_size_in_bytes < 8e6


def test_agentthink_cell_keeps_four_prefill_programs():
    """2 buckets x ONE lane x {fresh, continuing} whole-model prefill
    programs of 52 unrolled layers beside the round."""
    assert_prefill_programs(
        "nemotron3-nano-ep8", context=9216, expected=[
            (1024, 1, False), (1024, 1, True),
            (4096, 1, False), (4096, 1, True)])
