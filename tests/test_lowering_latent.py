"""The two latent configurations' programs compiled for compile-only v5e
devices (cells 3 and 4: ``mla-moe-joyai-d5``, ``xing4-mhc-d7``;
tests/lowering.py has the rule for a new configuration)."""
import math
import re

import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.attention import PALLAS, DecodeAttention
from tests.lowering import (
    MOVERS,
    assert_pinned,
    assert_prefill_programs,
    one_v5e,
    pinned,
    program_of,
    record,
    serving_precision,
    tpu_compile_check,
)

# the two latent cells' regions: layers, lanes, context (rows stored at 640)
LATENT_REGIONS = {"xing4-mhc-d7": (7, 16, 16384),
                  "mla-moe-joyai-d5": (5, 64, 4096)}


def _latent_decode_record(config):
    """The absorbed decode attention THROUGH ITS KERNEL (32 heads, value
    512, the module's own chunk) at a latent cell's region, compiled by
    XLA:TPU and Mosaic for a compile-only v5e device."""
    from dynamo_tpu.ops.latent_decode import latent_decode_attention
    one = one_v5e()

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    L, B, S = LATENT_REGIONS[config]
    region = (L, 1, B + 1, S, 640)
    with serving_precision():
        compiled = jax.jit(
            latent_decode_attention, static_argnums=(0, 7)).lower(
            DecodeAttention(PALLAS), arg((B, 32, 640)), arg(region),
            arg((L, 1, B, 4, 640)), arg((), jnp.int32),
            arg((B,), jnp.int32), arg((B,), jnp.int32), 512,
            arg((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    found = tpu_compile_check.region_copies(text, region)
    return {
        "ok": True, "region_shard": list(region),
        "mosaic_calls": text.count("tpu_custom_call"),
        "region_copies": {"count": len(found), "shapes": sorted(set(found))},
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        "region_bytes": 2 * math.prod(region)}


@pytest.mark.parametrize(
    "program", MOVERS + tuple(f"latent_decode_{c}" for c in LATENT_REGIONS))
def test_latent_movers_and_decode_copy_no_region_on_v5e(program):
    """The movers at the long-context latent cell's region, ``[7, 1, 17,
    16384, 640]`` (7 layers, one row kind, 16 + 1 lanes of 16384 tokens,
    rows stored at 640), and the decode kernel at that region and at the
    chat cell's ``[5, 1, 65, 4096, 640]``. A new region shape is a new
    chance for XLA:TPU to relayout it (a 576-wide row did, PR 31): ring ->
    region, region -> pool, both in one jit, and the decode attention's
    kernel, which takes the region whole and un-blocked
    (``memory_space=ANY``), leave the 2.5 GB (1.7 GB) region where it is:
    no copy of its size, temporaries under 5 % of it."""
    config = program.removeprefix("latent_decode_")
    rec = (_latent_decode_record(config) if config in LATENT_REGIONS
           else record("xing4-mhc-d7", program))
    assert rec["ok"], rec
    L, B, S = LATENT_REGIONS.get(config, LATENT_REGIONS["xing4-mhc-d7"])
    assert rec["region_shard"] == [L, 1, B + 1, S, 640]
    if config in LATENT_REGIONS:
        assert rec["mosaic_calls"] == 1      # the kernel, not the XLA loop
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < 0.05 * rec["region_bytes"], rec


# the two continuing programs of the long-context latent cell, by bucket:
# a ceiling on XLA's temporaries. The parent's programs held 0.451 / 0.715
# GB; the prior rows' workspace (32 heads x (192 + 128) values x 16384 rows
# in bfloat16 = 0.336 GB) lives through the whole program: 0.822 / 1.068 GB
# compiled (PR 39, PR 40), of the parent's + ~0.5 GB the chip has room for
CONTINUING_TEMP_CEILING = {2048: 0.86e9, 4096: 1.1e9}


# f32[1,32,256,640], bf16[1,1,32,4096,640]: 32 heads by the stored row
_HEADS_BY_STORED_ROW = re.compile(r"\w+\[(?:\d+,)*32,(?:\d+,)*640\]")


# those two programs' ``lowered_sha256`` (their expert layers hold every
# expert, so the looped row movements of models/moe.py pass them by)
CONTINUING_LOWERING = {2048: "70c281862d799f5e", 4096: "2138dfd01f25012d"}


# `%copy.116 = bf16[1,32,1,16384,192]{4,3,2,1,0:...} copy(%get-tuple-...)`:
# the workspace's keys laid out anew in front of the kernel
_WORKSPACE_COPY = re.compile(
    r"= bf16\[1,32,1,16384,(?:192|128)\]\S* copy(?:-start)?\(")


@pytest.mark.parametrize("what", ["no_region_copy", "no_op_at_the_rows_width",
                                  "temporaries", "lowering", "fused_kernel"])
@pytest.mark.parametrize("T", sorted(CONTINUING_TEMP_CEILING),
                         ids=lambda T: f"T{T}")
def test_latent_continuing_prefill_scores_at_the_heads_width(T, what):
    """A continuing ``[1, T]`` chunk of the long-context latent cell (all 7
    layers, ~45 s of compile each) expands its prior rows into a
    workspace and scores at 192 / 128: the compiled program copies
    nothing the size of the region, has no op whose shape carries 32
    heads x the stored row's 640 columns (the absorbed form's scores and
    accumulator did), its temporaries stay under the parent's plus the
    workspace, and its lowered text is the one recorded."""
    rec = record("xing4-mhc-d7", "batch_prefill_cont", width=T)
    assert rec["ok"], rec.get("error")
    assert rec["program"] == f"batch_prefill_cont_K1_T{T}_S16384"
    assert rec["region_shard"] == [7, 1, 17, 16384, 640]
    if what == "no_region_copy":
        assert rec["region_copies"] == {"count": 0, "shapes": []}
    elif what == "no_op_at_the_rows_width":
        assert not sorted(set(_HEADS_BY_STORED_ROW.findall(rec["text"])))
        assert "bf16[1,32,1,16384,192]" in rec["text"]   # the workspace
    elif what == "temporaries":
        assert rec["temp_bytes"] < CONTINUING_TEMP_CEILING[T], rec["temp_gb"]
    elif what == "fused_kernel":
        # PR 61: every layer's attention is the one Mosaic call, and it
        # reads the workspace where ``_expand_prior`` left it. XLA:TPU lays
        # a [.., rows, 192] buffer out rows-minor by itself (192 is no
        # whole number of 128-lane tiles); a kernel that asked for the
        # keys row-major got a 201 MB copy of them a layer (compiled here,
        # PR 61), so it takes them as COLUMNS (``swapaxes``: a bitcast)
        assert rec["mosaic_calls"] == 15 + 7   # the experts' products, + one
        assert "flash_prefill_attention" in rec["text"]
        assert not _WORKSPACE_COPY.findall(rec["text"])
        assert rec["weight_copies"] == [] or T == 4096   # [4096, 3584] is
        # an activation's shape at that bucket, and the parent's list
    else:
        assert rec["lowered_sha256"] == CONTINUING_LOWERING[T]


def test_long_context_latent_cell_keeps_four_prefill_programs():
    """2 buckets x 1 lane x {fresh, continuing}. The expansion of a
    continuing chunk's prior rows is a jit INSIDE the prefill program,
    which ``test_latent_continuing_prefill_...`` compiles as one module."""
    assert_prefill_programs("xing4-mhc-d7", [
        (2048, 1, False), (2048, 1, True), (4096, 1, False), (4096, 1, True)])


# every program the routed-expert chat cell runs (its prompts fit one
# bucket, so every chunk is fresh) and the long-context cell's round, at
# each cell's own depth
UNMOVED = {
    ("mla-moe-joyai-d5", 0): {
        "flush_ctx": "aa9a25ef5ee32101",
        "seal_blocks_w64": "60d93eac534dbceb",
        "flush_seal_w64": "0cf53d0f869aa38b",
        "round_seal_n4_w64": "b5cdb9902b515b77",
        "load_ctx_pages_n64": "217cccff59be759b",
        "batch_prefill_K2_T128": "49affdac97dd46ae",
    },
    ("xing4-mhc-d7", 0): {"round_seal_n4_w16": "f57339fb4108d2f4"},
}


@pinned(UNMOVED)
def test_programs_beside_the_continuing_latent_chunk_keep_their_lowering(
        key, program):
    assert_pinned(UNMOVED, key, program)


# the latent rounds' temporaries, compiled for the v5e at each cell's OWN
# depth (a 2-layer text hoists what depth 5 re-does a step): 0.232 / 0.294
# GB on the parent of PR 57, most of it the wqb and wkvb stacks transposed
# whole once a round and their layers' slices written out a step; 0.013 /
# 0.045 GB since. The ceiling stands between.
LATENT_ROUND_TEMP_CEILING = 0.05e9


@pytest.mark.parametrize("config,program", [
    ("mla-moe-joyai-d5", "round_seal_n4_w64"),
    ("mla-moe-joyai-d5", "batch_prefill_K2_T128"),
    ("xing4-mhc-d7", "round_seal_n4_w16")])
def test_latent_programs_relayout_neither_wqb_nor_wkvb_on_v5e(
        config, program):
    """The latent block's round and prefill read ``wqb`` and W_kvb where
    they lie. Until PR 57 the round transposed both stacks whole in ENTRY
    (``bf16[5,1536,6144]``, ``bf16[5,512,8192]``), wrote every layer's
    slice of each out again every decode step (five ``bf16[1,1536,6144]``
    and five ``bf16[1,512,8192]`` from one fusion a stack in the step
    loop) and moved them a third time into fast memory, and a prefill
    call wrote each layer's shard out twice. ``mla_moe._attn_in`` ends the
    query product ahead of the reshape to heads; the products over W_kvb
    read two head-major leaves made once at engine start
    (``llama.serving_params``), which the reader knows by their names."""
    rec = record(config, program_of(program))
    assert rec["ok"], rec.get("error")
    assert rec["layers"] == (5 if config == "mla-moe-joyai-d5" else 7)
    assert rec["weight_copies"] == [], rec["weight_copies"]
    if program.startswith("round_seal"):
        assert rec["temp_bytes"] < LATENT_ROUND_TEMP_CEILING, rec["temp_gb"]
