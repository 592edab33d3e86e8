"""The decode kernel as the ENGINE calls it lowers for a TPU — checked on
the CPU by cross-lowering (``lowering_platforms=("tpu",)`` runs Pallas's
TPU lowering rules: BlockSpec tiling, scalar prefetch, the GSPMD
partitioning refusal) — and the per-shard mapping over ``tp`` computes
what the reference computes (interpret mode on the virtual device mesh).

Both failures this file pins were live before PR 21: any tp>1 engine
died with "Mosaic kernels cannot be automatically partitioned", and the
int8-ctx scale BlockSpecs broke the (8, 128) tiling rule. Mosaic's own
compile (layout inference, VMEM fit) needs libtpu's compiler: that is
tools/tpu_compile_check.py (slow-marked below), then chip_smoke.py.

The K/V movers alone compile for compile-only v5e devices in under a
second, so THAT guard is tier-1: no region-shaped copy in ring -> region
or region -> pool (a third of the chip in both dense cells until PR 34).
"""
import math
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import (
    PALLAS,
    PALLAS_INTERPRET,
    REFERENCE,
    DecodeAttention,
    ctx_decode_attention,
    decode_attention_for,
)
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

# the CLI's default engine sizes (EngineConfig): 8 slots, 4096 context,
# 4-step rounds, 64-token pages (= the int8 scale group)
B, S, R, GROUP = 8, 4096, 4, 64


def _kernel_args(c, quant, layers=2):
    sds = jax.ShapeDtypeStruct

    ctx_dtype = jnp.int8 if quant else jnp.bfloat16
    kv = (layers, c.num_kv_heads, B + 1, S, c.head_dim)
    ring = (layers, c.num_kv_heads, B, R, c.head_dim)
    args = [
        sds((B, c.num_heads, c.head_dim), jnp.bfloat16),
        sds(kv, ctx_dtype), sds(kv, ctx_dtype),
        sds(ring, jnp.bfloat16), sds(ring, jnp.bfloat16),
        sds((), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.int32),
    ]
    scales = {"live": sds((B,), jnp.bool_)}
    if quant:
        sc = sds((layers, B + 1, S // GROUP), jnp.float32)
        scales.update(ctx_k_scale=sc, ctx_v_scale=sc)
    return args, scales


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("model", ["llama3_1b", "llama3_8b"])
def test_flash_decode_lowers_for_tpu(model, quant):
    """hd 64 / g 4 and hd 128 serving shapes, dense and int8 ctx, with
    the work list its wrapper builds from ``live``."""
    c = getattr(ModelConfig, model)()
    args, scales = _kernel_args(c, quant)
    lowered = jax.jit(ctx_decode_attention, static_argnums=0).trace(
        DecodeAttention(PALLAS), *args, **scales).lower(
        lowering_platforms=("tpu",)
    )
    assert "tpu_custom_call" in lowered.as_text()


def _abstract(make, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(make), shardings,
    )


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("tp", [1, 4])
def test_decode_step_lowers_for_tpu_on_tp_mesh(tp, kv_quant):
    """decode_step_impl with the kernel forced, GSPMD-sharded params /
    ctx / ring on a tp mesh of the virtual devices: the kernel must be
    shard-mapped (GSPMD cannot partition a Mosaic call)."""
    c = ModelConfig.llama3_1b(num_layers=1)
    mesh = make_mesh(MeshConfig(tp=tp), jax.devices()[:tp])
    params = _abstract(lambda: llama.init_params(c, 0),
                       llama.param_shardings(c, mesh))
    ctx = _abstract(
        lambda: llama.init_ctx(c, B, S, jnp.bfloat16, kv_quant=kv_quant,
                               group=GROUP),
        llama.ctx_shardings(c, mesh, kv_quant=kv_quant),
    )
    ring = _abstract(lambda: llama.init_ring(c, B, R, jnp.bfloat16),
                     llama.ring_shardings(c, mesh))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    lowered = llama.decode_step.trace(
        c, params, ctx, ring, i32(B), i32(B), i32(B), i32(),
        attn=DecodeAttention(PALLAS, mesh),
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


# small shapes for the interpreter: 4 kv heads shard over tp in {2, 4}
L_, NKV, NH, HD = 2, 4, 8, 16
B_, S_, R_, G_ = 2, 32, 2, 16


@pytest.fixture(scope="module")
def small():
    rng = np.random.RandomState(0)

    def f32(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)

    return dict(
        q=f32(B_, NH, HD),
        ck=f32(L_, NKV, B_ + 1, S_, HD), cv=f32(L_, NKV, B_ + 1, S_, HD),
        rk=f32(L_, NKV, B_, R_, HD), rv=f32(L_, NKV, B_, R_, HD),
        base=jnp.asarray([17, 30], jnp.int32),
    )


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("tp", [2, 4])
def test_shard_mapped_kernel_matches_reference(small, tp, quant):
    """The kernel mapped per shard over tp (interpret mode, virtual
    devices) vs the unsharded jnp reference: heads are independent, so
    sharding q/out on heads and ctx/ring on kv heads changes nothing."""
    d = small
    ck, cv, scales = d["ck"], d["cv"], ()
    if quant:
        def q8(x):
            g = np.asarray(x).reshape(L_, NKV, B_ + 1, S_ // G_, G_, HD)
            s = np.maximum(np.abs(g).max(axis=(1, 4, 5)) / 127.0, 1e-8)
            q = np.clip(np.rint(g / s[:, None, :, :, None, None]),
                        -127, 127).astype(np.int8).reshape(x.shape)
            return jnp.asarray(q), jnp.asarray(s, jnp.float32)

        (ck, ks), (cv, vs) = q8(ck), q8(cv)
        scales = (ks, vs)
    ctx_lens = d["base"] + 2
    mesh = make_mesh(MeshConfig(tp=tp), jax.devices()[:tp])
    attn = DecodeAttention(PALLAS_INTERPRET, mesh, chunk=16)
    args = (d["q"], ck, cv, d["rk"], d["rv"], jnp.int32(1), ctx_lens,
            d["base"]) + (scales or (None, None))
    # every lane, then a work list of lane 1 alone (replicated: each shard
    # walks it over its own heads): lane 0 comes back 0
    for live in (None, jnp.asarray([False, True])):
        got = jax.jit(ctx_decode_attention, static_argnums=0)(
            attn, *args, live)
        want = ctx_decode_attention(REFERENCE, *args, live)
        # interpret mode emulates the MXU's bf16 passes (test_flash_decode)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-3, atol=5e-3)
        assert live is None or not np.asarray(got)[0].any()
        # and the output really is head-sharded over the mesh
        assert len({s.index for s in got.addressable_shards}) == tp


def test_kv_heads_must_divide_tp(small):
    d = small
    mesh = make_mesh(MeshConfig(tp=8), jax.devices()[:8])
    with pytest.raises(ValueError, match="kv heads do not divide"):
        ctx_decode_attention(
            DecodeAttention(PALLAS_INTERPRET, mesh), d["q"], d["ck"],
            d["cv"], d["rk"], d["rv"], jnp.int32(0), d["base"] + 1,
            d["base"],
        )


def test_engine_selection_is_by_device_and_named():
    """CPU test meshes run the reference, by name; an implementation
    nobody wrote is an error, never a silent substitute."""
    mesh = make_mesh(MeshConfig(tp=1), jax.devices()[:1])
    assert decode_attention_for(mesh) is REFERENCE
    with pytest.raises(ValueError, match="unknown decode attention"):
        DecodeAttention("auto")


sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import tpu_compile_check  # noqa: E402

MOVERS = ("flush_ctx", "seal_blocks", "flush_seal")


def _v5e_or_skip():
    """The compile-only v5e topology, described inside a fixture (never at
    import: one process at a time may load libtpu), or a skip."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name=tpu_compile_check.TOPOLOGY)
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no compile-only v5e topology here: {exc!r:.200}")


# the dense kernel's serving shapes: (layers, K/V heads, lanes, region rows,
# query heads, head size) of cell 1, a tp = 4 shard of cell 2, cell 9, and
# the smoke's llama3_1b, whose head of 64 a hand-made DMA cannot slice
DENSE_KERNEL_SHAPES = {"mistral7b-w8": (32, 8, 8, 4096, 32, 128),
                       "nemo12b-tp4_shard": (40, 2, 16, 4096, 8, 128),
                       "jamba2-3b": (2, 1, 96, 4096, 20, 128),
                       "llama3_1b": (16, 8, 8, 4096, 32, 64)}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("shape", sorted(DENSE_KERNEL_SHAPES))
def test_the_work_list_kernel_compiles_for_the_v5e(shape, quant):
    """``flash_decode_attention`` through Mosaic for a compile-only v5e
    (~0.5 s each): a grid whose bound is the list's traced length, 512-row
    K and V blocks by the item's lane and chunk, the int8 region's scales
    as the layer's block. One Mosaic call, and (at a head of 128: XLA
    holds a narrower head's region rows-minor and relays it for ANY Mosaic
    call, the parent's too) no copy of the region in front of it."""
    one = jax.sharding.SingleDeviceSharding(_v5e_or_skip().devices[0])
    L, nkv, B, S, nh, hd = DENSE_KERNEL_SHAPES[shape]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    region = (L, nkv, B + 1, S, hd)
    kv = arg(region, jnp.int8 if quant else jnp.bfloat16)
    ring = arg((L, nkv, B, R, hd))
    scale = arg((L, B + 1, S // GROUP), jnp.float32) if quant else None
    # conftest's "highest" makes Mosaic refuse bf16 dots
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(ctx_decode_attention, static_argnums=0).lower(
            DecodeAttention(PALLAS), arg((B, nh, hd)), kv, kv, ring, ring,
            arg((), jnp.int32), arg((B,), jnp.int32), arg((B,), jnp.int32),
            scale, scale, arg((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%flash_decode_attention\S* = ", text)) == 1
    if hd == 128:
        assert not tpu_compile_check.region_copies(text, region)
        assert compiled.memory_analysis().temp_size_in_bytes < 4e6


@pytest.fixture(scope="module", params=["mistral7b-w8", "nemo12b-tp4"],
                ids=["tp1_8slots", "tp4_16slots"])
def mover_records(request):
    """The movers at the dense cells' K/V shapes (kvh 8, hd 128, S 4096,
    64-token pages; 8 slots on one chip, 16 over tp=4), 2 layers,
    compiled by XLA:TPU for compile-only v5e devices."""
    _v5e_or_skip()
    records = tpu_compile_check.compile_programs(
        config=request.param, layers=2, programs=MOVERS)
    return dict(zip(MOVERS, records))   # the tool keeps its table's order


@pytest.mark.parametrize("program", MOVERS)
def test_kv_movers_copy_no_region_on_v5e(mover_records, program):
    """ring -> region, region -> pool, and both in one jit: the compiled
    text has no ``copy`` the size of a region buffer (5-d or a flat view)
    and the program's temporaries stay under 5 % of one."""
    rec = mover_records[program]
    assert rec["ok"], rec
    assert rec["region_shard"][1:] in ([8, 9, 4096, 128], [2, 17, 4096, 128])
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < 0.05 * rec["region_bytes"], rec


@pytest.mark.parametrize("config", ["nemo12b-tp4", "jamba2-3b"])
def test_admit_first_compiles_for_the_v5e_in_place(config):
    """The one program a prefill dispatch (every first token sampled, every
    slot admitted: ``TpuEngine._build_jits.admit_first``) at a cell's own
    vocabulary and slots, the logits' vocabulary over tp = 4 in the
    four-chip cell: XLA:TPU takes it, the donated ``dev`` is updated in
    place (the [B, V] histogram is not copied) and its temporaries stay
    about the K rows of logits it samples."""
    _v5e_or_skip()
    (rec,) = tpu_compile_check.compile_programs(
        config=config, programs=("admit_first",))
    assert rec["ok"], rec.get("error")
    assert rec["program"] == "admit_first_K2"
    assert rec["mosaic_calls"] == 0
    assert rec["alias_gb"] == rec["output_gb"] > 0, rec
    assert rec["temp_bytes"] < 4e6, rec


# the two latent cells' regions: layers, lanes, context (rows stored at 640)
LATENT_REGIONS = {"xing4-mhc-d7": (7, 16, 16384),
                  "mla-moe-joyai-d5": (5, 64, 4096)}


@pytest.fixture(scope="module")
def latent_records():
    """The movers at the long-context latent cell's region,
    ``[7, 1, 17, 16384, 640]`` (7 layers, one row kind, 16 + 1 lanes of
    16384 tokens, rows stored at 640), and the absorbed decode attention
    THROUGH ITS KERNEL (32 heads, value 512, the module's own chunk) at
    that region and at the chat cell's ``[5, 1, 65, 4096, 640]``,
    compiled by XLA:TPU and Mosaic for a compile-only v5e device."""
    topo = _v5e_or_skip()
    records = dict(zip(MOVERS, tpu_compile_check.compile_programs(
        config="xing4-mhc-d7", programs=MOVERS)))
    from dynamo_tpu.ops.latent_decode import latent_decode_attention
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for config, (L, B, S) in LATENT_REGIONS.items():
        region = (L, 1, B + 1, S, 640)
        # conftest's "highest" makes Mosaic refuse bf16 dots
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(
                latent_decode_attention, static_argnums=(0, 7)).lower(
                DecodeAttention(PALLAS), arg((B, 32, 640)), arg(region),
                arg((L, 1, B, 4, 640)), arg((), jnp.int32),
                arg((B,), jnp.int32), arg((B,), jnp.int32), 512,
                arg((B,), jnp.bool_)).compile()
        text = compiled.as_text()
        found = tpu_compile_check.region_copies(text, region)
        records[f"latent_decode_{config}"] = {
            "ok": True, "region_shard": list(region),
            "mosaic_calls": text.count("tpu_custom_call"),
            "region_copies": {"count": len(found),
                              "shapes": sorted(set(found))},
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "region_bytes": 2 * math.prod(region)}
    return records


@pytest.mark.parametrize(
    "program", MOVERS + tuple(f"latent_decode_{c}" for c in LATENT_REGIONS))
def test_latent_movers_and_decode_copy_no_region_on_v5e(latent_records,
                                                        program):
    """A new region shape is a new chance for XLA:TPU to relayout it (a
    576-wide row did, PR 31): ring -> region, region -> pool, both in one
    jit, and the decode attention's kernel, which takes the region whole
    and un-blocked (``memory_space=ANY``), leave the 2.5 GB (1.7 GB)
    region where it is: no copy of its size, temporaries under 5 % of
    it."""
    rec = latent_records[program]
    assert rec["ok"], rec
    config = program.removeprefix("latent_decode_")
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


@pytest.fixture(scope="module", params=sorted(CONTINUING_TEMP_CEILING),
                ids=lambda T: f"T{T}")
def continuing_prefill_record(request):
    """A continuing ``[1, T]`` prefill of the long-context latent cell (all
    7 layers, region ``[7, 1, 17, 16384, 640]``), compiled by XLA:TPU and
    Mosaic for a compile-only v5e device (~45 s each), with its text."""
    _v5e_or_skip()
    # conftest's "highest" makes Mosaic refuse the grouped product's bf16
    # dots, and no serving process sets it
    with jax.default_matmul_precision("default"):
        (rec,) = tpu_compile_check.compile_programs(
            config="xing4-mhc-d7", programs=("batch_prefill_cont",),
            prefill_width=request.param, keep_text=True)
    return request.param, rec


# f32[1,32,256,640], bf16[1,1,32,4096,640]: 32 heads by the stored row
_HEADS_BY_STORED_ROW = re.compile(r"\w+\[(?:\d+,)*32,(?:\d+,)*640\]")


# those two programs' ``lowered_sha256`` on the parent of PR 46 (9362de9):
# their expert layers hold every expert, so the looped row movements of
# models/moe.py pass them by (8192 / 16384 sorted rows, padding alone dead:
# the loops lose there, PERF.md section 6, PR 46)
# PR 57 MEANT to move them (8f681471b1b3b42f / c4c1c5a74bec99f4 until then):
# the products over W_kvb read ``wkb`` / ``wvb``, the stack sliced inside
# ``_expand_prior``'s loop; temporaries 0.822 / 1.068 -> 0.762 / 1.011 GB
# PR 61 MEANT to move them (4b19bd970156cc91 / 843c34a9da1c84ae until
# then): the attention of every layer is ``fused_prefill_attention``, ONE
# Mosaic call (ops/flash_prefill.py) over the loops' own work list, reading
# the workspace where ``_expand_prior`` wrote it; temporaries 0.762 / 1.011
# -> 0.731 / 1.016 GB
CONTINUING_LOWERING = {2048: "70c281862d799f5e", 4096: "2138dfd01f25012d"}


# `%copy.116 = bf16[1,32,1,16384,192]{4,3,2,1,0:...} copy(%get-tuple-...)`:
# the workspace's keys laid out anew in front of the kernel
_WORKSPACE_COPY = re.compile(
    r"= bf16\[1,32,1,16384,(?:192|128)\]\S* copy(?:-start)?\(")


@pytest.mark.parametrize("what", ["no_region_copy", "no_op_at_the_rows_width",
                                  "temporaries", "lowering", "fused_kernel"])
def test_latent_continuing_prefill_scores_at_the_heads_width(
        continuing_prefill_record, what):
    """A continuing chunk over latent rows expands its prior rows into a
    workspace and scores at 192 / 128: the compiled program copies
    nothing the size of the region, has no op whose shape carries 32
    heads x the stored row's 640 columns (the absorbed form's scores and
    accumulator did), its temporaries stay under the parent's plus the
    workspace, and its lowered text is the one recorded."""
    T, rec = continuing_prefill_record
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
    """The cell's whole-model programs are a budget (the chip machine's
    compile cache holds ~190 MiB: six of them): a prefill program a
    (bucket, lanes, fresh | continuing), here 2 x 1 x 2, beside the
    round's two (with and without log-probs). The expansion of a
    continuing chunk's prior rows is a jit INSIDE the prefill program,
    which ``test_latent_continuing_prefill_...`` compiles as one module."""
    import json

    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(os.path.dirname(tpu_compile_check.__file__), "..",
                           "benchmarks", "configs",
                           "xing4-mhc-d7.json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    programs = {(T, e.prefill_lanes(T, group), continuing)
                for T in e.prefill_buckets
                for group in range(1, e.prefill_chunks_per_round + 1)
                for continuing in (False, True)}
    assert sorted(programs) == [(2048, 1, False), (2048, 1, True),
                                (4096, 1, False), (4096, 1, True)]


# the state-space hybrid cell's programs: what each may hold in XLA's
# temporaries, with a little room. Compiled, PR 41: the round 0.033 GB, a
# 4096-token chunk 1.014 GB, fresh or continuing. Since PR 46 an expert
# layer's two row movements loop over the live row blocks and a chunk holds
# 0.715 GB at 4096 tokens, 0.353 at 2048 (the straight-line gather's
# [40960, 4096] output and its un-sorted twin no longer live at once). A
# loop that copied the sorted-rows buffer it carries (335 MB at 4096
# tokens x 10 picks, 168 MB at 2048) would pass these ceilings, as
# LOOPED_TEMP_CEILING below holds the dense loops. 12.3 GB of weights, rows
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


@pytest.fixture(scope="module", params=sorted(HYBRID_TEMP_CEILING),
                ids=lambda p: f"{p[0]}_T{p[1]}")
def hybrid_record(request):
    """The fused round and the four ``[1, T]`` prefills of the state-space
    hybrid cell at its published widths (10 layers, region ``[1, 8, 33,
    8192, 128]``, nine ``[33, 128, 64, 128]`` float32 states), compiled by
    XLA:TPU and Mosaic for a compile-only v5e (~15-35 s)."""
    _v5e_or_skip()
    name, width = request.param
    with jax.default_matmul_precision("default"):
        (rec,) = tpu_compile_check.compile_programs(
            config="granite4h-ep2-d10", programs=(name,),
            prefill_width=width, keep_text=True)
    return request.param, rec


def test_hybrid_programs_copy_neither_the_state_nor_the_region(hybrid_record):
    """The recurrent state is rewritten in place by every decode step and
    written a lane at a prefill chunk's end; the region is read-only in
    the round and read through a sliced workspace by a continuing chunk:
    no ``copy`` the size of the region (553 MB a kind) or of one layer's
    float32 state (138 MB, 1.26 GB over nine), and temporaries that leave
    the chip its room: in a prefill, the buffer the expert layers' looped
    gather carries is updated in place."""
    key, rec = hybrid_record
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [1, 8, 33, 8192, 128]
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < HYBRID_TEMP_CEILING[key], rec["temp_gb"]
    assert rec["argument_gb"] < 12.5
    if key[0] != "round_seal":
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
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.ops import mamba2

    one = SingleDeviceSharding(_v5e_or_skip().devices[0])
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    L, H, P, N = 32, 128, 64, 128
    bf = jnp.bfloat16
    with jax.default_matmul_precision("default"):
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
    import json

    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(os.path.dirname(tpu_compile_check.__file__), "..",
                           "benchmarks", "configs",
                           "granite4h-ep2-d10.json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    assert (e.max_decode_slots, e.max_context) == (32, 8192)
    programs = {(T, e.prefill_lanes(T, group), continuing)
                for T in e.prefill_buckets
                for group in range(1, e.prefill_chunks_per_round + 1)
                for continuing in (False, True)}
    assert sorted(programs) == [(2048, 1, False), (2048, 1, True),
                                (4096, 1, False), (4096, 1, True)]


# the linear + sparse attention cell's programs (compiled, PR 45: the round
# 0.107 GB, a 4096-token chunk 0.364 GB fresh and 0.444 continuing) with a
# little room. 12.86 GB of weights, rows, compressed keys and state leave
# the chip ~3 GB
SPARSE_TEMP_CEILING = {"round_seal": 0.2e9, "batch_prefill": 0.5e9,
                       "batch_prefill_cont": 0.6e9}


@pytest.fixture(scope="module", params=sorted(SPARSE_TEMP_CEILING))
def sparse_record(request):
    """The fused round and the ``[1, 4096]`` prefills of the linear +
    sparse attention cell at its published widths (16 layers, region ``[4,
    2, 17, 32768, 128]``, compressed keys ``[4, 2, 17, 2048, 128]``, twelve
    ``[17, 32, 128, 128]`` float32 states), compiled by XLA:TPU and Mosaic
    for a compile-only v5e (~10-20 s)."""
    _v5e_or_skip()
    with jax.default_matmul_precision("default"):
        (rec,) = tpu_compile_check.compile_programs(
            config="minicpm-sala-d16", programs=(request.param,),
            prefill_width=4096, keep_text=True)
    return request.param, rec


def test_sparse_programs_hold_no_copy_of_the_region(sparse_record):
    """The decode step reads the region where it lies: the chosen blocks
    by a gather, the keys a step's compressed key averages by one slice a
    lane (a gather over lanes, or a rolled loop that carries the region,
    made XLA:TPU relayout all 1.14 GB of K in every step: PR 45), the
    dense read by the flash kernel (16 query heads a K/V head). XLA's
    temporaries say so: a materialised copy of K or V alone is 1.14 GB.
    (``region_copies`` also counts a layout change FUSED into the slices
    that read it and the state's asynchronous write-backs, which hold no
    buffer of their own: the ceiling on temporaries is the test.)"""
    name, rec = sparse_record
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [4, 2, 17, 32768, 128]
    assert rec["temp_bytes"] < SPARSE_TEMP_CEILING[name], rec["temp_gb"]
    assert 12.8 < rec["argument_gb"] < 12.95
    if name == "round_seal":
        # the dense read of a lane below the switch: one Mosaic call a
        # sparse layer
        assert rec["mosaic_calls"] >= 4
        assert rec["lowered_sha256"] == ROUND_LOWERING["minicpm-sala-d16"]


def test_sparse_cell_keeps_four_prefill_programs():
    """As the other long-prompt cells: 2 buckets x 1 lane x {fresh,
    continuing} whole-model prefill programs beside the round's two."""
    import json

    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(os.path.dirname(tpu_compile_check.__file__), "..",
                           "benchmarks", "configs",
                           "minicpm-sala-d16.json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    assert (e.max_decode_slots, e.max_context) == (16, 32768)
    programs = {(T, e.prefill_lanes(T, group), continuing)
                for T in e.prefill_buckets
                for group in range(1, e.prefill_chunks_per_round + 1)
                for continuing in (False, True)}
    assert sorted(programs) == [(2048, 1, False), (2048, 1, True),
                                (4096, 1, False), (4096, 1, True)]


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


@pytest.fixture(scope="module", params=sorted(KDA_TEMP_CEILING))
def kda_record(request):
    """The fused round and the continuing ``[1, 4096]`` prefill of the
    delta-rule + latent attention cell at its published widths (12 layers,
    latent rows ``[2, 1, 49, 20480, 640]``, ten ``[49, 32, 128, 128]``
    float32 states and ``[49, 3, 12288]`` windows, 64 held experts a
    layer), compiled by XLA:TPU and Mosaic for a compile-only v5e (~15-40
    s)."""
    _v5e_or_skip()
    with jax.default_matmul_precision("default"):
        (rec,) = tpu_compile_check.compile_programs(
            config="ling3-flash-ep8-d12", programs=(request.param,),
            prefill_width=4096, keep_text=True)
    return request.param, rec


def test_delta_rule_programs_copy_neither_the_state_nor_the_region(
        kda_record):
    """Every decode step rewrites ten 100 MB state leaves in place (the
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
    name, rec = kda_record
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [2, 1, 49, 20480, 640]
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["temp_bytes"] < KDA_TEMP_CEILING[name], rec["temp_gb"]
    assert 12.85 < rec["argument_gb"] < 12.95
    if name == "round_seal":
        assert rec["mosaic_calls"] >= 10 + 2 + 3 * 10
        assert rec["lowered_sha256"] == ROUND_LOWERING["ling3-flash-ep8-d12"]
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
    import json

    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(os.path.dirname(tpu_compile_check.__file__), "..",
                           "benchmarks", "configs",
                           "ling3-flash-ep8-d12.json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    assert (e.max_decode_slots, e.max_context) == (48, 20480)
    programs = {(T, e.prefill_lanes(T, group), continuing)
                for T in e.prefill_buckets
                for group in range(1, e.prefill_chunks_per_round + 1)
                for continuing in (False, True)}
    assert sorted(programs) == [(1024, 1, False), (1024, 1, True),
                                (4096, 1, False), (4096, 1, True)]


# the Mamba-1 + attention cell's programs at the WHOLE model's depth (28
# layers unrolled, 7.4 GB of arguments; compiled, PR 51: the round 0.091 GB
# of temporaries in ~16 s, the ``[2, 2048]`` fresh prefill 1.34 GB in ~26 s)
M1_TEMP_CEILING = {"round_seal": 0.15e9, "batch_prefill": 1.6e9}


@pytest.fixture(scope="module", params=sorted(M1_TEMP_CEILING))
def m1_record(request):
    """The fused round and the fresh ``[2, 2048]`` prefill of the Mamba-1
    + attention cell at its published widths and depth (region ``[2, 1,
    97, 4096, 128]``, 26 ``[97, 16, 5120]`` float32 states and ``[97, 3,
    5120]`` windows), compiled by XLA:TPU and Mosaic for a compile-only
    v5e."""
    _v5e_or_skip()
    with jax.default_matmul_precision("default"):
        (rec,) = tpu_compile_check.compile_programs(
            config="jamba2-3b", programs=(request.param,),
            prefill_width=2048, keep_text=True)
    return request.param, rec


def test_selective_scan_programs_relayout_neither_the_state_nor_a(m1_record):
    """Every decode step rewrites the live lanes of 26 state leaves in
    place (the step kernel aliases its state operand; the leaves ride the
    round's carry), a prefill chunk reads its lane's state and writes it
    in one tail pass: no synchronous ``copy`` (a relayout) of a state leaf
    or of ``A`` [16, 5120] held channels-minor, no copy the size of the
    K/V region, temporaries under their ceiling, and the kernels are
    there: 26 ``m1_step`` calls beside the two attention layers' in the
    round, the scan kernel in the prefill. XLA's memory-space assignment
    may park ONE state leaf in fast memory around the round's loop (an
    async copy-start / copy-done pair in and one out: 64 MB a round of
    four steps beside 24 GB of weight reads): that is its own decision
    and moves no layout."""
    name, rec = m1_record
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [2, 1, 97, 4096, 128]
    relayouts = [l for l in rec["text"].splitlines() if re.search(
        r"= f32\[(97,)?16,5120\]\S* copy\(", l)]
    assert not relayouts, relayouts[:3]
    assert not [s for s in rec["region_copies"]["shapes"]
                if not s.startswith("f32[97,16,5120]")], rec
    assert rec["region_copies"]["count"] <= 2, rec
    assert rec["temp_bytes"] < M1_TEMP_CEILING[name], rec["temp_gb"]
    assert 7.3 < rec["argument_gb"] < 7.5
    if name == "round_seal":
        assert rec["mosaic_calls"] == 26 + 2
        assert rec["lowered_sha256"] == ROUND_LOWERING["jamba2-3b"]
        # x_proj's 192 columns (dt rank 160 + 2 x 16) for 96 lanes
        assert "bf16[96,192]" in rec["text"]
    else:
        assert rec["mosaic_calls"] >= 26
        # a block of the looped first half: in_proj over 512 rows
        assert "bf16[512,10240]" in rec["text"]


def test_selective_scan_kernels_compile_at_the_published_widths():
    """Both kernels of ops/mamba1.py alone, through Mosaic for the v5e
    (~3 s): a 256-position scan block of one lane and a decode step over
    96 lanes' states in place, at inner 5120 and 16 state columns."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.ops import mamba1

    one = SingleDeviceSharding(_v5e_or_skip().devices[0])
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    T, I, N, L = 256, 5120, 16, 96
    bf = jnp.bfloat16
    with jax.default_matmul_precision("default"):
        jax.jit(mamba1.scan_pallas).lower(
            sd((T, I), bf), sd((T, I)), sd((T, N), bf), sd((T, N), bf),
            sd((N, I)), sd((I,)), sd((N, I))).compile()
        step = jax.jit(mamba1.scan_step_pallas, donate_argnums=(6,)).lower(
            sd((L, I), bf), sd((L, I)), sd((L, N), bf), sd((L, N), bf),
            sd((N, I)), sd((I,)), sd((L + 1, N, I)), sd((L,), jnp.int32),
            sd((1,), jnp.int32)).compile()
    text = step.as_text()
    assert "m1_step" in text
    # the state goes in and comes out in one buffer: no copy of it
    assert not re.search(r"= f32\[97,16,5120\]\S* copy\(", text)


def test_chat_rate_cell_keeps_nine_prefill_programs():
    """4 buckets x {1, 2} lanes fresh (the mix's prompts are one chunk),
    and continuing programs only where the check's long prompt goes."""
    import json

    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(os.path.dirname(tpu_compile_check.__file__), "..",
                           "benchmarks", "configs", "jamba2-3b.json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    assert (e.max_decode_slots, e.max_context) == (96, 4096)
    fresh = {(T, e.prefill_lanes(T, group)) for T in e.prefill_buckets
             for group in range(1, e.prefill_chunks_per_round + 1)}
    assert sorted(fresh) == [(256, 1), (256, 2), (512, 1), (512, 2),
                             (1024, 1), (1024, 2), (2048, 1), (2048, 2)]


# the window + full rotary GQA cell's programs at its depth (12 layers
# unrolled, 12.6 GB of arguments; compiled, PR 58: the flush 0 temporaries,
# the round 0.038 GB in ~20 s, the continuing ``[1, 4096]`` prefill 0.67 GB
# in ~55 s, the fresh one 0.65)
GQA_TEMP_CEILING = {"flush_ctx": 0.01e9, "round_seal": 0.08e9,
                    "batch_prefill_cont": 0.85e9}


@pytest.fixture(scope="module", params=[
    "flush_ctx", "round_seal",
    # ~55 s of compile: by hand and under ``-m slow`` (the tier-1 run stood
    # 84 s under its limit when the cell was added)
    pytest.param("batch_prefill_cont", marks=pytest.mark.slow)])
def gqa_record(request):
    """The flush, the fused round and the continuing ``[1, 4096]`` prefill
    of the window + full rotary GQA cell at its published widths (12
    layers: full rows ``[3, 8, 17, 17408, 128]``, window rows ``[9, 8, 17,
    512, 128]``, 72 / 48 query heads over 8 K/V heads, 32 held experts a
    layer), compiled by XLA:TPU and Mosaic for a compile-only v5e."""
    _v5e_or_skip()
    with jax.default_matmul_precision("default"):
        (rec,) = tpu_compile_check.compile_programs(
            config="laguna-s-ep8-d12", programs=(request.param,),
            prefill_width=4096, keep_text=True)
    return request.param, rec


def test_window_gqa_programs_copy_neither_the_region_nor_a_weight(gqa_record):
    """Rows of two lengths under two head counts: the flush wraps the
    window kind modulo its 512 rows and the round reads both kinds where
    they lie (q blocks of ``[8, 9, 128]`` and ``[8, 6, 128]`` a lane, each
    call under its own name); a continuing chunk un-rotates its lane's
    buffers into a workspace and reads its full layers' prior rows through
    a slice: no ``copy`` the size of the region's largest leaf (1.2 GB a
    kind), temporaries under their ceiling, and no layer's ``wq`` / ``wk``
    / ``wv`` laid out anew in front of its product (their products end
    ahead of the reshape to heads; what is left are same-layout prefetches
    of the gates' ``wg`` and, in the round, of one expert's matrices)."""
    name, rec = gqa_record
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
    import json

    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(os.path.dirname(tpu_compile_check.__file__), "..",
                           "benchmarks", "configs",
                           "laguna-s-ep8-d12.json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    assert (e.max_decode_slots, e.max_context) == (16, 17408)
    programs = {(T, e.prefill_lanes(T, group), continuing)
                for T in e.prefill_buckets
                for group in range(1, e.prefill_chunks_per_round + 1)
                for continuing in (False, True)}
    assert sorted(programs) == [(1024, 1, False), (1024, 1, True),
                                (1024, 2, False), (1024, 2, True),
                                (4096, 1, False), (4096, 1, True)]


# the one-part hybrid cell's programs at its depth (52 layers unrolled,
# 13.42 GB of arguments at 24 lanes; compiled, PR 60: the flush 0
# temporaries, the round 0.078 GB in ~24 s, the ``[1, 4096]`` prefills 0.48 /
# 0.49 GB in ~70 s each)
ONE_PART_TEMP_CEILING = {"flush_ctx": 0.01e9, "round_seal": 0.15e9,
                         "batch_prefill_cont": 0.7e9}


@pytest.fixture(scope="module", params=[
    "flush_ctx", "round_seal",
    # ~70 s of compile: by hand and under ``-m slow``
    pytest.param("batch_prefill_cont", marks=pytest.mark.slow)])
def one_part_record(request):
    """The flush, the fused round and the continuing ``[1, 4096]`` prefill
    of the one-part hybrid cell at its published widths (ALL 52 layers: 23
    Mamba-2 mixers of 64 heads in 8 B/C groups, 6 NoPE GQA layers of 32
    query heads over 2 K/V heads, 23 expert layers of 16 held two-matrix
    experts stored at 1920), compiled by XLA:TPU and Mosaic for a
    compile-only v5e."""
    _v5e_or_skip()
    with jax.default_matmul_precision("default"):
        (rec,) = tpu_compile_check.compile_programs(
            config="nemotron3-nano-ep8", programs=(request.param,),
            prefill_width=4096, keep_text=True)
    return request.param, rec


def test_one_part_programs_copy_neither_the_state_nor_the_region(
        one_part_record):
    """52 one-part layers in one program: the round steps 23 grouped
    states in place through ``m2_step`` (a block of two heads reads its
    group's row of B and C), reads six attention layers' rows at a query
    group of 16, and runs TWO grouped products an expert layer at tiles of
    [2688, 640] and [1920, 896]: no ``copy`` the size of the region's
    largest leaf or of a layer's float32 state, temporaries under their
    ceiling, and in the round no weight laid out anew (what is left are
    same-layout prefetches of the routers)."""
    name, rec = one_part_record
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
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.models import moe

    one = SingleDeviceSharding(_v5e_or_skip().devices[0])
    bf = jnp.bfloat16
    sd = lambda shape, dt=bf: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    H, I = 2688, moe.stored_width(1856)
    assert (moe.gmm_tile_n(H, I, 2), moe.gmm_tile_n(I, H, 2)) == (640, 896)

    def two(x, wu, wd, sizes):
        return moe._gmm_tpu(moe.relu2(moe._gmm_tpu(x, wu, sizes)), wd, sizes)

    with jax.default_matmul_precision("default"):
        text = jax.jit(two).lower(
            sd((rows, H)), sd((16, H, I)), sd((16, I, H)),
            sd((16,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 2


def test_mamba2_step_kernel_compiles_in_eight_groups():
    """``ops/mamba2.py: scan_step_pallas`` with B and C by GROUP, through
    Mosaic for the v5e: 24 lanes' ``[64, 64, 128]`` float32 states in
    place, B and C ``[24, 8, 128]``, a block of two heads reading its
    group's row; the kernel keeps its name."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.ops import mamba2

    one = SingleDeviceSharding(_v5e_or_skip().devices[0])
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    L, H, P, G, N = 24, 64, 64, 8, 128
    bf = jnp.bfloat16
    with jax.default_matmul_precision("default"):
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
    import json

    from dynamo_tpu.engine.config import EngineConfig

    with open(os.path.join(os.path.dirname(tpu_compile_check.__file__), "..",
                           "benchmarks", "configs",
                           "nemotron3-nano-ep8.json")) as f:
        e = EngineConfig(**json.load(f)["engine"])
    assert e.max_context == 9216
    programs = {(T, e.prefill_lanes(T, group), continuing)
                for T in e.prefill_buckets
                for group in range(1, e.prefill_chunks_per_round + 1)
                for continuing in (False, True)}
    assert sorted(programs) == [(1024, 1, False), (1024, 1, True),
                                (4096, 1, False), (4096, 1, True)]


# ``lowered_sha256`` (tools/tpu_compile_check.py: the StableHLO text before
# the compiler, Mosaic bodies masked) of the programs that share code with
# the continuing latent chunk and must NOT move with it: every program
# the routed-expert chat cell runs (its prompts fit one bucket, so every
# chunk is fresh) and the dense prefill, fresh and continuing, which shares
# ``prefill_attention``. Recorded on the parent of PR 40 (edc371c) by this
# test itself; a PR that MEANS to change one of these programs records the
# new digest here and says so (equal digests are what let a chip check of
# the cell be predicted ``unchanged``, PERF.md section 6, PR 35).
# PR 42 MEANT to move ``round_seal_n4_w64`` of the routed-expert chat cell
# (9c08905c1b04a832 on its parent): its decode attention reads the region
# through ops/latent_decode.py's kernel. The other five of that cell, which
# hold no decode step, and the dense prefills kept the digests below.
# PR 46 moved the routed-expert layer's row movements (models/moe.py) into
# loops where a call holds a share of the experts and sorts more than 4096
# rows: the ROUND of each routed-expert cell (320 / 512 / 64 sorted rows a
# step) and every program of the two latent cells, which hold every expert,
# stay straight-line, recorded on its parent (9362de9).
# PR 52 MEANT to move ``round_seal_n4_w32`` of the state-space hybrid cell
# (1f3088abd615d9a7 on its parent, 75f42ae), ONCE: its nine Mamba-2 layers
# step the live lanes' states through ``mamba2.scan_step_pallas`` where the
# every-lane XLA recurrence stood. The cell's four prefill programs, its
# flush, seal and load, and every program of the eight other
# configurations kept the parent's digests (CHANGES.md, PR 52).
# PR 53 MEANT to move the round of every configuration with a dense
# attention layer, ONCE: ``flash_decode_attention`` became one invocation a
# layer over a work list built from ``live`` (the 2-layer dense round
# 38264b5231907f4c on its parent, 308d9b1, pinned here since; the granite
# round 45ec7a29f1e8de72; at full depth ``ROUND_LOWERING`` below: jamba2-3b
# 02daac48e3b8de20, minicpm-sala-d16 c835954f482bb9f2). Every program of
# the three latent configurations (cells 3, 4, 8: their kernel's list is
# built by the same ops through ``attention.flat_items``) and every
# prefill, flush, seal and load program of all nine kept the parent's
# digests: 54 of 62 programs equal, the 6 rounds and the two dense
# ``decode_step`` programs moved (CHANGES.md, PR 53).
# PR 57 MEANT to move the round and every prefill program of the three
# latent configurations, ONCE (joyai ``round_seal_n4_w64`` 9eb8bd24fab037be
# and ``batch_prefill_K2_T128`` 459e2c202d3ac337, xing4 ``round_seal_n4_w16``
# 4a70f5eac1edcf3e, ling3's round 301254f0fe8b14b6, xing4's continuing
# ``[1, 2048]`` / ``[1, 4096]`` 8f681471b1b3b42f / c4c1c5a74bec99f4 on its
# parent, d39232c): the query product ends ahead of its reshape to heads
# and the products over W_kvb read ``wkb`` / ``wvb``
# (``llama.serving_params``). Their flush, seal and load programs and
# every program of the six configurations with no latent layer kept the
# parent's digests (CHANGES.md, PR 57).
# PR 61 MEANT to move every PREFILL program of the three latent
# configurations, ONCE, and none of their rounds (joyai
# ``batch_prefill_K2_T128`` 23c0aebdc3c5113f on its parent, 12cb44a; xing4's
# continuing pair above): the expanded latent chunk's attention is
# ``attention.fused_prefill_attention``, V at its own width in the fresh
# programs too. Their rounds, flush, seal, load and ``admit_first`` programs
# and every program of the eight configurations with no latent layer kept
# the parent's digests (CHANGES.md, PR 61).
UNMOVED = {
    ("mla-moe-joyai-d5", 0): {
        "flush_ctx": "aa9a25ef5ee32101",
        "seal_blocks_w64": "60d93eac534dbceb",
        "flush_seal_w64": "0cf53d0f869aa38b",
        "round_seal_n4_w64": "b5cdb9902b515b77",
        "load_ctx_pages_n64": "217cccff59be759b",
        "batch_prefill_K2_T128": "49affdac97dd46ae",
    },
    ("mistral7b-w8", 2): {
        "round_seal_n4_w8": "4fc6864b36262415",
        "batch_prefill_K2_T128": "587de9cf00cdecb3",
        "batch_prefill_cont_K2_T128_S4096": "2c9a09ec6d798ada",
    },
    ("xing4-mhc-d7", 0): {"round_seal_n4_w16": "f57339fb4108d2f4"},
    ("granite4h-ep2-d10", 0): {"round_seal_n4_w32": "5aace7fa74a39250"},
}
# the full-depth rounds the records above already compile: the latent
# cell 8's as PR 57 moved it (its two latent layers; 301254f0fe8b14b6 until
# then), the two PR 53 moved
ROUND_LOWERING = {"ling3-flash-ep8-d12": "2faf5c54da78563f",
                  "jamba2-3b": "85ce2cc7f19b7d4e",
                  "minicpm-sala-d16": "ebe6ccdc309a8d57"}
_UNMOVED_NAMES = {"mla-moe-joyai-d5": MOVERS + ("round_seal", "load_ctx_pages",
                                                "batch_prefill"),
                  "mistral7b-w8": ("round_seal", "batch_prefill",
                                   "batch_prefill_cont"),
                  "xing4-mhc-d7": ("round_seal",),
                  "granite4h-ep2-d10": ("round_seal",)}


@pytest.fixture(scope="module")
def unmoved_records():
    _v5e_or_skip()
    with jax.default_matmul_precision("default"):
        return {
            (config, layers): {
                r["program"]: r for r in tpu_compile_check.compile_programs(
                    config=config, layers=layers,
                    programs=_UNMOVED_NAMES[config])}
            for config, layers in UNMOVED}


@pytest.mark.parametrize("key,program", [
    (key, program) for key in sorted(UNMOVED) for program in UNMOVED[key]],
    ids=lambda v: v if isinstance(v, str) else v[0])
def test_programs_beside_the_continuing_latent_chunk_keep_their_lowering(
        unmoved_records, key, program):
    rec = unmoved_records[key][program]
    assert rec.get("lowered_sha256", rec.get("error")) == UNMOVED[key][program]


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
        unmoved_records, config, program):
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
    rec = unmoved_records[config, 0][program]
    assert rec["ok"], rec.get("error")
    assert rec["layers"] == (5 if config == "mla-moe-joyai-d5" else 7)
    assert rec["weight_copies"] == [], rec["weight_copies"]
    if program.startswith("round_seal"):
        assert rec["temp_bytes"] < LATENT_ROUND_TEMP_CEILING, rec["temp_gb"]


# the wide dense prefill programs loop their row-wise halves over the live
# row blocks (llama._live_rows, PR 44). Temporaries of the 2-layer
# ``[2, 4096]`` programs, compiled for the v5e, PR 44: 0.247 GB fresh and
# 0.550 continuing at Mistral-7B's int8 widths (the straight-line parent
# 0.407 / 0.776); 0.160 / 0.286 a chip at Nemo-12B's under tp = 4
# (0.264 / 0.383). A loop whose weights are sliced where XLA can hoist
# the slice copies every layer's weights in front of it and reads 0.64 /
# 0.95 GB: the ceilings sit between.
LOOPED_TEMP_CEILING = {
    ("mistral7b-w8", "batch_prefill"): 0.30e9,
    ("mistral7b-w8", "batch_prefill_cont"): 0.62e9,
    ("nemo12b-tp4", "batch_prefill"): 0.20e9,
    ("nemo12b-tp4", "batch_prefill_cont"): 0.33e9,
}


@pytest.fixture(scope="module", params=["mistral7b-w8", "nemo12b-tp4"])
def looped_records(request):
    _v5e_or_skip()
    with jax.default_matmul_precision("default"):
        records = tpu_compile_check.compile_programs(
            config=request.param, layers=2, prefill_width=4096,
            programs=("batch_prefill", "batch_prefill_cont"),
            keep_text=True)
    return request.param, dict(zip(("batch_prefill", "batch_prefill_cont"),
                                   records))


@pytest.mark.parametrize("program", ["batch_prefill", "batch_prefill_cont"])
def test_looped_dense_prefill_copies_no_weights_on_v5e(looped_records,
                                                       program):
    """The loop's body slices its layer's weights out of the stack itself
    and the slice fuses into the matmul: a block's products read
    ``[512, ...]`` and the temporaries stay under the ceiling (a copy of
    each layer's weights in front of the loops passes it by 2x)."""
    config, records = looped_records
    rec = records[program]
    assert rec["ok"], rec.get("error")
    assert rec["temp_bytes"] < LOOPED_TEMP_CEILING[config, program], (
        rec["temp_gb"])
    ffn = 14336 if config == "mistral7b-w8" else 3584
    assert f"bf16[512,{ffn}]" in rec["text"]      # a block's gate / up


def test_region_copies_reads_copy_and_copy_start():
    shard = (2, 2, 17, 4096, 128)
    text = """
  %copy.83 = bf16[2,2,17,4096,128]{4,1,3,2,0:T(2,128)(2,1)S(1)} copy(%gte.1)
  %copy-start.1 = (bf16[2,2,17,4096,128]{4,1,3,2,0:T(2,128)(2,1)}, bf16[2,2,17,4096,128]{4,1,3,2,0}, u32[]{:S(2)}) copy-start(%fusion.6)
  %copy-done.1 = bf16[2,2,17,4096,128]{4,1,3,2,0:T(2,128)(2,1)} copy-done(%copy-start.1)
  ROOT %copy.9 = bf16[2,2,69632,128]{3,2,1,0:T(8,128)(2,1)} copy(%bitcast.3)
  %copy.2 = s32[16]{0:T(128)} copy(%p.3)
  %fusion.5 = bf16[2,2,17,4096,128]{4,3,2,1,0} fusion(%copy.83), kind=kLoop
"""
    assert tpu_compile_check.region_copies(text, shard) == [
        "bf16[2,2,17,4096,128]", "bf16[2,2,17,4096,128]",
        "bf16[2,2,69632,128]"]


def test_weight_copies_reads_copies_and_materialised_slices():
    """Canned text of the parent's 2-layer nemo12b-tp4 prefill (PR 55): a
    slice of the wq stack written out a layer, its transposed copy, an
    async copy of a wk shard; not the dot's own fusion, not what a
    fusion's computation reads, not an activation of as many elements.
    Each with what it is (the two sides' orders differ: a relayout; the
    operand's order not in the text: a copy) and where it stands."""
    shards = ((5120, 1024), (5120, 256), (5120, 3584))
    text = """
%fused_computation.97 (param_0.1: bf16[2,5120,1024]) -> (bf16[1024,5120], bf16[1024,5120]) {
  %copy.1 = bf16[1024,5120]{1,0:T(8,128)(2,1)} copy(%param_0.1)
  ROOT %t = (bf16[1024,5120]{0,1}, bf16[1024,5120]{0,1}) tuple(%copy.1, %copy.1)
}

ENTRY %main.7_spmd (param.16: bf16[2,5120,1024]) -> bf16[2,256,5120] {
  %slice_bitcast_fusion = (bf16[1024,5120]{0,1:T(8,128)(2,1)S(1)}, bf16[1024,5120]{0,1:T(8,128)(2,1)S(1)}) fusion(%custom-call.4), kind=kLoop, calls=%fused_computation.97, metadata={op_name="jit(batch_prefill_impl)/vmap()/dot_general"}
  %get-tuple-element.545 = bf16[1024,5120]{0,1:T(8,128)(2,1)S(1)} get-tuple-element(%slice_bitcast_fusion), index=0
  %copy.33 = bf16[1024,5120]{1,0:T(8,128)(2,1)S(1)} copy(%get-tuple-element.545), metadata={op_name="jit(batch_prefill_impl)/vmap()/dot_general"}
  %copy-start.2 = (bf16[1,5120,256]{1,2,0:T(8,128)(2,1)S(1)}, bf16[1,5120,256]{2,1,0}, u32[]{:S(2)}) copy-start(%slice.2)
  %copy.34 = bf16[5120,3584]{0,1:T(8,128)(2,1)} copy(%somewhere.else)
  %copy.24 = bf16[2,2560,2,128]{3,1,2,0:T(8,128)(2,1)S(1)} copy(%bitcast.290)
  %fusion.52 = (f32[2,256]{1,0}, bf16[2,256,5120]{2,1,0}) fusion(%all-reduce, %all-reduce.1), kind=kLoop, calls=%fused_computation.86
  %fusion.68 = bf16[2,256,8,128]{1,3,2,0:T(8,128)(2,1)S(1)} fusion(%bitcast.276, %get-tuple-element.538), kind=kOutput, calls=%fused_computation.104
  ROOT %fusion.60 = bf16[2,256,5120]{2,1,0} fusion(%bitcast.285, %param.21), kind=kOutput, calls=%fused_computation.94
}
"""
    assert tpu_compile_check.weight_copies(text, *shards) == [
        "bf16[1024,5120] relayout entry", "bf16[1,5120,256] relayout entry",
        "bf16[5120,3584] copy entry",
        "bf16[1024,5120] slice entry", "bf16[1024,5120] slice entry"]
    # the count alone takes the [2,2560,2,128] activation for a wk shard
    assert "bf16[2,2560,2,128]" in tpu_compile_check.region_copies(
        text, (5120, 256))


def test_weight_copies_tells_a_prefetch_from_a_relayout_and_a_step_from_a_call():
    """Canned from the parents of PR 57. The latent round (joyai, depth 5):
    ENTRY transposes the whole wqb stack once a round; the step loop's
    body, and what it calls, writes a layer's slice of the transposed
    stack out again and moves it into fast memory as it lies. The delta-
    rule round (ling3, depth 12): ``w_bg`` [2560, 64] goes into memory
    space 1 in the order it has, a prefetch the step needs, which
    ROADMAP S6(f) took for a relayout while the reader was silent on
    both questions."""
    text = """
%fused_computation.1143 (param_0.9: bf16[5,1536,6144]) -> (bf16[1,1536,6144], bf16[1,1536,6144]) {
  %copy.7 = bf16[1,1536,6144]{1,2,0:T(8,128)(2,1)} copy(%param_0.9)
  ROOT %t = (bf16[1,1536,6144]{1,2,0}, bf16[1,1536,6144]{1,2,0}) tuple(%copy.7, %copy.7)
}

%called_by_the_body.3 (p.1: bf16[2560,64]) -> bf16[2560,64] {
  %p.1 = bf16[2560,64]{0,1:T(8,128)(2,1)} parameter(0)
  %copy-start.66 = (bf16[2560,64]{0,1:T(8,128)(2,1)S(1)}, bf16[2560,64]{0,1:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%p.1)
  ROOT %copy-done.66 = bf16[2560,64]{0,1:T(8,128)(2,1)S(1)} copy-done(%copy-start.66)
}

%region_27.sunk (arg: (s32[], bf16[5,1536,6144])) -> (s32[], bf16[5,1536,6144]) {
  %fusion.1161 = (bf16[1,1536,6144]{1,2,0:T(8,128)(2,1)}, bf16[1,1536,6144]{1,2,0:T(8,128)(2,1)S(1)}) fusion(%get-tuple-element.4250), kind=kLoop, calls=%fused_computation.1143, metadata={op_name="jit(engine_round_seal)/while/body/closed_call/slice"}
  %get-tuple-element.3796 = bf16[1,1536,6144]{1,2,0:T(8,128)(2,1)} get-tuple-element(%fusion.1161), index=0
  %copy-start.5 = (bf16[1,1536,6144]{1,2,0:T(8,128)(2,1)S(1)}, bf16[1,1536,6144]{1,2,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%get-tuple-element.3796)
  %call.2 = bf16[2560,64]{0,1:T(8,128)(2,1)S(1)} call(%w_bg), to_apply=%called_by_the_body.3
}

%region_28 (arg: (s32[], bf16[5,1536,6144])) -> pred[] {
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main.9 (params__layers____wqb__.1: bf16[5,1536,6144]) -> bf16[64,129280] {
  %params__layers____wqb__.1 = bf16[5,1536,6144]{2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.491 = bf16[5,1536,6144]{1,2,0:T(8,128)(2,1)} copy(%params__layers____wqb__.1)
  %copy.492 = bf16[2560,64]{0,1:T(8,128)(2,1)S(1)} copy(%w_bg_as_it_lies)
  %w_bg_as_it_lies = bf16[2560,64]{0,1:T(8,128)(2,1)} parameter(1)
  %while.108 = (s32[], bf16[5,1536,6144]{1,2,0:T(8,128)(2,1)}) while(%tuple.678), condition=%region_28, body=%region_27.sunk
}
"""
    assert tpu_compile_check.weight_copies(
        text, (5, 1536, 6144), (1536, 6144), (2560, 64)) == [
        "bf16[2560,64] prefetch loop",
        "bf16[1,1536,6144] prefetch loop",
        "bf16[1,1536,6144] slice loop", "bf16[1,1536,6144] slice loop",
        "bf16[5,1536,6144] relayout entry", "bf16[2560,64] prefetch entry"]


@pytest.fixture(scope="module", params=[
    (config, width) for config in ("nemo12b-tp4", "mistral7b-w8")
    for width in (256, 1024)], ids=lambda p: f"{p[0]}-T{p[1]}")
def narrow_prefill_records(request):
    _v5e_or_skip()
    config, width = request.param
    with jax.default_matmul_precision("default"):
        records = tpu_compile_check.compile_programs(
            config=config, layers=2, prefill_width=width,
            programs=("batch_prefill", "batch_prefill_cont"))
    return dict(zip(("batch_prefill", "batch_prefill_cont"), records))


@pytest.mark.parametrize("program", ["batch_prefill", "batch_prefill_cont"])
def test_dense_prefill_relayouts_no_projection_weight_on_v5e(
        narrow_prefill_records, program):
    """Every product of a dense prefill reads its weight where it lies in
    the layer stack. Until PR 55 the bf16 programs wrote each layer's wq
    and wk shard out twice, a slice of the stack and its transpose, in
    front of the product (four ``bf16[1024,5120]`` and four
    ``bf16[256,5120]`` in each of the four nemo12b-tp4 programs at 2
    layers; none in mistral7b-w8's, whose int8 weights dequantise in
    place): ``llama._layer_qkv`` ends the product ahead of the reshape
    to heads."""
    rec = narrow_prefill_records[program]
    assert rec["ok"], rec.get("error")
    assert rec["weight_copies"] == [], rec["weight_copies"]


@pytest.mark.slow
@pytest.mark.parametrize("tp,kv_quant", [(1, "none"), (4, "int8")])
def test_v5e_topology_compile(tp, kv_quant):
    """Full XLA:TPU + Mosaic compile of the decode step, the flush, the
    seal, the fused round and a prefill bucket for compile-only v5e
    devices (cut to 2 layers)."""

    # conftest pins matmul precision to "highest" for the CPU goldens;
    # Mosaic refuses a bf16 dot at that precision ("Bad lhs type"), and
    # no serving process sets it — compile what serving compiles
    with jax.default_matmul_precision("default"):
        records = tpu_compile_check.compile_programs(
            "llama3_1b", tp, kv_quant, layers=2)
    assert all(r["ok"] for r in records), records
    assert records[0]["mosaic_calls"] == 2
