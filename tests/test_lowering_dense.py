"""The dense configurations' programs for the v5e (cells 1, 2, 5 and the
smoke's llama3_1b; tests/lowering.py has the rule for a new configuration).

The decode kernel as the ENGINE calls it lowers for a TPU, checked on the
CPU by cross-lowering (``lowering_platforms=("tpu",)`` runs Pallas's TPU
lowering rules: BlockSpec tiling, scalar prefetch, the GSPMD partitioning
refusal), and the per-shard mapping over ``tp`` computes what the reference
computes (interpret mode on the virtual device mesh). Both failures pinned
here were live before PR 21: any tp>1 engine died with "Mosaic kernels
cannot be automatically partitioned", and the int8-ctx scale BlockSpecs
broke the (8, 128) tiling rule.

Compiled for compile-only v5e devices: the kernel at its serving shapes,
the K/V movers (no region-shaped copy in ring -> region or region -> pool:
a third of the chip in both dense cells until PR 34), ``admit_first``, the
looped and the narrow dense prefills, and the reader's own canned texts.
"""
import re

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
    PriorContext,
    ctx_decode_attention,
    decode_attention_for,
    dense_prefill_attention,
    prefill_attention,
)
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from tests.lowering import (
    MOVERS,
    assert_pinned,
    one_v5e,
    pinned,
    record,
    serving_precision,
    tpu_compile_check,
)

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


@pytest.mark.parametrize("with_ctx", [False, True], ids=["fresh", "ctx"])
@pytest.mark.parametrize("tp", [2, 4])
def test_shard_mapped_prefill_kernel_matches_the_loops(tp, with_ctx):
    """The fused prefill kernel mapped per shard over tp (interpret mode,
    virtual devices; 4 K/V heads and their 8 query heads over tp) against
    the unsharded XLA loops: heads are independent, the step list is built
    from replicated values, every shard walks it over its own heads, a
    dummy lane comes back 0, and the result is sharded over the heads."""
    rng = np.random.RandomState(tp)

    def f32(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)

    K, T, hd, lanes, span = 3, 32, 128, 4, 64
    q, k, v = f32(K, T, NH, hd), f32(K, T, NKV, hd), f32(K, T, NKV, hd)
    ctx = PriorContext(
        f32(1, NKV, lanes, span, hd), f32(1, NKV, lanes, span, hd),
        jnp.int32(0), jnp.asarray([2, 0, 3], jnp.int32)) if with_ctx else None
    qs = jnp.asarray([24, 0, 7] if with_ctx else [0, 0, 0], jnp.int32)
    sl = qs + jnp.asarray([32, 0, 19], jnp.int32)
    mesh = make_mesh(MeshConfig(tp=tp), jax.devices()[:tp])
    got = jax.jit(dense_prefill_attention, static_argnums=0)(
        DecodeAttention(PALLAS_INTERPRET, mesh), q, k, v, qs, sl, ctx)
    want = prefill_attention(q, k, v, qs, sl, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)
    assert not np.asarray(got)[1].any() and np.asarray(got)[2, :19].any()
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
    one = one_v5e()
    L, nkv, B, S, nh, hd = DENSE_KERNEL_SHAPES[shape]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    region = (L, nkv, B + 1, S, hd)
    kv = arg(region, jnp.int8 if quant else jnp.bfloat16)
    ring = arg((L, nkv, B, R, hd))
    scale = arg((L, B + 1, S // GROUP), jnp.float32) if quant else None
    with serving_precision():
        compiled = jax.jit(ctx_decode_attention, static_argnums=0).lower(
            DecodeAttention(PALLAS), arg((B, nh, hd)), kv, kv, ring, ring,
            arg((), jnp.int32), arg((B,), jnp.int32), arg((B,), jnp.int32),
            scale, scale, arg((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%flash_decode_attention\S* = ", text)) == 1
    if hd == 128:
        assert not tpu_compile_check.region_copies(text, region)
        assert compiled.memory_analysis().temp_size_in_bytes < 4e6


@pytest.mark.parametrize("program", MOVERS)
@pytest.mark.parametrize("config", ["mistral7b-w8", "nemo12b-tp4"],
                         ids=["tp1_8slots", "tp4_16slots"])
def test_kv_movers_copy_no_region_on_v5e(config, program):
    """ring -> region, region -> pool, and both in one jit, at the dense
    cells' K/V shapes (kvh 8, hd 128, S 4096, 64-token pages; 8 slots on
    one chip, 16 over tp=4), 2 layers: the compiled text has no ``copy``
    the size of a region buffer (5-d or a flat view) and the program's
    temporaries stay under 5 % of one."""
    rec = record(config, program, layers=2)
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
    rec = record(config, "admit_first")
    assert rec["ok"], rec.get("error")
    assert rec["program"] == "admit_first_K2"
    assert rec["mosaic_calls"] == 0
    assert rec["alias_gb"] == rec["output_gb"] > 0, rec
    assert rec["temp_bytes"] < 4e6, rec


# the dense round, which shares code with the prefill attentions
# (``ops/attention.py``) and must NOT move with them, and the dense prefill,
# fresh and continuing, at 2 layers: MOVED by PR 63 on purpose (the fused
# kernel a layer where the XLA loops were; 587de9cf00cdecb3 /
# 2c9a09ec6d798ada until then) and pinned again as it left them
UNMOVED = {
    ("mistral7b-w8", 2): {
        "round_seal_n4_w8": "4fc6864b36262415",
        "batch_prefill_K2_T128": "d255deb9b8fe3dac",
        "batch_prefill_cont_K2_T128_S4096": "fe6c7b8eb0fa194e",
    },
}


@pinned(UNMOVED)
def test_programs_beside_the_continuing_latent_chunk_keep_their_lowering(
        key, program):
    assert_pinned(UNMOVED, key, program)


# `%copy.7 = bf16[2,8,9,4096,128]{...} copy(`, `bf16[1,2,1,128,4096]`: the
# region, a layer or a lane of it, written out again or transposed in
# front of the kernel (no chunk of the 1024 bucket has 4096 rows)
_REGION_RELAYOUT = re.compile(
    r"= \(?bf16\[(?:\d+,)*(?:4096,128|128,4096)\]\S* (?:copy(?:-start)?|"
    r"transpose)\(")


@pytest.mark.parametrize("program", ["prefill", "prefill_cont",
                                     "batch_prefill", "batch_prefill_cont"])
@pytest.mark.parametrize("config", ["mistral7b-w8", "nemo12b-tp4"])
def test_dense_prefill_attention_is_the_fused_kernel_on_v5e(config, program):
    """PR 63: the solo, the continuing and the batched dense prefill
    programs, compiled for the v5e at 2 layers, hold ONE Mosaic call a
    layer (``flash_prefill_attention``: a group of query heads shares its
    K/V tile, scores and probabilities in VMEM) that reads the engine's
    region where it lies: nothing the region's size is copied and no
    ``[.., 4096, 128]`` slab of it is written out or transposed in front
    of the kernel (keys are read as the rows they are). Under ``tp = 4``
    the call is mapped over the tensor axis (a bare Mosaic call GSPMD
    refuses: "cannot be automatically partitioned"), 2 K/V heads and
    their 8 query heads a shard, and the attention adds no collective:
    the layers' all-reduces and the logits' are all there is."""
    rec = record(config, program, width=1024, layers=2)
    assert rec["ok"], rec.get("error")
    assert rec["decode_attention"] == PALLAS
    assert rec["mosaic_calls"] == 2
    assert len(re.findall(r"%flash_prefill_attention\S* = ",
                          rec["text"])) == 2
    assert rec["region_shard"] in ([2, 8, 9, 4096, 128],
                                   [2, 2, 17, 4096, 128])
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert not _REGION_RELAYOUT.findall(rec["text"])
    for collective in (" all-gather(", " all-to-all(",
                       " collective-permute("):
        assert collective not in rec["text"], collective
    assert rec["text"].count(" all-reduce(") == (
        5 if config == "nemo12b-tp4" else 0)


# the wide dense prefill programs loop their row-wise halves over the live
# row blocks (llama._live_rows, PR 44). Temporaries of the 2-layer
# ``[2, 4096]`` programs, compiled for the v5e, PR 44: 0.247 GB fresh and
# 0.550 continuing at Mistral-7B's int8 widths (the straight-line parent
# 0.407 / 0.776); 0.160 / 0.286 a chip at Nemo-12B's under tp = 4
# (0.264 / 0.383). A loop whose weights are sliced where XLA can hoist
# the slice copies every layer's weights in front of it and reads 0.64 /
# 0.95 GB: the ceilings sit between.
LOOPED_TEMP_CEILING = {
    ("mistral7b-w8", "batch_prefill"): 0.36e9,
    ("mistral7b-w8", "batch_prefill_cont"): 0.62e9,
    ("nemo12b-tp4", "batch_prefill"): 0.20e9,
    ("nemo12b-tp4", "batch_prefill_cont"): 0.33e9,
}


@pytest.mark.parametrize("program", ["batch_prefill", "batch_prefill_cont"])
@pytest.mark.parametrize("config", ["mistral7b-w8", "nemo12b-tp4"])
def test_looped_dense_prefill_copies_no_weights_on_v5e(config, program):
    """The loop's body slices its layer's weights out of the stack itself
    and the slice fuses into the matmul: a block's products read
    ``[512, ...]`` and the temporaries stay under the ceiling (a copy of
    each layer's weights in front of the loops passes it by 2x)."""
    rec = record(config, program, width=4096, layers=2)
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


@pytest.mark.parametrize("program", ["batch_prefill", "batch_prefill_cont"])
@pytest.mark.parametrize(
    "config,width", [(config, width)
                     for config in ("nemo12b-tp4", "mistral7b-w8")
                     for width in (256, 1024)],
    ids=lambda v: f"T{v}" if isinstance(v, int) else v)
def test_dense_prefill_relayouts_no_projection_weight_on_v5e(
        config, width, program):
    """Every product of a dense prefill reads its weight where it lies in
    the layer stack. Until PR 55 the bf16 programs wrote each layer's wq
    and wk shard out twice, a slice of the stack and its transpose, in
    front of the product (four ``bf16[1024,5120]`` and four
    ``bf16[256,5120]`` in each of the four nemo12b-tp4 programs at 2
    layers; none in mistral7b-w8's, whose int8 weights dequantise in
    place): ``llama._layer_qkv`` ends the product ahead of the reshape
    to heads."""
    rec = record(config, program, width=width, layers=2)
    assert rec["ok"], rec.get("error")
    assert rec["weight_copies"] == [], rec["weight_copies"]


@pytest.mark.slow
@pytest.mark.parametrize("tp,kv_quant", [(1, "none"), (4, "int8")])
def test_v5e_topology_compile(tp, kv_quant):
    """Full XLA:TPU + Mosaic compile of the decode step, the flush, the
    seal, the fused round and a prefill bucket for compile-only v5e
    devices (cut to 2 layers)."""
    with serving_precision():
        records = tpu_compile_check.compile_programs(
            "llama3_1b", tp, kv_quant, layers=2)
    assert all(r["ok"] for r in records), records
    assert records[0]["mosaic_calls"] == 2
