"""The engine <-> model seam (ROADMAP D2): the engine, the multihost replay
and spec/ reach the model layer through ``models/llama.py`` and nothing
else of ``models/``, and the three blocks (the dense decoder of that file,
models/mla_moe.py, models/ssm_moe.py) each meet the block protocol written
at its head. CPU, no engine start: imports by ``ast``, signatures by
``inspect``, the round's step by ``jax.eval_shape``.
"""
from __future__ import annotations

import ast
import dataclasses
import glob
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import REFERENCE, DecodeAttention
from dynamo_tpu.telemetry import metrics as tmetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = "dynamo_tpu/engine/engine.py"
CALLERS = [ENGINE, "dynamo_tpu/engine/multihost.py"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "dynamo_tpu/spec/*.py")))

# one tiny configuration a block and mixer family
FAMILIES = {
    "dense": ModelConfig.tiny,
    "latent": ModelConfig.tiny_mla_moe,
    "latent_mhc": ModelConfig.tiny_mla_moe_mhc,
    "mamba2": ModelConfig.tiny_ssm_moe,
    "lightning_sparse": ModelConfig.tiny_linear_sparse,
    "kda_latent": ModelConfig.tiny_kda_latent,
    "mamba1": ModelConfig.tiny_jamba,
    "differential": ModelConfig.tiny_phi4flash,
    "rotary_gqa": ModelConfig.tiny_laguna,
    "one_part": ModelConfig.tiny_nemotron_h,
}


def _imported(path: str, package: str) -> set[str]:
    """The submodules of ``package`` a file imports, at any depth of the
    file (a function-level import counts)."""
    with open(os.path.join(REPO, path)) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == package:
                found.update(a.name for a in node.names)
            elif node.module.startswith(package + "."):
                found.add(node.module[len(package) + 1:].split(".")[0])
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith(package + "."):
                    found.add(a.name[len(package) + 1:].split(".")[0])
    return found


@pytest.mark.parametrize("path", CALLERS)
def test_callers_import_the_front_door_only(path):
    assert _imported(path, "dynamo_tpu.models") <= {"llama", "config"}


def test_the_engine_knows_no_block():
    """No op of a block (its attention, its mirrors), no field of
    ``ModelConfig`` that names one. ``ring_attention`` is the
    sequence-parallel prefill plane's sharding helper, a plane of the
    dense decoder and no block's op."""
    assert _imported(ENGINE, "dynamo_tpu.ops") <= {"attention",
                                                   "ring_attention"}
    with open(os.path.join(REPO, ENGINE)) as f:
        text = f.read()
    read = {n.attr for n in ast.walk(ast.parse(text))
            if isinstance(n, ast.Attribute)}
    assert not read & {"mla", "hybrid", "hc", "_stats_at"}
    for word in ("mla_moe", "ssm_moe", "latent_decode", "sparse_attention"):
        assert word not in text, word


def _arity(sig: inspect.Signature):
    """(positional parameters, those of them without a default, the
    keyword-only names) of a signature."""
    ps = list(sig.parameters.values())
    pos = [p for p in ps if p.kind in (p.POSITIONAL_ONLY,
                                       p.POSITIONAL_OR_KEYWORD)]
    return (len(pos), sum(p.default is p.empty for p in pos),
            sorted(p.name for p in ps if p.kind == p.KEYWORD_ONLY))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_block_meets_the_written_protocol(family):
    c = FAMILIES[family]()
    block = llama.block_of(c)
    assert len(llama.PROTOCOL) == 21
    for name, sig in llama.PROTOCOL.items():
        # written once, in words, at the head of the front door
        assert f"    {name}(c" in llama.__doc__, name
        assert callable(getattr(llama, name))
        if block is not None:   # (the dense decoder's is the door's body)
            assert _arity(inspect.signature(getattr(block, name))) == \
                _arity(sig), name


@pytest.mark.parametrize("family", FAMILIES)
def test_the_counter_row_is_declared(family):
    c = FAMILIES[family]()
    layout = llama.stats_layout(c)
    zero = jax.eval_shape(lambda: llama.stats_zero(c))
    assert zero.dtype == jnp.int32 and zero.ndim == 1
    if family == "dense":
        # counts nothing; its round's dead three-wide carry is the one
        # exception to "as wide as the layout" (moving it moves the text
        # of every dense round program)
        assert layout == () and zero.shape == (3,)
    else:
        assert layout and zero.shape == (len(layout),)
    registered = tmetrics.request_histograms(
        tmetrics.TelemetryRegistry(), engine=True)
    for counter in layout:
        assert isinstance(counter, tmetrics.Counter)
        if counter.metric is not None:
            assert registered.get(counter.metric) is not None, counter
    fed = [k.metric for k in layout if k.metric is not None]
    assert len(fed) == len(set(fed))
    assert fed == {
        "dense": [],
        "latent": [tmetrics.MOE_TOUCHED[0], tmetrics.MOE_ROUTED[0],
                   tmetrics.MOE_LOAD_MAX[0]],
        "latent_mhc": [tmetrics.MOE_TOUCHED[0], tmetrics.MOE_ROUTED[0],
                       tmetrics.MOE_LOAD_MAX[0],
                       tmetrics.HC_SINKHORN_RESIDUAL[0]],
        "mamba2": [tmetrics.MOE_TOUCHED[0], tmetrics.MOE_ROUTED[0],
                   tmetrics.MOE_LOAD_MAX[0], tmetrics.MOE_PICKS_ROUTED[0],
                   tmetrics.SSM_STATE_ROWS_STEPPED[0]],
        "lightning_sparse": [],   # one dense MLP a layer: routes nothing
        "kda_latent": [tmetrics.MOE_TOUCHED[0], tmetrics.MOE_ROUTED[0],
                       tmetrics.MOE_LOAD_MAX[0],
                       tmetrics.MOE_PICKS_ROUTED[0],
                       tmetrics.MOE_GROUPS_KEPT_HERE[0],
                       tmetrics.KDA_STATE_ROWS_STEPPED[0]],
        # one dense MLP a layer: routes nothing; its step kernel counts
        "mamba1": [tmetrics.SSM_STATE_ROWS_STEPPED[0]],
        "differential": [tmetrics.SSM_STATE_ROWS_STEPPED[0]],
        # the one-group sigmoid router with a share; no recurrent leaf
        "rotary_gqa": [tmetrics.MOE_TOUCHED[0], tmetrics.MOE_ROUTED[0],
                       tmetrics.MOE_LOAD_MAX[0],
                       tmetrics.MOE_PICKS_ROUTED[0],
                       tmetrics.MOE_GROUPS_KEPT_HERE[0]],
        # the same router, and the Mamba-2 states its steps moved on
        "one_part": [tmetrics.MOE_TOUCHED[0], tmetrics.MOE_ROUTED[0],
                     tmetrics.MOE_LOAD_MAX[0], tmetrics.MOE_PICKS_ROUTED[0],
                     tmetrics.MOE_GROUPS_KEPT_HERE[0],
                     tmetrics.SSM_STATE_ROWS_STEPPED[0]],
    }[family]
    assert [k.f32_bits for k in layout if k.metric ==
            tmetrics.HC_SINKHORN_RESIDUAL[0]] == [True] * (
                family == "latent_mhc")


@pytest.mark.parametrize("family", FAMILIES)
def test_what_the_state_says_of_itself(family):
    """The four questions the engine asks at start-up, and which mirrors
    a configuration has."""
    c = FAMILIES[family]()
    called, why = llama.state_called(c), llama.transfer_refusal(c)
    assert (called is None) == (why is None) == (family == "dense")
    assert llama.pages_resume(c) == (
        family in ("dense", "latent", "latent_mhc"))
    assert llama.page_multiple(c) == (8 if family == "lightning_sparse"
                                      else 1)
    decode = llama.decode_mirror(c, 128, 4, REFERENCE)
    prefill = llama.prefill_mirror(c, REFERENCE)
    # every family with an attention layer mirrors its region reads: the
    # latent rows', the sparse layers', or (PR 53) the dense K and V rows'
    assert decode is not None
    live = np.array([True, False, True, False, False, False])
    seen = dict(decode(np.array([41, 9, 17, 300, 1, 1], np.int32), live, 4))
    # the hybrid stacks also say which PARTS the round's steps ran: a mixer
    # and a feed-forward part a layer, or ONE part a layer (PR 60)
    parts = {name: seen.pop(name) for name in list(seen)
             if name.startswith("dynamo_layer_parts_run_")}
    assert bool(parts) == (family not in ("dense", "latent", "latent_mhc"))
    if parts:
        assert sum(parts.values()) == 4 * c.num_layers * (
            1 if family == "one_part" else 2)
    if family in ("dense", "mamba2", "mamba1", "one_part"):
        # the jnp reference scores every lane's whole 128-row region
        assert seen == {tmetrics.DECODE_ATTN_ROWS_READ[0]: 4 * 6 * 128,
                        tmetrics.DECODE_ATTN_ROWS_LIVE[0]: 4 * (40 + 16)}
    if family == "differential":
        # rows of two lengths: the full layer's rows beside what its ONE
        # cross layer doubles, and the two window layers' 8-row buffers
        # (every lane's, whole, under the jnp reference) against min(n, 8)
        assert seen == {
            tmetrics.DECODE_ATTN_ROWS_READ[0]: 4 * 6 * 128,
            tmetrics.DECODE_ATTN_ROWS_LIVE[0]: 4 * (40 + 16),
            tmetrics.ATTN_SHARED_ROWS_READ[0]: 2 * 4 * 6 * 128,
            tmetrics.ATTN_WINDOW_ROWS_READ[0]: 2 * 4 * 6 * 8,
            tmetrics.ATTN_WINDOW_ROWS_BOUND[0]: 2 * 4 * 2 * 8}
    if family == "rotary_gqa":
        # its TWO full layers' rows a layer, the four window layers' 8-row
        # buffers, no rows shared with a cross layer, and the query-head
        # rows of each kind: 2 live lanes x 4 steps x (12 + 12 | 4 x 18)
        assert called.startswith("window rows") and "window rows" in why
        assert seen == {
            tmetrics.DECODE_ATTN_ROWS_READ[0]: 4 * 6 * 128,
            tmetrics.DECODE_ATTN_ROWS_LIVE[0]: 4 * (40 + 16),
            tmetrics.ATTN_WINDOW_ROWS_READ[0]: 4 * 4 * 6 * 8,
            tmetrics.ATTN_WINDOW_ROWS_BOUND[0]: 4 * 4 * 2 * 8,
            tmetrics.DECODE_ATTN_Q_ROWS_FULL[0]: 2 * 4 * 24,
            tmetrics.DECODE_ATTN_Q_ROWS_WINDOW[0]: 2 * 4 * 72}
    # every family mirrors the query blocks its prefill attentions ran (a
    # 300-row chunk over 512 prior rows and a dummy lane in the 1024
    # bucket: two blocks a layer), and which of them the fused kernel of
    # the expanded latent layers took: none under the jnp reference (the
    # CPU meshes), every latent layer's where the programs are traced for
    # a kernel, at a geometry inside its shape rule
    lanes = (1024, [512, 0], [812, 0])
    ran = dict(prefill(*lanes, 0, 4096))
    latent = c.num_layers if family in ("latent", "latent_mhc") else sum(
        t == "latent_attention"
        for t in (c.hybrid_dict or {}).get("layer_types", ()))
    assert bool(latent) == (family in ("latent", "latent_mhc", "kda_latent"))
    assert ran[tmetrics.PREFILL_ATTN_FUSED_BLOCKS[0]] == 0
    assert ran[tmetrics.PREFILL_ATTN_BLOCKS[0]] % 2 == 0
    assert (ran[tmetrics.PREFILL_ATTN_BLOCKS[0]]
            >= ran[tmetrics.PREFILL_ATTN_FUSED_BLOCKS[0]])
    if family in ("dense", "latent", "latent_mhc"):
        assert ran[tmetrics.PREFILL_ATTN_BLOCKS[0]] == 2 * c.num_layers
    kernel = llama.prefill_mirror(c, DecodeAttention("pallas"))
    # (PR 63) and every layer of the dense decoder, a group of query heads
    # a K/V head, unless the region a continuing chunk reads is int8
    # where its head is whole 128-lane tiles (the toy's 16 is not),
    # unless the region a continuing chunk reads is int8
    assert dict(kernel(*lanes, 0, 4096))[
        tmetrics.PREFILL_ATTN_FUSED_BLOCKS[0]] == 2 * latent
    if family == "dense":
        wide = dataclasses.replace(c, head_dim=128)
        for kv_quant, continuing, fresh in (("none", 2, 2), ("int8", 0, 2)):
            mirror = llama.prefill_mirror(
                wide, DecodeAttention("pallas"), kv_quant)
            assert dict(mirror(*lanes, 0, 4096))[
                tmetrics.PREFILL_ATTN_FUSED_BLOCKS[0]] == (
                continuing * c.num_layers)
            assert dict(mirror(1024, [0, 0], [300, 0], 0, 0))[
                tmetrics.PREFILL_ATTN_FUSED_BLOCKS[0]] == (
                fresh * c.num_layers)
    # a region that is no whole number of blocks: the loops run
    assert dict(kernel(*lanes, 0, 4000))[
        tmetrics.PREFILL_ATTN_FUSED_BLOCKS[0]] == 0
    assert (set(ran) > {tmetrics.PREFILL_ATTN_BLOCKS[0],
                        tmetrics.PREFILL_ATTN_FUSED_BLOCKS[0]}) == (
        family in ("lightning_sparse", "mamba1", "differential", "mamba2",
                   "one_part"))


@pytest.mark.parametrize("family", FAMILIES)
def test_serving_params_add_leaves_and_change_none(family):
    """What the engine hands its programs: every published leaf as it
    was (the benchmark's references read ``wkvb`` and ``wqb`` from
    ``engine.params``), and beside each ``wkvb`` its two parts by head,
    head-major with the latent minor, value for value; nothing else."""
    c = FAMILIES[family](dtype="float32")
    params = llama.init_params(c, 2)
    served = llama.serving_params(c, params)
    was = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    now = dict(jax.tree_util.tree_flatten_with_path(served)[0])
    assert all(now[path] is leaf for path, leaf in was.items())
    added = sorted(set(now) - set(was), key=str)
    sources = [path for path in was if path[-1].key == "wkvb"]
    assert len(added) == 2 * len(sources)
    assert bool(sources) == (family in ("latent", "latent_mhc", "kda_latent"))
    m = c.mla_dict or {}
    for path in sources:
        w = np.asarray(was[path]).reshape(
            *was[path].shape[:-1], c.num_heads,
            m["qk_nope_head_dim"] + m["v_head_dim"])
        w = np.moveaxis(w, -3, -1)   # [..., nh, nope + v, kv_rank]
        key = jax.tree_util.DictKey
        np.testing.assert_array_equal(
            now[path[:-1] + (key("wkb"),)], w[..., :m["qk_nope_head_dim"], :])
        np.testing.assert_array_equal(
            now[path[:-1] + (key("wvb"),)], w[..., m["qk_nope_head_dim"]:, :])


@pytest.mark.parametrize("family", FAMILIES)
def test_the_rounds_step_through_the_front_door(family):
    """ONE signature for every block: (ring, stepped, logits, stats), the
    stepped leaves and the counter row shaped as they went in."""
    c = FAMILIES[family](dtype="float32")
    B, R = 6, 4
    params = jax.eval_shape(
        lambda: llama.serving_params(c, llama.init_params(c, 0)))
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, B, 64, jnp.float32))
    ring = jax.eval_shape(lambda: llama.init_ring(c, B, R, jnp.float32))
    kinds = llama.stepped_kinds(c, ctx)
    assert set(kinds) <= set(ctx) and not set(kinds) & set(ring)
    assert bool(kinds) == (family in ("mamba2", "lightning_sparse",
                                      "kda_latent", "mamba1",
                                      "differential", "one_part"))
    stepped = {n: ctx[n] for n in kinds}
    stats = jax.eval_shape(lambda: llama.stats_zero(c))
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    out = jax.eval_shape(
        lambda *a: llama.round_step(c, *a, attn=REFERENCE),
        params, ctx, ring, stepped, i32, i32, i32,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.bool_), i32, stats)
    assert len(out) == 4
    new_ring, new_stepped, logits, new_stats = out
    same = lambda a, b: jax.tree.map(   # noqa: E731
        lambda x, y: (x.shape, x.dtype) == (y.shape, y.dtype), a, b)
    assert all(jax.tree.leaves(same(new_ring, ring)))
    assert jax.tree.structure(new_stepped) == jax.tree.structure(stepped)
    assert all(jax.tree.leaves(same(new_stepped, stepped)))
    assert logits.shape == (B, c.vocab_size)
    assert (new_stats.shape, new_stats.dtype) == (stats.shape, stats.dtype)
