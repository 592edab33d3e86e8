#!/usr/bin/env python
"""Compile the serving programs for a v5e WITHOUT a chip.

libtpu ships the TPU compiler, and
``jax.experimental.topologies.get_topology_desc`` hands out compile-only
v5e devices: shardings over them on ``ShapeDtypeStruct`` arguments take
``jit(f).trace(...).lower().compile()`` through XLA:TPU and Mosaic on a
CPU-only box. This is the check to run before spending a chip call on a
kernel or sharding change — a Mosaic refusal ("unsupported shape cast",
a BlockSpec the tiling rejects, "cannot be automatically partitioned")
shows up here in seconds, and so does a mover that makes XLA copy the
whole K/V region (``region_copies``: a third of the chip in both dense
cells until PR 34). It proves the programs COMPILE; whether they compute
the right thing, fit HBM at run time, or run fast is the chip's to say
(chip_smoke.py).

  python tools/tpu_compile_check.py                        # llama3_1b, tp=1
  python tools/tpu_compile_check.py --tp 4 --kv-quant int8
  python tools/tpu_compile_check.py --model-config llama3_8b_int8 --layers 4
  python tools/tpu_compile_check.py --config mistral7b-w8 --layers 2
  python tools/tpu_compile_check.py --config nemo12b-tp4 --layers 2 \\
      --programs flush_ctx,seal_blocks,round_seal --seal-width 512

``--config <name>`` takes the model, ``tp``, weight quantisation and
engine sizes from ``benchmarks/configs/<name>.json`` (what a cell of the
benchmark runs); without it the CLI's default engine sizes (B 8, S 4096,
R 4). Compiles one decode step (``llama.decode_step`` — every layer's
Mosaic call; not for the latent block, whose step is in the round), the
ring->ctx flush, the standalone ctx->pool seal at ``--seal-width``
entries, both in one jit, the fused round as the engine builds it
(``flush_every`` decode steps, flush, seal), the pool -> region load and
the pool's page gather / scatter at a long prompt's pages, one chunk's
prefill alone (``prefill``, ``prefill_cont``) and a batched prefill at the
lanes the engine gives a group of two: fresh
(``batch_prefill``) and continuing contexts in the region
(``batch_prefill_cont``), at the smallest bucket (``--prefill-width``
names another: the long-context cell's continuing ``[1, 4096]`` program
is held by tests/test_lowering_*.py), and the one program that samples
a prefill dispatch's first tokens and admits its slots (``admit_first``).
Prints one JSON line per program with XLA's memory analysis, the
region-shaped copies in the compiled text and what it writes out in the
shape of one layer's shard of a weight (``weight_copies``: a weight laid
out anew in front of its product, every call; a seventh of the four-chip
cell's device time until PR 55; read it at FULL depth before believing a
2-layer text: the compiler hoists or prefetches at depth 2 what it
re-does per layer per step at depth 40); exits 1 if any program fails
to compile. libtpu warns about ``TPU_ACCELERATOR_TYPE`` / worker hostnames
on a box with no TPU — harmless here.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import re
import sys
import time

# no chip is needed or wanted: compile-only devices come from the topology
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# repo-root invocation (python tools/tpu_compile_check.py) without install
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOPOLOGY = "v5e:2x2"  # the four-chip host; tp=1 uses its first device

# `%copy.3 = bf16[2,8,9,4096,128]{4,1,3,2,0:T(8,128)(2,1)} copy(%p)`, and
# `copy-start`, whose result is a tuple that leads with the copy's shape
# and holds its operand's next: (dtype, dimensions, the result's
# minor-to-major order, the operand's where the tuple gives it, the
# operand's name)
_COPY = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = \(?(\w+)\[([\d,]*)\](?:\{([\d,]*)[^\n]*?[)}])?"
    r" copy(?:-start)?\(%?([\w.\-]+)", re.M)
_COPY_START_SOURCE = re.compile(
    r"= \(\w+\[[\d,]*\]\{[^}]*\}, \w+\[[\d,]*\]\{([\d,]*)")
_MOSAIC_BODY = re.compile(r'\\22body\\22: \\22[^\\]*\\22')
# `%f.3 = (bf16[256,5120]{0,1:T(8,128)(2,1)S(1)}, bf16[256,5120]{...})
# fusion(%custom-call.5), kind=kLoop, calls=%fused_computation.129`: a
# fusion that writes a tuple (a dot's fusion is kOutput and writes one array)
_TUPLE_FUSION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = \(([^\n]*?)\) fusion\([^\n]*?"
    r"kind=(\w+), calls=", re.M)
_SHAPE = re.compile(r"(\w+)\[([\d,]+)\]")
# a computation of the module, `%name (params) -> result {` ... `}`; a
# fusion names its own by `calls=%name`
_COMPUTATION = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)^\}", re.M | re.S)
_FUSED = re.compile(r" fusion\([^\n]*?calls=%?([\w.\-]+)")
# an array-valued instruction's own minor-to-major order, by its name
_LAID_OUT = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[[\d,]*\]\{([\d,]*)", re.M)
_LOOP_BODY = re.compile(r" while\([^\n]*?body=%?([\w.\-]+)")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|\w+_computation)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def region_copies(hlo_text: str, *shards: tuple[int, ...]) -> list[str]:
    """The ``copy`` instructions of a compiled module whose result has as
    many elements as one chip's shard of a K/V buffer (the ctx region, the
    pool): the buffer itself (5-d) or any flat view of it.
    ``["bf16[2,8,9,4096,128]", ...]``"""
    sizes = {math.prod(s) for s in shards}
    found = []
    for m in _COPY.finditer(hlo_text):
        if m.group(2) and math.prod(map(int, m.group(2).split(","))) in sizes:
            found.append(f"{m.group(1)}[{m.group(2)}]")
    return found


def _in_loops(bodies: dict[str, str]) -> set[str]:
    """The computations a ``while`` runs: its body and whatever that
    calls, by name."""
    seen, todo = set(), [
        n for body in bodies.values() for n in _LOOP_BODY.findall(body)]
    while todo:
        name = todo.pop()
        if name in seen or name not in bodies:
            continue
        seen.add(name)
        for one, several in _CALLED.findall(bodies[name]):
            todo += [one] if one else [
                n.strip().lstrip("%") for n in several.split(",")]
    return seen


def weight_copies(hlo_text: str, *shards: tuple[int, ...]) -> list[str]:
    """What a compiled module writes out in the shape of one layer's shard
    of a projection or MLP weight, each with WHAT it is and WHERE it
    stands: ``"bf16[1,5120,256] relayout entry"``.

    What: the result of a ``copy`` / ``copy-start`` whose two sides differ
    in their minor-to-major order is a ``relayout`` (the weight laid out
    anew in front of its product, every call: a seventh of the four-chip
    cell's device time until PR 55); with one order on both sides it is a
    ``prefetch`` (the weight moved as it lies into the memory the product
    reads, which a step has to do anyway: ``bf16[2560,64]`` of cell 8,
    taken for a relayout until PR 57); ``copy`` where the operand's order
    is not in the text. An element of a tuple that a fusion other than a
    dot's writes is a ``slice`` (a slice of the layer stack written out,
    one output a layer: ``slice_bitcast_fusion``). Where: ``loop`` in a
    computation some ``while`` runs (a round's steps: every STEP), else
    ``entry`` (once a call).

    "The shape of": the shard's dimensions in any order, ones dropped
    (``[1024,5120]`` and ``[1,5120,1024]`` for a ``[5120,1024]`` shard).
    The element count alone, ``region_copies``' test, takes activations
    for weights here (at Mistral-7B's widths the rope halves of a ``[2,
    1024]`` chunk's q, ``bf16[2,1024,32,64]``, count as many as a ``wk``).
    Instructions INSIDE a fusion's computation are that fusion's reads,
    nothing written, and do not count."""
    def dims(shape):
        return tuple(sorted(int(d) for d in shape if int(d) != 1))

    wanted = {dims(s) for s in shards}
    fused = set(_FUSED.findall(hlo_text))
    bodies = dict(_COMPUTATION.findall(hlo_text))
    looped = _in_loops(bodies)
    found = []
    for name, body in bodies.items():
        if name in fused:
            continue
        where = "loop" if name in looped else "entry"
        orders = dict(_LAID_OUT.findall(body))
        written = []
        for m in _COPY.finditer(body):
            dtype, shape, order, operand = m.groups()
            source = _COPY_START_SOURCE.search(m.group(0))
            source = source.group(1) if source else orders.get(operand)
            written.append((dtype, shape, "copy" if source is None else
                            "prefetch" if source == order else "relayout"))
        for m in _TUPLE_FUSION.finditer(body):
            if m.group(2) != "kOutput":
                written += [(dtype, shape, "slice") for dtype, shape
                            in _SHAPE.findall(m.group(1))]
        found += [f"{dtype}[{shape}] {what} {where}"
                  for dtype, shape, what in written
                  if shape and dims(shape.split(",")) in wanted]
    return found


def compile_programs(model_config: str = "llama3_1b", tp: int = 1,
                     kv_quant: str = "none", layers: int = 0, *,
                     config: str = "", programs: tuple[str, ...] = (),
                     seal_width: int = 0, prefill_width: int = 0,
                     keep_text: bool = False) -> list[dict]:
    """Compile the serving programs for a tp-wide mesh of compile-only
    v5e devices. Returns one record per program: {"program", "ok",
    "seconds", "error" | memory fields, "region_copies", "weight_copies"}.
    ``config`` names a file of benchmarks/configs and overrides
    ``model_config`` and ``tp``; ``programs`` keeps the named ones only;
    ``prefill_width`` picks the prefill programs' bucket (0: the first);
    ``keep_text`` adds the compiled module's text to the record
    (``"text"``)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.attention import decode_attention_for
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    engine_kw = {}
    if config:
        path = os.path.join(REPO, "benchmarks", "configs", config + ".json")
        with open(path) as f:
            cfg = json.load(f)
        c = ModelConfig.from_hf_dict(cfg)
        if cfg.get("quant"):
            c = dataclasses.replace(c, quant=cfg["quant"])
        tp, engine_kw, model_config = int(cfg["tp"]), cfg["engine"], config
    else:
        c = getattr(ModelConfig, model_config)()
    if layers > 0:
        c = dataclasses.replace(c, num_layers=layers)

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY
    )
    mesh = make_mesh(MeshConfig(tp=tp), list(topo.devices))
    attn = decode_attention_for(mesh)  # TPU devices: the compiled kernel

    e = EngineConfig(kv_quant=kv_quant, **engine_kw)
    B, S, R = e.max_decode_slots, e.max_context, e.flush_every
    dtype = jnp.dtype(e.cache_dtype)

    def abstract(make, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(make), shardings,
        )

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    params = abstract(lambda: llama.init_params(c, 0),
                      llama.param_shardings(c, mesh))
    # as the engine hands them to its programs: the leaves a block makes
    # once at start (llama.serving_params) beside the published ones,
    # replicated as every block that makes any holds its weights
    published = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: published[path] if path in published
        else jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(lambda p: llama.serving_params(c, p), params))
    ctx = abstract(
        lambda: llama.init_ctx(c, B, S, dtype, kv_quant=kv_quant,
                               group=e.page_size),
        llama.ctx_shardings(c, mesh, kv_quant=kv_quant),
    )
    pool = abstract(
        lambda: llama.init_cache(c, e.num_pages, e.page_size, dtype,
                                 kv_quant=kv_quant),
        llama.cache_shardings(c, mesh, kv_quant=kv_quant),
    )
    ring = abstract(lambda: llama.init_ring(c, B, R, dtype),
                    llama.ring_shardings(c, mesh))
    # the batched prefill the cells run: a group of two (the most that
    # prefill_chunks_per_round's default lets form) at the first bucket
    T = prefill_width or e.prefill_buckets[0]
    if T not in e.prefill_buckets:
        raise ValueError(f"{T} is no prefill bucket of {e.prefill_buckets}")
    K = e.prefill_lanes(T, 2)
    counted = llama.moe_prefill_rows_sorted(c, K * T) > 0
    solo_counted = llama.moe_prefill_rows_sorted(c, T) > 0

    def largest_shard(state):
        a = max(jax.tree.leaves(state), key=lambda a: math.prod(a.shape))
        return a.sharding.shard_shape(a.shape), a.dtype.itemsize

    region_shard, itemsize = largest_shard(ctx)
    pool_shard, _ = largest_shard(pool)
    # a model with recurrent layers: one layer's float32 state, all
    # lanes, is the other buffer no program may copy whole (the few-MB
    # convolution windows beside it are left out)
    state_shards = [shard for shard, width in (
        largest_shard(ctx[n]) for n in llama.state_kinds(ctx)) if width == 4]

    # one layer's shard of each projection, MLP and expert weight (the
    # leaves under a key that starts with "w"; the int8 tensor of a
    # quantised one; an expert stack whole and one expert of it): what no
    # program should lay out anew per call. The dense and latent blocks
    # stack their layers along a leading axis, the hybrid ones list them
    # (a stack laid out anew WHOLE is not looked for by shape: at the 2
    # layers and 2 lanes tier-1 compiles, a chunk's ``[2, 256, 5120]``
    # activations have a ``wk`` stack's dimensions; ``temp_gb`` holds it,
    # as it held the latent rounds' two until PR 57)
    stacked = isinstance(params["layers"], dict)
    weight_shards = set()
    for path, w in jax.tree_util.tree_flatten_with_path(params["layers"])[0]:
        if any(str(getattr(k, "key", "")).startswith("w") for k in path):
            shard = w.sharding.shard_shape(w.shape)[int(stacked):]
            weight_shards |= {shard[i:] for i in range(len(shard) - 1)}

    # the round AS THE ENGINE BUILDS IT: its jits close over these three
    # attributes and nothing else of an engine
    eng = TpuEngine.__new__(TpuEngine)
    eng.config, eng.ecfg, eng.decode_attn = c, e, attn
    eng._build_jits()
    W = eng._seal_fuse_w
    dev = {
        "counts": i32(B, c.vocab_size),
        "keys": jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=rep),
        **{k: i32(B) for k in ("tokens", "ctx", "dest", "top_k", "adapter")},
        **{k: jax.ShapeDtypeStruct((B,), jnp.float32, sharding=rep)
           for k in ("temp", "top_p", "freq", "pres", "rep")},
    }
    sw = seal_width or W
    # pool <-> region and pool <-> host movers, at a long prompt's pages
    n_pages = min(e.max_pages_per_seq, 64)
    quant = kv_quant == "int8"
    page_data = jax.ShapeDtypeStruct(
        (2, c.cache_planes, c.num_kv_heads, n_pages, e.page_size, c.head_dim),
        jnp.int8 if quant else dtype, sharding=jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, None, "tp")))
    page_scales = jax.ShapeDtypeStruct(
        (2, c.cache_planes, n_pages), jnp.float32, sharding=rep)

    table = {
        "decode_step": lambda: llama.decode_step.trace(
            c, params, ctx, ring, i32(B), i32(B), i32(B), i32(), attn=attn,
        ),
        "flush_ctx": lambda: llama.flush_ctx.trace(
            ctx, ring, i32(B), i32(B), i32(B),
        ),
        "seal_blocks": lambda: llama.seal_blocks.trace(
            pool, ctx, i32(sw), i32(sw), i32(sw), page_size=e.page_size,
        ),
        "flush_seal": lambda: jax.jit(
            lambda pool, ctx, ring, dest, base, valid, slots, starts, pages:
            (ctx := llama.flush_ctx_impl(ctx, ring, dest, base, valid),
             llama.seal_blocks_impl(pool, ctx, slots, starts, pages,
                                    e.page_size)),
            donate_argnums=(0, 1),
        ).trace(pool, ctx, ring, i32(B), i32(B), i32(B),
                i32(sw), i32(sw), i32(sw)),
        "round_seal": lambda: eng._engine_round_seal.trace(
            params, ctx, ring, dev, pool, i32(W), i32(W), i32(W),
            R, False, False,
        ),
        "load_ctx_pages": lambda: llama.load_ctx_pages.trace(
            ctx, pool, i32(), i32(n_pages),
        ),
        "gather_pages": lambda: (
            llama.gather_pages_q if quant else llama.gather_pages
        ).trace(pool, i32(n_pages)),
        "scatter_pages": lambda: (
            llama.scatter_pages_q.trace(pool, i32(n_pages), page_data,
                                        page_scales)
            if quant else
            llama.scatter_pages.trace(pool, i32(n_pages), page_data)),
        # one chunk alone (the engine's program for a group of one at a
        # bucket the batched form does not take), fresh and continuing
        "prefill": lambda: llama.prefill.trace(
            c, params, ctx, i32(T), i32(), i32(), i32(), None, None, i32(),
            fresh=True, counted=solo_counted, attn=attn,
        ),
        "prefill_cont": lambda: llama.prefill.trace(
            c, params, ctx, i32(T), i32(), i32(), i32(), None, None, i32(),
            fresh=False, counted=solo_counted, attn=attn,
        ),
        # counted as the engine dispatches it: where the expert layers
        # move rows in the looped form the program also returns its count
        "batch_prefill": lambda: llama.batch_prefill.trace(
            c, params, ctx, i32(K, T), i32(K), i32(K), i32(K), 0, i32(K),
            counted=counted, attn=attn,
        ),
        "batch_prefill_cont": lambda: llama.batch_prefill.trace(
            c, params, ctx, i32(K, T), i32(K), i32(K), i32(K), S, i32(K),
            counted=counted, attn=attn,
        ),
        # the one program a prefill dispatch that samples every first
        # token of the group and admits its slots, on the logits as the
        # prefill leaves them (the vocabulary over tp) and ONE packed row
        # a lane
        "admit_first": lambda: eng._admit_first.trace(
            dev, jax.ShapeDtypeStruct(
                (K, c.vocab_size), jnp.float32,
                sharding=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(None, "tp"))),
            jax.ShapeDtypeStruct((K, engine_mod._ROW_W), jnp.uint32,
                                 sharding=rep),
            False,
        ),
    }
    if llama.block_of(c) is not None:
        # its step is in the round; no page transfer
        for name in ("decode_step", "gather_pages", "scatter_pages"):
            del table[name]
    unknown = sorted(set(programs) - set(table))
    if unknown:
        raise ValueError(f"unknown programs {unknown}; have {sorted(table)}")
    labels = {"seal_blocks": f"seal_blocks_w{sw}",
              "flush_seal": f"flush_seal_w{sw}",
              **{n: f"{n}_n{n_pages}" for n in
                 ("load_ctx_pages", "gather_pages", "scatter_pages")},
              "round_seal": f"round_seal_n{R}_w{W}",
              "admit_first": f"admit_first_K{K}",
              "prefill": f"prefill_T{T}",
              "prefill_cont": f"prefill_cont_T{T}_S{S}",
              "batch_prefill": f"batch_prefill_K{K}_T{T}",
              "batch_prefill_cont": f"batch_prefill_cont_K{K}_T{T}_S{S}"}
    out = []
    for name, trace in table.items():
        if programs and name not in programs:
            continue
        rec = {"program": labels.get(name, name),
               "model_config": model_config, "tp": tp,
               "kv_quant": kv_quant, "layers": c.num_layers,
               "decode_attention": attn.impl,
               "region_shard": list(region_shard)}
        t0 = time.monotonic()
        try:
            lowered = trace().lower()
            # equal across two trees = the same program before the
            # compiler (a Mosaic kernel's serialised body names the
            # tree's file paths: masked)
            rec["lowered_sha256"] = hashlib.sha256(_MOSAIC_BODY.sub(
                "", lowered.as_text()).encode()).hexdigest()[:16]
            compiled = lowered.compile()
        except Exception as exc:  # noqa: BLE001 — the refusal IS the result
            rec.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:2000])
        else:
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            copies = region_copies(text, region_shard, pool_shard) + [
                found for found in region_copies(text, *state_shards)
                if found.startswith("f32[")]
            rec.update(
                ok=True,
                argument_gb=round(mem.argument_size_in_bytes / 1e9, 3),
                temp_gb=round(mem.temp_size_in_bytes / 1e9, 3),
                output_gb=round(mem.output_size_in_bytes / 1e9, 3),
                alias_gb=round(mem.alias_size_in_bytes / 1e9, 3),
                temp_bytes=mem.temp_size_in_bytes,
                # the executable's own bytes, which a loaded program
                # keeps on the device (a cache entry is a fifth of it)
                code_mb=round(mem.generated_code_size_in_bytes / 1e6, 1),
                region_bytes=math.prod(region_shard) * itemsize,
                region_copies={"count": len(copies),
                               "shapes": sorted(set(copies))},
                weight_copies=sorted(weight_copies(text, *weight_shards)),
                mosaic_calls=text.count("tpu_custom_call"),
            )
            if keep_text:
                rec["text"] = text
        rec["seconds"] = round(time.monotonic() - t0, 2)
        out.append(rec)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpu_compile_check", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model-config", default="llama3_1b")
    ap.add_argument("--config", default="",
                    help="a name in benchmarks/configs (overrides "
                         "--model-config and --tp)")
    ap.add_argument("--tp", type=int, default=1, choices=(1, 2, 4))
    ap.add_argument("--kv-quant", default="none", choices=("none", "int8"))
    ap.add_argument("--layers", type=int, default=0,
                    help="cut depth (0 = the config's own)")
    ap.add_argument("--programs", default="",
                    help="comma-separated subset: decode_step, flush_ctx, "
                         "seal_blocks, flush_seal, round_seal, "
                         "load_ctx_pages, gather_pages, scatter_pages, "
                         "prefill, prefill_cont, batch_prefill, "
                         "batch_prefill_cont, admit_first")
    ap.add_argument("--seal-width", type=int, default=0,
                    help="entries of the standalone seal (0 = the fused "
                         "round's width)")
    ap.add_argument("--prefill-width", type=int, default=0,
                    help="the prefill programs' bucket (0 = the first)")
    args = ap.parse_args(argv)
    records = compile_programs(
        args.model_config, args.tp, args.kv_quant, args.layers,
        config=args.config, seal_width=args.seal_width,
        prefill_width=args.prefill_width,
        programs=tuple(p for p in args.programs.split(",") if p),
    )
    for rec in records:
        print(json.dumps(rec))
    return 0 if all(r["ok"] for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
