#!/usr/bin/env python
"""Compile the serving programs for a v5e WITHOUT a chip.

libtpu ships the TPU compiler, and
``jax.experimental.topologies.get_topology_desc`` hands out compile-only
v5e devices: shardings over them on ``ShapeDtypeStruct`` arguments take
``jit(f).trace(...).lower().compile()`` through XLA:TPU and Mosaic on a
CPU-only box. This is the check to run before spending a chip call on a
kernel or sharding change — a Mosaic refusal ("unsupported shape cast",
a BlockSpec the tiling rejects, "cannot be automatically partitioned")
shows up here in seconds. It proves the programs COMPILE; whether they
compute the right thing, fit HBM at run time, or run fast is the chip's
to say (chip_smoke.py).

  python tools/tpu_compile_check.py                        # llama3_1b, tp=1
  python tools/tpu_compile_check.py --tp 4 --kv-quant int8
  python tools/tpu_compile_check.py --model-config llama3_8b_int8 --layers 4

Compiles, at the CLI's default engine sizes (B 8, S 4096, R 4): one decode
step (``llama.decode_step`` — every layer's Mosaic call), the ring->ctx
flush, and the smallest batched prefill bucket (K 8 x T 128, fresh). Prints
one JSON line per program with XLA's memory analysis; exits 1 if any
program fails to compile. libtpu warns about ``TPU_ACCELERATOR_TYPE`` /
worker hostnames on a box with no TPU — harmless here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# no chip is needed or wanted: compile-only devices come from the topology
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# repo-root invocation (python tools/tpu_compile_check.py) without install
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"  # the four-chip host; tp=1 uses its first device


def compile_programs(model_config: str, tp: int, kv_quant: str,
                     layers: int = 0) -> list[dict]:
    """Compile decode step, flush and one prefill bucket for a tp-wide
    mesh of compile-only v5e devices. Returns one record per program:
    {"program", "ok", "seconds", "error" | memory fields}."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.attention import decode_attention_for
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY
    )
    mesh = make_mesh(MeshConfig(tp=tp), list(topo.devices))
    attn = decode_attention_for(mesh)  # TPU devices: the compiled kernel

    kw = {"num_layers": layers} if layers > 0 else {}
    c = getattr(ModelConfig, model_config)(**kw)
    e = EngineConfig(kv_quant=kv_quant)
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
    ctx = abstract(
        lambda: llama.init_ctx(c, B, S, dtype, kv_quant=kv_quant,
                               group=e.page_size),
        llama.ctx_shardings(c, mesh, kv_quant=kv_quant),
    )
    ring = abstract(lambda: llama.init_ring(c, B, R, dtype),
                    llama.ring_shardings(c, mesh))
    K, T = e.prefill_batch_max, e.prefill_buckets[0]

    programs = {
        "decode_step": lambda: llama.decode_step.trace(
            c, params, ctx, ring, i32(B), i32(B), i32(B), i32(), attn=attn,
        ),
        "flush_ctx": lambda: llama.flush_ctx.trace(
            ctx, ring, i32(B), i32(B), i32(B),
        ),
        f"batch_prefill_K{K}_T{T}": lambda: llama.batch_prefill.trace(
            c, params, ctx, i32(K, T), i32(K), i32(K), i32(K), 0, i32(K),
        ),
    }
    out = []
    for name, trace in programs.items():
        rec = {"program": name, "model_config": model_config, "tp": tp,
               "kv_quant": kv_quant, "layers": c.num_layers,
               "decode_attention": attn.impl}
        t0 = time.monotonic()
        try:
            compiled = trace().lower().compile()
        except Exception as exc:  # noqa: BLE001 — the refusal IS the result
            rec.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:2000])
        else:
            mem = compiled.memory_analysis()
            rec.update(
                ok=True,
                argument_gb=round(mem.argument_size_in_bytes / 1e9, 3),
                temp_gb=round(mem.temp_size_in_bytes / 1e9, 3),
                output_gb=round(mem.output_size_in_bytes / 1e9, 3),
                alias_gb=round(mem.alias_size_in_bytes / 1e9, 3),
                mosaic_calls=compiled.as_text().count("tpu_custom_call"),
            )
        rec["seconds"] = round(time.monotonic() - t0, 2)
        out.append(rec)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpu_compile_check", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model-config", default="llama3_1b")
    ap.add_argument("--tp", type=int, default=1, choices=(1, 2, 4))
    ap.add_argument("--kv-quant", default="none", choices=("none", "int8"))
    ap.add_argument("--layers", type=int, default=0,
                    help="cut depth (0 = the config's own)")
    args = ap.parse_args(argv)
    records = compile_programs(
        args.model_config, args.tp, args.kv_quant, args.layers
    )
    for rec in records:
        print(json.dumps(rec))
    return 0 if all(r["ok"] for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
