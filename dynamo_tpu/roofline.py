"""Per-step decode byte accounting + roofline attribution.

`roofline_frac` (bench.py, since r04) compares decode steps/s against
the WEIGHT-pass ceiling (peak HBM bandwidth / parameter bytes) — honest
for small-batch decode but a single opaque number: it says nothing
about where the other bytes go. This module decomposes the real
per-step HBM traffic of the fused decode round into its streams —
weights, live context KV (from actual per-slot context lengths, at the
kernel's chunk granularity), the int8 scale sidecar, the write ring,
and the logits row — so bench/profile lines can emit

  kv_bytes_per_step    KV-plane bytes per fused step (ctx + scales + ring)
  attn_roofline_frac   steps/s x total bytes-per-step / peak bandwidth
                       (fraction of the chip's bandwidth the measured
                       rate actually moves — the attributed roofline)

and the kv_quant=int8 claim ("live-KV HBM bytes <= 0.55x bf16") becomes
a reported ratio (`kv_ctx_bytes_vs_bf16`) instead of folklore.

All values are DERIVED from config + context lengths, not measured
counters — they are exact for the streams the fused round provably
moves (every weight byte, every live KV chunk the DMA-skip index map
admits) and they deliberately exclude second-order traffic (activation
spills, sampler temporaries). On CPU harnesses the byte fields stay
real (geometry is geometry) while there are no peaks to attribute
against: ``chip_info`` returns None for them, and an accelerator that is
not in the table is an error, never an assumed v5e.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

# chip peak table (bf16 FLOP/s, HBM B/s); device_kind -> (flops, bw).
# Published per-chip peaks (Google Cloud TPU documentation).
CHIP_PEAKS = {
    "TPU v5e": (197e12, 819e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v6e": (918e12, 1640e9),
}


def chip_info():
    """(device_kind, (peak_flops, peak_bw) | None, on_accelerator).
    The CPU has no peaks (None); an accelerator whose device_kind is not
    in CHIP_PEAKS raises — a utilization against a guessed peak is a
    made-up number."""
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    if dev.platform == "cpu":
        return kind, None, False
    for name, peak in CHIP_PEAKS.items():
        if name.lower() in kind.lower():
            return kind, peak, True
    raise ValueError(
        f"no peak FLOP/s / bandwidth on record for device_kind {kind!r}; "
        "add it to dynamo_tpu.roofline.CHIP_PEAKS with its source"
    )


def decode_byte_accounting(
    config,                      # models.config.ModelConfig
    ecfg,                        # engine.config.EngineConfig
    ctx_lens: Sequence[int],     # live per-slot context lengths
    param_bytes: int,
    steps_per_s: Optional[float] = None,
    peak_bw: Optional[float] = None,
) -> dict:
    """Decompose the fused decode round's per-step HBM bytes.

    Returns a dict with the per-stream breakdown (bytes/step), the
    aggregates (`kv_bytes_per_step`, `total_bytes_per_step`), the
    quantization ratio (`kv_ctx_bytes_vs_bf16` — live ctx + scale bytes
    vs the same geometry in bf16), and — when `steps_per_s`/`peak_bw`
    are given — `attn_roofline_frac`.
    """
    import jax.numpy as jnp

    from dynamo_tpu.ops.flash_decode import DEFAULT_CHUNK, _pick_chunk

    c, e = config, ecfg
    L, kvh, hd = c.num_layers, c.num_kv_heads, c.head_dim
    B, R = e.max_decode_slots, e.flush_every
    quant = e.kv_quant == "int8"
    compute_bytes = jnp.dtype(e.cache_dtype).itemsize
    kv_elem = 1 if quant else compute_bytes
    group = max(1, e.page_size)
    S = -(-e.max_context // group) * group if quant else e.max_context

    # live ctx stream: the kernel's DMA-skip admits whole CHUNKs up to
    # each lane's live context — round per lane to the chunk the kernel
    # would pick for this S (mirrors ops/flash_decode._pick_chunk)
    chunk = _pick_chunk(S, DEFAULT_CHUNK, group if quant else 1)
    lens = np.clip(np.asarray(list(ctx_lens), np.int64), 0, S)
    read_rows = np.ceil(lens / chunk).astype(np.int64) * chunk
    kv_ctx = int(2 * L * kvh * hd * read_rows.sum()) * kv_elem
    # int8 scale sidecar rides the same chunks: f32 per (layer, group),
    # no head axis
    kv_ctx_scales = (
        int(2 * L * (read_rows // group).sum()) * 4 if quant else 0
    )
    # write ring: read in full by every step's attention, one new row
    # written per lane per step; stays the compute dtype (it is tiny)
    ring_elems = 2 * L * kvh * B * R * hd
    kv_ring = (ring_elems + 2 * L * kvh * B * hd) * compute_bytes
    # logits row the sampler consumes (f32 accumulators)
    logits = B * c.vocab_size * 4

    bf16_equiv = int(2 * L * kvh * hd * read_rows.sum()) * 2
    kv_bytes = kv_ctx + kv_ctx_scales + kv_ring
    total = param_bytes + kv_bytes + logits
    out = {
        "bytes_per_step_breakdown": {
            "weights": param_bytes,
            "kv_ctx": kv_ctx,
            "kv_ctx_scales": kv_ctx_scales,
            "kv_ring": kv_ring,
            "logits": logits,
        },
        "kv_bytes_per_step": kv_bytes,
        "total_bytes_per_step": total,
        # live-context ratio vs the bf16 layout (the <= 0.55x pin):
        # int8 payload + f32-per-group sidecar over bf16 payload
        "kv_ctx_bytes_vs_bf16": (
            (kv_ctx + kv_ctx_scales) / bf16_equiv if bf16_equiv else None
        ),
        "attn_roofline_frac": (
            steps_per_s * total / peak_bw
            if steps_per_s and peak_bw else None
        ),
    }
    return out
