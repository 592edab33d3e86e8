"""The table of peaks and the byte arithmetic of one decode step.

Copied from ``dynamo_tpu/roofline.py`` (``CHIP_PEAKS``,
``decode_byte_accounting``) so that no later PR can move the yardstick.
Peaks are Google Cloud's published per-chip numbers ("TPU v5e": 197
TFLOP/s bf16, 819 GB/s HBM). A device kind that is not in the table is an
error, never a default.

Differences from the original accounting: it takes plain sizes (no
program types), counts the bytes ONE CHIP moves (weights and KV heads are
split over tp), and leaves out the write ring and the logits row (both
under 1 % here) — so it counts low, never high, and a share of the
roofline computed from it cannot pass 100 % by over-counting. That holds
for a dense block with full attention in every layer. A block whose step
does not read all of ``param_bytes`` (experts no token chose) or all of a
lane's context (window layers) carries a count of its own,
``benchmarks/bytes/<name>.py``, named by its configuration's ``"bytes"``
(``layer_metrics/step.decode_roofline.py`` looks it up).
"""
from __future__ import annotations

# device_kind substring -> (bf16 FLOP/s, HBM bytes/s), per chip
CHIP_PEAKS = {
    "TPU v5e": (197e12, 819e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v6e": (918e12, 1640e9),
}

DECODE_KERNEL_CHUNK = 512   # ops/flash_decode.py DEFAULT_CHUNK: the kernel
                            # reads whole chunks up to each lane's length


def peaks_for(device_kind: str) -> tuple[float, float]:
    for name, peak in CHIP_PEAKS.items():
        if name.lower() in device_kind.lower():
            return peak
    raise ValueError(f"no peaks on record for device kind {device_kind!r}; "
                     "add it to benchmarks/peaks.py with its source")


def kv_bytes_per_token(hf: dict, kv_elem_bytes: int = 2) -> int:
    """K and V of one position over all layers and kv heads."""
    nh = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // nh
    return (2 * hf["num_hidden_layers"] * hf.get("num_key_value_heads", nh)
            * hd * kv_elem_bytes)


def decode_bytes_per_step(hf: dict, param_bytes: int, ctx_lens: list[float],
                          tp: int, max_context: int) -> float:
    """HBM bytes one chip must read for one decode step: its share of the
    weights once, and the live context of every lane in whole chunks."""
    rows = 0.0
    for n in ctx_lens:
        n = min(max(n, 0.0), max_context)
        rows += -(-n // DECODE_KERNEL_CHUNK) * DECODE_KERNEL_CHUNK
    return (param_bytes + kv_bytes_per_token(hf) * rows) / tp
