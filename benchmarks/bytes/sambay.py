"""HBM bytes ONE chip must move for one decode step of the
decoder-hybrid-decoder stack (the configuration says ``"bytes":
"sambay"``; ``layer_metrics/step.decode_roofline.py`` calls this), and what
its Pallas kernels must move. No JAX: stdlib and the configuration's own
numbers.

Counted, per step (``decode_parts``):
  * ``weights``: what every step reads whole: the nine Mamba-1 mixers, the
    nine attention layers with their own K/V projections, the seven cross
    layers (W_q and W_o only), the seven gated memory units, every layer's
    dense SwiGLU, and the head, which is the embedding (tied; the
    embedding's row gather, the norms and the biases are tiny: left out);
  * ``shared_rows``: the ONE full layer's K and V rows of the live lanes,
    their exact lengths, x its readers (itself and the cross layers):
    stored once, read once a reader;
  * ``window_rows``: the window layers' rows, ``min(n, window)`` a live
    lane a layer (what the window admits: the kernel reads whole chunks
    of a lane's buffer, which is more);
  * ``state``: the LIVE lanes' recurrent state, read and written once
    each (the float32 [16, inner] state + the convolution's window).
Low, never high: a share of the roofline computed from it cannot pass
100 % by over-counting.
"""
from __future__ import annotations

STEPPED = "dynamo_ssm_state_rows_stepped"
SCANNED = "dynamo_ssm_scan_positions"
WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2
STATE_BYTES = 4      # the scan's state, A_log, D and dt_bias are float32


def shapes(hf: dict) -> dict:
    H, L = hf["hidden_size"], hf["num_hidden_layers"]
    I, N = hf.get("mamba_expand", 2) * H, hf.get("mamba_d_state", 16)
    W = hf.get("mamba_d_conv", 4)
    rank = hf.get("mamba_dt_rank", "auto")
    R = -(-H // 16) if rank == "auto" else int(rank)
    heads = hf["num_attention_heads"]
    hd = H // heads
    kv = hf["num_key_value_heads"] * hd
    half = L // 2
    n_m1 = half // 2 + 1             # even l <= half
    n_win = half // 2                # odd l < half
    n_cross = sum(1 for l in range(half + 2, L) if l % 2)
    n_gmu = sum(1 for l in range(half + 2, L) if l % 2 == 0)
    window = hf["sliding_window"]
    if isinstance(window, (list, tuple)):
        window = next(w for w in window if w is not None)
    return {
        # bytes, the float32 leaves at their own width
        "mamba": ((H * 2 * I + I * (R + 2 * N) + R * I + I * H + W * I + I)
                  * WEIGHT_BYTES + (N * I + 2 * I) * STATE_BYTES),
        "attn": (2 * H * heads * hd + 2 * H * kv) * WEIGHT_BYTES,
        "cross": 2 * H * heads * hd * WEIGHT_BYTES,
        "gmu": 2 * H * I * WEIGHT_BYTES,
        "mlp": 3 * H * hf["intermediate_size"] * WEIGHT_BYTES,
        "head": H * hf["vocab_size"] * WEIGHT_BYTES,
        "n_m1": n_m1, "n_win": n_win, "n_cross": n_cross, "n_gmu": n_gmu,
        "layers": L, "window": int(window),
        "row": 2 * kv * CACHE_BYTES,                     # K and V, a token
        "m1_state": N * I * STATE_BYTES,                 # bytes a layer
        "state_lane": N * I * STATE_BYTES + (W - 1) * I * CACHE_BYTES,
        "inner": I,
    }


def _delta(sources: dict, name: str):
    a = sources["before"]["histograms"].get(name)
    b = sources["after"]["histograms"].get(name)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return b["sum"] - a["sum"], b["count"] - a["count"]


def decode_parts(sources: dict, ctx_lens: list[float]) -> dict:
    """The step's counted bytes by what they are."""
    hf = sources["config"]
    s = shapes(hf)
    eng = hf["engine"]
    max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
    lens = [min(max(n, 0.0), max_ctx) for n in ctx_lens]
    return {
        "weights": (s["n_m1"] * s["mamba"] + (s["n_win"] + 1) * s["attn"]
                    + s["n_cross"] * s["cross"] + s["n_gmu"] * s["gmu"]
                    + s["layers"] * s["mlp"] + s["head"]),
        "shared_rows": sum(lens) * s["row"] * (1 + s["n_cross"]),
        "window_rows": (sum(min(n, s["window"]) for n in lens) * s["row"]
                        * s["n_win"]),
        "state": 2 * len(lens) * s["n_m1"] * s["state_lane"],
    }


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values()))


def diff_decode_bytes(sources: dict, ctx_lens: list[float]) -> float:
    """The bytes the differential decode kernel (the Mosaic call
    ``diff_decode_attention``: one a window, full or cross layer a step)
    must move in ONE decode step with lanes of those live context lengths:
    K and V of the full layer's rows once a reader and of the rows the
    window admits once a window layer (``decode_parts``: exact lengths,
    never the whole chunks fetched). Queries, outputs and the ring are
    left out: low, never high."""
    parts = decode_parts(sources, ctx_lens)
    return float(parts["shared_rows"] + parts["window_rows"])


def m1_step_bytes(lanes: float) -> callable:
    """hf -> the bytes the decode step kernel (``m1_step``) must move for
    ``lanes`` per-lane states of ONE layer: each [16, inner] float32 state
    read once and written once. Its row operands, A and its output (a
    sixteenth of that and less) are left out: low, never high."""
    return lambda hf: 2.0 * lanes * shapes(hf)["m1_state"]


def m1_states_stepped(sources: dict):
    """Per-lane states the program's counter says the window's decode
    rounds stepped, all layers, and the rounds: (states, rounds), or None
    without the counter."""
    return _delta(sources, STEPPED)


def m1_scan_bytes(positions: float) -> callable:
    """hf -> the bytes the prefill scan kernel (``m1_scan``) must move for
    ``positions`` scanned positions (layers counted in them): x and dt
    read, y written, float32 [inner] each. B, C, A, D and the state (a
    percent of that) are left out: low, never high."""
    return lambda hf: 3.0 * positions * shapes(hf)["inner"] * STATE_BYTES


def m1_positions_scanned(sources: dict):
    """Positions the host's mirror says the window's prefill dispatches
    scanned, all Mamba-1 layers, and the dispatches: (positions,
    dispatches), or None without the counter."""
    return _delta(sources, SCANNED)
