"""HBM bytes ONE chip must move for one decode step of the Mamba-1 +
attention hybrid (the configuration says ``"bytes": "jamba"``;
``layer_metrics/step.decode_roofline.py`` calls this), and what its two
Pallas kernels must move. No JAX: stdlib and the configuration's own
numbers.

Counted, per step (``decode_parts``):
  * ``weights``: what every step reads whole: each Mamba mixer (in_proj,
    x_proj, dt_proj, out_proj, the convolution, A_log, D, dt_bias), each
    attention layer's four projections, every layer's dense SwiGLU, and
    the head, which is the embedding (tied: V x hidden; the embedding row
    gather and the norms' gains are tiny: left out);
  * ``rows``: the attention layers' K and V rows of the live lanes, their
    exact lengths (one K/V head of 128: 1024 B a token over both layers);
  * ``state``: the LIVE lanes' recurrent state, read and written once
    each (the float32 [16, inner] state + the convolution's window). The
    step kernel touches the live lanes' states only; the convolution's
    window is rewritten for all lanes + 1, and what that moves beyond the
    live lanes' is not counted.
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
    H = hf["hidden_size"]
    I, N = hf["mamba_expand"] * H, hf["mamba_d_state"]
    R, W = hf["mamba_dt_rank"], hf["mamba_d_conv"]
    heads = hf["num_attention_heads"]
    hd = H // heads
    kv = hf["num_key_value_heads"] * hd
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    n_attn = sum(i % period == offset
                 for i in range(hf["num_hidden_layers"]))
    return {
        # bytes, the float32 leaves at their own width
        "mamba": ((H * 2 * I + I * (R + 2 * N) + R * I + I * H + W * I + I)
                  * WEIGHT_BYTES + (N * I + 2 * I) * STATE_BYTES),
        "attn": (2 * H * heads * hd + 2 * H * kv) * WEIGHT_BYTES,
        "mlp": 3 * H * hf["intermediate_size"] * WEIGHT_BYTES,
        "head": H * hf["vocab_size"] * WEIGHT_BYTES,
        "n_m1": hf["num_hidden_layers"] - n_attn,
        "n_attn": n_attn,
        "layers": hf["num_hidden_layers"],
        "kv_row": 2 * kv,                                # values a token
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
    rows = sum(min(max(n, 0.0), max_ctx) for n in ctx_lens)
    return {
        "weights": (s["n_m1"] * s["mamba"] + s["n_attn"] * s["attn"]
                    + s["layers"] * s["mlp"] + s["head"]),
        "rows": rows * s["kv_row"] * s["n_attn"] * CACHE_BYTES,
        "state": 2 * len(ctx_lens) * s["n_m1"] * s["state_lane"],
    }


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values()))


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
