"""HBM bytes ONE chip must move for one decode step of the delta-rule
(KDA) + latent attention hybrid with grouped sigmoid routing (the
configuration says ``"bytes": "kda_mla_moe"``;
``layer_metrics/step.decode_roofline.py`` calls this), and what the
delta-rule step kernel must move (``kda_step_bytes``). No JAX: stdlib and
the configuration's own numbers.

Counted, per step (``decode_parts``):
  * ``weights``: what every step reads whole: each KDA mixer (the fused
    q | k | v projection, the gate's matrix, the step's and output gate's,
    W_o; the convolution taps, gains and biases are tiny: left out), each
    latent mixer (W_q, W_kva, W_kvb, W_o), the leading dense MLPs, every
    expert layer's shared expert and router, and the head over the held
    slice of the vocabulary (the embedding row gather: left out);
  * ``experts``: the HELD routed experts the program's counter SAYS were
    touched (``dynamo_moe_experts_touched``, mean per step over the
    window) x one expert's three matrices;
  * ``rows``: the latent layers' cached rows of the live lanes at their
    exact lengths, at the width the region stores (640 values);
  * ``state``: the LIVE lanes' KDA state, read and written once each
    (float32 matrix state + the three convolution windows). The program's
    step rewrites the state of every lane, live or not: what it moves
    beyond the live lanes' is not counted.
Low, never high: a share of the roofline computed from it cannot pass
100 % by over-counting.
"""
from __future__ import annotations

TOUCHED = "dynamo_moe_experts_touched"
ROUTED = "dynamo_moe_tokens_routed"
STEPPED = "dynamo_kda_state_rows_stepped"
WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2
STATE_BYTES = 4      # the KDA state is float32


def shapes(hf: dict) -> dict:
    H, nh, D = hf["hidden_size"], hf["num_attention_heads"], hf["head_dim"]
    inner = nh * D
    L, period = hf["num_hidden_layers"], hf["layer_group_size"]
    n_latent = sum((l + 1) % period == 0 for l in range(L))
    n_dense = hf["first_k_dense_replace"]
    row = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    return {
        "kda": H * 3 * inner + H * inner + H * 2 * nh + inner * H,
        "latent": (H * nh * (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"])
                   + H * row + hf["kv_lora_rank"] * nh
                   * (hf["qk_nope_head_dim"] + hf["v_head_dim"])
                   + nh * hf["v_head_dim"] * H),
        "dense": 3 * H * hf["intermediate_size"],
        "expert": 3 * H * hf["moe_intermediate_size"],
        "shared": 3 * H * hf["moe_shared_expert_intermediate_size"],
        "router": H * hf["num_experts"],
        "head": H * hf["vocab_size"],
        "n_kda": L - n_latent, "n_latent": n_latent, "n_dense": n_dense,
        "n_expert": L - n_dense,
        "stored_row": -(-row // 128) * 128,        # values a token a layer
        "state_lane": (nh * D * D * STATE_BYTES
                       + (hf["short_conv_kernel_size"] - 1) * 3 * inner
                       * CACHE_BYTES),              # bytes a layer
        "kda_state": nh * D * D * STATE_BYTES,
    }


def _delta(sources: dict, name: str):
    a = sources["before"]["histograms"].get(name)
    b = sources["after"]["histograms"].get(name)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return b["sum"] - a["sum"], b["count"] - a["count"]


def _per_step(sources: dict, name: str):
    d = _delta(sources, name)
    if d is None:
        return None
    return d[0] / (d[1] * sources["engine_up"]["flush_every"])


def decode_parts(sources: dict, ctx_lens: list[float]) -> dict:
    """The step's counted bytes by what they are."""
    hf = sources["config"]
    s = shapes(hf)
    touched = _per_step(sources, TOUCHED) or 0.0   # no counter: count none
    eng = hf["engine"]
    max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
    rows = sum(min(max(n, 0.0), max_ctx) for n in ctx_lens)
    return {
        "weights": (s["n_kda"] * s["kda"] + s["n_latent"] * s["latent"]
                    + s["n_dense"] * s["dense"]
                    + s["n_expert"] * (s["shared"] + s["router"])
                    + s["head"]) * WEIGHT_BYTES,
        "experts": touched * s["expert"] * WEIGHT_BYTES,
        "rows": rows * s["stored_row"] * s["n_latent"] * CACHE_BYTES,
        "state": 2 * len(ctx_lens) * s["n_kda"] * s["state_lane"],
    }


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values()))


def gmm_decode(sources: dict):
    """The grouped expert product (the megablox ``gmm`` Pallas kernel) in
    ONE decode step, all expert layers: (HBM bytes it must read, floating
    point operations it must do, the trace labels of its decode-shaped
    calls). Bytes: the three matrices of every HELD expert the counter
    says was touched, once each. Ops: 2 x in x out for the three products
    of the picks that landed on a held expert
    (``dynamo_moe_tokens_routed`` counts those). None where the program
    has no counter."""
    hf = sources["config"]
    touched, picks = _per_step(sources, TOUCHED), _per_step(sources, ROUTED)
    if touched is None or picks is None:
        return None
    H, I_e = hf["hidden_size"], hf["moe_intermediate_size"]
    rows = hf["engine"]["max_decode_slots"] * hf["num_experts_per_tok"]
    labels = (f"gmm bf16[{rows},{I_e}]", f"gmm bf16[{rows},{H}]")
    return (touched * 3 * H * I_e * WEIGHT_BYTES, picks * 3 * 2 * H * I_e,
            labels)


def kda_step_bytes(lanes: float) -> callable:
    """hf -> the bytes the delta-rule step kernel must move for ``lanes``
    per-lane states of ONE layer: each [heads, D, D] float32 state read
    once and written once. Its five [heads, D] operand tiles and its
    output (0.6 % of that) are left out: low, never high."""
    return lambda hf: 2.0 * lanes * shapes(hf)["kda_state"]


def kda_states_stepped(sources: dict):
    """Per-lane states the program's counter says the window's decode
    rounds stepped, all layers, and the rounds: (states, rounds), or None
    without the counter."""
    return _delta(sources, STEPPED)
