"""HBM bytes ONE chip must move for one decode step of the state-space +
attention hybrid with routed experts (the configuration says ``"bytes":
"ssm_moe"``; ``layer_metrics/step.decode_roofline.py`` calls this). No
JAX: stdlib and the configuration's own numbers.

Counted, per step (``decode_parts``):
  * ``weights``: what every step reads whole: each Mamba mixer (input
    and output projections, convolution, gated norm), each attention
    layer's four projections, every layer's shared MLP and router, and
    the head, which is the embedding (tied: V x hidden; the embedding
    row gather and the norms are tiny: left out);
  * ``experts``: the HELD routed experts the program's counter SAYS were
    touched (``dynamo_moe_experts_touched``, mean per step over the
    window) x one expert's three matrices;
  * ``rows``: the attention layers' K and V rows of the live lanes, their
    exact lengths;
  * ``state``: the LIVE lanes' recurrent state, read and written once
    each (float32 SSM state + the convolution's window). The program's
    step rewrites the state of all lanes + 1, live or not: what it moves
    beyond the live lanes' is not counted.
Low, never high: a share of the roofline computed from it cannot pass
100 % by over-counting.
"""
from __future__ import annotations

TOUCHED = "dynamo_moe_experts_touched"
ROUTED = "dynamo_moe_tokens_routed"
WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2
STATE_BYTES = 4      # the SSM state is float32


def shapes(hf: dict) -> dict:
    H = hf["hidden_size"]
    nh, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    inner, W = nh * P, hf["mamba_d_conv"]
    conv = inner + 2 * N
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or H // heads
    kv = hf["num_key_value_heads"] * hd
    kinds = hf["layer_types"]
    return {
        "mamba": (H * (2 * inner + 2 * N + nh) + conv * (W + 1) + inner
                  + inner * H),
        "attn": 2 * H * heads * hd + 2 * H * kv,
        "expert": 3 * H * hf["intermediate_size"],
        "shared": 3 * H * hf["shared_intermediate_size"],
        "router": H * (hf.get("expert_share") or {}).get(
            "published_experts", hf["num_local_experts"]),
        "head": H * hf["vocab_size"],
        "n_ssm": sum(k == "mamba" for k in kinds),
        "n_attn": sum(k == "attention" for k in kinds),
        "layers": len(kinds),
        "kv_row": 2 * kv,                               # values a token
        "state_lane": (nh * P * N * STATE_BYTES
                       + (W - 1) * conv * CACHE_BYTES),  # bytes a layer
    }


def _per_step(sources: dict, name: str):
    a = sources["before"]["histograms"].get(name)
    b = sources["after"]["histograms"].get(name)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    steps = (b["count"] - a["count"]) * sources["engine_up"]["flush_every"]
    return (b["sum"] - a["sum"]) / steps


def decode_parts(sources: dict, ctx_lens: list[float]) -> dict:
    """The step's counted bytes by what they are."""
    hf = sources["config"]
    s = shapes(hf)
    touched = _per_step(sources, TOUCHED) or 0.0   # no counter: count none
    eng = hf["engine"]
    max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
    rows = sum(min(max(n, 0.0), max_ctx) for n in ctx_lens)
    return {
        "weights": (s["n_ssm"] * s["mamba"] + s["n_attn"] * s["attn"]
                    + s["layers"] * (s["shared"] + s["router"])
                    + s["head"]) * WEIGHT_BYTES,
        "experts": touched * s["expert"] * WEIGHT_BYTES,
        "rows": rows * s["kv_row"] * s["n_attn"] * CACHE_BYTES,
        "state": 2 * len(ctx_lens) * s["n_ssm"] * s["state_lane"],
    }


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values()))


def gmm_decode(sources: dict):
    """The grouped expert product (the megablox ``gmm`` Pallas kernel) in
    ONE decode step, all layers: (HBM bytes it must read, floating point
    operations it must do, the trace labels of its decode-shaped calls).
    Bytes: the three matrices of every HELD expert the counter says was
    touched, once each. Ops: 2 x in x out for the three products of the
    picks that landed on a held expert (``dynamo_moe_tokens_routed``
    counts those). None where the program has no counter."""
    hf = sources["config"]
    touched, picks = _per_step(sources, TOUCHED), _per_step(sources, ROUTED)
    if touched is None or picks is None:
        return None
    H, I_e = hf["hidden_size"], hf["intermediate_size"]
    rows = hf["engine"]["max_decode_slots"] * hf["num_experts_per_tok"]
    labels = (f"gmm bf16[{rows},{I_e}]", f"gmm bf16[{rows},{H}]")
    return (touched * 3 * H * I_e * WEIGHT_BYTES, picks * 3 * 2 * H * I_e,
            labels)
