"""HBM bytes ONE chip must move for one decode step of the window + full
GQA stack with routed experts (the configuration says ``"bytes":
"window_gqa_moe"``; ``layer_metrics/step.decode_roofline.py`` calls this),
and what its two decode attention calls and its grouped expert product
must move. No JAX: stdlib and the configuration's own numbers.

Counted, per step (``decode_parts``):
  * ``weights``: what every step reads whole: every attention layer's W_q
    and W_o at ITS number of query heads, W_k, W_v and the gate's W_g;
    the leading dense MLP; every expert layer's shared expert and router
    (at the PUBLISHED width); the head over the held slice of the
    vocabulary (the embedding's row gather, the norms and the router's
    bias are tiny: left out);
  * ``experts``: the HELD routed experts the program's counter SAYS were
    touched (``dynamo_moe_experts_touched``, mean per step over the
    window) x one expert's three matrices;
  * ``full_rows``: the full layers' K and V rows of the live lanes at
    their exact lengths (the round's own rows wait in the ring and are
    among them);
  * ``window_rows``: the window layers' rows, ``min(n, window)`` a live
    lane a layer (what the window admits: the kernel reads whole chunks of
    a lane's buffer, which is more).
Low, never high: a share of the roofline computed from it cannot pass
100 % by over-counting.
"""
from __future__ import annotations

TOUCHED = "dynamo_moe_experts_touched"
ROUTED = "dynamo_moe_tokens_routed"
WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2


def shapes(hf: dict) -> dict:
    H, hd = hf["hidden_size"], hf["head_dim"]
    kv = hf["num_key_value_heads"] * hd
    kinds, heads = hf["layer_types"], hf["num_attention_heads_per_layer"]
    share = hf.get("expert_share") or {"published_experts": hf["num_experts"]}
    n_dense = len(hf["mlp_only_layers"])
    return {
        # values, all layers: W_q + W_o + the gate at the layer's own heads
        "attn": sum(2 * H * n * hd + H * n + 2 * H * kv for n in heads),
        "dense": 3 * H * hf["intermediate_size"],
        "expert": 3 * H * hf["moe_intermediate_size"],
        "shared": 3 * H * hf["shared_expert_intermediate_size"],
        "router": H * share["published_experts"],
        "head": H * hf["vocab_size"],
        "n_dense": n_dense, "n_expert": len(kinds) - n_dense,
        "n_full": sum(t == "full_attention" for t in kinds),
        "n_win": sum(t == "sliding_attention" for t in kinds),
        "window": int(hf["sliding_window"]),
        "row": 2 * kv * CACHE_BYTES,                  # K and V, a token
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
    lens = [min(max(n, 0.0), max_ctx) for n in ctx_lens]
    return {
        "weights": (s["attn"] + s["n_dense"] * s["dense"]
                    + s["n_expert"] * (s["shared"] + s["router"])
                    + s["head"]) * WEIGHT_BYTES,
        "experts": touched * s["expert"] * WEIGHT_BYTES,
        "full_rows": sum(lens) * s["row"] * s["n_full"],
        "window_rows": (sum(min(n, s["window"]) for n in lens) * s["row"]
                        * s["n_win"]),
    }


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values()))


def full_decode_bytes(sources: dict, ctx_lens: list[float]) -> float:
    """The bytes the full layers' decode attention (the Mosaic call
    ``full_gqa_decode_attention``: one a full layer a step) must move in
    ONE decode step with lanes of those live context lengths: K and V of
    every live lane's rows at their exact lengths, never the whole chunks
    fetched. Queries, outputs and the ring's tail are left out: low, never
    high."""
    return float(decode_parts(sources, ctx_lens)["full_rows"])


def window_decode_bytes(sources: dict, ctx_lens: list[float]) -> float:
    """The same of the window layers' call
    (``window_gqa_decode_attention``): the rows the WINDOW admits, a
    layer."""
    return float(decode_parts(sources, ctx_lens)["window_rows"])


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
