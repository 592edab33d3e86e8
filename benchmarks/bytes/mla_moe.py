"""HBM bytes ONE chip must read for one decode step of the
latent-attention + routed-expert block (the configuration says
``"bytes": "mla_moe"``; ``layer_metrics/step.decode_roofline.py`` calls
this). No JAX: stdlib and the configuration's own numbers.

Counted, per step:
  * the weights every step reads whole: attention (all layers), the
    leading dense MLPs, the shared experts and routers, the output head
    (the embedding is a row gather and the norms are tiny: left out);
  * the routed experts the program's counter SAYS were touched
    (``dynamo_moe_experts_touched``: distinct experts read, summed over a
    round's steps and expert layers; its mean per step over the window) x
    one expert's three matrices. Not all of them, and not an expectation
    from the batch width;
  * the latent rows of the live context: row values x the cache's element
    size x each lane's live length x layers. The values of a row, not the
    tile-padded width it is stored at, and the exact lengths, not whole
    chunks.
Low, never high: a share of the roofline computed from it cannot pass
100 % by over-counting.
"""
from __future__ import annotations

TOUCHED = "dynamo_moe_experts_touched"
WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2


def shapes(hf: dict) -> dict:
    H, nh = hf["hidden_size"], hf["num_attention_heads"]
    qk = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    row = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    L = hf["num_hidden_layers"]
    n_dense = min(hf["first_k_dense_replace"], L)
    I_e = hf["moe_intermediate_size"]
    return {
        "attn": (H * hf["q_lora_rank"] + hf["q_lora_rank"] * nh * qk
                 + H * row + hf["kv_lora_rank"] * nh * (
                     hf["qk_nope_head_dim"] + hf["v_head_dim"])
                 + nh * hf["v_head_dim"] * H),
        "dense_mlp": 3 * H * hf["intermediate_size"],
        "expert": 3 * H * I_e,
        "shared": 3 * H * I_e * hf["n_shared_experts"],
        "router": H * hf["n_routed_experts"],
        "head": H * hf["vocab_size"],
        "row": row, "layers": L, "n_dense": n_dense,
        "n_expert_layers": L - n_dense,
    }


def touched_per_step(sources: dict):
    """Mean over the window of the routed experts one decode step read,
    summed over its expert layers; None where the program has no such
    counter."""
    a = sources["before"]["histograms"].get(TOUCHED)
    b = sources["after"]["histograms"].get(TOUCHED)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    steps = (b["count"] - a["count"]) * sources["engine_up"]["flush_every"]
    return (b["sum"] - a["sum"]) / steps


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    s = shapes(sources["config"])
    touched = touched_per_step(sources) or 0.0   # no counter: count none
    weights = (s["layers"] * s["attn"] + s["n_dense"] * s["dense_mlp"]
               + s["n_expert_layers"] * (s["shared"] + s["router"])
               + s["head"] + touched * s["expert"])
    eng = sources["config"]["engine"]
    max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
    rows = sum(min(max(n, 0.0), max_ctx) for n in ctx_lens)
    return (weights * WEIGHT_BYTES
            + rows * s["row"] * s["layers"] * CACHE_BYTES)


def gmm_decode(sources: dict):
    """The grouped expert product (the megablox ``gmm`` Pallas kernel) in
    ONE decode step, all expert layers: (HBM bytes it must read, floating
    point operations it must do, the trace labels of its decode-shaped
    calls). Bytes: the three matrices of every expert the counter says
    was touched, once each (activations, a few MB, left out: low). Ops:
    2 x rows x in x out for the three products of the live picks the
    counter ``dynamo_moe_tokens_routed`` reports. None where the program
    has no counter."""
    hf = sources["config"]
    touched = touched_per_step(sources)
    a = sources["before"]["histograms"].get("dynamo_moe_tokens_routed")
    b = sources["after"]["histograms"].get("dynamo_moe_tokens_routed")
    if touched is None or a is None or b is None:
        return None
    steps = (b["count"] - a["count"]) * sources["engine_up"]["flush_every"]
    picks = (b["sum"] - a["sum"]) / steps
    H, I_e = hf["hidden_size"], hf["moe_intermediate_size"]
    rows = hf["engine"]["max_decode_slots"] * hf["num_experts_per_tok"]
    labels = (f"gmm bf16[{rows},{I_e}]", f"gmm bf16[{rows},{H}]")
    return (touched * 3 * H * I_e * WEIGHT_BYTES, picks * 3 * 2 * H * I_e,
            labels)
