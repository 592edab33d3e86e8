"""HBM bytes ONE chip must move for one decode step of the linear-attention
+ block-sparse-attention hybrid (the configuration says ``"bytes":
"sala"``; ``layer_metrics/step.decode_roofline.py`` calls this). No JAX:
stdlib and the configuration's own numbers.

Counted, per step (``decode_parts``):
  * ``weights``: what every step reads whole: each linear-attention mixer
    (q, k, v, gate and output projections), each sparse layer's (q, k, v,
    gate, output), every layer's SwiGLU MLP, and the head (hidden x V,
    untied; the embedding's row gather and the norms are tiny: left out);
  * ``rows``: the sparse layers' K and V rows a live lane reads: a lane
    whose newest position is at or past ``dense_len`` reads ``topk x
    block_size`` rows a K/V group (the selection), one below it its own
    rows (the dense read);
  * ``keys``: the compressed keys VISIBLE to a live lane past
    ``dense_len`` (one every ``kernel_stride`` positions). The program
    scores every lane's whole compressed region, live lane or not: what
    it reads beyond the visible keys of the live lanes is not counted;
  * ``state``: the LIVE lanes' matrix state, read and written once each
    (float32). The program's step rewrites the state of all lanes + 1,
    live or not: what it moves beyond the live lanes' is not counted.
Low, never high: a share of the roofline computed from it cannot pass
100 % by over-counting.
"""
from __future__ import annotations

WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2
STATE_BYTES = 4      # the matrix state is float32


def shapes(hf: dict) -> dict:
    H, I = hf["hidden_size"], hf["intermediate_size"]
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or H // heads
    kv = hf["num_key_value_heads"] * hd
    lin = hf["lightning_nh"] * hf["lightning_head_dim"]
    kinds = hf["mixer_types"]
    return {
        "linear": 5 * H * lin,                      # q, k, v, gate, out
        "sparse": 3 * H * heads * hd + 2 * H * kv,  # q, gate, out; k, v
        "mlp": 3 * H * I,
        "head": H * hf["vocab_size"],
        "n_lin": sum(k == "lightning-attn" for k in kinds),
        "n_sparse": sum(k == "minicpm4" for k in kinds),
        "layers": len(kinds),
        "kv_row": 2 * kv,                           # values a token a layer
        "kc_row": kv,
        "state_lane": (hf["lightning_nh"] * hf["lightning_head_dim"] ** 2
                       * STATE_BYTES),              # bytes a layer
    }


def decode_parts(sources: dict, ctx_lens: list[float]) -> dict:
    """The step's counted bytes by what they are."""
    hf = sources["config"]
    s, sp = shapes(hf), hf["sparse_config"]
    eng = hf["engine"]
    max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
    chosen = sp["topk"] * sp["block_size"]
    rows = keys = 0.0
    for n in ctx_lens:
        n = min(max(n, 0.0), max_ctx)
        if n - 1 >= sp["dense_len"]:
            rows += min(chosen, n)
            keys += max(n - sp["kernel_size"], 0.0) // sp["kernel_stride"]
        else:
            rows += n
    return {
        "weights": (s["n_lin"] * s["linear"] + s["n_sparse"] * s["sparse"]
                    + s["layers"] * s["mlp"] + s["head"]) * WEIGHT_BYTES,
        "rows": rows * s["kv_row"] * s["n_sparse"] * CACHE_BYTES,
        "keys": keys * s["kc_row"] * s["n_sparse"] * CACHE_BYTES,
        "state": 2 * len(ctx_lens) * s["n_lin"] * s["state_lane"],
    }


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values()))
