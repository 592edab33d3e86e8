"""HBM bytes ONE chip must read for one decode step of the LOOPED dense
decoder (the configuration says ``"bytes": "looped"``;
``layer_metrics/step.decode_roofline.py`` calls this). No JAX: stdlib and
the configuration's own numbers.

Counted, per step (``decode_parts``):
  * ``stack``: the layer stack's matrices (wq, wk, wv, wo, wg, wu, wd of
    every weight layer), read once a LOOP STEP: the same bytes
    ``total_ut_steps`` times a token, whatever the batch (they do not fit
    any cache between passes: 4.93 GB at the published widths);
  * ``head``: the output head, once (the embedding is a row gather, the
    norms' gains and the gate are kilobytes: left out);
  * ``rows``: the live lanes' K and V rows over EVERY cache plane (a plane
    a (step, layer): ``total_ut_steps * num_hidden_layers``), in the whole
    512-row chunks the decode kernel fetches (``peaks.DECODE_KERNEL_CHUNK``)
    as ``peaks.decode_bytes_per_step`` counts the dense cells'.
The write ring, the logits row and the activations are left out. Low, never
high: a share of the roofline computed from it cannot pass 100 % by
over-counting.

No ``full_decode_bytes``: the looped decoder's decode attention is the
dense decoder's call, which a trace shows as ``flash_decode_attention``,
not under the name ``kernel.full_gqa_decode_roofline`` reads.
"""
from __future__ import annotations

WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2
DECODE_KERNEL_CHUNK = 512   # peaks.DECODE_KERNEL_CHUNK


def shapes(hf: dict) -> dict:
    H, heads = hf["hidden_size"], hf["num_attention_heads"]
    hd = hf.get("head_dim") or H // heads
    kvh = hf.get("num_key_value_heads", heads)
    layers, steps = hf["num_hidden_layers"], int(hf.get("total_ut_steps", 1))
    return {
        "layer": (2 * H * heads * hd + 2 * H * kvh * hd
                  + 3 * H * hf["intermediate_size"]) * WEIGHT_BYTES,
        "head": H * hf["vocab_size"] * WEIGHT_BYTES,
        "layers": layers, "steps": steps, "planes": layers * steps,
        # K and V of one position over every plane
        "kv_token": 2 * layers * steps * kvh * hd * CACHE_BYTES,
    }


def decode_parts(sources: dict, ctx_lens: list[float]) -> dict:
    """The step's counted bytes by what they are."""
    hf = sources["config"]
    s, eng = shapes(hf), hf["engine"]
    max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
    rows = sum(-(-min(max(n, 0.0), max_ctx) // DECODE_KERNEL_CHUNK)
               * DECODE_KERNEL_CHUNK for n in ctx_lens)
    return {"stack": s["steps"] * s["layers"] * s["layer"],
            "head": s["head"],
            "rows": rows * s["kv_token"]}


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values())
                 ) / int(sources["config"].get("tp", 1))
