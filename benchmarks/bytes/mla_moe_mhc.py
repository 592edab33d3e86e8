"""HBM bytes ONE chip must read for one decode step of the
latent-attention + routed-expert block with a residual of n mixed
streams (the configuration says ``"bytes": "mla_moe_mhc"``;
``layer_metrics/step.decode_roofline.py`` calls this). No JAX: stdlib,
the configuration's own numbers, and the count of the block without
streams beside this file (``bytes/mla_moe.py``, loaded by path).

Counted, per step: everything ``bytes/mla_moe.py`` counts (the weights
every step reads whole; the routed experts the program's counter SAYS
were touched; the values of each lane's OWN live latent rows, exact
lengths: not the longest lane's, which is what the XLA decode attention
reads for every lane, ``step.decode_attn_live_share``) plus the
hyper-connection weights: two ``phi [n hidden, n + n + n n]`` a layer in
the weights' dtype (the 3 gains and 24 offsets a sublayer are left out).
The streams themselves (16 lanes x 4 x 3584 values, read and written a
few times a sublayer) are activations, a few MB a step: left out. Low,
never high.
"""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_bytes_mla_moe",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mla_moe.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)


def hc_weights(hf: dict) -> int:
    n = hf["hc_mult"]
    return hf["num_hidden_layers"] * 2 * n * hf["hidden_size"] * (
        2 * n + n * n)


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return (plain.decode_bytes_per_step(sources, ctx_lens)
            + hc_weights(sources["config"]) * plain.WEIGHT_BYTES)


# The grouped expert product in ONE decode step is the block's own,
# unchanged by the streams (decode calls are ``gmm bf16[slots x picks,
# width]`` = 64 rows here, prefill's 16384)
gmm_decode = plain.gmm_decode
