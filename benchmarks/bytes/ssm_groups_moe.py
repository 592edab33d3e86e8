"""HBM bytes ONE chip must move for one decode step of the ONE-PART hybrid
stack (the configuration says ``"bytes": "ssm_groups_moe"``;
``layer_metrics/step.decode_roofline.py`` calls this). No JAX: stdlib and
the configuration's own numbers.

Counted, per step (``decode_parts``):
  * ``weights``: what every step reads whole: each Mamba mixer (input and
    output projections over inner + 2 G N + heads columns, convolution,
    gated norm), each attention layer's four projections, every expert
    layer's shared expert (two matrices) and router, and the untied head
    (hidden x the vocabulary held; the embedding row gather and the norms
    are tiny: left out);
  * ``experts``: the HELD routed experts the program's counter SAYS were
    touched (``dynamo_moe_experts_touched``, mean per step over the
    window) x one expert's TWO matrices at the PUBLISHED width. The
    program stores an expert at the next whole number of 128-lane columns
    (1856 -> 1920, zeros beyond): those 64 columns and rows are bytes it
    moves and nobody asked for, so they are NOT counted;
  * ``rows``: the attention layers' K and V rows of the live lanes, their
    exact lengths;
  * ``state``: the LIVE lanes' recurrent state, read and written once
    each (float32 SSM state + the convolution's window over inner + 2 G N
    channels).
Low, never high: a share of the roofline computed from it cannot pass
100 % by over-counting.
"""
from __future__ import annotations

TOUCHED = "dynamo_moe_experts_touched"
ROUTED = "dynamo_moe_tokens_routed"
STEPPED = "dynamo_ssm_state_rows_stepped"
WEIGHT_BYTES = 2     # bf16, as the configuration states
CACHE_BYTES = 2
STATE_BYTES = 4      # the SSM state is float32


def stored_width(n: int) -> int:
    """The width the program stores an expert's matrices at (its
    ``moe.stored_width``, restated: no JAX here): what the kernel's label
    in a trace shows, never what is counted."""
    return n if n <= 128 or n % 128 == 0 else -(-n // 128) * 128


def shapes(hf: dict) -> dict:
    H = hf["hidden_size"]
    nh, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    G, N, W = hf["n_groups"], hf["ssm_state_size"], hf["conv_kernel"]
    inner = nh * P
    conv = inner + 2 * G * N
    heads, hd = hf["num_attention_heads"], hf["head_dim"]
    kv = hf["num_key_value_heads"] * hd
    pattern = hf["hybrid_override_pattern"]
    return {
        "mamba": H * (inner + conv + nh) + conv * (W + 1) + inner + inner * H,
        "attn": 2 * H * heads * hd + 2 * H * kv,
        "expert": 2 * H * hf["moe_intermediate_size"],
        "shared": 2 * H * hf["moe_shared_expert_intermediate_size"],
        "mlp": 2 * H * hf["intermediate_size"],
        "router": H * (hf.get("expert_share") or {}).get(
            "published_experts", hf["n_routed_experts"]),
        "head": H * hf["vocab_size"],
        "n_ssm": pattern.count("M"), "n_attn": pattern.count("*"),
        "n_experts": pattern.count("E"), "n_mlp": pattern.count("-"),
        "kv_row": 2 * kv,                               # values a token
        "ssm_state": nh * P * N * STATE_BYTES,          # bytes a layer a lane
        "state_lane": (nh * P * N * STATE_BYTES
                       + (W - 1) * conv * CACHE_BYTES),
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
        "weights": (s["n_ssm"] * s["mamba"] + s["n_attn"] * s["attn"]
                    + s["n_experts"] * (s["shared"] + s["router"])
                    + s["n_mlp"] * s["mlp"] + s["head"]) * WEIGHT_BYTES,
        "experts": touched * s["expert"] * WEIGHT_BYTES,
        "rows": rows * s["kv_row"] * s["n_attn"] * CACHE_BYTES,
        "state": 2 * len(ctx_lens) * s["n_ssm"] * s["state_lane"],
    }


def decode_bytes_per_step(sources: dict, ctx_lens: list[float]) -> float:
    return float(sum(decode_parts(sources, ctx_lens).values()))


def gmm_decode(sources: dict):
    """The grouped expert product (the megablox ``gmm`` Pallas kernel) in
    ONE decode step, all expert layers: (HBM bytes it must read, floating
    point operations it must do, the trace labels of its decode-shaped
    calls). TWO products an expert (no gate matrix). Bytes: the two
    matrices of every HELD expert the counter says was touched, once each,
    at the PUBLISHED width. Ops: 2 x in x out for the two products of the
    picks that landed on a held expert (``dynamo_moe_tokens_routed``
    counts those). The labels carry the STORED width (the first product's
    output). None where the program has no counter."""
    hf = sources["config"]
    touched, picks = _per_step(sources, TOUCHED), _per_step(sources, ROUTED)
    if touched is None or picks is None:
        return None
    H, I_e = hf["hidden_size"], hf["moe_intermediate_size"]
    rows = hf["engine"]["max_decode_slots"] * hf["num_experts_per_tok"]
    labels = (f"gmm bf16[{rows},{stored_width(I_e)}]", f"gmm bf16[{rows},{H}]")
    return (touched * 2 * H * I_e * WEIGHT_BYTES, picks * 2 * 2 * H * I_e,
            labels)


def m2_step_bytes(hf: dict, states: float) -> float:
    """The bytes the Mamba-2 decode step kernel (``m2_step``) must move for
    ``states`` per-lane states (layers counted in them): each [heads, head,
    d_state] float32 state read once and written once. Its row operands
    (dt x, B, C, the decays) and its output, under a hundredth of that,
    are left out: low, never high."""
    return 2.0 * states * shapes(hf)["ssm_state"]


def m2_states_stepped(sources: dict):
    """Per-lane states the program's counter says the window's decode
    rounds stepped, all Mamba-2 layers, and the rounds: (states, rounds),
    or None without the counter."""
    return _delta(sources, STEPPED)
