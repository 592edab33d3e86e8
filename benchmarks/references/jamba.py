"""The plain reference of the Mamba-1 + attention hybrid (``model_type:
jamba`` as AI21-Jamba2-3B's config.json parameterises it, ``num_experts``
1: every feed-forward part one dense SwiGLU).

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, the whole sequence at once, the selective scan AS WRITTEN (a
``lax.scan`` over time, one position a step, the state [d_inner, d_state]
as published), no cache, no batching, no kernels, no padding. It imports
nothing from the program and takes the engine's own weight pytree, so both
sides compute the same model. One layer at a time, each waited for, so
that only one layer's float32 copy is ever held beside the engine; the
head ``VOCAB_BLOCK`` rows at a time. A block changes no sum.

The block (h [T, hidden]; RMSNorm eps from the config; no biases but the
convolution's and dt's):

  h0 = embed[ids]. For each layer: h += mixer(norm(h)); h +=
  swiglu(norm(h)). Logits: norm(h) @ embed^T (tied). No multipliers, no
  rotary anywhere.
  layer i is attention where i % attn_layer_period == attn_layer_offset,
    else Mamba (``JambaConfig.layers_block_type``).
  attention: q, k, v projections, ``num_attention_heads`` query heads on
    ``num_key_value_heads`` K/V heads of hidden / heads values, causal
    softmax with scale head_dim^-1/2, output projection.
  mamba: [x | z] = u W_in (d_inner each, d_inner = expand x hidden); x =
    silu(causal depthwise conv (width d_conv) + b); [dt_r | B | C] = x W_x
    (dt_rank / d_state / d_state); dt_r, B and C EACH through an RMSNorm
    with a learned gain (the family's dt_layernorm, b_layernorm,
    c_layernorm); dt = softplus(dt_r W_dt + dt_bias) [d_inner]; A =
    -exp(A_log) [d_inner, d_state]; per channel h_t = exp(dt_t A) h_{t-1}
    + dt_t x_t B_t, y_t = h_t C_t + D x_t; out = (y * silu(z)) W_out.

Weights (the program's pytree): ``embed`` [V, H], ``norm_f``, and
``layers``, a list of one dict a layer: ln1, ln2, w_g / w_u / w_d;
attention layers wq, wk, wv, wo; mamba layers w_in [H, 2 d_inner] (x
first), conv_w [d_conv, d_inner] (row d_conv - 1 on the current
position), conv_b, w_x, dt_norm, b_norm, c_norm, w_dt, dt_bias, A_log
(float32, held [d_state, d_inner]: transposed here to the published
[d_inner, d_state]), D, w_out.

Departures from the published model: none in the mathematics.

``control`` (never set by the benchmark; tools/mla_moe_control.py --config
jamba2-3b and the CPU tests set it) computes what a FAULTY program would,
to show what the tolerances below catch. ``boundary`` is the position of
the first chunk boundary the long prompt crosses (the largest prefill
bucket):
  ``"state_bf16"``    the scan's state rounded to bfloat16 after every
      position (reported whichever way it reads: not required to fail. On
      the chip it reads 1.1-1.4 x sound over the check's prompts: this
      check does not hold the state's precision with room to spare, and the
      configuration's ``assumed`` says what does; at toy widths in float32
      it fails by 100 x);
  ``"no_inner_norms"``  the three RMSNorms on dt_r / B / C left out;
  ``"state_zeroed"``  the state dropped at the boundary;
  ``"conv_zeroed"``   the convolution's window dropped at the boundary;
  ``"fp8"``  both operands of every matmul rounded to float8_e4m3fn.

THE TOLERANCES, their reasons and the readings behind them: see the
constants below and PERF.md section 6 (PR 51).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (prompt tokens, decode steps): two prompts over the largest bucket (2048:
# a fresh chunk, then a CONTINUING one that starts from the lane's state,
# window and rows): one whose continuing chunk is 252 tokens, and one that
# ends ON the boundary's first position (2049: its last logits and first
# decode steps stand on what crossed, which is where a dropped window
# shows: at 2300 that control read as sound, 0.0462 against 0.0455, PERF.md
# section 6); one that crosses a live-row block of its 1024 bucket (700:
# two 512-row blocks of the halves, three 256-row scan blocks, the last
# ragged, the fourth never run), one far shorter than its 256 bucket
# (padding must not reach the state); 48 decode steps each cross twelve
# round boundaries of 4
CHECK_PROMPTS = ((2300, 48), (2049, 48), (700, 48), (90, 48))
# Set from the chip's readings (PERF.md section 6, PR 51: nineteen sound
# readings at as many weight seeds, eleven of them at these prompts, the
# controls at three seeds, a prompt at a time at three).
# The distance between a sound bfloat16 program and this float32 reference
# is plain rounding over 28 layers (no router, no near-tie to flip), the
# same at every prompt length: mean 0.0421-0.0469 a prompt and 0.0429-0.0456
# over these four, max 0.172-0.253 (2.5 x the dense 32-layer block's: a
# Mamba-1 layer rounds x, dt_r, B and C to bfloat16 between its four small
# products).
# MEAN judges: 2.0 x the largest sound reading (0.0456), 0.42 x the weakest
# required control's (conv_zeroed 0.213-0.224 over these prompts, 0.765 at
# the 2049 prompt alone; then state_zeroed 0.34-0.38, fp8 0.86-0.93,
# no_inner_norms 2.4-2.6).
# MAX is an extreme of 3840 comparisons, so it gets the wider room above:
# 2.2 x the largest sound reading (0.253), 0.19 x the smallest required
# control's (fp8 2.88-3.03; state_zeroed 5.0-5.4, conv_zeroed 6.0-6.5,
# no_inner_norms 6.5-6.7).
# state_bf16 is NAMED, not required: it reads 1.03-1.10 x sound at the two
# short prompts and 1.23-1.82 x at the two long ones (mean 0.049-0.065 over
# these prompts, max 0.23-0.41), seed by seed, so no limit with room on
# both sides separates it (the configuration's ``assumed`` says what holds
# the state's float32 instead).
CHECK_TOL_MAX = 0.55
CHECK_TOL_MEAN = 0.09
# what tools/mla_moe_control.py runs against this check: each of the first
# has to FAIL it, the last is reported whichever way it reads
CONTROLS_REQUIRED = ("no_inner_norms", "state_zeroed", "conv_zeroed", "fp8")
CONTROLS_NAMED = ("state_bf16",)

VOCAB_BLOCK = 16384

FP8_MAX = 448.0   # largest finite float8_e4m3fn


def to_fp8(a, axis):
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX,
                    1e-12)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def to_bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def mm(x, w, control):
    if control == "fp8":
        x, w = to_fp8(x, -1), to_fp8(w, 0)
    return x @ w


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, wg, wu, wd, control=None):
    return mm(jax.nn.silu(mm(x, wg, control)) * mm(x, wu, control), wd,
              control)


def layer_kinds(hf: dict) -> list[str]:
    """``JambaConfig.layers_block_type``."""
    return ["attention" if i % hf["attn_layer_period"]
            == hf["attn_layer_offset"] else "mamba"
            for i in range(hf["num_hidden_layers"])]


def hyper(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    return {
        "kinds": layer_kinds(hf), "eps": float(hf["rms_norm_eps"]),
        "heads": heads, "kv_heads": hf["num_key_value_heads"],
        "hd": hf["hidden_size"] // heads,
        "inner": hf["mamba_expand"] * hf["hidden_size"],
        "N": hf["mamba_d_state"], "R": hf["mamba_dt_rank"],
        "W": hf["mamba_d_conv"],
    }


def attention(hp, lp, x, control=None):
    """NoPE grouped-query attention over the whole sequence x [T, H]
    (already normed), a K/V head and its query heads at a time."""
    T = x.shape[0]
    nh, kvh, hd = hp["heads"], hp["kv_heads"], hp["hd"]
    rep = nh // kvh
    q = mm(x, lp["wq"], control).reshape(T, kvh, rep, hd)
    k = mm(x, lp["wk"], control).reshape(T, kvh, hd)
    v = mm(x, lp["wv"], control).reshape(T, kvh, hd)
    pos = jnp.arange(T)
    ok = pos[:, None] >= pos[None, :]

    def group(g):
        s = jnp.einsum("trd,sd->rts", q[:, g], k[:, g]) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
        return jnp.einsum("rts,sd->trd", p, v[:, g])

    o = jax.lax.map(group, jnp.arange(kvh))            # [kvh, T, rep, hd]
    o = o.transpose(1, 0, 2, 3).reshape(T, nh * hd)
    return mm(o, lp["wo"], control)


def mamba(hp, lp, u, boundary, control=None):
    """One Mamba-1 mixer over the whole sequence u [T, H] (already
    normed): the recurrence as written, a scan over time on a [d_inner,
    d_state] state."""
    T = u.shape[0]
    I, N, R, W = hp["inner"], hp["N"], hp["R"], hp["W"]
    x, z = jnp.split(mm(u, lp["w_in"], control), 2, -1)
    pos = jnp.arange(T)
    conv = lp["conv_b"]
    for j in range(W):
        # row W - 1 of the weight is on the current position
        back = W - 1 - j
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[:T]
        if control == "conv_zeroed":
            # at or past the boundary, inputs from before it are gone
            lost = (pos >= boundary) & (pos - back < boundary)
            shifted = jnp.where(lost[:, None], 0.0, shifted)
        conv = conv + shifted * lp["conv_w"][j]
    x = jax.nn.silu(conv)
    dt, B, C = jnp.split(mm(x, lp["w_x"], control), [R, R + N], -1)
    if control != "no_inner_norms":
        dt, B, C = (rms_norm(a, lp[g], hp["eps"]) for a, g in (
            (dt, "dt_norm"), (B, "b_norm"), (C, "c_norm")))
    dt = jax.nn.softplus(mm(dt, lp["w_dt"], control) + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"]).T                        # [I, N] as published

    def step(h, inp):
        t, x_t, dt_t, B_t, C_t = inp
        if control == "state_zeroed":
            h = jnp.where(t == boundary, 0.0, h)
        h = (jnp.exp(dt_t[:, None] * A) * h
             + (dt_t * x_t)[:, None] * B_t[None, :])
        if control == "state_bf16":
            h = to_bf16(h)
        return h, h @ C_t + lp["D"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((I, N), jnp.float32),
                        (pos, x, dt, B, C))
    return mm(y * jax.nn.silu(z), lp["w_out"], control)


def held_to_stated_weights(params: dict) -> None:
    """The configuration states bfloat16 weights, unquantised: a pytree
    that is not this block's, or holds a weight in fewer than 16 bits or
    as integers, is refused (the reference computes the STATED model)."""
    missing = {"embed", "norm_f", "layers"} - set(params)
    if missing or not isinstance(params["layers"], (list, tuple)):
        raise ValueError(
            "the engine's weights are not this block's (no "
            f"{sorted(missing) or 'list of layers'}): the program did not "
            "build the configuration it was given")
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        dt = jnp.dtype(leaf.dtype)
        if not jnp.issubdtype(dt, jnp.floating) or dt.itemsize < 2:
            raise ValueError(
                f"weight {jax.tree_util.keystr(path)} is held as {dt}: "
                "the configuration states bfloat16 weights, unquantised")


def logprobs(hf: dict, params: dict, tokens: list[int], positions: list[int],
             control: str | None = None) -> np.ndarray:
    """log-softmax over the vocabulary, [len(positions), V], of the next
    token after each of ``positions`` of ``tokens``."""
    refused = {
        "num_experts": hf["num_experts"] != 1,
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": not hf.get("tie_word_embeddings", True),
        "sliding_window": hf.get("sliding_window") is not None,
        "biases": bool(hf["mamba_proj_bias"]) or not hf["mamba_conv_bias"],
    }
    if any(refused.values()):
        raise ValueError("the reference does not build "
                         f"{sorted(k for k, v in refused.items() if v)}")
    held_to_stated_weights(params)
    hp = hyper(hf)
    boundary = max(hf["engine"]["prefill_buckets"])

    # jitted only so that each piece is one program instead of dozens of
    # eager ops; one program a layer KIND
    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(lp, h, kind):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        x = rms_norm(h, lp["ln1"], hp["eps"])
        h = h + (attention(hp, lp, x, control) if kind == "attention"
                 else mamba(hp, lp, x, boundary, control))
        x = rms_norm(h, lp["ln2"], hp["eps"])
        return h + swiglu(x, lp["w_g"], lp["w_u"], lp["w_d"], control)

    V = params["embed"].shape[0]
    vb = min(VOCAB_BLOCK, V)

    @jax.jit
    def head_block(norm_f, embed, h, pos, v0):
        h = rms_norm(h[pos], norm_f.astype(jnp.float32), hp["eps"])
        rows = jax.lax.dynamic_slice_in_dim(embed, v0, vb, 0)
        return mm(h, rows.astype(jnp.float32).T, control)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        for kind, lp in zip(hp["kinds"], params["layers"]):
            # waited for: the next layer's float32 copy is made only
            # after this one's is dropped
            h = jax.block_until_ready(layer(lp, h, kind=kind))
        pos = jnp.asarray(positions, jnp.int32)
        blocks = []
        for v0 in range(0, V, vb):
            # the last block slides back (dynamic_slice clamps): cut what
            # it repeats
            got = np.asarray(head_block(params["norm_f"], params["embed"],
                                        h, pos, jnp.int32(v0)))
            blocks.append(got[:, max(0, v0 + vb - V):])
        logits = np.concatenate(blocks, -1).astype(np.float64)
    logits -= logits.max(-1, keepdims=True)
    return (logits - np.log(np.exp(logits).sum(-1, keepdims=True))).astype(
        np.float32)
