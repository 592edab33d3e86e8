"""The plain reference of the latent-attention + routed-expert block
(DeepSeek-V3's, as JoyAI-LLM-Flash's config.json parameterises it).

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, the whole sequence at once, EXPANDED attention (K and V of
every head rebuilt from the latent; nothing absorbed), no cache, no
batching, no kernels, no padding, every expert computed for every token
and combined by a [T, E] weight matrix that is zero where the router did
not pick. It imports nothing from the program and takes the engine's own
weight pytree, so both sides compute the same model.

The block (h [T, hidden], RMSNorm eps from the config, no biases):

  attention, every layer:  x = norm(h); c_q = norm(x W_qa); q = c_q W_qb
    -> heads of [q_nope | q_rope]; x W_kva -> [c_kv | k_rope]; c_kv =
    norm(c_kv); c_kv W_kvb -> heads of [k_nope | v]; rotary on q_rope and
    on k_rope (shared by all heads), pairs interleaved as the published
    code means by ``rope_interleave``: values (2i, 2i+1) are de-interleaved
    to (i, i + r/2) and turned by the rotate-half rule; scores
    [q_nope|q_rope].[k_nope|k_rope] / sqrt(nope + rope), causal softmax,
    o = P v, h += concat(o) W_o.
  layers below first_k_dense_replace:  SwiGLU of intermediate_size.
  other layers:  s = sigmoid(x2 W_r) (float32); the top k of s + bias are
    selected; w = s[selected] / (sum + 1e-20) * routed_scaling_factor;
    y = sum_e w_e SwiGLU_e(x2) + SwiGLU_shared(x2).
  final norm, untied head.

Weights (the program's pytree): ``layers`` stacks the attention weights
of all layers ([L, ...]: ln1, ln2, wqa, q_norm, wqb, wkva, kv_norm, wkvb,
wo); ``dense`` stacks the dense MLPs (wg, wu, wd); ``experts`` is a list,
one dict per expert layer (wr, bias, we_g/we_u [E, H, I], we_d [E, I, H],
ws_g, ws_u, ws_d). Expert matrices are converted to float32 ``BLOCK``
experts at a time, so the reference fits beside a 13 GB engine.

Departures from the published model: none in the mathematics. The MTP
module is not part of next-token logits and is absent on both sides.

``control`` (never set by the benchmark; tools/mla_moe_control.py and
the CPU tests set it) computes what a FAULTY program would, to show what
the tolerances below catch: ``"fp8"`` rounds BOTH operands of every
matmul to float8_e4m3fn (3 mantissa bits; weights scaled per output
channel, activations per token): the nearest precision below the
bfloat16 the configuration states. ``"lane_swap"`` is a local fault: ONE
compared position of each prompt answers with its neighbour's state (a
lane that read another lane's row). ``"router_bf16"`` computes the
router's scores in bfloat16 where the configuration keeps float32, and
``"experts_int8"`` rounds every routed expert matrix to 8 bits
(symmetric, per output channel): the two the check cannot see, kept so
that a sharper comparison can be held to them (below).

THE TOLERANCES, their reasons and the chip readings behind them
(TPU v5e, PERF.md section 6, PR 31; 5120 comparisons a run: the top 20
tokens at 256 positions, 32 decode steps after each of four 96-token and
four 600-token prompts; sound = the program as served, bf16 weights and
activations, float32 accumulation, router scores and combine in float32):

  Plain bf16 rounding is not what sets the distance here. THE ROUTER
  does: top 8 of 256 is a discontinuous function of activations that
  carry bf16's error, and the 8th and 9th selection scores of a token lie
  0.005-0.007 apart, so a bf16 program picks ONE expert differently from
  this float32 reference at 20-28 % of (token, layer) pairs (a plain-jnp
  bf16 forward at these widths, CPU; 20-22 % with rounding at the matmul
  inputs ONLY, so no bf16 program avoids them, and one flip moves the
  layers after it). A flip swaps the least-weighted of eight experts for
  its neighbour; with random weights an expert's output is of the
  residual stream's own size, so a position with a flip of its own reads
  ~0.17-0.20 and one without ~0.02. That is the distance between a
  correct bf16 program and this reference, argued INTO the limits, not
  removed by comparing fewer positions. Sound runs read mean 0.095-0.154
  and max 1.57-2.42 (twenty-one weight seeds; PERF.md keeps the later
  ones). How far the mean moves with the WEIGHTS' seed (0.103-0.154 over
  fifteen at the served init) is what 256 positions cannot average away.
  MEAN 0.25 lies between the largest sound reading (0.154: 1.6x of room)
  and the fp8 control's smallest (0.51-0.64 over fourteen seeds: 2x).
  MAX 3.4 lies between the largest sound reading (2.42, the extreme of
  5120 flip-laden draws: 1.4x) and the lane_swap control's smallest
  (4.63-6.17 over fourteen seeds: 1.36x); that control moves the mean by
  0.08 only, so MAX is what catches a fault at one position, and MEAN
  what catches one everywhere (fp8's max, 2.35-3.18, stays under MAX).
  A dropped layer, a wrong rope pairing, a bias leaking into the combine
  weights, a mis-scaled expert or the 2.5 left out move MANY positions by
  a nat and more, and fail both.
  WEIGHTS HELD IN FEWER BITS are not a matter of tolerance: the
  configuration states bfloat16 and ``"quant": null``, what the engine
  holds is what its steps stream (a second copy of 9.7 GB of experts does
  not fit the chip), and ``held_to_stated_weights`` refuses a pytree
  with a leaf that is no float of 16 bits or more, or that is not this
  block's. A program that streams 8-bit experts holds them so, and the
  check stops there.
  WHAT THE CHECK CANNOT SEE, said plainly: a loss of precision WITHIN a
  factor ~1.5 of bf16's own. Router scores in bf16 add flips, 1.16-1.40x
  the same seed's sound mean (0.143-0.192 over eight seeds: inside the
  band the weights' seed alone spans); expert matrices ROUNDED to int8
  but held in bf16 (per channel: 7 bits against bf16's 8; no fewer bytes
  streamed) read 0.99-1.12x. Neither can fail a fixed limit that every
  sound seed passes. With the routing HELD to the program's picks the
  CPU emulation reads 0.0113-0.0122 sound over four weight seeds and
  0.0138-0.0149 with int8 experts: a comparison that is told the
  program's picks (the launcher would have to pass them; PERF.md section
  7) could hold the check to that control; nothing can hold it to the
  bf16 router, whose only effect IS the flips.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHECK_PROMPTS = ((96, 32), (600, 32)) * 4   # (prompt tokens, decode steps)
CHECK_TOL_MAX = 3.4
CHECK_TOL_MEAN = 0.25

BLOCK = 32   # experts converted to float32 at a time


FP8_MAX = 448.0   # largest finite float8_e4m3fn


def to_fp8(a, axis):
    """``a`` rounded to float8_e4m3fn (3 mantissa bits) after scaling
    each slice along ``axis`` to the format's range: the usual 8-bit
    float recipe (per output channel for weights, per token for
    activations)."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX,
                    1e-12)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def to_bf16(a):
    """A float32 array rounded to bfloat16's 8 significant bits."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def mm(x, w, control):
    """x [..., in] @ w [in, out]; under ``control == "fp8"`` both operands
    are rounded to 8-bit floats first."""
    if control == "fp8":
        x, w = to_fp8(x, -1), to_fp8(w, 0)
    return x @ w


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_interleaved(x, positions, theta):
    """x [T, ..., r], pairs (2i, 2i+1): de-interleave, then rotate-half
    (the published ``apply_rotary_pos_emb_interleave``)."""
    r = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r,))
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def swiglu(x, wg, wu, wd, control=None):
    return mm(jax.nn.silu(mm(x, wg, control)) * mm(x, wu, control), wd,
              control)


def attention(hp, lp, h, control=None):
    """One layer's attention over the whole sequence h [T, H]; ``lp`` is
    the layer's weights in float32."""
    nh, nope, rope, vd = hp["heads"], hp["nope"], hp["rope"], hp["v"]
    rank = hp["kv_rank"]
    T = h.shape[0]
    pos = jnp.arange(T)
    x = rms_norm(h, lp["ln1"], hp["eps"])
    c_q = rms_norm(mm(x, lp["wqa"], control), lp["q_norm"], hp["eps"])
    q = mm(c_q, lp["wqb"], control).reshape(T, nh, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kv = mm(x, lp["wkva"], control)
    c_kv = rms_norm(kv[:, :rank], lp["kv_norm"], hp["eps"])
    k_rope = rope_interleaved(kv[:, rank:], pos, hp["theta"])      # [T, r]
    q_rope = rope_interleaved(q_rope, pos, hp["theta"])
    kvb = mm(c_kv, lp["wkvb"], control).reshape(T, nh, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (T, nh, rope))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(nope + rope)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    return h + mm(o.reshape(T, nh * vd), lp["wo"], control)


def combine_weights(hp, x2, wr, bias, control):
    """[T, E] float32: the router's weight of each expert for each token,
    zero where it was not picked."""
    if control == "router_bf16":
        # reduce_precision, not astype: XLA:TPU allows itself excess
        # precision and drops a float32 -> bfloat16 -> float32 round
        # trip inside a fusion (the control then read as sound on the
        # chip, PERF.md section 6), while this op is kept
        s = to_bf16(jax.nn.sigmoid(to_bf16(to_bf16(x2) @ wr)))
    else:
        s = jax.nn.sigmoid(x2 @ wr)
    order = jnp.argsort(-(s + bias), axis=-1)[:, : hp["top_k"]]
    picked = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], order].set(1.0)
    w = s * picked
    if hp["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * hp["scale"]


def round_to_int8(w):
    """Symmetric per-output-channel 8-bit rounding of [E, in, out]."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0,
                    1e-10)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def expert_block(x2, w, wg, wu, wd, control):
    """The contribution of a block of experts: x2 [T, H], w [T, n]."""
    wg, wu, wd = (a.astype(jnp.float32) for a in (wg, wu, wd))
    if control == "experts_int8":
        wg, wu, wd = round_to_int8(wg), round_to_int8(wu), round_to_int8(wd)
    if control == "fp8":
        x2, wg, wu, wd = (to_fp8(x2, -1), to_fp8(wg, 1), to_fp8(wu, 1),
                          to_fp8(wd, 1))
    g = jnp.einsum("th,ehi->eti", x2, wg)
    u = jnp.einsum("th,ehi->eti", x2, wu)
    a = jax.nn.silu(g) * u
    if control == "fp8":
        a = to_fp8(a, -1)
    y = jnp.einsum("eti,eih->eth", a, wd)
    return jnp.einsum("te,eth->th", w, y)


def held_to_stated_weights(params: dict) -> None:
    """The configuration states bfloat16 weights and no quantisation
    (``"quant": null``), and what the engine holds is what its steps
    stream. A pytree that is not this block's, or holds a weight in
    fewer than 16 bits or as integers, is refused here: the reference
    computes the STATED model and never dequantises by a scheme of the
    program's choosing."""
    missing = {"embed", "lm_head", "norm_f", "layers", "dense",
               "experts"} - set(params)
    if missing:
        raise ValueError(
            "the engine's weights are not this block's (no "
            f"{sorted(missing)}): the program did not build the "
            "configuration it was given")
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
    if hf.get("rope_scaling") or hf.get("num_nextn_predict_layers"):
        raise ValueError("the reference has no rope scaling / MTP module")
    if (hf["scoring_func"], hf["n_group"], hf["topk_group"]) != (
            "sigmoid", 1, 1) or not hf.get("rope_interleave", True):
        raise ValueError("the reference has sigmoid scores, one group and "
                         "interleaved rotary pairs only")
    hp = {
        "heads": hf["num_attention_heads"], "nope": hf["qk_nope_head_dim"],
        "rope": hf["qk_rope_head_dim"], "v": hf["v_head_dim"],
        "kv_rank": hf["kv_lora_rank"], "theta": float(hf["rope_theta"]),
        "eps": float(hf["rms_norm_eps"]),
        "top_k": hf["num_experts_per_tok"],
        "norm_topk": bool(hf["norm_topk_prob"]),
        "scale": float(hf["routed_scaling_factor"]),
    }
    n_dense = hf["first_k_dense_replace"]
    held_to_stated_weights(params)
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)

    # jitted only so that each piece is one program instead of dozens of
    # eager ops; the layer index is a value, one program for all layers
    attn = jax.jit(lambda l, layers, h: attention(
        hp, f32(jax.tree.map(lambda a: a[l], layers)), h, control))
    norm2 = jax.jit(lambda l, layers, h: rms_norm(
        h, layers["ln2"][l].astype(jnp.float32), hp["eps"]))
    dense = jax.jit(lambda l, d, x2: swiglu(
        x2, *(d[n][l].astype(jnp.float32) for n in ("wg", "wu", "wd")),
        control))
    weights = jax.jit(lambda x2, wr, b: combine_weights(
        hp, x2, wr.astype(jnp.float32), b.astype(jnp.float32), control))
    shared = jax.jit(lambda x2, ep: swiglu(
        x2, *(ep[n].astype(jnp.float32) for n in ("ws_g", "ws_u", "ws_d")),
        control))

    def block_of(n):
        return jax.jit(lambda x2, w, ep, e0: expert_block(
            x2, jax.lax.dynamic_slice_in_dim(w, e0, n, 1),
            *(jax.lax.dynamic_slice_in_dim(ep[k], e0, n, 0)
              for k in ("we_g", "we_u", "we_d")), control))

    def head(norm_f, w, h, pos):
        if control == "lane_swap":
            # ONE compared position answers with its neighbour's state
            pos = pos.at[pos.shape[0] // 2].add(-1)
        h = rms_norm(h[pos], norm_f.astype(jnp.float32), hp["eps"])
        return jax.nn.log_softmax(mm(h, w.astype(jnp.float32), control), -1)

    blocks: dict = {}
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        for l in range(hf["num_hidden_layers"]):
            h = attn(jnp.int32(l), params["layers"], h)
            x2 = norm2(jnp.int32(l), params["layers"], h)
            if l < n_dense:
                h = h + dense(jnp.int32(l), params["dense"], x2)
                continue
            ep = params["experts"][l - n_dense]
            E = ep["wr"].shape[1]
            n = min(BLOCK, E)
            if E % n:
                raise ValueError(f"{E} experts do not divide into blocks")
            block = blocks.setdefault(n, block_of(n))
            w = weights(x2, ep["wr"], ep["bias"])
            y = shared(x2, ep)
            for e0 in range(0, E, n):
                y = y + block(x2, w, ep, jnp.int32(e0))
            h = h + y
        return np.asarray(jax.jit(head)(
            params["norm_f"], params["lm_head"], h,
            jnp.asarray(positions, jnp.int32)))
