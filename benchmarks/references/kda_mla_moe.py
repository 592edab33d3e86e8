"""The plain reference of the delta-rule linear attention (KDA) + latent
attention (MLA) hybrid with grouped sigmoid routing (``ling3_flash``: the
keys of Ling-3.0-flash's config.json, with the chip's share of a stated
deployment under ``expert_share``).

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, the whole sequence at once, the delta rule's recurrence AS
WRITTEN (a ``lax.scan`` over time, one position a step; no chunks, no
UT transform), the short convolutions as sums over their taps, the latent
attention EXPANDED per head under one whole causal softmax (no absorbed
form, no cache), the router as written, every held expert computed for
every token and weighted by what the router gave it; no batching, no
kernels, no padding. It imports nothing from the program and takes the
engine's own weight pytree, so both sides compute the same model. Large
pieces are computed in blocks (experts ``EXPERT_BLOCK`` at a time,
attention ``HEAD_BLOCK`` heads at a time): a block changes no sum.

The stack (h [T, hidden]; RMSNorm eps ``rms_norm_eps``; no biases):

  h = embed[ids]; for each layer h += mixer(norm(h)); h += ffn(norm(h));
  logits = norm(h) W_head (untied, over the held slice of the vocabulary:
  the configuration's ``vocab_size`` IS the slice).
  Layer l is ``latent`` if (l + 1) % layer_group_size == 0, else ``kda``.
  ``kda``, heads of D = head_dim keys and values:
    [q | k | v] = x W_qkv; each stream through its causal depthwise
    convolution of short_conv_kernel_size taps (tap W - 1 on the current
    position, zeros before the sequence), then SiLU; q and k divided by
    their L2 norm over D (sqrt(sum + 1e-6)), q x D^-1/2;
    g = kda_lower_bound x sigmoid(exp(A_log_h) (x W_f + dt_bias)) a
    channel, a = exp(g); [b | z] = x W_bg, b = sigmoid(b) a head;
    S' = Diag(a_t) S_{t-1}; S_t = S' + b_t k_t (v_t - S'^T k_t)^T;
    o_t = S_t^T q_t;  y = RMSNorm_D(o_t; gain) x sigmoid(z) a head; W_o.
  ``latent``: q = x W_q [T, heads, nope + rope]; [c | k_rope] = x W_kva,
    c = RMSNorm(c); rotary on q's rope part and on k_rope (interleaved
    pairs, theta); K_h = [c W_kvb^K_h | k_rope], V_h = c W_kvb^V_h; causal
    softmax at scale (nope + rope)^-1/2; W_o.
  ffn: layers below first_k_dense_replace one SwiGLU (W_g, W_u, W_d). The
    others: s = sigmoid(x W_r) over the PUBLISHED num_experts; c = s +
    bias; n_group contiguous groups, a group's score the sum of its two
    best c; the topk_group best groups stay, the rest are masked out; the
    num_experts_per_tok best c among what stays are picked (the lowest
    index first among equals); weights s at the picks / their sum x
    routed_scaling_factor. This chip holds experts ``index x held`` up to
    ``(index + 1) x held``: a pick held elsewhere adds nothing. The shared
    expert is added ungated.

What the catalog's copy of the config leaves open is the configuration's
``assumed`` (A1-A8): the safe-gate form of g, no rotary in the KDA
layers, L2 q/k norm in KDA and the latent's own RMSNorm in MLA, the
head-wise gate on KDA only, b without a factor 2, interleaved rope pairs
and no mscale, the file's own model_type, the group score.

Weights (the program's pytree): ``embed`` [V, H], ``head`` [H, V],
``norm_f``, and ``layers``, one dict a layer: ln1, ln2; kda: w_qkv [H, 3
heads D], conv_w [W, 3 heads D], w_f [H, heads D], A_log [heads], dt_bias
[heads D], w_bg [H, 2 heads], o_norm [D], wo; latent: wq, wkva, kv_norm,
wkvb, wo; dense: w_g, w_u, w_d; experts: wr [H, E], bias [E], we_g, we_u
[held, H, I], we_d [held, I, H], ws_g, ws_u, ws_d.

``control`` (never set by the benchmark; tools/mla_moe_control.py
--config ling3-flash-ep8-d12 and the CPU tests set it) computes what a
FAULTY program would, to show what the tolerances below catch.
``boundary`` is the first chunk boundary a long prompt crosses (the
largest prefill bucket):
  ``"fp8"``  both operands of every weight matmul rounded to
      float8_e4m3fn (the nearest precision under the stated bfloat16);
  ``"delta_off"``  the correction left out: S = S' + b k v^T;
  ``"gate_per_head"``  one decay a head (the channels' mean g);
  ``"state_zeroed"``  the KDA state dropped at the boundary;
  ``"conv_zeroed"``  the three convolution windows dropped there;
  ``"no_group_mask"``  top k over all experts, no group kept or dropped;
  ``"share_index_1"``  the held experts taken for the next chip's;
  ``"state_bf16"``  the KDA state rounded to bfloat16 after every step;
  ``"router_bf16"``  the router's inputs and its scores in bfloat16.

THE TOLERANCES, their reasons and the readings behind them: the constants
below and PERF.md section 6 (PR 47).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (prompt tokens, decode steps). One of 4100: a fresh chunk of 4096 and a
# continuing one of 4, so that the KDA state, the three convolution
# windows (the continuing chunk's first three positions read them) and the
# latent rows all cross a chunk boundary, and the first decode steps stand
# on what crossed. One short with 72 decode steps (18 rounds of 4): there
# the step kernel, not the chunked scan, carries the state for most of the
# sequence.
CHECK_PROMPTS = ((4100, 16), (200, 72))
# Set from the chip's readings at the published widths (my chip runs, PR
# 47; PERF.md section 6). SOUND, eleven weight seeds: mean 0.108-0.145, max
# 0.71-1.11. Almost all of it is the ROUTER: the same stack with twelve
# dense layers and no experts reads mean 0.028-0.029 / max 0.11-0.13 (the
# bfloat16 roundings of twelve layers), and with experts a near-tied pick or
# a near-tied fourth group that flips under those roundings swaps up to a
# group's worth of this chip's routed sum at that position (ten expert
# layers, ~10 % of adjacent score pairs within the rounding), as in the two
# latent cells. fp8 (the nearest precision under the stated bfloat16) reads
# mean 0.499 / max 2.36: it fails by the MEAN, which is the sharp test
# (0.2: 1.4 x the largest sound seed, 5 sd above their mean, 0.4 x fp8).
# The required controls read mean 0.24 (group mask off), 0.27 (convolution
# windows dropped at the chunk boundary), 0.55 (state dropped there), 0.64
# (the next chip's experts), 1.5 (one decay a head), 2.3 (no delta term).
# MAX is a guard against a local fault only (3.0: the extreme of 1760
# flip-laden comparisons moves from seed to seed, and the long-document
# latent cell met one sound seed in ~35 at twice its usual extreme); the
# faults above that touch few positions read 2.3-5.9. What passes, and is
# therefore NOT held by this check: the state rounded to bfloat16 (0.162)
# and the router in bfloat16 (0.141); tests/test_kda.py holds both at toy
# widths in float32, and the state leaves' dtype at the published ones.
CHECK_TOL_MAX = 3.0
CHECK_TOL_MEAN = 0.2
CONTROLS_REQUIRED = ("fp8", "delta_off", "gate_per_head", "state_zeroed",
                     "conv_zeroed", "no_group_mask", "share_index_1")
CONTROLS_NAMED = ("state_bf16", "router_bf16")

EXPERT_BLOCK = 4    # held experts at a time
HEAD_BLOCK = 4      # latent-attention heads at a time
L2_EPS = 1e-6
FP8_MAX = 448.0     # largest finite float8_e4m3fn


def to_fp8(a, axis):
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX,
                    1e-12)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def to_bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def mm(x, w, control):
    w = w.astype(jnp.float32)
    if control == "fp8":
        x, w = to_fp8(x, -1), to_fp8(w, 0)
    return x @ w


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def hyper(hf: dict) -> dict:
    E = hf["num_experts"]
    share = hf.get("expert_share") or {"of": 1, "index": 0}
    held = hf.get("num_local_experts", E)
    L, period = hf["num_hidden_layers"], hf["layer_group_size"]
    return {
        "eps": hf["rms_norm_eps"], "heads": hf["num_attention_heads"],
        "D": hf["head_dim"], "W": hf["short_conv_kernel_size"],
        "bound": float(hf["kda_lower_bound"]),
        "kinds": ["latent" if (l + 1) % period == 0 else "kda"
                  for l in range(L)],
        "n_dense": hf["first_k_dense_replace"],
        "kv_rank": hf["kv_lora_rank"], "nope": hf["qk_nope_head_dim"],
        "rope": hf["qk_rope_head_dim"], "v": hf["v_head_dim"],
        "theta": float(hf["rope_theta"]),
        "E": E, "held": held, "first": share["index"] * held,
        "top_k": hf["num_experts_per_tok"], "groups": hf["n_group"],
        "kept": hf["topk_group"], "scale": hf["routed_scaling_factor"],
    }


def kda(hp, lp, x, boundary, control=None):
    """One delta-rule mixer over the whole sequence x [T, H] (already
    normed): the recurrence as written, a scan over time."""
    T = x.shape[0]
    nh, D, W = hp["heads"], hp["D"], hp["W"]
    pos = jnp.arange(T)
    qkv = mm(x, lp["w_qkv"], control)
    conv = jnp.zeros_like(qkv)
    for j in range(W):
        back = W - 1 - j     # tap W - 1 is on the current position
        shifted = jnp.pad(qkv, ((back, 0), (0, 0)))[:T]
        if control == "conv_zeroed":
            # at or past the boundary, inputs from before it are gone
            lost = (pos >= boundary) & (pos - back < boundary)
            shifted = jnp.where(lost[:, None], 0.0, shifted)
        conv = conv + shifted * lp["conv_w"][j].astype(jnp.float32)
    q, k, v = (a.reshape(T, nh, D)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q, k = l2_norm(q) * D ** -0.5, l2_norm(k)
    f = (mm(x, lp["w_f"], control) + lp["dt_bias"]).reshape(T, nh, D)
    g = hp["bound"] * jax.nn.sigmoid(jnp.exp(lp["A_log"])[:, None] * f)
    if control == "gate_per_head":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    bz = mm(x, lp["w_bg"], control)
    b, z = jax.nn.sigmoid(bz[:, :nh]), bz[:, nh:]

    def step(S, inp):
        t, q_t, k_t, v_t, g_t, b_t = inp
        if control == "state_zeroed":
            S = jnp.where(t == boundary, 0.0, S)
        S = jnp.exp(g_t)[:, :, None] * S                  # [nh, D_k, D_v]
        seen = jnp.einsum("hkv,hk->hv", S, k_t)
        if control == "delta_off":
            seen = jnp.zeros_like(seen)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None]
        if control == "state_bf16":
            S = to_bf16(S)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((nh, D, D), jnp.float32),
                        (pos, q, k, v, g, b))
    y = rms_norm(o, lp["o_norm"].astype(jnp.float32), hp["eps"])
    y = y * jax.nn.sigmoid(z)[:, :, None]
    return mm(y.reshape(T, nh * D), lp["wo"], control)


def rope_pairs(x, pos, theta):
    """Interleaved rotary over the last axis: values (2i, 2i + 1) are one
    pair, turned by pos x theta^(-2i / width)."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def latent(hp, lp, x, control=None):
    """One latent-attention mixer over x [T, H] (already normed)."""
    T = x.shape[0]
    nh, nope, rope, vd, rank = (hp["heads"], hp["nope"], hp["rope"],
                                hp["v"], hp["kv_rank"])
    pos = jnp.arange(T)
    q = mm(x, lp["wq"], control).reshape(T, nh, nope + rope)
    kv = mm(x, lp["wkva"], control)
    c = rms_norm(kv[:, :rank], lp["kv_norm"].astype(jnp.float32), hp["eps"])
    k_rope = rope_pairs(kv[:, rank:], pos, hp["theta"])
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], pos, hp["theta"])], -1)
    kvb = mm(c, lp["wkvb"], control).reshape(T, nh, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope[:, None], (T, nh, rope))],
        -1)
    v = kvb[..., nope:]
    ok = pos[:, None] >= pos[None, :]

    def heads(i):
        at = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, i * HEAD_BLOCK, HEAD_BLOCK, 1)
        s = jnp.einsum("thd,shd->hts", at(q), at(k)) * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", p, at(v))

    hb = min(HEAD_BLOCK, nh)
    assert nh % hb == 0
    o = jax.lax.map(heads, jnp.arange(nh // hb))        # [nh/hb, T, hb, v]
    o = o.transpose(1, 0, 2, 3).reshape(T, nh * vd)
    return mm(o, lp["wo"], control)


def top_indices(c, k):
    """The k largest of the last axis, the lowest index first among
    equals."""
    return jnp.argsort(-c, axis=-1, stable=True)[..., :k]


def combine_weights(hp, lp, x2, control=None):
    """[T, E] float32 over ALL the published experts: the router's weight
    where it picked, zero elsewhere."""
    wr = lp["wr"].astype(jnp.float32)
    if control == "router_bf16":
        s = to_bf16(jax.nn.sigmoid(to_bf16(to_bf16(x2) @ to_bf16(wr))))
    else:
        s = jax.nn.sigmoid(x2 @ wr)
    c = s + lp["bias"]
    T, G = c.shape[0], hp["groups"]
    if control != "no_group_mask":
        per = c.reshape(T, G, -1)
        best2 = jnp.take_along_axis(per, top_indices(per, 2), -1).sum(-1)
        keep = top_indices(best2, hp["kept"])                  # [T, kept]
        kept = jnp.zeros((T, G), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        c = jnp.where(jnp.repeat(kept, hp["E"] // G, axis=1), c, -jnp.inf)
    sel = top_indices(c, hp["top_k"])
    w = jnp.take_along_axis(s, sel, -1)
    w = w / w.sum(-1, keepdims=True) * hp["scale"]
    return jnp.zeros_like(s).at[jnp.arange(T)[:, None], sel].set(w)


def expert_block(x2, w, wg, wu, wd, control):
    """The contribution of a block of experts: x2 [T, H], w [T, n]."""
    wg, wu, wd = (a.astype(jnp.float32) for a in (wg, wu, wd))
    if control == "fp8":
        x2, wg, wu, wd = (to_fp8(x2, -1), to_fp8(wg, 1), to_fp8(wu, 1),
                          to_fp8(wd, 1))
    a = jax.nn.silu(jnp.einsum("th,ehi->eti", x2, wg)) * jnp.einsum(
        "th,ehi->eti", x2, wu)
    if control == "fp8":
        a = to_fp8(a, -1)
    return jnp.einsum("te,eth->th", w, jnp.einsum("eti,eih->eth", a, wd))


def swiglu(x, wg, wu, wd, control=None):
    a = jax.nn.silu(mm(x, wg, control)) * mm(x, wu, control)
    return mm(a, wd, control)


def routed(hp, lp, x2, control=None, first=None):
    """This chip's part of the routed experts' sum: the held experts are
    experts ``first`` .. ``first + held`` of the router's."""
    first = hp["first"] if first is None else first
    if control == "share_index_1":
        first = (first + hp["held"]) % hp["E"]
    w = combine_weights(hp, lp, x2, control)
    held = lp["we_g"].shape[0]
    nb = min(EXPERT_BLOCK, held)
    assert held % nb == 0

    def block(i, y):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, i * nb, nb, 0)
        wi = jax.lax.dynamic_slice_in_dim(w, first + i * nb, nb, 1)
        return y + expert_block(x2, wi, cut(lp["we_g"]), cut(lp["we_u"]),
                                cut(lp["we_d"]), control)

    return jax.lax.fori_loop(0, held // nb, block, jnp.zeros_like(x2))


def held_to_stated_weights(params: dict) -> None:
    """The configuration states bfloat16 weights, unquantised: a pytree
    that is not this block's, or holds a weight in fewer than 16 bits or
    as integers, is refused (the reference computes the STATED model)."""
    missing = {"embed", "head", "norm_f", "layers"} - set(params)
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
    """log-softmax over the held slice of the vocabulary, [len(positions),
    V], of the next token after each of ``positions`` of ``tokens``."""
    refused = {
        "q_lora_rank": hf["q_lora_rank"] is not None,
        "score_function": hf["score_function"] != "sigmoid",
        "kda_safe_gate / linear_silu / use_qk_norm off": not (
            hf["kda_safe_gate"] and hf["linear_silu"] and hf["use_qk_norm"]),
        "gate granularity / group_norm_size": (
            hf["gated_attention_proj_granularity_type"] != "head_wise"
            or hf["group_norm_size"] != 1),
        "use_mla_nope / use_nGPT / value_norm / up_proj_norm / "
        "scale_router_input / use_kda_lora": any(
            hf[k] for k in ("use_mla_nope", "use_nGPT", "value_norm",
                            "up_proj_norm", "scale_router_input",
                            "use_kda_lora")),
        "a non-zero clamp": any(
            x != 0 for k in ("expert_swiglu_limit_list",
                             "share_expert_swiglu_limit_list")
            for x in hf[k]),
    }
    if any(refused.values()):
        raise ValueError("the reference does not build "
                         f"{sorted(k for k, v in refused.items() if v)}")
    held_to_stated_weights(params)
    hp = hyper(hf)
    boundary = max(hf["engine"]["prefill_buckets"])
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    small = lambda lp: {k: v for k, v in lp.items()  # noqa: E731
                        if v.ndim == 1}

    # jitted only so that each piece is one program instead of dozens of
    # eager ops; one program a layer KIND (weights stay as they are held
    # and are widened one product at a time: mm)
    @functools.partial(jax.jit, static_argnames=("kind",))
    def mixer(lp, h, kind):
        norms = f32(small(lp))
        x = rms_norm(h, norms["ln1"], hp["eps"])
        lp = dict(lp, **norms)
        mix = (latent(hp, lp, x, control) if kind == "latent"
               else kda(hp, lp, x, boundary, control))
        h = h + mix
        return h, rms_norm(h, norms["ln2"], hp["eps"])

    @jax.jit
    def feed_forward(lp, h, x2):
        if "wr" not in lp:
            return h + swiglu(x2, lp["w_g"], lp["w_u"], lp["w_d"], control)
        lp = dict(lp, bias=lp["bias"].astype(jnp.float32))
        return (h + routed(hp, lp, x2, control)
                + swiglu(x2, lp["ws_g"], lp["ws_u"], lp["ws_d"], control))

    @jax.jit
    def head(norm_f, w, h, pos):
        return mm(rms_norm(h[pos], norm_f.astype(jnp.float32), hp["eps"]),
                  w, control)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        for kind, lp in zip(hp["kinds"], params["layers"]):
            h, x2 = mixer(lp, h, kind=kind)
            # one layer at a time ON THE DEVICE too: JAX enqueues ahead
            # and gives every queued program its buffers at once
            h = feed_forward(lp, h, x2).block_until_ready()
        logits = np.asarray(head(
            params["norm_f"], params["head"], h,
            jnp.asarray(positions, jnp.int32))).astype(np.float64)
    logits -= logits.max(-1, keepdims=True)
    return (logits - np.log(np.exp(logits).sum(-1, keepdims=True))).astype(
        np.float32)
