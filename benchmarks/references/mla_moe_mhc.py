"""The plain reference of the latent-attention + routed-expert block with
a residual of n mixed streams and YaRN rotary (Xing4.0-29B-A4B's
config.json: ``model_type`` ``xing4_0``).

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, the whole sequence at once, EXPANDED attention, no cache,
no kernels, nothing of the program; it takes the engine's own weight
pytree. Attention, the dense MLP, the router, the experts and the shared
expert are the DeepSeek-V3 block's, and their plain parts (``mm``,
``rms_norm``, ``swiglu``, ``combine_weights``, ``expert_block``,
``held_to_stated_weights``, the 8-bit roundings) are imported from
``references/mla_moe.py``, benchmark code beside this file. Written here:

  STREAMS (manifold-constrained hyper-connections, arXiv:2512.24880, on
  Hyper-Connections arXiv:2409.19606), in the textbook ``[T, n, n]``
  form. ``X_0 = [e; e; e; e]`` (n = ``hc_mult`` copies of the embedding
  row). For each of a layer's two sublayers F (attention after ln1, MLP
  or experts after ln2), with its own (phi, a, b):
      x = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)        [n C]
      t = x phi                                               [n + n + n n]
      H_pre  = sigmoid(a_pre t_pre + b_pre)                   [n]
      H_post = 2 sigmoid(a_post t_post + b_post)              [n]
      H_res  = SK(clip(a_res mat(t_res) + b_res, lo, hi))     [n, n]
      SK: M = exp(.), then ``hc_sinkhorn_iters`` times
          M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
      u = H_pre X;   X' = H_res X + H_post^T F(u)
  HEAD: ``logits = lm_head(rms(sum_n X_L[n]; norm_f))``.
  YARN on the rotary dimensions, as DeepSeek-V3's modelling file reads
  this ``rope_scaling`` dict: ``extra_i = theta^(-2i/d)``, ``inter =
  extra / factor``, ``low, high = floor / ceil(d ln(orig / (beta 2 pi)) /
  (2 ln theta))`` for ``beta_fast`` / ``beta_slow`` clipped to [0, d-1],
  ``ramp = clip((i - low) / (high - low), 0, 1)``, ``inv_freq = inter
  ramp + extra (1 - ramp)``; cos and sin times ``m(mscale) /
  m(mscale_all_dim)``, the softmax scale ``(nope + rope)^-0.5
  m(mscale_all_dim)^2``, ``m(k) = 0.1 k ln(factor) + 1``.

ASSUMED, where the config's keys do not decide (each also under
``assumed`` in ``configs/xing4-mhc-d7.json``):
  * ``rope_interleave`` true (the key is absent; DeepSeek-V3's default,
    the pairing ``references/mla_moe.py:rope_interleaved`` writes out);
  * n copies of the embedding at the bottom and a plain sum at the head
    (Hyper-Connections section 3; the config has no key for a learned
    head mix);
  * ``x`` is normalised WITHOUT a learned gain; rows are normalised
    before columns; ``hc_eps`` sits in the Sinkhorn denominators;
  * ``torch_dtype`` bfloat16;
  * the draw of the new weights (the program's ``init_params``): phi
    normal x 1/sqrt(n C); the three gains a 1.0 and b_res normal x 1, so
    that the token-dependent term and the Sinkhorn iterations both move
    the logits (a trained model's small gates would hide both from any
    check); b_pre, b_post 0.
Departures from the published model: none in the mathematics beyond the
assumptions above. The MTP module is not part of next-token logits and is
absent on both sides.

Computed in BLOCKS so that it fits beside a 13 GB engine at a 5000-token
prompt, and under the serving path's own peak (``memory_peak_bytes`` is
read after the check): attention ``Q_BLOCK`` queries at a time (scores of
32 heads x 256 x 5000 are 0.16 GB), experts ``E_BLOCK`` at a time (their
products for ``T_BLOCK`` tokens at a time, as the dense MLP's: its
9216-wide activations for 5000 tokens are 0.56 GB), the head ``V_BLOCK``
vocabulary columns at a time (the whole head in float32 is 1.9 GB).

``control`` (never set by the benchmark; ``tools/mla_moe_mhc_control.py``
and the CPU tests set it) computes what a FAULTY program would:
  ``hc_iters_1``  one Sinkhorn iteration instead of ``hc_sinkhorn_iters``
  ``hc_static``   the token-dependent term ``x phi`` dropped (H from b)
  ``yarn_off``    plain rotary frequencies, factor 1, plain scale
      — three that leave out part of the mathematics: each MUST fail;
  ``mix_bf16``    the coefficients computed in bfloat16 (x, the product,
      every sigmoid, exp and Sinkhorn division rounded to 8 bits)
  ``fp8``         both operands of every matmul in float8_e4m3fn
      — two lower-precision controls: at least one must fail.

THE PROMPTS. (5000, 48): beyond ``original_max_position_embeddings``
4096, so YaRN's slowed dimensions and its scale are what the last ~950
positions read, and the engine runs a FRESH 4096-token chunk, a
CONTINUING chunk (absorbed attention over the region's rows) and decode
over both. (96, 48) and (600, 48): one fresh chunk, the regime cell 3's
check covers. 144 compared positions: decode steps are cheap, and the
mean of a heavy-tailed distance (the router's flips) steadies with them.

THE TOLERANCES, their reasons and the chip readings behind them: the
lines above ``CHECK_TOL_MAX`` / ``CHECK_TOL_MEAN`` below.
"""
from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "bench_mla_moe_plain", os.path.join(_HERE, "mla_moe.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)

CHECK_PROMPTS = ((5000, 48), (96, 48), (600, 48))
# The readings behind the two limits (TPU v5e; my chip runs, PR 37;
# PERF.md section 6; 2880 comparisons a run: the top 20 tokens at 144
# positions; sound = the program as served: bf16 weights, streams and
# activations, float32 accumulation, router scores, combine weights and
# mixing coefficients in float32). Every reading below is at THESE
# prompts; an earlier set (96 positions) read sound 0.312 / hc_iters_1
# 0.523 at one seed and is not counted.
#   sound, twenty-three weight seeds: mean 0.306-0.391, max 2.95-4.60
#   at twenty-two of them and 5.20 AT ONE (1235265473, drawn by the
#   driver's check of PR 37, which a first limit of 5.0 refused; read
#   again position by position: mean 0.312, among the smallest, and ONE
#   of the 144 positions, decode step 41 after the 5000-token prompt,
#   where the program's twenty best tokens stand 2.4 nats above the
#   reference's on average; the next position reads 3.13).
#   Three times cell 3's distance, and THE ROUTER'S FLIPS are nearly
#   all of it: a plain emulation on the CPU at these widths
#   (tools/mla_moe_mhc_cause.py: this file's own pieces rounded where a
#   bf16 program rounds, against this file in float32, two weight seeds)
#   reads mean 0.433-0.435 / max 3.1-3.5 with no program at all; with
#   the picks HELD to the float32 pass's 0.043-0.047 / 0.21-0.24; with
#   the mixing coefficients held instead 0.416-0.432 (nothing gained:
#   the coefficients follow the streams and add no distance of their
#   own). 2 % of (token, pick) pairs differ in the first expert layer,
#   23-24 % in the fifth: top 4 of 64 is a discontinuous function of
#   activations that carry bf16's error, a flip swaps an expert that
#   weighs a quarter of the routed output, and moved streams flip the
#   next layer's picks.
#   controls (four seeds; tools/mla_moe_mhc_control.py replays
#   ONE engine generation through each):
#     hc_iters_1   mean 0.574-0.676   max 4.20-4.54   fails MEAN
#     hc_static    mean 1.650-1.714   max 5.26-6.62   fails MEAN
#     yarn_off     mean 3.635-3.674   max 7.68-8.54   fails both
#     fp8          mean 1.583-1.643   max 4.96-5.88   fails MEAN
#     mix_bf16     mean 0.306-0.400: 0.94-1.02x its seed's sound
#                  reading, PASSES, and is reported as passing: the
#                  distance is the router's, so one more rounding of
#                  coefficients that a bf16 state already rounds moves
#                  nothing the check can see (PERF.md section 7: a check
#                  told the program's picks would stand at ~0.05).
# MEAN 0.46 lies between the largest sound reading (x 1.18; 4.6
# standard deviations of the twenty-three above their mean, 0.351 and
# 0.0235) and the smallest reading of hc_iters_1, the weakest control
# that must fail (x 1.25 below it). EVERY control that has to fail
# fails MEAN; none rests on MAX.
# MAX is the extreme of 2880 flip-laden comparisons, and its tail is
# long: over three seeds kept position by position (432 positions) a
# position's largest distance reads 0.65 at the median, 1.9 at p90, 3.3
# at p99, and past 2.5 it falls off by e every ~0.5 nats, so a run's
# extreme passes 5.0 about once in 30-50 runs (seen: once in some 35,
# mine and the driver's), 6.0 once in 250-600 and 7.0 once in 2000-8000
# (the exponential tail of the positions; a Gumbel fit of the 23
# extremes, 3.75 +- 0.49). A position the
# flips have moved half way to an unrelated state reads ~2.4 on average
# over its twenty tokens and ~5 at the worst of them; a position that
# is WHOLLY wrong reads ~4.4 and ~7 (logits of standard deviation 1.2
# over 131072 tokens), which is what yarn_off reads at every position:
# max 7.68-8.54. So in this cell MAX cannot tell one bad position from
# a sound program's worst, whatever its value, until the check is told
# the program's picks (PERF.md section 7); it stands at 7.0, between
# the largest sound extreme (x 1.35) and the smallest reading of a
# program whose every position is wrong (x 1.10 below yarn_off's), as
# the guard against values no sound position can produce.
CHECK_TOL_MAX = 7.0
CHECK_TOL_MEAN = 0.46

Q_BLOCK = 256    # queries scored at a time
E_BLOCK = 2      # experts converted to float32 at a time
V_BLOCK = 16384  # vocabulary columns of the head at a time
T_BLOCK = 1024   # tokens through an MLP or a block of experts at a time


def yarn_inv_freq(d: int, theta: float, s: dict) -> np.ndarray:
    def index_turning(n_rot):
        return (d * math.log(s["original_max_position_embeddings"]
                             / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(index_turning(s["beta_fast"])), 0)
    high = min(math.ceil(index_turning(s["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    i = np.arange(d // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (extra / s["factor"] * ramp + extra * (1 - ramp)).astype(
        np.float32)


def yarn_m(factor: float, k: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * k * math.log(factor) + 1.0


def rotary(hf: dict, control):
    """(inv_freq [rope/2], factor on cos and sin, softmax scale)."""
    d, theta = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    scale = (hf["qk_nope_head_dim"] + d) ** -0.5
    s = hf.get("rope_scaling")
    if s is None or control == "yarn_off":
        i = np.arange(d // 2, dtype=np.float64)
        return (theta ** (-2.0 * i / d)).astype(np.float32), 1.0, scale
    m_all = yarn_m(s["factor"], s["mscale_all_dim"])
    return (yarn_inv_freq(d, theta, s),
            yarn_m(s["factor"], s["mscale"]) / m_all, scale * m_all ** 2)


def rope_pairs(x, inv_freq, times):
    """x [T, ..., r] at positions 0..T-1, pairs (2i, 2i+1): de-interleave,
    then rotate-half (the published
    ``apply_rotary_pos_emb_interleave``), cos and sin times ``times``."""
    r = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], -1)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r,))
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return (x * (jnp.cos(ang) * times)
            + jnp.concatenate([-x2, x1], -1) * (jnp.sin(ang) * times))


def attention(hp, lp, u, control=None):
    """One layer's attention over the whole sequence: u [T, H] (the
    sublayer's input, before ln1) -> its output [T, H] (after W_o)."""
    mm, rms = plain.mm, plain.rms_norm
    nh, nope, rope, vd = hp["heads"], hp["nope"], hp["rope"], hp["v"]
    rank, T = hp["kv_rank"], u.shape[0]
    inv_freq, times, scale = hp["rotary"]
    x = rms(u, lp["ln1"], hp["eps"])
    c_q = rms(mm(x, lp["wqa"], control), lp["q_norm"], hp["eps"])
    q = mm(c_q, lp["wqb"], control).reshape(T, nh, nope + rope)
    kv = mm(x, lp["wkva"], control)
    c_kv = rms(kv[:, :rank], lp["kv_norm"], hp["eps"])
    k_rope = rope_pairs(kv[:, rank:], inv_freq, times)
    q_rope = rope_pairs(q[..., nope:], inv_freq, times)
    kvb = mm(c_kv, lp["wkvb"], control).reshape(T, nh, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_rope[:, None], (T, nh, rope))], -1)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    v, pos = kvb[..., nope:], jnp.arange(T)
    out = []
    for q0 in range(0, T, Q_BLOCK):
        s = jnp.einsum("thd,shd->hts", q[q0:q0 + Q_BLOCK], k) * scale
        seen = pos[q0:q0 + Q_BLOCK, None] >= pos[None, :]
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out, 0).reshape(T, nh * vd)
    return mm(o, lp["wo"], control)


def sinkhorn(m, iters: int, eps: float, rnd):
    """m [T, n, n] positive -> rows, then columns, normalised ``iters``
    times; ``rnd`` rounds every result (the identity, or to bfloat16)."""
    for _ in range(iters):
        m = rnd(m / rnd(m.sum(-1, keepdims=True) + eps))
        m = rnd(m / rnd(m.sum(-2, keepdims=True) + eps))
    return m


def mix(hp, X, phi, a, b, control=None):
    """One sublayer's coefficients from the state X [T, n, C]:
    (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    T, n, _ = X.shape
    rnd = plain.to_bf16 if control == "mix_bf16" else (lambda z: z)
    v = X.reshape(T, -1)
    x = rnd(v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                              + hp["eps"]))
    if control == "hc_static":
        x = jnp.zeros_like(x)
    t = rnd(plain.mm(x, rnd(phi), control))
    pre = rnd(jax.nn.sigmoid(rnd(a[0] * t[:, :n] + b[:n])))
    post = rnd(2.0 * jax.nn.sigmoid(rnd(a[1] * t[:, n:2 * n] + b[n:2 * n])))
    res = jnp.clip(rnd(a[2] * t[:, 2 * n:] + b[2 * n:]),
                   hp["clamp"][0], hp["clamp"][1]).reshape(T, n, n)
    iters = 1 if control == "hc_iters_1" else hp["iters"]
    return pre, post, sinkhorn(rnd(jnp.exp(res)), iters, hp["hc_eps"], rnd)


def sublayer(hp, X, hc, F, control=None):
    """X' = H_res X + H_post^T F(H_pre X); ``hc`` = (phi, a, b)."""
    pre, post, res = mix(hp, X, *hc, control)
    f = F(jnp.einsum("ti,tic->tc", pre, X))
    return (jnp.einsum("tij,tjc->tic", res, X)
            + post[:, :, None] * f[:, None, :])


def logprobs(hf: dict, params: dict, tokens: list[int], positions: list[int],
             control: str | None = None) -> np.ndarray:
    """log-softmax over the vocabulary, [len(positions), V], of the next
    token after each of ``positions`` of ``tokens``."""
    if hf.get("num_nextn_predict_layers"):
        raise ValueError("the reference has no MTP module")
    if (hf["scoring_func"], hf["n_group"], hf["topk_group"]) != (
            "sigmoid", 1, 1) or not hf.get("rope_interleave", True):
        raise ValueError("the reference has sigmoid scores, one group and "
                         "interleaved rotary pairs only")
    s = hf.get("rope_scaling")
    if s is not None and s.get("type") != "yarn":
        raise ValueError(f"the reference has YaRN only, not {s!r}")
    hp = {
        "heads": hf["num_attention_heads"], "nope": hf["qk_nope_head_dim"],
        "rope": hf["qk_rope_head_dim"], "v": hf["v_head_dim"],
        "kv_rank": hf["kv_lora_rank"], "eps": float(hf["rms_norm_eps"]),
        "top_k": hf["num_experts_per_tok"],
        "norm_topk": bool(hf["norm_topk_prob"]),
        "scale": float(hf["routed_scaling_factor"]),
        "rotary": rotary(hf, control),
        "iters": int(hf["hc_sinkhorn_iters"]), "hc_eps": float(hf["hc_eps"]),
        "clamp": (float(hf["mhc_h_res_clamp_min"]),
                  float(hf["mhc_h_res_clamp_max"])),
    }
    n, n_dense = int(hf["hc_mult"]), hf["first_k_dense_replace"]
    plain.held_to_stated_weights(params)
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)

    def layer_of(l, layers):
        return f32(jax.tree.map(lambda a: a[l], layers))

    def hc_of(lp, sub):
        return lp["hc_phi"][sub], lp["hc_a"][sub], lp["hc_b"][sub]

    # jitted so that each piece is one program; the layer index is a
    # value, one program for all layers
    @jax.jit
    def attn_sublayer(l, layers, X):
        lp = layer_of(l, layers)
        return sublayer(hp, X, hc_of(lp, 0),
                        lambda u: attention(hp, lp, u, control), control)

    @jax.jit
    def mlp_in(l, layers, X):
        """The second sublayer up to F's input: coefficients, ln2(u)."""
        lp = layer_of(l, layers)
        pre, post, res = mix(hp, X, *hc_of(lp, 1), control)
        u = jnp.einsum("ti,tic->tc", pre, X)
        return plain.rms_norm(u, lp["ln2"], hp["eps"]), post, res

    @jax.jit
    def mlp_out(X, y, post, res):
        return (jnp.einsum("tij,tjc->tic", res, X)
                + post[:, :, None] * y[:, None, :])

    dense = jax.jit(lambda l, d, x2: plain.swiglu(
        x2, *(d[k][l].astype(jnp.float32) for k in ("wg", "wu", "wd")),
        control))
    weights = jax.jit(lambda x2, wr, b: plain.combine_weights(
        hp, x2, wr.astype(jnp.float32), b.astype(jnp.float32), control))
    shared = jax.jit(lambda x2, ep: plain.swiglu(
        x2, *(ep[k].astype(jnp.float32) for k in ("ws_g", "ws_u", "ws_d")),
        control))

    def block_of(size):
        return jax.jit(lambda x2, w, ep, e0: plain.expert_block(
            x2, jax.lax.dynamic_slice_in_dim(w, e0, size, 1),
            *(jax.lax.dynamic_slice_in_dim(ep[k], e0, size, 0)
              for k in ("we_g", "we_u", "we_d")), control))

    @jax.jit
    def head_in(norm_f, X, pos):
        return plain.rms_norm(X[pos].sum(axis=1), norm_f.astype(jnp.float32),
                              hp["eps"])

    def head_block(size):
        return jax.jit(lambda w, h, v0: plain.mm(
            h, jax.lax.dynamic_slice_in_dim(w, v0, size, 1).astype(
                jnp.float32), control))

    with jax.default_matmul_precision("highest"):
        e = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        X = jnp.broadcast_to(e[:, None], (e.shape[0], n, e.shape[1]))
        block = None
        for l in range(hf["num_hidden_layers"]):
            X = attn_sublayer(jnp.int32(l), params["layers"], X)
            x2, post, res = mlp_in(jnp.int32(l), params["layers"], X)
            ep = None if l < n_dense else params["experts"][l - n_dense]
            ys = []
            for t0 in range(0, x2.shape[0], T_BLOCK):
                xb = x2[t0:t0 + T_BLOCK]
                if ep is None:
                    ys.append(dense(jnp.int32(l), params["dense"], xb))
                    continue
                E = ep["wr"].shape[1]
                size = min(E_BLOCK, E)
                if E % size:
                    raise ValueError(f"{E} experts do not divide into blocks")
                block = block or block_of(size)
                w = weights(xb, ep["wr"], ep["bias"])
                y = shared(xb, ep)
                for e0 in range(0, E, size):
                    y = y + block(xb, w, ep, jnp.int32(e0))
                ys.append(y)
            X = mlp_out(X, jnp.concatenate(ys, 0), post, res)
        h = head_in(params["norm_f"], X, jnp.asarray(positions, jnp.int32))
        V = params["lm_head"].shape[1]
        size = min(V_BLOCK, V)
        if V % size:
            raise ValueError(f"{V} vocabulary columns do not divide into "
                             "blocks")
        block = head_block(size)
        logits = jnp.concatenate(
            [block(params["lm_head"], h, jnp.int32(v0))
             for v0 in range(0, V, size)], -1)
        return np.asarray(jax.nn.log_softmax(logits, -1))
