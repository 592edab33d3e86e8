"""The plain reference of the decoder-hybrid-decoder stack (``model_type:
phi4flash`` as Phi-4-mini-flash-reasoning's config.json parameterises it;
the SambaY architecture of arXiv 2507.06607): Mamba-1 mixers, DIFFERENTIAL
attention behind a window, one full differential attention layer, and a
cross-decoder of gated memory units and cross differential attention over
that one layer's keys and values.

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, EVERY position through ALL layers (no row stops half-way up),
the selective scan as written (a ``lax.scan`` over time on the published
[d_inner, d_state] state), explicit [T, T] masks, heads of ``head_dim``
(64) as published with no pair-wide rows, no cache, no batching, no
kernels, no padding. It imports nothing from the program and takes the
engine's own weight pytree, so both sides compute the same model. One
layer at a time, each waited for; the attention one K/V PAIR at a time
(four query heads' [T, T] maps), the head ``VOCAB_BLOCK`` rows at a time:
a block changes no sum.

The block (h [T, hidden]; LN = LayerNorm, mean removed, gain and bias,
eps ``layer_norm_eps``; l counts layers from 0, L of them, L / 2 = half):

  h0 = embed[ids]. Each layer: h += mixer(LN1(h)); h += swiglu(LN2(h))
  (gate first, no bias). Logits: LN_f(h) @ embed^T (tied). No rotary, no
  position embedding.
  roles: even l <= half Mamba-1; odd l < half window differential
    attention; l = half + 1 full differential attention; above it even l
    gated memory units, odd l cross differential attention.
  mamba-1: [x | z] = u W_in; x = silu(causal depthwise conv (width 4) +
    b); [dt_r | B | C] = x W_x, NO norms on them; dt = softplus(dt_r W_dt
    + dt_bias); A = -exp(A_log); h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t;
    y_t = h_t C_t + D x_t; out = (y silu(z)) W_out. Layer ``half`` also
    hands m = y (BEFORE the gate) to the gated memory units.
  gated memory unit: out = (m * silu(x W_1)) W_2, m of the same position.
  differential attention: q = x W_q + b_q [heads, hd]; k, v = x W_k + b_k,
    x W_v + b_v [kv_heads, hd] (a cross layer has no W_k / W_v and reads
    layer half + 1's k and v). Query pair j = heads (2j, 2j + 1) = (q1,
    q2); K/V pair g = heads (2g, 2g + 1): k1 = k_2g, k2 = k_2g+1, V = [v_2g
    | v_2g+1]; pair j reads K/V pair j // (query pairs / K/V pairs). A_i =
    softmax(q_i k_i^T / sqrt(hd)) over the visible keys; o = (A_1 - lam
    A_2) V; o = RMSNorm(o; gain [2 hd], eps layer_norm_eps) x (1 -
    lam_init); out = concat(o) W_o + b_o. lam = exp(lq1 . lk1) - exp(lq2 .
    lk2) + lam_init, lam_init = 0.8 - 0.6 exp(-0.3 l) (computed HERE from
    l: the program's stored copy is not read). Visible keys at position t:
    a window layer t - window + 1 .. t, the others 0 .. t.

Weights (the program's pytree): ``embed`` [V, H], ``norm_f`` / ``norm_f_b``
and ``layers``, one dict a layer: ln1 / ln1_b / ln2 / ln2_b, w_g / w_u /
w_d; Mamba layers w_in (x first), conv_w [4, inner] (row 3 on the current
position), conv_b, w_x, w_dt, dt_bias, A_log (float32, held [d_state,
d_inner]: transposed here), D, w_out; attention layers wq, bq, wo, bo,
sub_norm, lam_q1 / lam_k1 / lam_q2 / lam_k2 and, but for the cross ones,
wk, bk, wv, bv; gated memory units w1, w2.

Departures from the published description, all listed in the
configuration's ``assumed``: the Mamba sizes, the differential form and its
initialisation, the biases and the roles are as the family's modeling code
has them, none of them a key of the catalog's config.

``control`` (never set by the benchmark; the CPU tests and
tools/mla_moe_control.py --config phi4-mini-flash set it) computes what a
FAULTY program would. ``boundary`` is the first chunk boundary the long
prompts cross (the largest prefill bucket):
  ``"window_minus"`` / ``"window_plus"``  the window one key short / long;
  ``"lam_wrong_layer"``  every layer's lam_init taken of the layer below;
  ``"cross_own_rows"``  the cross layers reading NO rows of layer half + 1
      (their own, empty: the output of a softmax over nothing, 0);
  ``"m_after_gate"``  the gated memory units fed y silu(z), not y;
  ``"m_stale"``  m of the boundary's position fed at every position past it;
  ``"window_dropped"``  a window layer's keys from before the boundary lost
      at and past it (a buffer not carried across chunks);
  ``"state_zeroed"``  the scan's state dropped at the boundary (named, not
      required: on the chip it reads 1.4 x sound, under both limits);
  ``"state_bf16"``  the scan's state rounded to bfloat16 after every
      position (named, not required: see the configuration's ``assumed``);
  ``"fp8"``  both operands of every matmul rounded to float8_e4m3fn.

THE TOLERANCES, their reasons and the readings behind them: the constants
below and PERF.md section 6 (PR 54).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (prompt tokens, decode steps). With prefill buckets to 4096 and a window
# buffer of 512 rows a lane:
#   9000: THREE chunks (4096 + 4096 + 808): two continuing chunks start
#     from the lane's Mamba state, convolution window, window buffer
#     (wrapped sixteen times) and layer 17's rows, and m crosses with them;
#   4696: one continuing chunk of 600, longer than the window (its window
#     layers read the buffer's 511 prior rows and then their own);
#   1000 + 40: decode crosses 1024, a multiple of the buffer's length, and
#     ten round boundaries: the ring's flush wraps inside a round;
#   490 + 40: decode crosses 512: the first wrap of a lane's buffer, and
#     the first position whose window is narrower than its context;
#   90: far shorter than its bucket and its window (padding must reach
#     neither state nor buffer).
CHECK_PROMPTS = ((9000, 24), (4696, 40), (1000, 40), (490, 40), (90, 16))
# Set from the chip's readings (PERF.md section 6, PR 54: the sound readings
# at the weight seeds of every run made there, the controls at one seed, all
# at these prompts and the published widths).
# The distance between a sound bfloat16 program and this float32 reference
# is plain rounding over 32 layers (no router, no near-tie to flip): mean
# 0.0267-0.0312, max 0.120-0.167 over nine seeds (two thirds of the
# 28-layer Mamba-1 stack's: nine scans, not 26).
# MEAN judges: 1.9 x the largest sound reading (0.0312), 0.13 x fp8's
# (0.461: the nearest precision below the stated one) and 0.53 x the weakest
# required control's (window_dropped 0.113; then cross_own_rows 0.150,
# m_stale 0.177, m_after_gate 0.325, lam_wrong_layer 0.646).
# MAX is an extreme of 3200 comparisons, so it gets the wider room above:
# 2.7 x the largest sound reading (0.167), 0.21 x fp8's (2.11), 0.60 x the
# smallest required control's (cross_own_rows 0.749; window_dropped 1.39,
# m_stale 1.50, m_after_gate 1.51, lam_wrong_layer 2.58).
# window_minus (one key short of 512) reads mean 0.047 / max 0.561 at the one
# seed it was read at: over the MAX limit by a quarter, under the mean's. It
# is NAMED, with window_plus, not required: the chip's check does not hold
# the window's edge with room on both sides (one key in 512 of eight of 32
# layers moves a bfloat16 program's log-probs little more than its own
# rounding does); tests/test_sambay.py holds it in float32 at toy widths,
# where both read 10 x the tolerance and more, and against the plain form
# key by key; window_plus reads 0.048 / 0.267 (passes both). state_zeroed
# (the scan's state dropped at the 4096 boundary) reads 0.042 / 0.346: under
# both limits (nine Mamba-1 layers whose state decays over a few hundred
# positions; m_stale and window_dropped, which cross the same boundary,
# fail by both): NAMED too, held at toy widths on the CPU. state_bf16 reads
# as sound (0.031 / 0.221), as in the other state-space cells: named, and
# the configuration's ``assumed`` says what holds the state's float32.
CHECK_TOL_MAX = 0.45
CHECK_TOL_MEAN = 0.06
# what tools/mla_moe_control.py runs against this check: each of the first
# has to FAIL it, the others are reported whichever way they read
CONTROLS_REQUIRED = ("lam_wrong_layer", "cross_own_rows", "m_after_gate",
                     "m_stale", "window_dropped", "fp8")
CONTROLS_NAMED = ("window_minus", "window_plus", "state_zeroed", "state_bf16")

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


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def swiglu(x, wg, wu, wd, control=None):
    return mm(jax.nn.silu(mm(x, wg, control)) * mm(x, wu, control), wd,
              control)


def layer_kinds(hf: dict) -> list[str]:
    L = hf["num_hidden_layers"]
    half = L // 2
    return [("mamba" if l % 2 == 0 else "window") if l <= half
            else "full" if l == half + 1
            else "gmu" if l % 2 == 0 else "cross" for l in range(L)]


def hyper(hf: dict) -> dict:
    H, heads = hf["hidden_size"], hf["num_attention_heads"]
    rank = hf.get("mamba_dt_rank", "auto")
    window = hf["sliding_window"]
    if isinstance(window, (list, tuple)):
        window = next(w for w in window if w is not None)
    return {
        "kinds": layer_kinds(hf), "eps": float(hf["layer_norm_eps"]),
        "heads": heads, "kv_heads": hf["num_key_value_heads"],
        "hd": H // heads, "window": int(window),
        "inner": hf.get("mamba_expand", 2) * H,
        "N": hf.get("mamba_d_state", 16), "W": hf.get("mamba_d_conv", 4),
        "R": -(-H // 16) if rank == "auto" else int(rank),
    }


def lam_init(l):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))


def differential(hp, lp, l, x, kv, visible, control=None):
    """One differential attention layer over the whole sequence: x [T, H]
    (already normed), ``kv`` = (k, v) [T, kv_heads, hd] each (the layer's
    own, or another layer's), ``visible`` [T, T] bool (query, key); None:
    no key at all, and the maps' read is 0."""
    T = x.shape[0]
    nh, kvh, hd = hp["heads"], hp["kv_heads"], hp["hd"]
    pairs, kv_pairs = nh // 2, kvh // 2
    rep = pairs // kv_pairs
    q = (mm(x, lp["wq"], control) + lp["bq"]).reshape(T, kv_pairs, rep, 2, hd)
    k = kv[0].reshape(T, kv_pairs, 2, hd)
    v = kv[1].reshape(T, kv_pairs, 2 * hd)
    li = lam_init(l - 1 if control == "lam_wrong_layer" else l)
    lam = (jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"]))
           - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + li)

    def group(g):
        # [rep, 2, T, T]: the two maps of each query pair on this K/V pair
        s = jnp.einsum("trid,sid->rits", q[:, g], k[:, g]) / np.sqrt(hd)
        a = jax.nn.softmax(jnp.where(visible[None, None], s, -jnp.inf), -1)
        return jnp.einsum("rts,sd->trd", a[:, 0] - lam * a[:, 1], v[:, g])

    if visible is None:
        o = jnp.zeros((kv_pairs, T, rep, 2 * hd))
    else:                                            # [kv_pairs, T, rep, 2hd]
        o = jax.lax.map(group, jnp.arange(kv_pairs))
    o = o.transpose(1, 0, 2, 3).reshape(T, pairs, 2 * hd)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + hp["eps"])
    o = o * lp["sub_norm"] * (1.0 - li)
    return mm(o.reshape(T, nh * hd), lp["wo"], control) + lp["bo"]


def keys_values(hp, lp, x, control=None):
    T = x.shape[0]
    shape = (T, hp["kv_heads"], hp["hd"])
    return ((mm(x, lp["wk"], control) + lp["bk"]).reshape(shape),
            (mm(x, lp["wv"], control) + lp["bv"]).reshape(shape))


def mamba(hp, lp, u, boundary, control=None):
    """One Mamba-1 mixer over the whole sequence u [T, H] (already
    normed): (the mixer's output, the scan's output y before the gate)."""
    T = u.shape[0]
    I, N, R, W = hp["inner"], hp["N"], hp["R"], hp["W"]
    x, z = jnp.split(mm(u, lp["w_in"], control), 2, -1)
    pos = jnp.arange(T)
    conv = lp["conv_b"]
    for j in range(W):
        back = W - 1 - j   # row W - 1 of the weight is on the current position
        conv = conv + jnp.pad(x, ((back, 0), (0, 0)))[:T] * lp["conv_w"][j]
    x = jax.nn.silu(conv)
    dt, B, C = jnp.split(mm(x, lp["w_x"], control), [R, R + N], -1)
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
    gated = y * jax.nn.silu(z)
    return mm(gated, lp["w_out"], control), (
        gated if control == "m_after_gate" else y)


def held_to_stated_weights(params: dict) -> None:
    """The configuration states bfloat16 weights, unquantised: a pytree
    that is not this block's, or holds a weight in fewer than 16 bits or
    as integers, is refused (the reference computes the STATED model)."""
    missing = {"embed", "norm_f", "norm_f_b", "layers"} - set(params)
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
    L = hf["num_hidden_layers"]
    refused = {
        "mb_per_layer": hf["mb_per_layer"] != 2,
        "num_hidden_layers": L % 4 != 0,
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": not hf.get("tie_word_embeddings", True),
        "biases": bool(hf.get("mlp_bias")) or bool(hf.get("lm_head_bias")),
    }
    if any(refused.values()):
        raise ValueError("the reference does not build "
                         f"{sorted(k for k, v in refused.items() if v)}")
    held_to_stated_weights(params)
    hp = hyper(hf)
    boundary = max(hf["engine"]["prefill_buckets"])
    T = len(tokens)
    window = hp["window"] + {"window_minus": -1, "window_plus": 1}.get(
        control, 0)

    def visible(kind):
        """[T, T] bool: may query t read key s."""
        pos = jnp.arange(T)
        ok = pos[:, None] >= pos[None, :]
        if kind == "window":
            ok &= pos[:, None] - pos[None, :] < window
            if control == "window_dropped":
                ok &= ~((pos[:, None] >= boundary)
                        & (pos[None, :] < boundary))
        return ok

    # jitted only so that each piece is one program instead of dozens of
    # eager ops; one program a layer KIND (the depth l is a value)
    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(lp, h, shared, l, kind):
        kv, m = shared
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        x = layer_norm(h, lp["ln1"], lp["ln1_b"], hp["eps"])
        if kind == "mamba":
            mix, y = mamba(hp, lp, x, boundary, control)
            if control == "m_stale" and T > boundary:
                y = jnp.where((jnp.arange(T) >= boundary)[:, None],
                              y[boundary], y)
            m = jnp.where(l == L // 2, y, m)
        elif kind == "gmu":
            mix = mm(m * jax.nn.silu(mm(x, lp["w1"], control)), lp["w2"],
                     control)
        elif kind == "cross":
            mix = differential(
                hp, lp, l, x, kv,
                None if control == "cross_own_rows" else visible(kind),
                control)
        else:
            own = keys_values(hp, lp, x, control)
            if kind == "full":
                kv = own
            mix = differential(hp, lp, l, x, own, visible(kind), control)
        h = h + mix
        x = layer_norm(h, lp["ln2"], lp["ln2_b"], hp["eps"])
        return (h + swiglu(x, lp["w_g"], lp["w_u"], lp["w_d"], control),
                (kv, m))

    V = params["embed"].shape[0]
    vb = min(VOCAB_BLOCK, V)

    @jax.jit
    def head_block(norm_f, norm_f_b, embed, h, at, v0):
        h = layer_norm(h[at], norm_f.astype(jnp.float32),
                       norm_f_b.astype(jnp.float32), hp["eps"])
        rows = jax.lax.dynamic_slice_in_dim(embed, v0, vb, 0)
        return mm(h, rows.astype(jnp.float32).T, control)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        kvh, hd = hp["kv_heads"], hp["hd"]
        shared = ((jnp.zeros((T, kvh, hd)), jnp.zeros((T, kvh, hd))),
                  jnp.zeros((T, hp["inner"])))
        for l, (kind, lp) in enumerate(zip(hp["kinds"], params["layers"])):
            # waited for: the next layer's float32 copy is made only
            # after this one's is dropped
            h, shared = jax.block_until_ready(
                layer(lp, h, shared, jnp.int32(l), kind=kind))
        at = jnp.asarray(positions, jnp.int32)
        blocks = []
        for v0 in range(0, V, vb):
            # the last block slides back (dynamic_slice clamps): cut what
            # it repeats
            got = np.asarray(head_block(
                params["norm_f"], params["norm_f_b"], params["embed"], h, at,
                jnp.int32(v0)))
            blocks.append(got[:, max(0, v0 + vb - V):])
        logits = np.concatenate(blocks, -1).astype(np.float64)
    logits -= logits.max(-1, keepdims=True)
    return (logits - np.log(np.exp(logits).sum(-1, keepdims=True))).astype(
        np.float32)
