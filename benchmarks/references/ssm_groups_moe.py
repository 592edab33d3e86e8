"""The plain reference of the ONE-PART hybrid stack (``nemotron_h`` as
NVIDIA-Nemotron-3-Nano-30B-A3B's config.json parameterises it): every
layer is one RMSNorm and ONE part, named by its letter of
``hybrid_override_pattern``: a Mamba-2 mixer with B and C in groups, a
position-free GQA mixer, an expert layer of ungated relu^2 experts, or a
dense relu^2 MLP.

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, the whole sequence at once, the Mamba-2 recurrence AS
WRITTEN (a ``lax.scan`` over time, one position a step; no chunks), no
cache, no batching, no kernels, no padding. It imports nothing from the
program and takes the engine's own weight pytree, so both sides compute
the same model. Large pieces are computed in blocks (attention a query
head at a time, experts ``EXPERT_BLOCK_ROWS`` rows at a time, the head
``VOCAB_BLOCK`` rows at a time) so that the reference fits beside a 14 GB
engine; a block changes no sum.

The block (x [T, hidden]; one eps; no biases but the convolution's):

  x0 = embed[ids]. For each layer l: x += part_l(RMSNorm(x; g_l)). Logits:
  RMSNorm(x; g_f) @ head (untied where the file says so, else embed^T).
  M: [z | xBC | dt] = a W_in (inner / inner + 2 G N / heads; inner =
    heads x head_dim, NOT expand x hidden); xBC = silu(causal depthwise
    conv (width conv_kernel) + b); x [heads, P], B and C [G, N], head h
    reads group h // (heads / G); dt = softplus(dt + dt_bias), A =
    -exp(A_log); per head S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer
    B_g,t), y_t = S_t C_g,t + D x_t; y = y * silu(z) FIRST, then RMSNorm
    over EACH GROUP's inner / G channels, one gain of inner; y W_out.
  *: q, k, v projections, GQA (query head h reads K/V head h // (heads /
    kv_heads)), NO rotary, causal softmax with scale 1 / sqrt(head_dim),
    output projection.
  E: s = sigmoid(a W_r) over ALL the published experts; the top k of s +
    bias are picked; weights s[picks] / (sum + 1e-20) x
    routed_scaling_factor (the bias selects and does not weigh); expert e
    = relu(a W_u^e)^2 W_d^e, no gate matrix; the shared expert has the
    same form and is added ungated.
  -: relu(a W_u)^2 W_d.

THE SHARE. The configuration holds ``n_routed_experts`` of the
``expert_share.published_experts`` a layer (experts ``index * held`` up to
``(index + 1) * held``): the weight pytree has those experts only, the
router keeps its published width and its picks, and a pick that lands on
an expert held elsewhere adds nothing, here as in the program. ``routed``
and ``shared`` are the two parts of an expert layer, exposed so that a
test can add the shares up to the uncut layer.

Weights (the program's pytree): ``embed`` [V, H], ``head`` [H, V],
``norm_f``, and ``layers``, a list of one dict a layer, each with ``ln1``
and its part's leaves: M w_in, conv_w [conv_kernel, conv_dim] (row
conv_kernel - 1 on the current position), conv_b, A_log, dt_bias, D
(float32), norm, w_out; * wq, wk, wv, wo; E wr [H, E], bias [E], we_u
[held, H, I'], we_d [held, I', H], ws_u, ws_d; - w_u, w_d. The program
STORES an expert's two matrices at I' >= the published width, the columns
of W_u and the rows of W_d beyond it zero: relu(0)^2 = 0 meets a zero row,
so the sums here are the published model's whatever I' is.

Departures from the published model: none in the mathematics (what the
file does not state is listed in the configuration under ``assumed``).

``control`` (never set by the benchmark; tools/mla_moe_control.py
--config nemotron3-nano-ep8 and the CPU tests set it) computes what a
FAULTY program would, to show what the tolerances below catch.
``boundary`` is the position of the first chunk boundary the long prompts
cross (the largest prefill bucket):
  ``"one_group"``  every head reads B/C group 0;
  ``"norm_all"``   the gated norm over all of inner, not a group;
  ``"gate_after_norm"``  norm(y) * silu(z) instead of norm(y * silu(z));
  ``"relu"``       relu without the square, in every expert and the shared;
  ``"swiglu"``     silu(u) * u in the experts' place (a gated unit over
      the one input matrix there is);
  ``"scale_1"``    routed_scaling_factor left out;
  ``"bias_in_weights"``  the combine weights from s + bias;
  ``"picks_wrapped"``  a pick held elsewhere counted here (wrapped onto
      the held experts);
  ``"no_shared"``  the shared expert dropped;
  ``"rotary"``     rotate-half rotary (rope_theta) on q and k;
  ``"state_zeroed"``  the SSM state dropped at the boundary: a continuing
      chunk started from zeros;
  ``"conv_zeroed"``   the convolution's window dropped at the boundary;
  ``"fp8"``  both operands of every matmul rounded to float8_e4m3fn: the
      nearest precision below the stated bfloat16;
  ``"state_bf16"``  the SSM state rounded to bfloat16 after every step.

THE TOLERANCES, their reasons and the readings behind them: see the
constants below and PERF.md section 6 (PR 60).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (prompt tokens, decode steps): two prompts over 4096 (a fresh and a
# CONTINUING chunk: 23 states and 23 windows cross the boundary), one with
# a 204-token tail and one that ends ON the boundary's first position
# (4097: its last logits and first decode steps stand on what crossed);
# one under 128 (a scan shorter than its chunk); one that is no multiple
# of 128 and far shorter than its 4096 bucket would be (1100 in the 4096
# bucket: padding must not reach the state); 32 decode steps each cross
# eight round boundaries of 4
CHECK_PROMPTS = ((4300, 32), (4097, 32), (97, 32), (1100, 32))
# Set from the chip's readings (PERF.md section 6, PR 60: the sound
# program at eleven weight seeds, the controls at one, these prompts; the
# configuration's ``assumed`` has every control's numbers). The distance
# between a sound bfloat16 program and this float32 reference is the
# router's near-ties, as in the other sigmoid-routed cells: the sixth pick
# of 128 flips under the roundings of the layers below, 23 expert layers
# deep, and where the flipped expert is one of the 16 held here a relu^2
# expert x 2.5 comes or goes (mean 0.111-0.164, max 1.00-2.35 over the top
# 20 of 128 steps).
# MEAN judges: 1.8x the largest sound reading (0.164), 0.58x the reading of
# the weakest required control (fp8 0.516: the nearest precision below the
# stated bfloat16; then state_zeroed 0.559, scale_1 0.613, swiglu 0.773,
# gate_after_norm 1.26, one_group / norm_all 1.31, relu 1.55,
# picks_wrapped 2.41, no_shared 2.96).
# MAX is an extreme of 2560 comparisons that the router's flips set (2.35
# on a sound seed): 1.9x the largest sound reading, so that it refuses a gross
# fault only (picks_wrapped 6.2, no_shared 6.7) and the mean does the
# judging; the other required controls read 2.2-4.4.
CHECK_TOL_MAX = 4.5
CHECK_TOL_MEAN = 0.3
# what tools/mla_moe_control.py runs against this check: each of the first
# has to FAIL it, the last are reported whichever way they read. NAMED
# because they read INSIDE the band sound seeds span (my chip run, PR 60):
# bias_in_weights 0.117 / 1.86 (the bias is drawn x 0.01 in score units:
# two percent of a weight), rotary 0.155 / 1.93 (six of 52 layers, whose
# near-uniform random-weight attention adds little to a residual stream the
# experts dominate), state_bf16 0.183 / 1.81 (as cell 6: the check does not
# hold the state's precision), and conv_zeroed 0.276 / 4.36 (under both
# limits, if only just). What holds them: tests/test_ssm_groups_moe.py
# at toy widths in float32, where each moves the log-probs by >= 10 x the
# tolerance.
CONTROLS_REQUIRED = ("one_group", "norm_all", "gate_after_norm", "relu",
                     "swiglu", "scale_1", "picks_wrapped", "no_shared",
                     "state_zeroed", "fp8")
CONTROLS_NAMED = ("bias_in_weights", "rotary", "conv_zeroed", "state_bf16")

EXPERT_BLOCK_ROWS = 16384   # experts x tokens computed at a time
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


def act(u, control=None):
    """relu(u)^2, the stack's one activation."""
    if control == "relu":
        return jax.nn.relu(u)
    if control == "swiglu":
        return jax.nn.silu(u) * u
    return jnp.square(jax.nn.relu(u))


def mlp(x, wu, wd, control=None):
    return mm(act(mm(x, wu, control), control), wd, control)


def hyper(hf: dict) -> dict:
    held = hf["n_routed_experts"]
    share = hf.get("expert_share") or {
        "published_experts": held, "of": 1, "index": 0}
    nh, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    return {
        "pattern": str(hf["hybrid_override_pattern"]),
        "eps": float(hf["layer_norm_epsilon"]),
        "heads": hf["num_attention_heads"],
        "kv_heads": hf["num_key_value_heads"], "hd": hf["head_dim"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "nh": nh, "P": P, "G": hf["n_groups"], "N": hf["ssm_state_size"],
        "W": hf["conv_kernel"], "inner": nh * P,
        "top_k": hf["num_experts_per_tok"],
        "scale": float(hf["routed_scaling_factor"]),
        "held": held, "first": share["index"] * held,
        "tied": bool(hf["tie_word_embeddings"]),
    }


def rotate_half(x, theta):
    """The control's rotary: x [T, heads, hd] at positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(hp, lp, x, control=None):
    """Position-free GQA over the whole sequence x [T, H] (normed)."""
    T = x.shape[0]
    nh, kvh, hd = hp["heads"], hp["kv_heads"], hp["hd"]
    q = mm(x, lp["wq"], control).reshape(T, nh, hd)
    k = mm(x, lp["wk"], control).reshape(T, kvh, hd)
    v = mm(x, lp["wv"], control).reshape(T, kvh, hd)
    if control == "rotary":
        q, k = rotate_half(q, hp["theta"]), rotate_half(k, hp["theta"])
    pos = jnp.arange(T)
    ok = pos[:, None] >= pos[None, :]

    def head(h):   # one query head at a time: a [T, T] map
        g = h // (nh // kvh)
        s = (q[:, h] @ k[:, g].T) / np.sqrt(hd)
        return jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1) @ v[:, g]

    o = jax.lax.map(head, jnp.arange(nh))              # [nh, T, hd]
    return mm(o.transpose(1, 0, 2).reshape(T, nh * hd), lp["wo"], control)


def mamba(hp, lp, x, boundary, control=None):
    """One Mamba-2 mixer over the whole sequence x [T, H] (normed): the
    recurrence as written, a scan over time."""
    T = x.shape[0]
    nh, P, G, N, W = hp["nh"], hp["P"], hp["G"], hp["N"], hp["W"]
    inner = hp["inner"]
    zxd = mm(x, lp["w_in"], control)
    z, xbc, dt = jnp.split(zxd, [inner, 2 * inner + 2 * G * N], -1)
    pos = jnp.arange(T)
    conv = lp["conv_b"]
    for j in range(W):
        # row W - 1 of the weight is on the current position
        back = W - 1 - j
        shifted = jnp.pad(xbc, ((back, 0), (0, 0)))[:T]
        if control == "conv_zeroed":
            # at or past the boundary, inputs from before it are gone
            lost = (pos >= boundary) & (pos - back < boundary)
            shifted = jnp.where(lost[:, None], 0.0, shifted)
        conv = conv + shifted * lp["conv_w"][j]
    xbc = jax.nn.silu(conv)
    xs, B, C = jnp.split(xbc, [inner, inner + G * N], -1)
    xs = xs.reshape(T, nh, P)
    # head h reads group h // (heads / G)
    of_head = jnp.arange(nh) // (nh // G)
    if control == "one_group":
        of_head = jnp.zeros_like(of_head)
    B = B.reshape(T, G, N)[:, of_head]                 # [T, nh, N]
    C = C.reshape(T, G, N)[:, of_head]
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])

    def step(S, inp):
        t, x_t, dt_t, B_t, C_t = inp
        if control == "state_zeroed":
            S = jnp.where(t == boundary, 0.0, S)
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if control == "state_bf16":
            S = to_bf16(S)
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32),
                        (pos, xs, dt, B, C))
    y = (y + lp["D"][:, None] * xs).reshape(T, inner)
    gate = jax.nn.silu(z)
    # the norm over EACH GROUP's channels; one gain of inner
    per = (1, inner) if control == "norm_all" else (G, inner // G)

    def normed(a):
        a = a.reshape(T, *per)
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + hp["eps"])
        return a.reshape(T, inner) * lp["norm"]

    y = normed(y) * gate if control == "gate_after_norm" else normed(y * gate)
    return mm(y, lp["w_out"], control)


def combine_weights(hp, x, wr, bias, control=None):
    """[T, E] float32 over ALL the published experts: the normalised,
    scaled scores of the picks, zero where the router did not pick."""
    s = jax.nn.sigmoid(x @ wr)
    _, sel = jax.lax.top_k(s + bias, hp["top_k"])
    w = jnp.take_along_axis(s + bias if control == "bias_in_weights" else s,
                            sel, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if control != "scale_1":
        w = w * hp["scale"]
    if control == "picks_wrapped":
        sel = hp["first"] + sel % hp["held"]
    return jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], sel].add(w)


def expert_block(x, w, wu, wd, control):
    """The contribution of a block of experts: x [T, H], w [T, n]."""
    wu, wd = wu.astype(jnp.float32), wd.astype(jnp.float32)
    if control == "fp8":
        x, wu, wd = to_fp8(x, -1), to_fp8(wu, 1), to_fp8(wd, 1)
    a = act(jnp.einsum("th,ehi->eti", x, wu), control)
    if control == "fp8":
        a = to_fp8(a, -1)
    return jnp.einsum("te,eth->th", w, jnp.einsum("eti,eih->eth", a, wd))


def routed(hp, lp, x, control=None):
    """This share's part of the routed experts' sum: x [T, H] -> [T, H].
    ``lp`` holds the experts ``hp["first"]`` .. + held of the router's."""
    held = lp["we_u"].shape[0]
    w = combine_weights(hp, x, lp["wr"].astype(jnp.float32),
                        lp["bias"].astype(jnp.float32), control)
    w = jax.lax.dynamic_slice_in_dim(w, hp["first"], held, 1)
    n = max(1, min(held, EXPERT_BLOCK_ROWS // max(x.shape[0], 1)))
    while held % n:
        n -= 1
    y = jnp.zeros_like(x)
    for e0 in range(0, held, n):
        y = y + expert_block(x, w[:, e0:e0 + n], lp["we_u"][e0:e0 + n],
                             lp["we_d"][e0:e0 + n], control)
    return y


def shared(lp, x, control=None):
    return mlp(x, lp["ws_u"].astype(jnp.float32),
               lp["ws_d"].astype(jnp.float32), control)


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
        "mlp_hidden_act": hf["mlp_hidden_act"] != "relu2",
        "mamba_hidden_act": hf["mamba_hidden_act"] != "silu",
        "n_group": hf["n_group"] != 1 or hf["topk_group"] != 1,
        "norm_topk_prob": not hf["norm_topk_prob"],
        "n_shared_experts": hf["n_shared_experts"] != 1,
        "biases": bool(hf.get("attention_bias") or hf.get("mlp_bias")
                       or hf.get("use_bias") or hf.get("mamba_proj_bias")),
        "use_conv_bias": not hf["use_conv_bias"],
        "sliding_window": hf.get("sliding_window") is not None,
        "pattern": (not set(hf["hybrid_override_pattern"]) <= set("ME*-")
                    or len(hf["hybrid_override_pattern"])
                    != hf["num_hidden_layers"]),
    }
    if any(refused.values()):
        raise ValueError("the reference does not build "
                         f"{sorted(k for k, v in refused.items() if v)}")
    held_to_stated_weights(params)
    hp = hyper(hf)
    boundary = max(hf["engine"]["prefill_buckets"])
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    small = lambda lp: f32({k: v for k, v in lp.items()  # noqa: E731
                            if not k.startswith("we_")})

    # jitted only so that each piece is one program instead of dozens of
    # eager ops; one program a layer KIND
    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(lp, h, kind):
        big = {k: v for k, v in lp.items() if k.startswith("we_")}
        lp = dict(small(lp), **big)
        x = rms_norm(h, lp["ln1"], hp["eps"])
        if kind == "M":
            return h + mamba(hp, lp, x, boundary, control)
        if kind == "*":
            return h + attention(hp, lp, x, control)
        if kind == "-":
            return h + mlp(x, lp["w_u"], lp["w_d"], control)
        y = routed(hp, lp, x, control)
        if control != "no_shared":
            y = y + shared(lp, x, control)
        return h + y

    head_w = params["embed"] if hp["tied"] else params["head"]
    V = params["embed"].shape[0]
    vb = min(VOCAB_BLOCK, V)

    @jax.jit
    def head_block(norm_f, w, h, pos, v0):
        h = rms_norm(h[pos], norm_f.astype(jnp.float32), hp["eps"])
        if hp["tied"]:
            cols = jax.lax.dynamic_slice_in_dim(w, v0, vb, 0).T
        else:
            cols = jax.lax.dynamic_slice_in_dim(w, v0, vb, 1)
        return mm(h, cols.astype(jnp.float32), control)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        for kind, lp in zip(hp["pattern"], params["layers"]):
            h = layer(lp, h, kind=kind)
        pos = jnp.asarray(positions, jnp.int32)
        blocks = []
        for v0 in range(0, V, vb):
            # the last block slides back (dynamic_slice clamps): cut what
            # it repeats
            got = np.asarray(head_block(params["norm_f"], head_w, h, pos,
                                        jnp.int32(v0)))
            blocks.append(got[:, max(0, v0 + vb - V):])
        logits = np.concatenate(blocks, -1).astype(np.float64)
    logits -= logits.max(-1, keepdims=True)
    return (logits - np.log(np.exp(logits).sum(-1, keepdims=True))).astype(
        np.float32)
