"""The plain reference of the state-space + attention hybrid with routed
experts (``granitemoehybrid`` as granite-4.0-h-small's config.json
parameterises it).

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, the whole sequence at once, the Mamba-2 recurrence AS
WRITTEN (a ``lax.scan`` over time, one position a step; no chunks), no
cache, no batching, no kernels, no padding. It imports nothing from the
program and takes the engine's own weight pytree, so both sides compute
the same model. Large pieces are computed in blocks (attention a KV head
at a time, experts ``EXPERT_BLOCK`` at a time, the head ``VOCAB_BLOCK``
rows at a time) so that the reference fits beside a 13 GB engine; a
block changes no sum.

The block (h [T, hidden]; RMSNorm eps from the config; no biases but the
convolution's):

  h0 = embed[ids] * embedding_multiplier. For each layer, with r =
  residual_multiplier: h += r * mixer(norm(h)); h += r * (experts(x) +
  shared(x)), x = norm(h). Logits: norm(h) @ embed^T / logits_scaling.
  attention layers (``layer_types[l] == "attention"``): q, k, v
    projections, GQA, NO rotary, causal softmax with scale
    attention_multiplier, output projection.
  mamba layers: [z | xBC | dt] = u W_in (d_inner / d_inner + 2 d_state /
    heads); xBC = silu(causal depthwise conv (width d_conv) + b); x
    [heads, d_head], B, C [d_state] shared by all heads; dt = softplus(dt
    + dt_bias), A = -exp(A_log); per head S_t = exp(dt_t A) S_{t-1} +
    dt_t (x_t outer B_t), y_t = S_t C_t + D x_t; y = y * silu(z) FIRST,
    then RMSNorm over all d_inner values with a learned gain; y W_out.
  experts, every layer: logits = x W_r over ALL the published experts;
    the top k LOGITS are picked, weights = softmax over the picked;
    expert e = (silu(a) * b) W_out^e with [a | b] = x W_in^e; the shared
    MLP has the same form and is added ungated.

THE SHARE. The configuration holds ``num_local_experts`` of the
``expert_share.published_experts`` a layer (experts ``index * held`` up to
``(index + 1) * held``): the weight pytree has those experts only, the
router keeps its published width and its picks, and a pick that lands on
an expert held elsewhere adds nothing, here as in the program. What the
other chips would add is absent from the result that goes on to the next
layer. ``routed`` and ``shared`` are the two parts of a layer, exposed so
that a test can add the shares up to the uncut layer.

Weights (the program's pytree): ``embed`` [V, H], ``norm_f``, and
``layers``, a list of one dict a layer: ln1, ln2, wr [H, E], we_g / we_u
[held, H, I] (the two halves of the published fused input matrix), we_d
[held, I, H], ws_g / ws_u / ws_d; attention layers wq, wk, wv, wo; mamba
layers w_in, conv_w [d_conv, conv_dim] (row d_conv - 1 on the current
position), conv_b, A_log, dt_bias, D (float32), norm, w_out.

Departures from the published model: none in the mathematics.

``control`` (never set by the benchmark; tools/mla_moe_control.py
--config granite4h-ep2-d10 and the CPU tests set it) computes what a
FAULTY program would, to show what the tolerances below catch. ``boundary`` is the position of the first
chunk boundary the long prompt crosses (the largest prefill bucket):
  ``"state_zeroed"``  the SSM state dropped at the boundary;
  ``"conv_zeroed"``   the convolution's window dropped at the boundary;
  ``"padding"``       the padding of the prompt's last bucket let into
      the state: the pad tokens (id 0) run through the stack after the
      prompt, invisible to attention (NoPE: positions carry nothing) and
      fed to the recurrence, before the first decode step;
  ``"no_multipliers"``  the four multipliers left out (1, 1, 1 /
      sqrt(head_dim), 1);
  ``"gate_after_norm"``  norm(y) * silu(z) instead of norm(y * silu(z));
  ``"fp8"``  both operands of every matmul rounded to float8_e4m3fn;
  ``"state_bf16"``  the SSM state rounded to bfloat16 after every step
      (reported whichever way it reads: not required to fail). It PASSES
      (mean 0.0015-0.0022, max 0.011-0.017): this check does not hold the
      state's precision, and the configuration's ``assumed`` says what does.

THE TOLERANCES, their reasons and the readings behind them: see the
constants below and PERF.md section 6 (PR 41).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (prompt tokens, decode steps): two prompts over 4096 (a fresh and a
# continuing chunk: the state and the window cross a chunk boundary): one
# whose continuing chunk is 204 tokens, and one that ends ON the boundary's
# first position (4097: its last logits and first decode steps stand on
# what crossed, which is where a dropped window shows: at 4300 that control
# read as sound, 0.0098 / 0.00112 against 0.0098 / 0.00108, PERF.md
# section 6); one short and no multiple of the scan's 256 (a ragged last
# chunk), one far shorter than its 2048 bucket (padding must not reach the
# state); 48 decode steps each cross twelve round boundaries of 4
CHECK_PROMPTS = ((4300, 48), (4097, 48), (333, 48), (1100, 48))
# Set from the chip's readings (PERF.md section 6, PR 41: twenty-two
# weight seeds sound, the controls at four of them, these prompts). Unlike
# the other routed-expert blocks the distance between a sound bfloat16
# program and this float32 reference is NOT set by the router's near-ties
# here: it is plain rounding (max 0.0070-0.0143, mean 0.00104-0.00147, no
# outlier at any seed; the residual multiplier 0.22 and logits / 16 damp
# what a swapped tenth pick adds).
# MEAN judges: 2.0x the largest sound reading (0.00147), 0.63x the
# smallest reading of the weakest required control (conv_zeroed 0.00475-
# 0.0052; then state_zeroed 0.0096, fp8 0.0110, padding 0.070,
# gate_after_norm 0.077, no_multipliers 0.84). state_bf16 (0.0015-0.0020)
# passes, as reported.
# MAX is an extreme of 3840 comparisons, so it gets the wider room above:
# 2.1x the largest sound reading (0.0143), 0.69x the smallest fp8 reading
# (0.0433-0.0588); every boundary control stands over it (state_zeroed
# 0.163, conv_zeroed 0.254, gate_after_norm 0.347, padding 0.883).
CHECK_TOL_MAX = 0.03
CHECK_TOL_MEAN = 0.003
# what tools/mla_moe_control.py runs against this check: each of the first
# has to FAIL it, the last is reported whichever way it reads
CONTROLS_REQUIRED = ("state_zeroed", "conv_zeroed", "padding",
                     "no_multipliers", "gate_after_norm", "fp8")
CONTROLS_NAMED = ("state_bf16",)

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


def swiglu(x, wg, wu, wd, control=None):
    return mm(jax.nn.silu(mm(x, wg, control)) * mm(x, wu, control), wd,
              control)


def hyper(hf: dict, control=None) -> dict:
    share = hf.get("expert_share") or {
        "published_experts": hf["num_local_experts"], "of": 1, "index": 0}
    none = control == "no_multipliers"
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    return {
        "kinds": list(hf["layer_types"]), "eps": float(hf["rms_norm_eps"]),
        "heads": heads, "kv_heads": hf["num_key_value_heads"], "hd": hd,
        "nh": hf["mamba_n_heads"], "P": hf["mamba_d_head"],
        "N": hf["mamba_d_state"], "W": hf["mamba_d_conv"],
        "inner": hf["mamba_n_heads"] * hf["mamba_d_head"],
        "top_k": hf["num_experts_per_tok"],
        "first": share["index"] * hf["num_local_experts"],
        "emb": 1.0 if none else float(hf["embedding_multiplier"]),
        "res": 1.0 if none else float(hf["residual_multiplier"]),
        "att": (1.0 / np.sqrt(hd) if none
                else float(hf["attention_multiplier"])),
        "logit": 1.0 if none else float(hf["logits_scaling"]),
    }


def attention(hp, lp, x, visible, control=None):
    """NoPE GQA over the whole sequence x [T, H] (already normed).
    ``visible`` [T] bool: False keys are seen by no query (the padding
    control's pad positions; all True otherwise)."""
    T = x.shape[0]
    nh, kvh, hd = hp["heads"], hp["kv_heads"], hp["hd"]
    rep = nh // kvh
    q = mm(x, lp["wq"], control).reshape(T, kvh, rep, hd)
    k = mm(x, lp["wk"], control).reshape(T, kvh, hd)
    v = mm(x, lp["wv"], control).reshape(T, kvh, hd)
    pos = jnp.arange(T)
    ok = (pos[:, None] >= pos[None, :]) & visible[None, :]

    def group(g):   # one KV head and its query heads at a time
        s = jnp.einsum("trd,sd->rts", q[:, g], k[:, g]) * hp["att"]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
        return jnp.einsum("rts,sd->trd", p, v[:, g])

    o = jax.lax.map(group, jnp.arange(kvh))            # [kvh, T, rep, hd]
    o = o.transpose(1, 0, 2, 3).reshape(T, nh * hd)
    return mm(o, lp["wo"], control)


def mamba(hp, lp, x, boundary, control=None):
    """One Mamba-2 mixer over the whole sequence x [T, H] (already
    normed): the recurrence as written, a scan over time."""
    T = x.shape[0]
    nh, P, N, W, inner = hp["nh"], hp["P"], hp["N"], hp["W"], hp["inner"]
    zxd = mm(x, lp["w_in"], control)
    z, xbc, dt = jnp.split(zxd, [inner, 2 * inner + 2 * N], -1)
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
    xs, B, C = jnp.split(xbc, [inner, inner + N], -1)
    xs = xs.reshape(T, nh, P)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])

    def step(S, inp):
        t, x_t, dt_t, B_t, C_t = inp
        if control == "state_zeroed":
            S = jnp.where(t == boundary, 0.0, S)
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        if control == "state_bf16":
            S = to_bf16(S)
        return S, jnp.einsum("hpn,n->hp", S, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32),
                        (pos, xs, dt, B, C))
    y = (y + lp["D"][:, None] * xs).reshape(T, inner)
    if control == "gate_after_norm":
        y = rms_norm(y, lp["norm"], hp["eps"]) * jax.nn.silu(z)
    else:
        y = rms_norm(y * jax.nn.silu(z), lp["norm"], hp["eps"])
    return mm(y, lp["w_out"], control)


def combine_weights(hp, x2, wr):
    """[T, E] float32 over ALL the published experts: softmax over the
    top k logits, zero where the router did not pick."""
    logits = x2 @ wr
    top, sel = jax.lax.top_k(logits, hp["top_k"])
    w = jax.nn.softmax(top, -1)
    return jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], sel].set(w)


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


def routed(hp, lp, x2, control=None):
    """This share's part of the routed experts' sum: x2 [T, H] -> [T, H].
    ``lp`` holds the experts ``hp["first"]`` .. + held of the router's."""
    held = lp["we_g"].shape[0]
    w = combine_weights(hp, x2, lp["wr"].astype(jnp.float32))
    w = jax.lax.dynamic_slice_in_dim(w, hp["first"], held, 1)
    n = max(1, min(held, EXPERT_BLOCK_ROWS // max(x2.shape[0], 1)))
    while held % n:
        n -= 1
    y = jnp.zeros_like(x2)
    for e0 in range(0, held, n):
        y = y + expert_block(
            x2, w[:, e0:e0 + n], lp["we_g"][e0:e0 + n],
            lp["we_u"][e0:e0 + n], lp["we_d"][e0:e0 + n], control)
    return y


def shared(lp, x2, control=None):
    return swiglu(x2, *(lp[n].astype(jnp.float32)
                        for n in ("ws_g", "ws_u", "ws_d")), control)


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


def padded_for_control(hf: dict, tokens, positions):
    """The ``padding`` control's sequence: the prompt, the pad tokens of
    its last chunk's bucket (id 0), then the decoded tokens; the
    positions moved past the pads; and which keys attention may see."""
    n = positions[0] + 1                       # the prompt's length
    buckets = sorted(hf["engine"]["prefill_buckets"])
    last = n % buckets[-1] or buckets[-1]
    pads = next(b for b in buckets if b >= last) - last
    seq = list(tokens[:n]) + [0] * pads + list(tokens[n:])
    moved = [p if p < n - 1 else p + pads for p in positions]
    # the logits after the prompt are the prompt's own (computed before
    # any pad): only the decode steps see the fault
    visible = np.ones(len(seq), bool)
    visible[n:n + pads] = False
    return seq, moved, visible


def logprobs(hf: dict, params: dict, tokens: list[int], positions: list[int],
             control: str | None = None) -> np.ndarray:
    """log-softmax over the vocabulary, [len(positions), V], of the next
    token after each of ``positions`` of ``tokens``."""
    refused = {
        "position_embedding_type": hf.get("position_embedding_type") != "nope",
        "mamba_n_groups": hf["mamba_n_groups"] != 1,
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": not hf.get("tie_word_embeddings"),
        "biases": bool(hf.get("attention_bias") or hf["mamba_proj_bias"]),
    }
    if any(refused.values()):
        raise ValueError("the reference does not build "
                         f"{sorted(k for k, v in refused.items() if v)}")
    held_to_stated_weights(params)
    hp = hyper(hf, control)
    boundary = max(hf["engine"]["prefill_buckets"])
    visible = np.ones(len(tokens), bool)
    if control == "padding":
        tokens, positions, visible = padded_for_control(hf, tokens, positions)
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    small = lambda lp: f32({k: v for k, v in lp.items()  # noqa: E731
                            if not k.startswith("we_")})

    # jitted only so that each piece is one program instead of dozens of
    # eager ops; one program a layer KIND
    @functools.partial(jax.jit, static_argnames=("kind",))
    def mixer(lp, h, visible, kind):
        lp = small(lp)
        x = rms_norm(h, lp["ln1"], hp["eps"])
        mix = (attention(hp, lp, x, visible, control) if kind == "attention"
               else mamba(hp, lp, x, boundary, control))
        h = h + hp["res"] * mix
        return h, rms_norm(h, lp["ln2"], hp["eps"])

    @jax.jit
    def experts(lp, h, x2):
        return h + hp["res"] * (routed(hp, lp, x2, control)
                                + shared(lp, x2, control))

    V = params["embed"].shape[0]
    vb = min(VOCAB_BLOCK, V)

    @jax.jit
    def head_block(norm_f, embed, h, pos, v0):
        h = rms_norm(h[pos], norm_f.astype(jnp.float32), hp["eps"])
        rows = jax.lax.dynamic_slice_in_dim(embed, v0, vb, 0)
        return mm(h, rows.astype(jnp.float32).T, control) / hp["logit"]

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32) * hp["emb"]
        vis = jnp.asarray(visible)
        for kind, lp in zip(hp["kinds"], params["layers"]):
            h, x2 = mixer(lp, h, vis, kind=kind)
            h = experts(lp, h, x2)
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
