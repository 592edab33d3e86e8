"""The plain reference of the linear-attention + block-sparse-attention
hybrid (``minicpm_sala`` as MiniCPM-SALA's config.json parameterises it,
with the selection's geometry under ``sparse_config``).

Straightforward ``jax.numpy``: float32, ``jax.default_matmul_precision(
"highest")``, the whole sequence at once, the linear attention's
recurrence AS WRITTEN (a ``lax.scan`` over time, one position a step; no
chunks), the selection by ``argsort`` over explicit block scores, no
cache, no batching, no kernels, no padding. It imports nothing from the
program and takes the engine's own weight pytree, so both sides compute
the same model. Large pieces are computed in blocks (the MLP ``ROW_BLOCK``
rows and ``MLP_COLUMN_BLOCK`` intermediate columns at a time, the linear
attention ``HEAD_BLOCK`` heads at a time, the sparse attention a K/V group
and ``QUERY_BLOCK`` queries at a time, the head ``VOCAB_BLOCK`` columns at a time) so that a
12k-token sequence fits beside a 13 GB engine; a block changes no sum.

The stack (h [T, hidden]; RMSNorm eps from the config; no biases):

  h0 = scale_emb x embed[ids]. For each layer, with r = scale_depth /
  sqrt(depth_scale_layers) (the PUBLISHED depth, whatever cut the file
  holds): h += r x mixer(norm(h)); h += r x MLP(norm(h)), MLP(x) =
  (silu(x W_g) * x W_u) W_d. Logits: (norm(h) / (hidden /
  dim_model_base)) W_head, untied.
  ``lightning-attn``: q, k, v = x W_q, x W_k, x W_v, each [T, heads, D];
    RMSNorm over D on q and on k (learned gain); rotary (rotate-half
    over the whole head, theta) on q and k; per head S_t = lam_h S_{t-1}
    + k_t^T v_t, o_t = (q_t / sqrt(D)) S_t, lam_h = exp(-2^(-8 (h + 1) /
    heads)); RMSNorm over the heads x D concatenated values (learned
    gain); o * sigmoid(x W_z); W_o.
  ``minicpm4``: q [T, heads, hd], k, v [T, kv_heads, hd]; RMSNorm over hd
    on q and k; NO rotary; scale 1 / sqrt(hd). Compressed keys a K/V
    head: kc_j = mean(k[stride j : stride j + kernel]), visible to a query
    at t once stride j + kernel - 1 <= t. A query at t >= dense_len: a
    head's p_h = softmax_j(q_h . kc_j / sqrt(hd)) over the visible j; its
    K/V group's s[j] = sum_h p_h[j]; a block's score = max of s over the
    kernels whose rows overlap the block; chosen: the first init_blocks
    blocks, every block that overlaps the last window_size positions, and
    the best-scored others until topk blocks in all; one softmax over the
    tokens u <= t of the chosen blocks, each head its own scores, the
    group one set. A query at t < dense_len attends every u <= t. Then o *
    sigmoid(x W_z); W_o.

Departures from the published code, both stated in the configuration's
``assumed``: the dense / sparse switch is per query POSITION (t >=
dense_len), where the published code switches per call on the call's
length (a chunked prefill followed by token-by-token decode can only
reproduce a rule per position); and the values the catalog's copy of the
config does not give (``sparse_config``, the decay's slopes) are the
family's published conventions.

Weights (the program's pytree): ``embed`` [V, H], ``head`` [H, V],
``norm_f``, and ``layers``, one dict a layer: ln1, ln2, w_g, w_u [H, I],
w_d [I, H]; wq, wk, wv, wo, wz (the gate), q_norm, k_norm; linear layers
also o_norm.

``control`` (never set by the benchmark; tools/mla_moe_control.py
--config minicpm-sala-d16 and the CPU tests set it) computes what a
FAULTY program would, to show what the tolerances below catch.
``boundary`` is the first chunk boundary a long prompt crosses (the
largest prefill bucket), ``n`` the prompt's length:
  ``"fp8"``  both operands of every weight matmul rounded to
      float8_e4m3fn (a precision under the stated bfloat16);
  ``"state_zeroed"``  the linear layers' state dropped at the boundary;
  ``"decay_one"``  lam_h = 1: nothing is forgotten;
  ``"no_topk"``  the selection keeps the forced blocks only (the leading
      ones and the window);
  ``"kc_stale"``  decode selects on the prompt's compressed keys only
      (those complete at position n - 1);
  ``"no_gate"``  the sigmoid gates left out of both mixers;
  ``"state_bf16"``  the linear layers' state rounded to bfloat16 after
      every step (reported whichever way it reads: not required to fail).

THE TOLERANCES, their reasons and the readings behind them: see the
constants below and PERF.md section 6 (PR 45).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (prompt tokens, decode steps). One short: every query below dense_len,
# the dense read only. One of 4097: a fresh and a continuing chunk, so the
# linear layers' state crosses a chunk boundary and the last logits and
# first decode steps stand on what crossed. One that ends at 8170, so that
# its 48 decode steps cross dense_len (8192): the lane changes from the
# dense read to the selected one mid-stream, and compressed keys completed
# by decode steps enter the selection. One of 12300 (three chunks of 4096
# and one of 12): the third chunk's prefill and every decode step select
# 64 of ~130-193 blocks. 48 decode steps each cross twelve round
# boundaries of 4.
CHECK_PROMPTS = ((333, 48), (4097, 48), (8170, 48), (12300, 48))
# Set from the chip's readings at the published widths (PERF.md section 6,
# PR 45: weight seeds sound and the controls, these prompts); the reasons
# stand beside the numbers there and in the configuration's ``assumed``.
CHECK_TOL_MAX = 0.1
CHECK_TOL_MEAN = 0.01
# what tools/mla_moe_control.py runs against this check: each of the first
# has to FAIL it, the last two are reported whichever way they read (both
# PASS on the chip, see above)
CONTROLS_REQUIRED = ("fp8", "state_zeroed", "decay_one", "no_topk",
                     "no_gate")
CONTROLS_NAMED = ("kc_stale", "state_bf16")

ROW_BLOCK = 2048      # MLP rows at a time
MLP_COLUMN_BLOCK = 4096   # and columns of its intermediate width
HEAD_BLOCK = 8        # linear-attention heads at a time
QUERY_BLOCK = 128     # sparse attention queries at a time
VOCAB_BLOCK = 16384   # head columns at a time

FP8_MAX = 448.0   # largest finite float8_e4m3fn


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


def hyper(hf: dict) -> dict:
    sp = hf["sparse_config"]
    heads = hf["num_attention_heads"]
    return {
        "kinds": list(hf["mixer_types"]), "eps": float(hf["rms_norm_eps"]),
        "heads": heads, "kv_heads": hf["num_key_value_heads"],
        "hd": hf.get("head_dim") or hf["hidden_size"] // heads,
        "lin_heads": hf["lightning_nh"], "D": hf["lightning_head_dim"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "emb": float(hf["scale_emb"]),
        "res": float(hf["scale_depth"]) / np.sqrt(
            hf.get("depth_scale_layers", hf["num_hidden_layers"])),
        "logit": hf["hidden_size"] / hf["dim_model_base"],
        "kernel": sp["kernel_size"], "stride": sp["kernel_stride"],
        "block": sp["block_size"], "topk": sp["topk"],
        "init": sp["init_blocks"], "window": sp["window_size"],
        "dense_len": sp["dense_len"],
    }


def rotate_half(x, pos, theta):
    """x [T, heads, D] at positions pos [T]: the first half of a head
    pairs with the second."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def lightning(hp, lp, x, boundary, control=None):
    """One linear-attention mixer over the whole sequence x [T, H]
    (already normed): the recurrence as written, a scan over time."""
    T = x.shape[0]
    nh, D = hp["lin_heads"], hp["D"]
    pos = jnp.arange(T)
    hb = min(HEAD_BLOCK, nh)
    lam_all = jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, nh + 1) / nh)))
    if control == "decay_one":
        lam_all = jnp.ones_like(lam_all)
    q_gain = lp["q_norm"].astype(jnp.float32)
    k_gain = lp["k_norm"].astype(jnp.float32)

    def heads(h0):
        """``hb`` heads from head h0 on: heads are independent until the
        output norm, and a few at a time keep the float32 q, k, v of a
        12k-token sequence small."""
        cols = lambda w: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, h0 * D, hb * D, 1)
        q = mm(x, cols(lp["wq"]), control).reshape(T, hb, D)
        k = mm(x, cols(lp["wk"]), control).reshape(T, hb, D)
        v = mm(x, cols(lp["wv"]), control).reshape(T, hb, D)
        q = rotate_half(rms_norm(q, q_gain, hp["eps"]), pos,
                        hp["theta"]) / np.sqrt(D)
        k = rotate_half(rms_norm(k, k_gain, hp["eps"]), pos, hp["theta"])
        lam = jax.lax.dynamic_slice_in_dim(lam_all, h0, hb)

        def step(S, inp):
            t, q_t, k_t, v_t = inp
            if control == "state_zeroed":
                S = jnp.where(t == boundary, 0.0, S)
            S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
            if control == "state_bf16":
                S = to_bf16(S)
            return S, jnp.einsum("hd,hde->he", q_t, S)

        _, o = jax.lax.scan(step, jnp.zeros((hb, D, D), jnp.float32),
                            (pos, q, k, v))
        return o                                          # [T, hb, D]

    o = jax.lax.map(heads, jnp.arange(0, nh, hb))       # [nh / hb, T, hb, D]
    o = o.transpose(1, 0, 2, 3)
    o = rms_norm(o.reshape(T, nh * D), lp["o_norm"].astype(jnp.float32),
                 hp["eps"])
    if control != "no_gate":
        o = o * jax.nn.sigmoid(mm(x, lp["wz"], control))
    return mm(o, lp["wo"], control)


def sparse(hp, lp, x, n_prompt, control=None):
    """One block-sparse mixer over the whole sequence x [T, H] (already
    normed), a K/V group and a block of queries at a time."""
    T = x.shape[0]
    nh, kvh, hd = hp["heads"], hp["kv_heads"], hp["hd"]
    rep = nh // kvh
    block, stride, kernel = hp["block"], hp["stride"], hp["kernel"]
    q = rms_norm(mm(x, lp["wq"], control).reshape(T, kvh, rep, hd),
                 lp["q_norm"].astype(jnp.float32), hp["eps"])
    k = rms_norm(mm(x, lp["wk"], control).reshape(T, kvh, hd),
                 lp["k_norm"].astype(jnp.float32), hp["eps"])
    v = mm(x, lp["wv"], control).reshape(T, kvh, hd)
    scale = 1.0 / np.sqrt(hd)
    # compressed keys: every window that lies wholly inside the sequence
    J = max((T - kernel) // stride + 1, 0)
    NB = -(-T // block)
    pos = jnp.arange(T)
    QB = min(QUERY_BLOCK, T)
    n_q = -(-T // QB)
    pad = n_q * QB - T
    # which compressed keys overlap which block, by the rows they cover:
    # a short list a block (-1 pads it), so that a block's score is a max
    # over a few gathered columns and not over a [queries, NB, J] tensor
    j0 = np.arange(J) * stride
    b0 = np.arange(NB) * block
    over = [np.flatnonzero((j0 < b + block) & (j0 + kernel > b)) for b in b0]
    width = max([len(js) for js in over] + [1])
    overlap = np.full((NB, width), -1)
    for b, js in enumerate(over):
        overlap[b, :len(js)] = js
    overlap = jnp.asarray(overlap)

    def group(g):
        k_g, v_g = k[:, g], v[:, g]                          # [T, hd]
        if J:
            idx = j0[:, None] + np.arange(kernel)[None, :]
            kc = k_g[idx].mean(1)                            # [J, hd]
        q_g = jnp.pad(q[:, g], ((0, pad), (0, 0), (0, 0)))

        def queries(i):
            t = i * QB + jnp.arange(QB)                      # [QB]
            q_b = jax.lax.dynamic_slice_in_dim(q_g, i * QB, QB, 0)
            ok = pos[None, :] <= t[:, None]                  # [QB, T]
            if J:
                seen = t if control != "kc_stale" else jnp.minimum(
                    t, n_prompt - 1)
                visible = (j0[None, :] + kernel - 1 <= seen[:, None])
                lc = jnp.einsum("qrd,jd->rqj", q_b, kc) * scale
                p = jax.nn.softmax(
                    jnp.where(visible[None], lc, -jnp.inf), -1)
                s = jnp.where(visible, p.sum(0), 0.0)        # [QB, J]
                score = jnp.max(jnp.where(
                    overlap[None] >= 0, s[:, jnp.maximum(overlap, 0)], 0.0),
                    -1)                                      # [QB, NB]
                forced = (b0[None, :] < hp["init"] * block) | (
                    (b0[None, :] + block - 1 >= t[:, None] - hp["window"] + 1))
                valid = b0[None, :] <= t[:, None]
                forced = forced & valid
                key = jnp.where(forced, jnp.inf, score)
                key = jnp.where(valid, key, -jnp.inf)
                rank = jnp.argsort(jnp.argsort(-key, -1, stable=True), -1)
                chosen = (rank < hp["topk"]) & valid
                if control == "no_topk":
                    chosen = forced
                chosen = chosen | (t < hp["dense_len"])[:, None]
                ok = ok & jnp.repeat(chosen, block, axis=1)[:, :T]
            sc = jnp.einsum("qrd,td->rqt", q_b, k_g) * scale
            p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), -1)
            return jnp.einsum("rqt,td->qrd", p, v_g)

        return jax.lax.map(queries, jnp.arange(n_q)).reshape(
            n_q * QB, rep, hd)[:T]

    o = jax.lax.map(group, jnp.arange(kvh))              # [kvh, T, rep, hd]
    o = o.transpose(1, 0, 2, 3).reshape(T, nh * hd)
    if control != "no_gate":
        o = o * jax.nn.sigmoid(mm(x, lp["wz"], control))
    return mm(o, lp["wo"], control)


def mlp(lp, x, control=None):
    """SwiGLU, ``ROW_BLOCK`` rows at a time."""
    T, H = x.shape
    rb = min(ROW_BLOCK, T)
    n = -(-T // rb)
    xb = jnp.pad(x, ((0, n * rb - T), (0, 0))).reshape(n, rb, H)

    I = lp["w_g"].shape[1]
    cb = min(MLP_COLUMN_BLOCK, I)

    def rows(xr):
        def columns(j, y):
            """``cb`` of the intermediate columns: their part of the sum
            over the intermediate width (float32 copies of the three
            matrices are then 0.2 GB, not 0.8)."""
            wg, wu = (jax.lax.dynamic_slice_in_dim(lp[n], j * cb, cb, 1)
                      for n in ("w_g", "w_u"))
            wd = jax.lax.dynamic_slice_in_dim(lp["w_d"], j * cb, cb, 0)
            a = jax.nn.silu(mm(xr, wg, control)) * mm(xr, wu, control)
            return y + mm(a, wd, control)

        return jax.lax.fori_loop(0, I // cb, columns, jnp.zeros_like(xr))

    return jax.lax.map(rows, xb).reshape(n * rb, H)[:T]


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
    """log-softmax over the vocabulary, [len(positions), V], of the next
    token after each of ``positions`` of ``tokens``."""
    sp = hf.get("sparse_config") or {}
    refused = {
        "mixer_types": not set(hf["mixer_types"]) <= {"lightning-attn",
                                                      "minicpm4"},
        "qk_norm / gates / output norm off": not (
            hf["qk_norm"] and hf["use_output_gate"] and hf["use_output_norm"]
            and hf["attn_use_output_gate"]),
        "attn_use_rope": bool(hf["attn_use_rope"]),
        "lightning_use_rope off": not hf["lightning_use_rope"],
        "lightning_scale": hf["lightning_scale"] != "1/sqrt(d)",
        "lightning_nkv": hf["lightning_nkv"] != hf["lightning_nh"],
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
        "attention_bias": bool(hf.get("attention_bias")),
        "sparse_config": set(sp) != {
            "kernel_size", "kernel_stride", "block_size", "topk",
            "init_blocks", "window_size", "dense_len"},
    }
    if any(refused.values()):
        raise ValueError("the reference does not build "
                         f"{sorted(k for k, v in refused.items() if v)}")
    held_to_stated_weights(params)
    hp = hyper(hf)
    boundary = max(hf["engine"]["prefill_buckets"])
    n_prompt = positions[0] + 1
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
        mix = (sparse(hp, lp, x, n_prompt, control) if kind == "minicpm4"
               else lightning(hp, lp, x, boundary, control))
        h = h + hp["res"] * mix
        return h, rms_norm(h, norms["ln2"], hp["eps"])

    @jax.jit
    def feed_forward(lp, h, x2):
        return h + hp["res"] * mlp(lp, x2, control)

    V = params["head"].shape[1]
    vb = min(VOCAB_BLOCK, V)

    @jax.jit
    def head_columns(head, v0):
        """``vb`` columns of the head, cut in a program of their own, so
        that no program holds a float32 copy of the whole [hidden, V]
        matrix."""
        return jax.lax.dynamic_slice_in_dim(head, v0, vb, 1)

    @jax.jit
    def head_block(norm_f, columns, h, pos):
        h = rms_norm(h[pos], norm_f.astype(jnp.float32),
                     hp["eps"]) / hp["logit"]
        return mm(h, columns, control)

    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32) * hp["emb"]
        for kind, lp in zip(hp["kinds"], params["layers"]):
            h, x2 = mixer(lp, h, kind=kind)
            # one layer at a time ON THE DEVICE too: JAX enqueues ahead and
            # gives every queued program its output buffers at once, 32
            # programs x 2 x [T, hidden] float32 (3 GB and more beside the
            # engine: the cell's memory peak until this line; my chip
            # runs, PR 45)
            h = feed_forward(lp, h, x2).block_until_ready()
        pos = jnp.asarray(positions, jnp.int32)
        blocks = []
        for v0 in range(0, V, vb):
            # the last block slides back (dynamic_slice clamps): cut what
            # it repeats
            got = np.asarray(head_block(
                params["norm_f"],
                head_columns(params["head"], jnp.int32(v0)), h, pos))
            blocks.append(got[:, max(0, v0 + vb - V):])
        logits = np.concatenate(blocks, -1).astype(np.float64)
    logits -= logits.max(-1, keepdims=True)
    return (logits - np.log(np.exp(logits).sum(-1, keepdims=True))).astype(
        np.float32)
