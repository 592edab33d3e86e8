"""The state-space + attention hybrid with routed experts on the served
path (the ``granitemoehybrid`` block): layers of TWO KINDS in one stack,
Mamba-2 mixers (ops/mamba2.py) and, one in a period, a NoPE GQA
attention layer; every layer then runs softmax-routed experts, of which
this chip may hold a SHARE, plus a shared MLP.

What differs from models/llama.py and models/mla_moe.py, and how the
engine meets it:

  - A LANE'S STATE IS NOT ROWS ONLY. The attention layers keep K/V rows,
    addressable by position, in a region of the dense geometry
    (``{"k", "v"}: [L_attn, kvh, lanes, S, hd]``: the dense movers, the
    ring and both attention ops are used as they are, at the attention
    layers' ordinal). Each Mamba layer keeps, per lane, a float32 SSM
    state [heads, d_head, d_state] and the last ``d_conv - 1`` inputs of
    its convolution: NOT addressable by position, the same size whatever
    the context. They live beside the rows in what ``init_ctx`` returns,
    under names that end in ``_state`` (``llama.row_kinds`` passes over
    them, so no mover sees them as a row): one array a Mamba layer,
    ``lanes + 1`` wide like the region.
  - DECODE updates them every step where the region is read-only until
    the round's flush: the engine's round carries them through its
    ``fori_loop``. A lane that is not live (freed, or being prefilled)
    steps with ``dt`` = 0: its state decays by exp(0) and is fed 0, i.e.
    it is written back bit for bit, with no select over the state.
  - PREFILL reads a continuing chunk's state from its lane (zeros when
    the chunk is fresh) and writes the state after the chunk's last REAL
    position: padding runs with ``dt`` 0 and the convolution's window is
    gathered at the real length. In a bucket of two row blocks or more a
    chunk's per-row work (both row-wise halves of every layer kind and the
    chunked scans) runs the blocks that hold a real row only
    (``live_row_block``, models/live_rows.py); the masks then cover the
    tail of the last live block.
  - NO PREFIX REUSE: a page of K/V rows without the recurrent state at
    its boundary cannot resume a prompt, so the engine turns its prefix
    cache off for this block, and the planes that move rows only refuse
    it by name (engine.py: ``_refuse_row_only_planes``).
  - EXPERTS. ``logits = x W_r`` over the PUBLISHED number of experts in
    float32; the top k logits are picked and the combine weights are the
    softmax over those k. The grouped product (models/moe.py) is told
    which experts it holds; a pick held elsewhere adds nothing here.
  - The four multipliers: the embedding x ``embedding_multiplier``, each
    sublayer's output x ``residual_multiplier``, the attention's softmax
    scale = ``attention_multiplier``, the logits / ``logits_scaling``;
    the head is the embedding (tied).

THE SAME STACK WITH OTHER MIXERS (a config whose ``layer_types`` name
them; nothing here names a model). What a layer does follows from its
kind, what a region holds from its leaves:

  - ``linear_attention`` (ops/lightning.py): q, k, v of [heads, D], an
    RMSNorm over D on q and k, rotary on both, then ``S_t = lam_h S_{t-1}
    + k_t^T v_t``, ``o_t = q_t S_t / sqrt(D)``; an RMSNorm over the
    concatenated heads, a sigmoid gate from the layer's input, W_o. Its
    state is a ``lin_state`` leaf [lanes + 1, heads, D, D] float32 a
    layer, stepped and written as the SSM state is (a lane that is not
    live: decay 1, key 0).
  - ``sparse_attention`` (ops/sparse_attention.py): NoPE GQA with an
    RMSNorm on q and k and a sigmoid gate, whose queries at or past
    ``dense_len`` attend a SELECTION of blocks scored on COMPRESSED keys.
    Those are a row kind of their own, ``kc`` [L_sparse, kvh, lanes, S /
    stride, hd]: one row every ``stride`` positions, written where K rows
    are written (by a prefill chunk's tail; by the decode STEP that
    completes one, so ``kc`` rides the round's carry beside the recurrent
    leaves: ``stepped_kinds``). Decode gathers the chosen blocks'
    rows from the region; a lane below ``dense_len`` takes the dense
    read. Prefill scores the whole causal context under the selection's
    mask.
  - ``kda`` (ops/kda.py): delta-rule linear attention with a gate per
    CHANNEL. q, k, v each pass a short causal depthwise convolution (one
    ``kda_conv_state`` leaf [lanes + 1, W - 1, 3 x heads x D] a layer: the
    three streams' windows side by side) and SiLU; q and k are
    L2-normalised over D, q scaled by D^-1/2; the gate ``g = bound x
    sigmoid(exp(A_log) (x W_f + dt_bias))`` in (bound, 0) a channel and the
    step ``b = sigmoid(x W_b)`` a head, both float32; ``S' = Diag(e^g) S``,
    ``S = S' + b k (v - S'^T k)^T``, ``o = S^T q`` on a ``kda_state`` leaf
    [lanes + 1, heads, D, D] float32 a layer; an RMSNorm over D a head, a
    sigmoid gate a HEAD from the layer's input, W_o. No rotary. On TPU
    devices the decode step is one Pallas kernel a layer that follows a
    work list of the LIVE lanes and rewrites their states in place (the
    XLA form steps every lane, one that is not live with g 0, b 0, k 0).
  - ``mamba1`` (ops/mamba1.py): the selective scan with a decay per
    channel AND state column. ``[x | z] = u W_in``; x through the short
    causal depthwise convolution (bias, SiLU; an ``m1_conv_state`` leaf
    [lanes + 1, W - 1, inner] a layer); ``[dt_r | B | C] = x W_x``, EACH
    through an RMSNorm with a gain (where the parameters hold the gains:
    a family without the inner norms has none); ``dt = softplus(dt_r W_dt
    + dt_bias)``, ``A = -exp(A_log)``; ``h = exp(dt A) h + (dt x) outer
    B``, ``y = h C + D x`` on an ``m1_state`` leaf [lanes + 1, d_state,
    inner] float32 a layer (channels MINOR: the published [inner, d_state]
    has a minor dimension of 16, which the chip tiles to 128); ``y silu(z)
    W_out``, no out-norm. Prefill is one Pallas kernel a scan block on TPU
    devices (a ``lax.scan`` elsewhere), decode one kernel a layer over the
    live lanes' states in place, as the delta-rule step.
  - THE DIFFERENTIAL FORM (a config with ``differential``) on the
    ``attention`` kind and on the two kinds below: adjacent heads pair.
    Query pair j = heads (2j, 2j + 1), K/V pair g = heads (2g, 2g + 1);
    ``A_i = softmax(q_i k_i^T / sqrt(hd))``, ``o = (A_1 - lam A_2) [v_2g |
    v_2g+1]``, an RMSNorm over the 2 hd values (one gain a layer) x (1 -
    lam_init), W_o; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
    ``lam_init = 0.8 - 0.6 exp(-0.3 l)``; projection biases. A K/V head
    PAIR is held as ONE row of 2 hd (``row_heads``: the region is [L, kvh /
    2, lanes, S, 2 hd], a free reshape of the projections' columns), and a
    query head rides in zero-padded to 2 hd, its values in its own half:
    the two score maps are then one pass of the attention ops as they are
    (GQA group 4 over 128-wide rows), subtracted afterwards. With
    ``layer_norm`` the stack's norms are LayerNorms (mean removed, gain and
    bias).
  - ``window_attention``: that form behind a window of ``window``
    positions. Its rows are a row kind of their own, ``wk`` / ``wv``
    [L_window, kvh / 2, lanes, window_rows, 2 hd]: ROWS OF A SECOND LENGTH
    in the region, a lane's last ``window_rows`` (a power of two >= window)
    as a modular buffer, position p in slot p mod that, the same size
    whatever the context. A decode step writes the ring (which holds both
    kinds); the round's flush writes the short kind modulo its length
    (llama.flush_ctx_impl); a prefill chunk's tail rewrites the lane's
    buffer whole, each slot with the chunk's last position on it; a
    continuing chunk un-rotates the buffer into a workspace of its last
    rows (``_window_prior``). Both attention ops take the window as a BOUND
    (a lower loop bound over key blocks in prefill, the buffer's chunks
    and a mask in decode), never a [T, S] map.
  - THE ROTARY GQA FORM (a config with a rotary rule a layer kind,
    ``rope``) on the ``attention`` and ``window_attention`` kinds, where
    the differential form is not stated: plain softmax GQA over the
    stack's ONE K/V geometry (``row_heads``: the region, the ring and the
    movers are as above, the window kind's rows in the same ``wk`` / ``wv``
    modular buffers), with EVERY LAYER'S OWN NUMBER OF QUERY HEADS
    (``heads_by_layer``: ``wq`` [H, heads_l hd], ``wo`` [heads_l hd, H]; a
    query group of heads_l / kvh rows a K/V head, whatever it is a layer).
    q and k are rotated BY THE KIND'S RULE (ops/rope.py: ``kind_rotary``:
    theta a kind, rotate-half over the first ``rot`` dimensions of the
    head with the rest passed through, YaRN's inverse frequencies over the
    rotated dimensions with its factor on cos and sin, which is no factor
    on the softmax scale once part of the head passes through); K rows are
    stored rotated. A sigmoid GATE A HEAD multiplies the attention output
    before W_o: ``z = x W_g`` [H, heads_l] float32 from the layer's normed
    input, ``sigmoid(z_h) o_h``. The window and full paths are the
    differential form's (``_rows_prefill``, the decode step's one branch):
    the two forms differ in what happens to q, k and v before and to o
    after. Each kind's decode call has its own name in a trace.
  - ``cross_attention``: that form with ``w_q`` / ``w_o`` only, over the
    rows of ANOTHER layer (``rows_from``, an ``attention`` layer below it):
    it keeps no rows, and the rows it reads are indexed by that layer's
    ordinal, not its own. ``gmu``: the gated memory unit ``(m * silu(x
    W_1)) W_2`` over the scan output ``m`` (before its gate) of the
    Mamba-1 layer ``scan_from`` at the same position: a transient of the
    step or chunk, never cached. Where every layer above ``rows_from`` is
    one of these two, nothing up there writes a row or a state, and a
    PREFILL CHUNK'S ROWS STOP after that layer's K/V projection: only the
    chunk's last real row climbs the rest (``_climb``), every chunk, final
    or not.
  - ``latent_attention``: models/mla_moe.py's attention (imported, not
    copied) on ONE ``kv`` row leaf [L_latent, 1, lanes, S, stored] in
    place of K and V: prefill expands K and V per head (a continuing
    chunk's prior rows through ``_expand_prior``), decode absorbs
    (ops/latent_decode.py) at the latent layers' ordinal. Latent rows and
    a recurrent state share one region: the movers carry the row kinds a
    region holds, the round's carry the ``*_state`` leaves.
  - the feed-forward part is experts + a shared MLP where the config has
    experts (after ``n_dense`` leading layers of one dense SwiGLU, if it
    says so), else one dense SwiGLU; the head is the embedding where the
    config ties them, else a matrix of its own.
  - A SECOND ROUTER (``router: sigmoid_groups``): float32 ``s = sigmoid(x
    W_r)`` over the published experts, ``c = s + bias``; the experts lie
    in ``n_group`` contiguous groups whose score is the sum of their two
    best ``c``; the ``topk_group`` best groups stay, the top k of ``c``
    among them are picked, weighted by ``s`` (no bias) over their sum x
    ``routed_scaling_factor``. The share is told to the grouped product
    as for the softmax router; a fifth counter says how many tokens kept
    the group(s) held here. With ONE group every expert stays in the
    running: the top k of ``c``, no group scored.

  - LAYERS OF ONE PART (a config with ``one_part``): a layer is ONE norm
    and ONE part, ``x = x + part(norm(x))``: a mixer kind's mixer and no
    feed-forward part, or a kind of its own whose part IS the feed-forward
    part (``experts``: routed experts + a shared one; ``mlp``: one dense
    MLP) and no mixer. ``_parts`` says which of the two parts a layer of a
    kind has, in either stack; every program asks there (``_layer_out``
    for the decode step, the straight-line prefill and the climb,
    ``_mix_out`` for the looped prefill's second half). A one-part layer's
    parameters are its one norm and its part's leaves; what counts expert
    layers counts the layers that ROUTE (``dims(c)["routes"]``).
  - MAMBA-2 WITH B/C GROUPS (``mamba_n_groups`` G > 1): the convolution
    runs over inner + 2 G N channels, B and C are [G, N] a position and
    head h reads group h // (heads / G) (ops/mamba2.py: the chunked scan
    takes the [Q, Q] map a group, the step and the ``m2_step`` kernel a
    group's row for their heads); the gated RMSNorm is over EACH GROUP's
    inner / G channels, gate first, one gain of inner. inner is heads x
    head, whatever ``expand`` says. One group traces as it always did.
  - EXPERTS WITHOUT A GATE MATRIX (``expert_act: relu2``): an expert is
    ``W_d relu(x W_u)^2``, two matrices and two grouped products, and so
    are the shared expert and a dense MLP of that stack; no gate leaf
    exists. An expert stack whose width is no whole number of 128-lane
    columns is STORED at the next one (``moe.stored_width``: zero columns
    of W_u, zero rows of W_d: exact), so that every weight tile is whole.

Every function here is reached through the ``llama`` names
(``llama.block_of``), as models/mla_moe.py is.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models import mla_moe
from dynamo_tpu.models.live_rows import live_row_trips, over_live_blocks
from dynamo_tpu.models.mla_moe import _mlp, _rms   # the same norm and SwiGLU
from dynamo_tpu.models.moe import (
    grouped_experts,
    move_block,
    relu2,
    rows_moved,
    stored_width,
)
from dynamo_tpu.ops import kda, lightning, mamba1, mamba2, sparse_attention
from dynamo_tpu.ops.attention import (
    NEG_INF,
    PALLAS_INTERPRET,
    REFERENCE_IMPL,
    DecodeAttention,
    PriorContext,
    ctx_decode_attention,
    dense_chunk_rows,
    dense_round_rows,
    fused_prefill_attention,
    prefill_attention,
    region_trips,
)
from dynamo_tpu.ops.latent_decode import latent_decode_attention
from dynamo_tpu.ops.rope import (
    apply_rope,
    apply_rope_leading,
    kind_rotary,
    rope_cos_sin,
    rope_inv_freq,
)
from dynamo_tpu.telemetry.metrics import (
    ATTN_SHARED_ROWS_READ,
    ATTN_WINDOW_ROWS_BOUND,
    ATTN_WINDOW_ROWS_READ,
    DECODE_ATTN_Q_ROWS_FULL,
    DECODE_ATTN_Q_ROWS_WINDOW,
    DECODE_ATTN_ROWS_READ,
    KDA_STATE_ROWS_STEPPED,
    LAYER_PARTS_RUN,
    MOE_GROUPS_KEPT_HERE,
    MOE_LOAD_MAX,
    MOE_PICKS_ROUTED,
    MOE_ROUTED,
    MOE_TOUCHED,
    PREFILL_LAYER_ROWS,
    PREFILL_LAYER_ROWS_SKIPPED,
    SPARSE_ATTN_ROWS_LIVE,
    SPARSE_ATTN_ROWS_READ,
    SPARSE_PREFILL_SCORED,
    SPARSE_PREFILL_SELECTED,
    SSM_SCAN_POSITIONS,
    SSM_STATE_ROWS_STEPPED,
    Counter,
)

Params = dict[str, Any]
Cache = dict[str, Any]

SSM, CONV = "ssm_state", "conv_state"   # the recurrent leaves of a ctx
LIN = "lin_state"     # a linear-attention layer's matrix state
KC = "kc"             # the sparse layers' compressed-key rows
KDA, KDA_CONV = "kda_state", "kda_conv_state"   # a delta-rule layer's
                      # matrix state and its three convolution windows
M1, M1_CONV = "m1_state", "m1_conv_state"   # a Mamba-1 layer's [N, inner]
                      # state (channels minor) and its convolution window
KV = mla_moe.ROW      # the latent layers' one row kind
WK, WV = "wk", "wv"   # the window layers' K/V rows: a modular buffer a lane
ROW_LAYERS = ("attention", "sparse_attention")   # kinds that keep K/V rows
                      # of the region's length
# kinds whose prefill runs ``prefill_attention`` (or, the latent kind, its
# fused form)
PREFILL_ATTENTION_KINDS = ("attention", "sparse_attention",
                           "window_attention", "cross_attention",
                           "latent_attention")
# the kinds of a stack of ONE-PART layers whose one part is the feed-forward
# part (routed experts + a shared one | one dense MLP): no mixer, no state
FFN_KINDS = ("experts", "mlp")
# kinds whose mixer is a recurrence over a per-lane state
STATE_KINDS = ("mamba", "mamba1", "kda", "linear_attention")
# the kinds the differential form covers where the config states it
DIFF_KINDS = ("attention", "window_attention", "cross_attention")
DIFF_KERNEL = "diff_decode_attention"   # their decode kernel in a trace
# the kinds of the ROTARY GQA form (a config with a rotary rule a kind:
# ``rope``), their scopes and their decode kernels' names in a trace
GQA_KINDS = ("attention", "window_attention")
GQA_SCOPES = {"attention": "full_gqa_attn",
              "window_attention": "window_gqa_attn"}
GQA_KERNELS = {"attention": "full_gqa_decode_attention",
               "window_attention": "window_gqa_decode_attention"}
ROPE_SCOPES = {"attention": "rope_full", "window_attention": "rope_window"}
# prefill: where every layer above the one whose rows the cross layers read
# writes neither rows nor state, only a chunk's last real row climbs them
# (tests set it False: every row climbs, the logits must not move)
SKIP_ROWS = True


def dims(c: ModelConfig) -> dict[str, Any]:
    k = c.hybrid_dict
    kinds = k["layer_types"]
    d = {
        "kinds": kinds,
        "n_ssm": sum(t == "mamba" for t in kinds),
        "n_lin": sum(t == "linear_attention" for t in kinds),
        "n_attn": sum(t in ROW_LAYERS for t in kinds),
        "n_sparse": sum(t == "sparse_attention" for t in kinds),
        "n_kda": sum(t == "kda" for t in kinds),
        "n_m1": sum(t == "mamba1" for t in kinds),
        "n_win": sum(t == "window_attention" for t in kinds),
        "diff": bool(k.get("differential")),
        # a rotary rule a layer kind ({} = the attention kinds are NoPE),
        # and every layer's own number of query heads over the stack's one
        # K/V geometry
        "rotary": {kind: dict(rule) for kind, rule in k.get("rope", ())},
        "heads": tuple(k.get("heads_by_layer")
                       or (c.num_heads,) * len(kinds)),
        "n_latent": sum(t == "latent_attention" for t in kinds),
        "experts": "num_local_experts" in k,
        # leading layers whose feed-forward part is one dense MLP
        "n_dense": k.get("n_dense", 0),
        # layers of ONE part (a mixer kind, or a kind of FFN_KINDS) where
        # the config says so; else every layer = mixer + feed-forward part
        "one_part": bool(k.get("one_part")),
        # an expert (and the shared and dense MLPs beside them): "swiglu"
        # W_d (silu(x W_g) * x W_u) or "relu2" W_d relu(x W_u)^2, which
        # has no gate matrix
        "act": k.get("expert_act", "swiglu"),
    }
    # which layers ROUTE: in a one-part stack the ``experts`` kind, else
    # every layer past the leading dense ones of a config with experts
    d["routes"] = tuple(
        (t == "experts") if d["one_part"] else
        (d["experts"] and i >= d["n_dense"]) for i, t in enumerate(kinds))
    if "mamba_n_heads" in k:
        inner = k["mamba_n_heads"] * k["mamba_d_head"]
        G = k.get("mamba_n_groups", 1)
        d.update({
            "nh": k["mamba_n_heads"], "P": k["mamba_d_head"],
            "N": k["mamba_d_state"], "W": k["mamba_d_conv"],
            # B and C in G groups of N: head h reads group h // (nh / G)
            "G": G,
            "inner": inner, "conv": inner + 2 * G * k["mamba_d_state"],
            "chunk": k["mamba_chunk_size"],
        })
    if d["experts"]:
        d.update({
            "E": k["published_experts"], "held": k["num_local_experts"],
            "K": k["num_experts_per_tok"], "I_e": k["intermediate_size"],
            # the width the held experts' matrices are stored at (zeros
            # beyond the published I_e: moe.stored_width)
            "I_st": stored_width(k["intermediate_size"]),
            "I_s": k["shared_intermediate_size"],
            # the first expert held here; None: all of them, and the
            # grouped product traces as it does without a share
            "first": (k["share_index"] * k["num_local_experts"]
                      if k["share_of"] > 1 else None),
        })
    if d["experts"] and k.get("router") == "sigmoid_groups":
        d.update({"groups": k["n_group"], "kept": k["topk_group"],
                  "scale": k["routed_scaling_factor"]})
    if d["n_kda"]:
        d.update({"kda_heads": k["kda_heads"], "kda_dim": k["kda_head_dim"],
                  "kda_inner": k["kda_heads"] * k["kda_head_dim"],
                  "kda_W": k["kda_conv"], "kda_bound": k["kda_lower_bound"]})
    if d["n_m1"]:
        d.update({"m1_inner": k["m1_inner"], "m1_N": k["m1_state"],
                  "m1_rank": k["m1_dt_rank"], "m1_W": k["m1_conv"]})
    if d["n_win"]:
        d.update({"window": k["window"], "window_rows": k["window_rows"]})
    if "rows_from" in k:
        # the layer whose rows the cross layers read (and its ordinal
        # among the layers that keep full rows), the layer whose scan
        # output the gated memory units read, and whether a prefill
        # chunk's rows stop at the first: nothing above it keeps anything
        above = kinds[k["rows_from"] + 1:]
        d.update({
            "rows_from": k["rows_from"], "scan_from": k["scan_from"],
            "rows_row": sum(t in ROW_LAYERS
                            for t in kinds[:k["rows_from"]]),
            "climbs": all(t in ("gmu", "cross_attention") for t in above),
            "n_cross": sum(t == "cross_attention" for t in kinds),
        })
    if "lightning_heads" in k:
        d.update({
            "lin_heads": k["lightning_heads"],
            "lin_dim": k["lightning_head_dim"],
            "sparse": sparse_attention.Geometry.of(dict(k["sparse"])),
        })
    return d


def _form(d, kind: str):
    """Which softmax-attention form a layer of ``kind`` runs on the window
    / full row paths they share (``_rows_prefill``, the decode step's one
    branch): ``"diff"`` the differential form, ``"gqa"`` the rotary GQA
    form with a gate a head; None: the kind's own path."""
    if d["diff"] and kind in DIFF_KINDS:
        return "diff"
    if d["rotary"] and kind in GQA_KINDS:
        return "gqa"
    return None


def row_heads(c: ModelConfig) -> tuple[int, int]:
    """(heads, width) of a K or V row as the region holds it: under the
    differential form adjacent K/V heads pair into ONE row of twice the
    width (a free reshape of the projections' columns; the form reads the
    pair anyway, and a minor dimension of 64 is tiled to 128 on the
    chip)."""
    if dims(c)["diff"]:
        return c.num_kv_heads // 2, 2 * c.head_dim
    return c.num_kv_heads, c.head_dim


def routes(c: ModelConfig) -> bool:
    """Whether a round's counter row carries routing counters: a
    feed-forward part that is one dense MLP routes nothing."""
    return dims(c)["experts"]


def sparse_layers(c: ModelConfig):
    """(the block-sparse attention's geometry or None, how many layers
    run it): what the host's mirrors of their reads are computed from."""
    d = dims(c)
    return d.get("sparse") if d["n_sparse"] else None, d["n_sparse"]


LIN_CHUNK = 256   # positions a chunk of the linear attention's prefill
SPARSE_QK_GAIN = 2.0   # init_params: the sparse layers' q / k norm gains


def kv_row_bytes(c: ModelConfig, itemsize: int) -> float:
    """Bytes one token holds in the ctx region: K and V in the layers
    that keep rows, and its share of a compressed key in the sparse
    ones."""
    d = dims(c)
    rows = d["n_attn"] * 2 * c.kv_dim * itemsize
    if d["n_latent"]:
        rows += d["n_latent"] * mla_moe.dims(c)["stored"] * itemsize
    if d["n_sparse"]:
        rows += d["n_sparse"] * c.kv_dim * itemsize / d["sparse"].stride
    return rows


def window_bytes(c: ModelConfig, itemsize: int) -> int:
    """Bytes one lane holds in window rows, whatever its context: the
    window layers' modular buffers."""
    d = dims(c)
    if not d["n_win"]:
        return 0
    return d["n_win"] * d["window_rows"] * 2 * c.kv_dim * itemsize


def state_bytes(c: ModelConfig, itemsize: int) -> int:
    """Bytes one lane holds in recurrent state, whatever its context."""
    d = dims(c)
    total = 0
    if d["n_ssm"]:
        total += d["n_ssm"] * (d["nh"] * d["P"] * d["N"] * 4
                               + (d["W"] - 1) * d["conv"] * itemsize)
    if d["n_lin"]:
        total += d["n_lin"] * d["lin_heads"] * d["lin_dim"] ** 2 * 4
    if d["n_kda"]:
        total += d["n_kda"] * (
            d["kda_heads"] * d["kda_dim"] ** 2 * 4
            + (d["kda_W"] - 1) * 3 * d["kda_inner"] * itemsize)
    if d["n_m1"]:
        total += d["n_m1"] * d["m1_inner"] * (
            d["m1_N"] * 4 + (d["m1_W"] - 1) * itemsize)
    return total


# ---------------------------------------------------------------------------
# Parameters

def init_params(config: ModelConfig, rng: jax.Array | int = 0) -> Params:
    """Random parameters, normal x 1/sqrt(fan_in), and Mamba-2's own
    initialisation of the recurrence: ``A`` uniform in 1..16, ``dt``
    log-uniform in 0.001..0.1 (``dt_bias`` its inverse softplus), ``D``
    1. Heads then remember over tens to thousands of positions, so a
    state dropped at a chunk boundary changes the logits. A delta-rule
    layer's gate is drawn to the same end: ``dt_bias`` uniform in -8..-4.5
    a channel under a gate matrix of half the usual scale, so that a
    channel's decay ``a`` lies in ~0.91..0.998, and ``W_b`` at the usual
    scale, so that the step ``b`` spans ~0.1..0.9. A Mamba-1 layer takes
    its family's: ``A_log`` = log(1..N) down every channel's state column,
    ``dt`` log-uniform in 0.001..0.1 a channel, ``D`` 1, the three inner
    norms' gains 1 (where the config has them). Under the differential
    form an attention layer has projection biases (x 0.1), four ``lam``
    vectors (x 0.1), the pair norm's gain 1 and ``lam_init`` = 0.8 - 0.6
    exp(-0.3 l) of its depth l; a LayerNorm stack a bias (x 0.1) beside
    each gain. A layer of the rotary GQA form has ``wq`` / ``wo`` at ITS
    number of query heads and ``wg`` [H, heads], the gate's. A layer of a
    ONE-PART stack holds one norm and its part's leaves only; experts
    without a gate matrix hold two matrices each (``we_u``, ``we_d``), and
    every expert stack is drawn at the published width and STORED at
    ``moe.stored_width`` of it (zero columns of W_u, zero rows of W_d)."""
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    c, d = config, dims(config)
    if c.quant is not None:
        raise ValueError("the state-space hybrid block has no int8 weights")
    dtype = jnp.dtype(c.dtype)
    keys = iter(jax.random.split(
        rng, 4 + (24 if d["diff"] else 16) * c.num_layers))

    def rnd(*shape, scale=None):
        scale = scale or 1.0 / np.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    H = c.hidden_size

    u = lambda lo, hi, *shape: jax.random.uniform(  # noqa: E731
        next(keys), shape, jnp.float32, lo, hi)

    layer_norm = bool(c.hybrid_dict.get("layer_norm"))
    bias = lambda n: rnd(n, scale=0.1)  # noqa: E731

    def diff_attention(i, own_rows):
        """The differential form's parameters: a cross layer has neither
        ``wk`` nor ``wv`` (it reads another layer's rows)."""
        hd = c.head_dim
        lp = dict(wq=rnd(H, c.q_dim), bq=bias(c.q_dim),
                  wo=rnd(c.q_dim, H), bo=bias(H),
                  sub_norm=jnp.ones((2 * hd,), dtype),
                  lam_init=jnp.float32(0.8 - 0.6 * np.exp(-0.3 * i)))
        for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2"):
            lp[name] = 0.1 * jax.random.normal(next(keys), (hd,), jnp.float32)
        if own_rows:
            lp.update(wk=rnd(H, c.kv_dim), bk=bias(c.kv_dim),
                      wv=rnd(H, c.kv_dim), bv=bias(c.kv_dim))
        return lp

    gated = d["act"] == "swiglu"

    def stored(a, axis):
        """An expert stack drawn at the published width, at the width it
        is stored at: zeros beyond ``I_e`` along ``axis``."""
        if d["I_st"] == d["I_e"]:
            return a
        return jnp.pad(a, [(0, d["I_st"] - d["I_e"] if ax == axis else 0)
                           for ax in range(a.ndim)])

    def layer(i, kind):
        lp = {"ln1": jnp.ones((H,), dtype)}
        if not d["one_part"]:
            lp["ln2"] = jnp.ones((H,), dtype)
        if layer_norm:
            lp.update(ln1_b=bias(H), ln2_b=bias(H))
        if d["routes"][i]:
            if "groups" in d:
                # in SCORE units, as the latent block draws it (the 8th
                # and 9th best of hundreds of sigmoid scores lie ~0.005
                # apart)
                lp["bias"] = 0.01 * jax.random.normal(
                    next(keys), (d["E"],), jnp.float32)
            lp["wr"] = rnd(H, d["E"])
            if gated:
                # the published fused input matrix [H, 2 I] as its two halves
                lp.update(
                    we_g=stored(rnd(d["held"], H, d["I_e"]), 2),
                    we_u=stored(rnd(d["held"], H, d["I_e"]), 2),
                    we_d=stored(rnd(d["held"], d["I_e"], H), 1),
                    ws_g=rnd(H, d["I_s"]), ws_u=rnd(H, d["I_s"]),
                    ws_d=rnd(d["I_s"], H))
            else:   # two matrices an expert: no gate
                lp.update(
                    we_u=stored(rnd(d["held"], H, d["I_e"]), 2),
                    we_d=stored(rnd(d["held"], d["I_e"], H), 1),
                    ws_u=rnd(H, d["I_s"]), ws_d=rnd(d["I_s"], H))
        elif not d["one_part"] or kind == "mlp":
            I = c.intermediate_size
            if gated:
                lp.update(w_g=rnd(H, I), w_u=rnd(H, I), w_d=rnd(I, H))
            else:
                lp.update(w_u=rnd(H, I), w_d=rnd(I, H))
        if kind in FFN_KINDS:
            return lp
        if _form(d, kind) == "diff":
            lp.update(diff_attention(i, kind != "cross_attention"))
            return lp
        if kind == "gmu":
            lp.update(w1=rnd(H, d["m1_inner"]), w2=rnd(d["m1_inner"], H))
            return lp
        if _form(d, kind) == "gqa":
            q_dim = d["heads"][i] * c.head_dim
            lp.update(wq=rnd(H, q_dim), wk=rnd(H, c.kv_dim),
                      wv=rnd(H, c.kv_dim), wg=rnd(H, d["heads"][i]),
                      wo=rnd(q_dim, H))
            return lp
        if kind == "attention":
            lp.update(wq=rnd(H, c.q_dim), wk=rnd(H, c.kv_dim),
                      wv=rnd(H, c.kv_dim), wo=rnd(c.q_dim, H))
            return lp
        if kind == "sparse_attention":
            # q and k gains of 2: a softmax row's logits have a spread of
            # ~4, so its mass sits on few keys and WHICH blocks are read
            # changes the output (gains of 1 give near-flat rows, and a
            # dropped selection changes nothing a check can see)
            gain = jnp.full((c.head_dim,), SPARSE_QK_GAIN, dtype)
            lp.update(wq=rnd(H, c.q_dim), wk=rnd(H, c.kv_dim),
                      wv=rnd(H, c.kv_dim), wo=rnd(c.q_dim, H),
                      wz=rnd(H, c.q_dim), q_norm=gain, k_norm=gain)
            return lp
        if kind == "kda":
            inner, nh = d["kda_inner"], d["kda_heads"]
            lp.update(
                # q | k | v, and their three convolutions, side by side
                w_qkv=rnd(H, 3 * inner),
                conv_w=rnd(d["kda_W"], 3 * inner,
                           scale=1.0 / np.sqrt(d["kda_W"])),
                w_f=rnd(H, inner, scale=0.5 / np.sqrt(H)),
                A_log=jnp.log(u(0.8, 1.25, nh)),
                dt_bias=u(-8.0, -4.5, inner),
                # the step b | the output gate, a scalar a head each
                w_bg=rnd(H, 2 * nh),
                o_norm=jnp.ones((d["kda_dim"],), dtype),
                wo=rnd(inner, H))
            return lp
        if kind == "mamba1":
            I, N, R = d["m1_inner"], d["m1_N"], d["m1_rank"]
            dt = jnp.exp(u(np.log(1e-3), np.log(1e-1), I))
            lp.update(
                w_in=rnd(H, 2 * I),                   # x | the gate z
                conv_w=rnd(d["m1_W"], I, scale=1.0 / np.sqrt(d["m1_W"])),
                conv_b=rnd(I, scale=0.1),
                w_x=rnd(I, R + 2 * N),                # dt_r | B | C
                w_dt=rnd(R, I),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                # channels minor, as the state is held
                A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[:, None], (N, I)),
                D=jnp.ones((I,), jnp.float32),
                w_out=rnd(I, H))
            if c.hybrid_dict.get("m1_inner_norms", True):
                lp.update(dt_norm=jnp.ones((R,), dtype),
                          b_norm=jnp.ones((N,), dtype),
                          c_norm=jnp.ones((N,), dtype))
            return lp
        if kind == "latent_attention":
            m = mla_moe.dims(c)
            lp.update(
                wq=rnd(H, m["nh"] * (m["nope"] + m["rope"])),
                wkva=rnd(H, m["row"]),
                kv_norm=jnp.ones((m["kv_rank"],), dtype),
                wkvb=rnd(m["kv_rank"], m["nh"] * (m["nope"] + m["v"])),
                wo=rnd(m["nh"] * m["v"], H))
            return lp
        if kind == "linear_attention":
            inner = d["lin_heads"] * d["lin_dim"]
            one = jnp.ones((d["lin_dim"],), dtype)
            lp.update(wq=rnd(H, inner), wk=rnd(H, inner), wv=rnd(H, inner),
                      wo=rnd(inner, H), wz=rnd(H, inner), q_norm=one,
                      k_norm=one, o_norm=jnp.ones((inner,), dtype))
            return lp
        dt = jnp.exp(u(np.log(1e-3), np.log(1e-1), d["nh"]))
        lp.update(
            w_in=rnd(H, d["inner"] + d["conv"] + d["nh"]),
            conv_w=rnd(d["W"], d["conv"], scale=1.0 / np.sqrt(d["W"])),
            conv_b=rnd(d["conv"], scale=0.1),
            A_log=jnp.log(u(1.0, 16.0, d["nh"])),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            D=jnp.ones((d["nh"],), jnp.float32),
            norm=jnp.ones((d["inner"],), dtype),
            w_out=rnd(d["inner"], H),
        )
        return lp

    params = {
        "embed": rnd(c.vocab_size, H, scale=1.0 / np.sqrt(H)),
        "norm_f": jnp.ones((H,), dtype),
        # one entry a layer, NOT stacked: the kinds differ, and a
        # kernel's operand sliced out of a stack is a copy of it
        "layers": [layer(i, kind) for i, kind in enumerate(d["kinds"])],
    }
    if layer_norm:
        params["norm_f_b"] = bias(H)
    if not c.tie_word_embeddings:
        params["head"] = rnd(H, c.vocab_size)
    return params


def param_shardings(config: ModelConfig, mesh: Mesh) -> Params:
    """One chip runs a layer without its exchange: everything
    replicated, a mesh with tp or ep > 1 refused."""
    for axis in ("tp", "ep"):
        if mesh.shape.get(axis, 1) > 1:
            raise ValueError(
                f"the state-space hybrid block is not sharded over "
                f"{axis!r} (mesh {dict(mesh.shape)}): the chip's share of "
                "the experts is the configuration's (expert_share), and "
                "the exchange around it is not built")
    shapes = jax.eval_shape(lambda: init_params(config, 0))
    return jax.tree.map(
        lambda x: NamedSharding(mesh, P(*([None] * x.ndim))), shapes)


def serving_params(config: ModelConfig, params: Params) -> Params:
    """The published parameters, each ``latent_attention`` layer's beside
    its ``wkvb`` by head (``mla_moe.by_head``: ``wkb``, ``wvb``), made
    once where the engine takes them; every other kind reads its
    published leaves."""
    def served(lp):
        if "wkvb" not in lp:
            return lp
        wkb, wvb = mla_moe.by_head(config, lp["wkvb"])
        return dict(lp, wkb=wkb, wvb=wvb)

    return dict(params, layers=[served(lp) for lp in params["layers"]])


# ---------------------------------------------------------------------------
# Cache spec: rows for the attention layers, a state for the others

def _rows(c: ModelConfig, lanes: int, length: int, dtype,
          compressed: bool = True, window_rows: int = 0) -> Cache:
    """The row kinds of a region, a ring or the pool, ``length`` rows a
    lane; the window layers' kinds where ``window_rows`` says how many
    rows a lane holds of them (the pool holds none)."""
    d = dims(c)
    if d["n_latent"]:
        # the latent layers' one row kind, in place of K and V
        return {KV: jnp.zeros((d["n_latent"], 1, lanes, length,
                               mla_moe.dims(c)["stored"]), dtype)}
    heads, width = row_heads(c)
    shape = (d["n_attn"], heads, lanes, length, width)
    rows = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if d["n_win"] and window_rows:
        for name in (WK, WV):
            rows[name] = jnp.zeros(
                (d["n_win"], heads, lanes, window_rows, width), dtype)
    if d["n_sparse"] and compressed:
        # one compressed key every ``stride`` positions of a sparse layer
        rows[KC] = jnp.zeros(
            (d["n_sparse"], c.num_kv_heads, lanes,
             length // d["sparse"].stride, c.head_dim), dtype)
    return rows


def _refuse_quant(kv_quant: str) -> None:
    if kv_quant != "none":
        raise ValueError(
            f"kv_quant={kv_quant!r}: the int8 KV plane cannot carry a "
            "recurrent state (its scale grid is per page of rows)")


def init_cache(config, num_pages, page_size, dtype=None, kv_quant="none"):
    _refuse_quant(kv_quant)
    return _rows(config, num_pages, page_size,
                 dtype or jnp.dtype(config.dtype))


def init_ctx(config, batch, ctx_len, dtype=None, kv_quant="none",
             group=128):
    _refuse_quant(kv_quant)
    d = dims(config)
    dtype = dtype or jnp.dtype(config.dtype)
    # a window layer keeps a lane's last ``window_rows`` rows, position p
    # in slot p mod that, whatever the context: rows of two LENGTHS in one
    # region (a round's rows wait in the ring, a chunk's in the program)
    ctx = _rows(config, batch + 1, ctx_len, dtype,
                window_rows=d.get("window_rows", 0))
    if d["n_ssm"]:
        ctx[SSM] = [
            jnp.zeros((batch + 1, d["nh"], d["P"], d["N"]), jnp.float32)
            for _ in range(d["n_ssm"])]
        ctx[CONV] = [jnp.zeros((batch + 1, d["W"] - 1, d["conv"]), dtype)
                     for _ in range(d["n_ssm"])]
    if d["n_lin"]:
        ctx[LIN] = [
            jnp.zeros((batch + 1, d["lin_heads"], d["lin_dim"],
                       d["lin_dim"]), jnp.float32)
            for _ in range(d["n_lin"])]
    if d["n_kda"]:
        ctx[KDA] = [
            jnp.zeros((batch + 1, d["kda_heads"], d["kda_dim"],
                       d["kda_dim"]), jnp.float32)
            for _ in range(d["n_kda"])]
        ctx[KDA_CONV] = [
            jnp.zeros((batch + 1, d["kda_W"] - 1, 3 * d["kda_inner"]), dtype)
            for _ in range(d["n_kda"])]
    if d["n_m1"]:
        # [N, inner], NOT the published [inner, N]: a minor dimension of
        # 16 is tiled to 128 on the chip (8 x the bytes, a relayout a step)
        ctx[M1] = [jnp.zeros((batch + 1, d["m1_N"], d["m1_inner"]),
                             jnp.float32) for _ in range(d["n_m1"])]
        ctx[M1_CONV] = [
            jnp.zeros((batch + 1, d["m1_W"] - 1, d["m1_inner"]), dtype)
            for _ in range(d["n_m1"])]
    return ctx


def init_ring(config, batch, ring_len, dtype=None):
    # a step that completes a compressed key writes it into the region's
    # own leaf (it rides the round's carry): the ring holds K and V only,
    # the window layers' beside the full layers'
    if ring_len > dims(config).get("window_rows", ring_len):
        raise ValueError(
            f"a ring of {ring_len} rows is flushed into a window layer's "
            f"buffer of {dims(config)['window_rows']} rows a lane: "
            "flush_every must not pass the window's rows")
    return _rows(config, batch, ring_len, dtype or jnp.dtype(config.dtype),
                 compressed=False, window_rows=ring_len)


def cache_shardings(config: ModelConfig, mesh: Mesh,
                    kv_quant: str = "none") -> Cache:
    _refuse_quant(kv_quant)
    s = NamedSharding(mesh, P(None, None, None, None, None))
    if dims(config)["n_latent"]:
        return {KV: s}
    out = {"k": s, "v": s}
    if dims(config)["n_sparse"]:
        out[KC] = s
    return out


def _window_shardings(config: ModelConfig, mesh: Mesh) -> Cache:
    if not dims(config)["n_win"]:
        return {}
    s = NamedSharding(mesh, P(None, None, None, None, None))
    return {WK: s, WV: s}


def ring_shardings(config: ModelConfig, mesh: Mesh) -> Cache:
    rows = cache_shardings(config, mesh)
    return dict({n: s for n, s in rows.items() if n != KC},   # init_ring's
                **_window_shardings(config, mesh))


def stepped_kinds(config: ModelConfig, state: Cache) -> tuple[str, ...]:
    """A region's leaves that a decode STEP writes, where K and V are
    read-only until the round's flush: the recurrent leaves, and the
    compressed-key rows (a step that completes one writes it where it
    belongs). They ride the round's carry; the ring holds none of them."""
    return tuple(sorted(n for n in state
                        if n.endswith("_state") or n == KC))


def ctx_shardings(config: ModelConfig, mesh: Mesh,
                  kv_quant: str = "none") -> Cache:
    out = dict(cache_shardings(config, mesh, kv_quant),
               **_window_shardings(config, mesh))
    d = dims(config)
    if d["n_ssm"]:
        out[SSM] = [NamedSharding(mesh, P(None, None, None, None))] * d["n_ssm"]
        out[CONV] = [NamedSharding(mesh, P(None, None, None))] * d["n_ssm"]
    if d["n_lin"]:
        out[LIN] = [NamedSharding(mesh, P(None, None, None, None))] * d["n_lin"]
    if d["n_kda"]:
        out[KDA] = [NamedSharding(mesh, P(None, None, None, None))] * d["n_kda"]
        out[KDA_CONV] = [NamedSharding(mesh, P(None, None, None))] * d["n_kda"]
    if d["n_m1"]:
        for name in (M1, M1_CONV):
            out[name] = [NamedSharding(mesh, P(None, None, None))] * d["n_m1"]
    return out


# ---------------------------------------------------------------------------
# Forward pieces

def route(c: ModelConfig, lp, x):
    """The published router, float32 over ALL the deployment's experts:
    (picks [N, K], combine weights [N, K], and for the grouped router
    whether each token kept a group held here [N] bool, else None).

    Softmax form: the top k logits picked, softmax over the picked.
    ``sigmoid_groups``: scores ``s = sigmoid(logits)``, ``c = s + bias``;
    a group's score is the sum of its two best ``c``; the ``kept`` best
    groups stay and the rest are masked out; the top k of ``c`` among what
    stays; weights ``s`` at the picks over their sum, x ``scale``."""
    d = dims(c)
    with jax.named_scope("moe_route"):
        logits = jnp.matmul(x, lp["wr"], preferred_element_type=jnp.float32)
        if "groups" not in d:
            top, sel = jax.lax.top_k(logits, d["K"])
            return sel, jax.nn.softmax(top, axis=-1), None
        s = jax.nn.sigmoid(logits)
        biased = s + lp["bias"]
        N, G = s.shape[0], d["groups"]
        size = d["E"] // G
        masked = biased   # one group: every expert stays in the running
        if G > 1:
            # a group's two best as two maxima (top_k of 2 lowers to a sort
            # of every group on the TPU: 2.6 % of a decode step's device
            # time)
            per = biased.reshape(N, G, size)
            at = jnp.argmax(per, axis=-1, keepdims=True)
            second = jnp.max(jnp.where(
                jnp.arange(size) == at, -jnp.inf, per), axis=-1)
            _, keep = jax.lax.top_k(per.max(-1) + second, d["kept"])  # [N, kept]
            kept = jnp.zeros((N, G), bool).at[
                jnp.arange(N)[:, None], keep].set(True)
            masked = jnp.where(jnp.repeat(kept, size, axis=1), biased,
                               -jnp.inf)
        _, sel = jax.lax.top_k(masked, d["K"])
        w = jnp.take_along_axis(s, sel, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * d["scale"]
        if G == 1:
            return sel, w, jnp.ones((N,), bool)
        # the groups that hold this chip's experts (one, a part of one,
        # or several: config.py's rule on the share)
        first = d["first"] or 0
        here = kept[:, first // size:(first + d["held"] - 1) // size + 1]
        return sel, w, here.any(axis=1)


KDA_STEPPED = Counter(KDA_STATE_ROWS_STEPPED[0])
SSM_STEPPED = Counter(SSM_STATE_ROWS_STEPPED[0])   # Mamba-1 or Mamba-2


def stats_layout(c: ModelConfig) -> tuple[Counter, ...]:
    """What a step's counters hold, in order: held experts touched, picks
    that landed on a held expert, most tokens on one held expert, all
    picks of routed tokens (four columns every stack's row has; a stack
    that routes nothing carries them at 0 and feeds no histogram); under
    the grouped router, routed tokens that kept a group held here; with
    delta-rule layers, the per-lane matrix states their steps moved on
    (the live lanes', a layer); with Mamba-1 or Mamba-2 layers (a stack
    has one of the two kinds), the same of theirs."""
    d = dims(c)
    router = [m[0] if routes(c) else None for m in (
        MOE_TOUCHED, MOE_ROUTED, MOE_LOAD_MAX, MOE_PICKS_ROUTED)]
    return (tuple(Counter(m) for m in router)
            + ((Counter(MOE_GROUPS_KEPT_HERE[0]),) if "groups" in d else ())
            + ((KDA_STEPPED,) if d["n_kda"] else ())
            + ((SSM_STEPPED,) if d["n_m1"] or d["n_ssm"] else ()))


def stats_zero(c: ModelConfig):
    """A step's counters (``stats_layout``) before any layer."""
    return jnp.zeros(len(stats_layout(c)), jnp.int32)


def merge_stats(a, b):
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2])]
                     + [a[i] + b[i] for i in range(3, a.shape[0])])


def _move_block(d, n_tokens: int) -> int:
    """``moe.move_block`` for the expert layers of a program over
    ``n_tokens`` positions; 0 without expert layers."""
    if not d["experts"]:
        return 0
    return move_block(n_tokens, d["K"], d["first"] is not None)


def prefill_rows_sorted(c: ModelConfig, n_tokens: int) -> int:
    """(token, pick) rows the expert layers of one prefill program over
    ``n_tokens`` positions sort, where they move rows in the looped form;
    0 where they do not, or there are none."""
    d = dims(c)
    return (n_tokens * d["K"] * sum(d["routes"])
            if _move_block(d, n_tokens) else 0)


def _seen(c: ModelConfig, load, here, valid, n_tokens: int, stats):
    """``stats`` with one expert layer's counters merged in: ``load`` the
    tokens each held expert received, ``here`` the router's third value."""
    picks = (n_tokens if valid is None else valid.sum()) * dims(c)["K"]
    seen = [jnp.sum(load > 0), load.sum(), load.max(),
            jnp.asarray(picks, jnp.int32)]
    if here is not None:
        seen.append(jnp.sum(here if valid is None else here & valid))
    seen += [0] * (stats.shape[0] - len(seen))   # counters not a router's
    return merge_stats(stats, jnp.stack(seen).astype(jnp.int32))


def _norm(c: ModelConfig, lp, name: str, x):
    """The stack's norm with gain ``lp[name]``: a LayerNorm (mean removed,
    gain and bias) where the parameters hold its bias, else the
    RMSNorm."""
    if name + "_b" not in lp:
        return _rms(x, lp[name], c.rms_norm_eps)
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + c.rms_norm_eps)).astype(x.dtype)
            * lp[name] + lp[name + "_b"])


def _dense(lp, x, g: str, u: str, d: str):
    """One MLP over the leaves named: SwiGLU where the layer holds a gate
    matrix, else ``W_d relu(x W_u)^2``."""
    if g in lp:
        return _mlp(x, lp[g], lp[u], lp[d])
    return relu2(x @ lp[u]) @ lp[d]


def _shared(lp, x):
    with jax.named_scope("moe_shared"):
        return _dense(lp, x, "ws_g", "ws_u", "ws_d")


def _experts(c: ModelConfig, lp, x, sel, w, valid):
    """The grouped product over this chip's share of the experts, by the
    stack's activation: (sum over the held picks [N, H], tokens a held
    expert received)."""
    d = dims(c)
    return grouped_experts(x, sel, w, lp.get("we_g"), lp["we_u"], lp["we_d"],
                           valid, first=d["first"], act=d["act"])


def _ffn(c: ModelConfig, lp, x, valid, stats):
    """Routed experts (this chip's share) + the shared MLP, ungated."""
    sel, w, here = route(c, lp, x)
    y, load = _experts(c, lp, x, sel, w, valid)
    return y + _shared(lp, x), _seen(c, load, here, valid, x.shape[0], stats)


def _parts(c: ModelConfig, kind: str):
    """What a layer of ``kind`` is made of: (whether it runs a MIXER, the
    norm in front of its FEED-FORWARD part or None where it has none). A
    layer of two parts is both, ``ln2`` between them. In a stack of
    one-part layers (``one_part``) a layer is one norm and one part: a
    mixer kind's mixer, or the feed-forward part of a kind of
    ``FFN_KINDS`` behind ``ln1``, the layer's only norm. The ONE place
    that says so: every program below asks here."""
    if not dims(c)["one_part"]:
        return True, "ln2"
    return (False, "ln1") if kind in FFN_KINDS else (True, None)


def _layer_out(c: ModelConfig, kind: str, lp, h, mix, valid, stats):
    """A layer of ``kind`` from its mixer's output ``mix`` on (None where
    the layer has no mixer): the residual, then the feed-forward part
    behind its norm, where the layer has one (``_parts``)."""
    mixes, norm = _parts(c, kind)
    r = jnp.asarray(c.hybrid_dict["residual_multiplier"], h.dtype)
    if mixes:
        h = h + r * mix
    if norm is None:
        return h, stats
    x = _norm(c, lp, norm, h)
    if "wr" in lp:   # the layer routes: a leading dense layer does not
        y, stats = _ffn(c, lp, x, valid, stats)
    else:
        with jax.named_scope("mlp"):
            y = _dense(lp, x, "w_g", "w_u", "w_d")
    return h + r * y, stats


def _embed(c: ModelConfig, params, tokens, dtype):
    m = jnp.asarray(c.hybrid_dict["embedding_multiplier"], dtype)
    return params["embed"][tokens].astype(dtype) * m


def _logits(c: ModelConfig, params, h):
    h = _norm(c, params, "norm_f", h)
    head = params["head"] if "head" in params else params["embed"].T
    y = jnp.matmul(h, head, preferred_element_type=jnp.float32)
    return y / c.hybrid_dict["logits_scaling"]


def _qkv(c: ModelConfig, lp, x):
    """No rotary. The softmax scale, ``attention_multiplier``, rides on q
    (x sqrt(head_dim), which the attention ops divide out again): the
    product is taken in float32 and rounded once."""
    N = x.shape[0]
    k = c.hybrid_dict["attention_multiplier"] * np.sqrt(c.head_dim)
    q = ((x @ lp["wq"]).astype(jnp.float32) * k).astype(x.dtype)
    return (q.reshape(N, c.num_heads, c.head_dim),
            (x @ lp["wk"]).reshape(N, c.num_kv_heads, c.head_dim),
            (x @ lp["wv"]).reshape(N, c.num_kv_heads, c.head_dim))


def _gqa_in(c: ModelConfig, kind: str, lp, x, pos):
    """The rotary GQA form's in-projection: [N, H] at positions ``pos`` [N]
    -> q [N, heads, hd] at the LAYER'S OWN number of query heads (its
    ``wq``'s columns), k and v [N, kvh, hd], and the gate's input z [N,
    heads] float32, one scalar a query head. q and k are rotated by the
    kind's rule (ops/rope.py: ``kind_rotary``: theta, the leading
    dimensions that rotate, YaRN's factor on cos and sin), so K rows are
    stored rotated; the softmax scale is the attention ops' own
    1 / sqrt(hd)."""
    N, hd = x.shape[0], c.head_dim
    # each product ends as an [N, out] array: left to fold the reshape to
    # heads (and the rotary after it) into the product, XLA:TPU lays every
    # layer's wq / wk / wv out anew in front of it, every call
    # (tools/tpu_compile_check.py ``weight_copies``; PERF.md section 6,
    # PR 55)
    q, k, v = (jax.lax.optimization_barrier(x @ lp[w]).reshape(N, -1, hd)
               for w in ("wq", "wk", "wv"))
    with jax.named_scope("head_gate"):
        z = jnp.matmul(x, lp["wg"], preferred_element_type=jnp.float32)
    with jax.named_scope(ROPE_SCOPES[kind]):
        inv_freq, factor = kind_rotary(hd, dims(c)["rotary"][kind])
        cos, sin = rope_cos_sin(pos, inv_freq)
        q = apply_rope_leading(q, cos, sin, factor)
        k = apply_rope_leading(k, cos, sin, factor)
    return q, k, v, z


def _gqa_out(lp, o, z):
    """``o`` [N, heads, hd] from the attention, ``z`` [N, heads] float32
    -> the mixer's output: ``sigmoid(z_h) o_h`` a head (the product in
    float32, rounded once), W_o."""
    with jax.named_scope("head_gate"):
        o = (o.astype(jnp.float32)
             * jax.nn.sigmoid(z)[..., None]).astype(o.dtype)
    return o.reshape(o.shape[0], -1) @ lp["wo"]


def _ring_write(ring, names, row: int, new, ring_pos):
    """A decode step's K and V rows ``new`` ([B, heads, w] each) into slot
    ``ring_pos`` of the ring's leaves ``names`` at layer ordinal ``row``."""
    for name, rows in zip(names, new):
        ring[name] = jax.lax.dynamic_update_slice(
            ring[name],
            rows.transpose(1, 0, 2)[None, :, :, None, :].astype(
                ring[name].dtype), (row, 0, 0, ring_pos, 0))


def _ssm_in(c: ModelConfig, lp, x):
    """[N, H] -> the gate z [N, inner], the convolution's input [N,
    conv], dt [N, heads] float32 (after its bias and softplus)."""
    d = dims(c)
    with jax.named_scope("ssm_in_proj"):
        zxd = x @ lp["w_in"]
    z, xbc, dt = jnp.split(zxd, [d["inner"], d["inner"] + d["conv"]], -1)
    return z, xbc, jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])


def _ssm_out(c: ModelConfig, lp, y, xs, z):
    """``y`` [N, heads, P] float32 from the scan -> the mixer's output:
    + D x, the gate FIRST, then the norm (over all heads' channels, or
    over EACH GROUP's where B and C come in groups; one gain of inner),
    then W_out."""
    G = dims(c)["G"]
    with jax.named_scope("ssm_out"):
        y = y + lp["D"][:, None] * xs.astype(jnp.float32)
        y = y.reshape(y.shape[0], -1) * jax.nn.silu(z.astype(jnp.float32))
        if G > 1:
            y = y.reshape(y.shape[0], G, -1)
        var = jnp.mean(y * y, axis=-1, keepdims=True)
        y = (y * jax.lax.rsqrt(var + c.rms_norm_eps)).astype(z.dtype)
        if G > 1:
            y = y.reshape(y.shape[0], -1)
        return (y * lp["norm"]) @ lp["w_out"]


def _split_xbc(c: ModelConfig, xbc):
    """The convolved [.., conv] -> x [.., heads, P], B and C [.., N] (one
    group) or [.., G, N] (head h reads group h // (heads / G))."""
    d = dims(c)
    GN = d["G"] * d["N"]
    xs, B, C = jnp.split(xbc, [d["inner"], d["inner"] + GN], -1)
    if d["G"] > 1:
        B, C = (a.reshape(*a.shape[:-1], d["G"], d["N"]) for a in (B, C))
    return xs.reshape(*xs.shape[:-1], d["nh"], d["P"]), B, C


def _gated(o, z):
    """``o * sigmoid(z)``, the product in float32 and rounded once."""
    return (o.astype(jnp.float32)
            * jax.nn.sigmoid(z.astype(jnp.float32))).astype(z.dtype)


def _lin_in(c: ModelConfig, lp, x, positions):
    """[N, H] -> q (scaled), k, v [N, heads, D] and the gate's input z
    [N, heads x D]: an RMSNorm over D on q and k, then rotary (rotate-
    half over the whole head) at ``positions`` [N]."""
    d = dims(c)
    N, nh, D = x.shape[0], d["lin_heads"], d["lin_dim"]
    with jax.named_scope("lin_attn_proj"):
        q, k, v = ((x @ lp[w]).reshape(N, nh, D) for w in ("wq", "wk", "wv"))
        z = x @ lp["wz"]
        q = _rms(q, lp["q_norm"], c.rms_norm_eps)
        k = _rms(k, lp["k_norm"], c.rms_norm_eps)
        cos, sin = rope_cos_sin(positions, rope_inv_freq(D, c.rope_theta))
        k = apply_rope(k, cos, sin)
        q = (apply_rope(q, cos, sin).astype(jnp.float32)
             / np.sqrt(D)).astype(x.dtype)
    return q, k, v, z


def _lin_out(c: ModelConfig, lp, o, z):
    """``o`` [N, heads, D] float32 from the recurrence -> the mixer's
    output: the norm over all heads' values, the gate, W_o."""
    with jax.named_scope("lin_attn_out"):
        o = o.reshape(o.shape[0], -1)
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + c.rms_norm_eps) * lp["o_norm"].astype(
            jnp.float32)
        return _gated(o, z) @ lp["wo"]


def _l2(x):
    """``x`` / its L2 norm over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_in(c: ModelConfig, lp, x):
    """[N, H] -> the three convolutions' input [N, 3 x inner] (q | k |
    v), the log of the per-channel decay g [N, heads, D] float32 in
    (bound, 0), the step b [N, heads] float32 in (0, 1), and the output
    gate's input z [N, heads] float32."""
    d = dims(c)
    N, nh = x.shape[0], d["kda_heads"]
    with jax.named_scope("kda_proj"):
        qkv = x @ lp["w_qkv"]
    with jax.named_scope("kda_gate"):
        f = jnp.matmul(x, lp["w_f"], preferred_element_type=jnp.float32)
        f = (f + lp["dt_bias"]).reshape(N, nh, -1)
        g = d["kda_bound"] * jax.nn.sigmoid(
            jnp.exp(lp["A_log"])[:, None] * f)
        bz = jnp.matmul(x, lp["w_bg"], preferred_element_type=jnp.float32)
    return qkv, g, jax.nn.sigmoid(bz[:, :nh]), bz[:, nh:]


def _kda_qkv(c: ModelConfig, qkv):
    """The convolved streams [..., 3 x inner] -> q (L2-normalised, x
    D^-1/2), k (L2-normalised) float32 and v, each [..., heads, D]."""
    d = dims(c)
    q, k, v = (a.reshape(*a.shape[:-1], d["kda_heads"], d["kda_dim"])
               for a in jnp.split(qkv, 3, axis=-1))
    return _l2(q) / np.sqrt(d["kda_dim"]), _l2(k), v


def _kda_out(c: ModelConfig, lp, o, z, dtype):
    """``o`` [N, heads, D] float32 from the recurrence -> the mixer's
    output: an RMSNorm over D a head, the gate a head, W_o."""
    with jax.named_scope("kda_out"):
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = (o * jax.lax.rsqrt(var + c.rms_norm_eps)
             * lp["o_norm"].astype(jnp.float32)
             * jax.nn.sigmoid(z)[..., None])
        return o.reshape(o.shape[0], -1).astype(dtype) @ lp["wo"]


def _m1_in(c: ModelConfig, lp, x):
    """[N, H] -> the gate's input z and the convolution's input, each
    [N, inner] (the published in-projection gives x first, then z)."""
    with jax.named_scope("m1_in_proj"):
        xs, z = jnp.split(x @ lp["w_in"], 2, axis=-1)
    return z, xs


def _m1_ssm(c: ModelConfig, lp, xs):
    """The convolved ``xs`` [N, inner] -> dt [N, inner] float32 (after
    its projection, bias and softplus), B and C [N, d_state]: ``x_proj``,
    then an RMSNorm with a gain on each of its three parts where the
    parameters hold the gains (a family without the inner norms has
    none)."""
    d = dims(c)
    R, N = d["m1_rank"], d["m1_N"]
    with jax.named_scope("m1_xproj"):
        dt, B, C = jnp.split(xs @ lp["w_x"], [R, R + N], axis=-1)
        if "dt_norm" in lp:
            dt, B, C = (_rms(a, lp[g], c.rms_norm_eps) for a, g in (
                (dt, "dt_norm"), (B, "b_norm"), (C, "c_norm")))
        dt = jnp.matmul(dt, lp["w_dt"], preferred_element_type=jnp.float32)
        return jax.nn.softplus(dt + lp["dt_bias"]), B, C


def _m1_out(lp, y, z):
    """``y`` [N, inner] float32 from the scan (D x in it) -> the mixer's
    output: the gate, W_out; no norm."""
    with jax.named_scope("m1_out"):
        return (y * jax.nn.silu(z.astype(jnp.float32))).astype(
            z.dtype) @ lp["w_out"]


def _gmu(lp, x, m):
    """The gated memory unit: ``(m * silu(x W_1)) W_2`` with ``m`` [N,
    inner] float32 another layer's scan output (before ITS gate) at the
    same positions; the product in float32 and rounded once."""
    with jax.named_scope("gmu"):
        g = jax.nn.silu((x @ lp["w1"]).astype(jnp.float32))
        return (m * g).astype(x.dtype) @ lp["w2"]


def _diff_q(c: ModelConfig, lp, x):
    """[N, H] -> the differential form's queries against PAIR-WIDE rows,
    [N, heads, 2 hd]: query pair j is heads (2j, 2j + 1); the first holds
    its values in the row's first half and zeros in the second, the other
    the reverse, so that against a K row ``[k1 | k2]`` each scores its own
    key and both read the pair's one V row: two score maps in one pass of
    the attention ops as they are (GQA group 4: pair j reads K/V pair j //
    2). The softmax scale rides on q (x sqrt(2 hd), which the ops divide
    out), the product in float32 and rounded once."""
    N, hd = x.shape[0], c.head_dim
    k = c.hybrid_dict["attention_multiplier"] * np.sqrt(2 * hd)
    q = ((x @ lp["wq"] + lp["bq"]).astype(jnp.float32) * k).astype(x.dtype)
    q = q.reshape(N, c.num_heads // 2, 2, hd)
    zero = jnp.zeros_like(q[:, :, 0])
    return jnp.stack(
        [jnp.concatenate([q[:, :, 0], zero], -1),
         jnp.concatenate([zero, q[:, :, 1]], -1)], 2
    ).reshape(N, c.num_heads, 2 * hd)


def _diff_kv(c: ModelConfig, lp, x):
    """[N, H] -> K and V rows as the region holds them, [N, pairs, 2 hd]:
    ``[k_2g | k_2g+1]`` and ``[v_2g | v_2g+1]``, a free reshape of the
    projections' columns."""
    N = x.shape[0]
    return ((x @ lp["wk"] + lp["bk"]).reshape(N, *row_heads(c)),
            (x @ lp["wv"] + lp["bv"]).reshape(N, *row_heads(c)))


def _diff_out(c: ModelConfig, lp, o):
    """``o`` [N, heads, 2 hd], what the two maps of every query pair read
    of the pair-wide V -> the mixer's output: ``o_1 - lam o_2``, an
    RMSNorm over the 2 hd values with the layer's one gain, x (1 -
    lam_init), W_o and its bias. ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
    lam_init``, float32."""
    N, dtype = o.shape[0], o.dtype
    o = o.astype(jnp.float32).reshape(N, c.num_heads // 2, 2, -1)
    lam = (jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"]))
           - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lp["lam_init"])
    o = o[:, :, 0] - lam * o[:, :, 1]
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = (o * jax.lax.rsqrt(var + c.rms_norm_eps)
         * lp["sub_norm"].astype(jnp.float32) * (1.0 - lp["lam_init"]))
    return o.reshape(N, -1).astype(dtype) @ lp["wo"] + lp["bo"]


def _row_attention(q, k_new, v_new, last, prior, below):
    """ONE query row a chunk over its whole causal context: q [K, heads,
    w] of the chunk's row ``last`` [K] against the chunk's own rows up to
    it (``k_new`` / ``v_new`` [K, T, kvh, w]) and, where ``prior`` is a
    ``PriorContext`` workspace ([1, kvh, K, span, w] a kind), its first
    ``below`` [K] rows. [K, heads, w]. The scores of one row are a vector:
    no blocks, one softmax over both parts."""
    K, nh, w = q.shape
    T, kvh = k_new.shape[1:3]
    f32 = jnp.float32
    qg = q.reshape(K, kvh, nh // kvh, w)
    scale = 1.0 / np.sqrt(w)
    s = jnp.einsum("kgrh,ktgh->kgrt", qg, k_new.astype(q.dtype),
                   preferred_element_type=f32) * scale
    s = jnp.where(jnp.arange(T) <= last[:, None, None, None], s, NEG_INF)
    if prior is not None:
        span = prior.k.shape[3]
        s0 = jnp.einsum("kgrh,gksh->kgrs", qg, prior.k[0].astype(q.dtype),
                        preferred_element_type=f32) * scale
        s0 = jnp.where(jnp.arange(span) < below[:, None, None, None],
                       s0, NEG_INF)
        s = jnp.concatenate([s0, s], -1)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("kgrt,ktgh->kgrh", p[..., -T:], v_new.astype(q.dtype),
                   preferred_element_type=f32)
    if prior is not None:
        o = o + jnp.einsum("kgrs,gksh->kgrh", p[..., :span],
                           prior.v[0].astype(q.dtype),
                           preferred_element_type=f32)
    return o.reshape(K, nh, w).astype(q.dtype)


def _window_prior(ctx_kv, layer: int, slots, q_starts):
    """The prior context of K continuing chunks in window layer ``layer``:
    each lane's modular buffer sliced out of the region and UN-ROTATED
    into a workspace [1, kvh, K, W, w] a kind whose row i holds position
    q_start - W + i (``prefill_attention`` under a window; rows of
    positions below 0, and all of a fresh chunk's, are masked there)."""
    K, W = slots.shape[0], ctx_kv[WK].shape[3]
    at = (q_starts[:, None] + jnp.arange(W, dtype=jnp.int32)) % W  # [K, W]

    def lanes(buf):
        size = (1, buf.shape[1], 1, W, buf.shape[4])
        return jnp.concatenate([
            jnp.take(jax.lax.dynamic_slice(
                buf, (layer, 0, slots[i], 0, 0), size), at[i], axis=3)
            for i in range(K)], axis=2)

    return PriorContext(lanes(ctx_kv[WK]), lanes(ctx_kv[WV]),
                        jnp.int32(0), jnp.arange(K, dtype=jnp.int32))


def _no_bias(lp):
    return jnp.zeros((lp["conv_w"].shape[1],), jnp.float32)


def _rows_of(ctx_kv):
    """A region's (or a ring's) K rows, or its latent rows where it keeps
    those instead: what gives its dtype and its length."""
    return ctx_kv["k"] if "k" in ctx_kv else ctx_kv[KV]


def _sparse_in(c: ModelConfig, lp, x):
    """[N, H] -> q [N, heads, hd], k, v [N, kvh, hd], z [N, heads x hd]:
    an RMSNorm over hd on q and k, no rotary."""
    N = x.shape[0]
    q = (x @ lp["wq"]).reshape(N, c.num_heads, c.head_dim)
    k = (x @ lp["wk"]).reshape(N, c.num_kv_heads, c.head_dim)
    v = (x @ lp["wv"]).reshape(N, c.num_kv_heads, c.head_dim)
    return (_rms(q, lp["q_norm"], c.rms_norm_eps),
            _rms(k, lp["k_norm"], c.rms_norm_eps), v, x @ lp["wz"])


def _sparse_prefill(c: ModelConfig, lp, x, ctx_kv, row: int, sp: int, slots,
                    q_starts, seq_lens, span: int, K: int, T: int):
    """One sparse layer over K chunks: (the mixer's output [K T, H], k, v
    [K, T, kvh, hd], the lanes' compressed keys with this chunk's written
    in [K, kvh, S / stride, hd]). ``row`` / ``sp``: the layer's ordinal
    among the layers that keep rows / among the sparse ones."""
    g = dims(c)["sparse"]
    kvh, hd = c.num_kv_heads, c.head_dim
    with jax.named_scope("nope_attn"):
        q, k, v, z = _sparse_in(c, lp, x)
        q, k, v = (a.reshape(K, T, *a.shape[1:]) for a in (q, k, v))
        Sc = ctx_kv[KC].shape[3]
        masks, kcs = [], []
        for i in range(K):
            with jax.named_scope("sparse_compress"):
                if span:
                    at = jnp.maximum(q_starts[i] - g.stride, 0)
                    tail = jax.lax.dynamic_slice(
                        ctx_kv["k"], (row, 0, slots[i], at, 0),
                        (1, kvh, 1, g.stride, hd))[0, :, 0].transpose(1, 0, 2)
                    kc_lane = jax.lax.dynamic_slice(
                        ctx_kv[KC], (sp, 0, slots[i], 0, 0),
                        (1, kvh, 1, Sc, hd))[0, :, 0]
                else:
                    tail = jnp.zeros((g.stride, kvh, hd), k.dtype)
                    kc_lane = jnp.zeros((kvh, Sc, hd), ctx_kv[KC].dtype)
                kc_lane = sparse_attention.overlay(
                    kc_lane, sparse_attention.compress_chunk(g, k[i], tail),
                    q_starts[i] // g.stride - 1)
            with jax.named_scope("sparse_select"):
                masks.append(sparse_attention.prefill_block_mask(
                    g, q[i].reshape(T, kvh, -1, hd), kc_lane.astype(q.dtype),
                    q_starts[i], seq_lens[i] - q_starts[i]))
            kcs.append(kc_lane)
        with jax.named_scope("sparse_attn"):
            o = prefill_attention(
                q, k, v, q_starts, seq_lens,
                _prior_rows(ctx_kv, row, slots, span), ctx_span=span,
                block_masks=jnp.stack(masks), mask_block=g.block)
        mix = _gated(o.reshape(K * T, c.q_dim), z) @ lp["wo"]
    return mix, k, v, jnp.stack(kcs)


def _sparse_attend(c: ModelConfig, q, k, v, ctx_kv, row: int, sp: int,
                    slots, q_starts, seq_lens, span: int):
    """One sparse layer's attention over K chunks, from its in-projection's
    q [K, T, heads, hd], k and v [K, T, kvh, hd]: (o [K, T, heads, hd],
    the lanes' compressed keys with this chunk's written in [K, kvh, S /
    stride, hd]). ``row`` / ``sp``: the layer's ordinal among the layers
    that keep rows / among the sparse ones."""
    g = dims(c)["sparse"]
    kvh, hd = c.num_kv_heads, c.head_dim
    K, T = q.shape[:2]
    with jax.named_scope("nope_attn"):
        Sc = ctx_kv[KC].shape[3]
        masks, kcs = [], []
        for i in range(K):
            with jax.named_scope("sparse_compress"):
                if span:
                    at = jnp.maximum(q_starts[i] - g.stride, 0)
                    tail = jax.lax.dynamic_slice(
                        ctx_kv["k"], (row, 0, slots[i], at, 0),
                        (1, kvh, 1, g.stride, hd))[0, :, 0].transpose(1, 0, 2)
                    kc_lane = jax.lax.dynamic_slice(
                        ctx_kv[KC], (sp, 0, slots[i], 0, 0),
                        (1, kvh, 1, Sc, hd))[0, :, 0]
                else:
                    tail = jnp.zeros((g.stride, kvh, hd), k.dtype)
                    kc_lane = jnp.zeros((kvh, Sc, hd), ctx_kv[KC].dtype)
                kc_lane = sparse_attention.overlay(
                    kc_lane, sparse_attention.compress_chunk(g, k[i], tail),
                    q_starts[i] // g.stride - 1)
            with jax.named_scope("sparse_select"):
                masks.append(sparse_attention.prefill_block_mask(
                    g, q[i].reshape(T, kvh, -1, hd), kc_lane.astype(q.dtype),
                    q_starts[i], seq_lens[i] - q_starts[i]))
            kcs.append(kc_lane)
        with jax.named_scope("sparse_attn"):
            o = prefill_attention(
                q, k, v, q_starts, seq_lens,
                _prior_rows(ctx_kv, row, slots, span), ctx_span=span,
                block_masks=jnp.stack(masks), mask_block=g.block)
    return o, jnp.stack(kcs)


def _sparse_decode(c: ModelConfig, lp, x, ctx_kv, ring, kc, row: int,
                   sp: int, ctx_lens, ring_base, ring_pos, live,
                   attn: DecodeAttention):
    """One sparse layer, one token a lane: (the mixer's output [B, H],
    the ring with the new rows, the compressed keys with the one this
    step completed). A lane at or past ``dense_len`` reads its selected
    blocks; one below it takes the dense read."""
    g = dims(c)["sparse"]
    B = x.shape[0]
    t = ctx_lens - 1
    with jax.named_scope("nope_attn"):
        q, k, v, z = _sparse_in(c, lp, x)
        _ring_write(ring, ("k", "v"), row, (k, v), ring_pos)
        with jax.named_scope("sparse_compress"):
            kc = sparse_attention.compress_step(
                g, ctx_kv["k"], ring["k"], kc, row, sp, t, ring_base, live)
        selects = live & (t >= g.dense_len)
        o_sel, _ = sparse_attention.decode_attention(
            g, q, ctx_kv["k"], ctx_kv["v"], kc, ring["k"], ring["v"],
            row, sp, ctx_lens, ring_base, selects)
        # the dense read (a Mosaic call over the work list of the live
        # lanes below the switch) only when there is such a lane
        dense = live & ~selects
        o = jax.lax.cond(
            jnp.any(dense),
            lambda: ctx_decode_attention(
                attn, q, ctx_kv["k"], ctx_kv["v"], ring["k"], ring["v"],
                jnp.int32(row), ctx_lens, ring_base, live=dense),
            lambda: jnp.zeros_like(o_sel))
        o = jnp.where(selects[:, None, None], o_sel, o)
        mix = _gated(o.reshape(B, c.q_dim), z) @ lp["wo"]
    return mix, ring, kc


def _prior_rows(ctx_kv, layer: int, slots, span: int):
    """The prior context of K continuing chunks: their lanes' rows of
    attention layer ``layer``, SLICED out of the region into a workspace
    ([1, kvh, K, span, hd] a kind: 16.8 MB a lane at 8192 rows of 8 heads
    of 128) that the attention's loop then reads. Handing the region
    itself to that loop, as the dense decoder does, makes XLA:TPU copy it
    whole before the tail's in-place writes (the loop carries it as an
    invariant: 553 MB a kind here, compile-only v5e, PR 41). None when
    ``span`` is 0 (every chunk fresh)."""
    if not span:
        return None
    K = slots.shape[0]

    def lanes(buf):
        size = (1, buf.shape[1], 1, span, buf.shape[4])
        return jnp.concatenate([
            jax.lax.dynamic_slice(buf, (layer, 0, slots[i], 0, 0), size)
            for i in range(K)], axis=2)

    return PriorContext(lanes(ctx_kv["k"]), lanes(ctx_kv["v"]),
                        jnp.int32(0), jnp.arange(K, dtype=jnp.int32))


def _refuse_adapters(params):
    if params.get("adapters") is not None:
        raise ValueError("the state-space hybrid block carries no LoRA bank")


# ---------------------------------------------------------------------------
# Prefill

# Heights of the row blocks a prefill chunk's per-row work loops over: the
# two row-wise halves of every layer kind (_mix_in, _mix_out) in blocks of
# LIVE_ROW_BLOCK, the chunked scans of the recurrent kinds (_scan_block) in
# blocks of SCAN_ROW_BLOCK. Both chosen on the chip
# (tools/hybrid_rows_bench.py, PERF.md section 6, PR 49): the halves'
# matmuls want 512 rows a trip, a scan wants ONE 256-row chunk a trip (a
# trip of two is a loop in a loop: 1.25 x the straight-line scan at full
# length where one chunk a trip is 0.87 x).
LIVE_ROW_BLOCK = 512
SCAN_ROW_BLOCK = 256
# The layer kinds whose stacks loop. A stack of lightning and block-sparse
# layers stays straight-line: its looped layers are 2-7 % faster a row, but
# served whole (full chunks, so little to skip) it read no gain end to end
# and its check's distance rose by a sixth: the looped program rounds what
# crosses a half's boundary to the cache dtype as written, which the
# straight-line program's fusions skip, and the sparse layers' block
# selection flips on such roundings (PERF.md section 6, PR 49).
LIVE_ROW_KINDS = ("mamba", "attention", "kda", "latent_attention", "mamba1",
                  "window_attention", "cross_attention", "gmu") + FFN_KINDS


def live_row_block(c: ModelConfig, T: int) -> int:
    """Rows a block of the hybrid block's live-row loops for a prefill
    chunk of bucket width ``T`` (the halves' height; the scans' is
    ``SCAN_ROW_BLOCK``), or 0 where its halves and scans run straight-line
    over all T rows: a bucket of one block or of no whole number of them,
    scan blocks that are no whole part of a block or no whole number of a
    scan's chunks (a block's scan must cut its rows where the whole
    chunk's would), and a stack with a layer kind outside
    ``LIVE_ROW_KINDS``. Decided by the shapes and the kinds, one decision
    a program; the host's mirror (``llama.prefill_positions_run``) calls
    it too."""
    R, Rs, d = LIVE_ROW_BLOCK, SCAN_ROW_BLOCK, dims(c)
    chunks = [q for n, q in ((d["n_ssm"], d.get("chunk")),
                             (d["n_kda"], kda.CHUNK), (d["n_lin"], LIN_CHUNK),
                             (d["n_m1"], mamba1.GROUP))
              if n]
    if (T % R or T // R < 2 or R % Rs or any(Rs % q for q in chunks)
            or any(kind not in LIVE_ROW_KINDS for kind in d["kinds"])):
        return 0
    return R


def _mix_in(c: ModelConfig, kind: str, lp, h, pos):
    """The row-wise FIRST half of a layer of ``kind``: the norm and the
    mixer's in-projection. ``h`` [N, H], ``pos`` [N] the rows' positions
    -> what the kind's sequence operation takes, a tuple of [N, ...]."""
    if kind == "latent_attention":
        q_nope, q_rope, row = mla_moe._attn_in(c, lp, h, pos)
        k, v = mla_moe._expand_kv(c, lp, row)
        return jnp.concatenate([q_nope, q_rope], -1), k, v, row
    x = _norm(c, lp, "ln1", h)
    if kind == "gmu":
        return (x,)
    form = _form(dims(c), kind)
    if form == "diff":
        return (_diff_q(c, lp, x),) + (
            () if kind == "cross_attention" else _diff_kv(c, lp, x))
    if form == "gqa":
        return _gqa_in(c, kind, lp, x, pos)
    if kind == "attention":
        return _qkv(c, lp, x)
    if kind == "sparse_attention":
        return _sparse_in(c, lp, x)
    if kind == "linear_attention":
        return _lin_in(c, lp, x, pos)
    if kind == "kda":
        return _kda_in(c, lp, x)
    if kind == "mamba1":
        return _m1_in(c, lp, x)
    return _ssm_in(c, lp, x)


def _scan_block(c: ModelConfig, kind: str, lp, S, *blk):
    """A run of one lane's rows through a recurrent layer's chunked scan,
    from the state ``S`` the rows before left: ([o], the state after the
    run's last real row). The run is a whole chunk of T rows, or one block
    of them (a whole number of the scan's chunks). Padding is masked here:
    it neither decays nor feeds the state."""
    d = dims(c)
    if kind == "linear_attention":
        q, k, v, real = blk
        with jax.named_scope("lin_attn_scan"):
            o, S = lightning.chunk_scan(
                q, k, v, jnp.asarray(lightning.log_decays(d["lin_heads"])),
                real, S, LIN_CHUNK)
    elif kind == "kda":
        qkv, g, b, real = blk
        with jax.named_scope("kda_scan"):
            o, S = kda.chunk_scan(*_kda_qkv(c, qkv), g, b, real, S)
    elif kind == "mamba1":
        xs, real = blk
        dt, Bm, Cm = _m1_ssm(c, lp, xs)
        with jax.named_scope("m1_scan"):
            o, S = mamba1.chunk_scan(
                xs, dt, Bm, Cm, -jnp.exp(lp["A_log"]), lp["D"], S,
                jnp.sum(real, dtype=jnp.int32))
    else:
        xbc, dt, real = blk
        xs, Bm, Cm = _split_xbc(c, xbc)
        with jax.named_scope("ssm_scan"):
            o, S = mamba2.chunk_scan(
                xs, jnp.where(real[:, None], dt, 0.0), -jnp.exp(lp["A_log"]),
                Bm, Cm, S, d["chunk"])
    return [o], S


def _mix_out(c: ModelConfig, kind: str, lp, h, *seq):
    """The row-wise SECOND half of a layer of ``kind``, up to the expert
    sort: the mixer's out-norm, gate and out-projection from what its
    sequence operation returned (``seq``, [N, ...] each), the residual,
    the norm in front of the feed-forward part and, where the layer
    routes, the router and the shared MLP: (h after the mixer, x = that
    norm of it, picks, combine weights, the shared MLP of x[, whether a
    token kept a group held here]); where it does not, the dense MLP and
    its residual too: (h after the layer,). Which of the two parts a
    layer has is ``_parts``': a layer with no mixer takes no ``seq`` and
    runs its feed-forward part on ``h``; one with no feed-forward part
    ends at the mixer's residual."""
    mixes, norm = _parts(c, kind)
    form = _form(dims(c), kind)
    if not mixes:
        pass
    elif kind == "gmu":
        mix = _gmu(lp, *seq)
    elif form == "diff":
        mix = _diff_out(c, lp, *seq)
    elif form == "gqa":
        mix = _gqa_out(lp, *seq)
    elif kind in ("attention", "latent_attention"):
        o, = seq
        mix = o.reshape(o.shape[0], -1) @ lp["wo"]
    elif kind == "sparse_attention":
        o, z = seq
        mix = _gated(o.reshape(o.shape[0], c.q_dim), z) @ lp["wo"]
    elif kind == "linear_attention":
        mix = _lin_out(c, lp, *seq)
    elif kind == "kda":
        mix = _kda_out(c, lp, *seq, h.dtype)
    elif kind == "mamba1":
        mix = _m1_out(lp, *seq)
    else:
        y, xbc, z = seq
        mix = _ssm_out(c, lp, y, _split_xbc(c, xbc)[0], z)
    r = jnp.asarray(c.hybrid_dict["residual_multiplier"], h.dtype)
    if mixes:
        h = h + r * mix
    if norm is None:
        return (h,)
    x = _norm(c, lp, norm, h)
    if "wr" not in lp:   # a leading dense layer
        with jax.named_scope("mlp"):
            return (h + r * _dense(lp, x, "w_g", "w_u", "w_d"),)
    sel, w, here = route(c, lp, x)
    return (h, x, sel, w, _shared(lp, x)) + (() if here is None else (here,))


@functools.partial(jax.jit, static_argnames=("half", "kind", "c", "R"))
def _live_half(half, kind: str, c: ModelConfig, lp, trips, rows, R: int):
    """``half`` (_mix_in or _mix_out: no row of its output depends on
    another row) of a layer of ``kind`` over the row blocks that hold a
    live row (live_rows.over_live_blocks): ``rows`` its per-row operands
    [K, T, ...], ``trips`` [K] the lanes' live blocks. A tuple of [K, T,
    ...], rows of blocks that never ran 0. The layer's weights are
    arguments, so the same-kind layers of a program, unrolled in the
    caller, share ONE traced and lowered loop body."""
    return over_live_blocks(
        lambda lane, r0, blk: list(half(c, kind, lp, *blk)), trips, rows, R)


@functools.partial(jax.jit, static_argnames=("kind", "c", "R"))
def _live_scan(kind: str, c: ModelConfig, lp, trips, rows, state, R: int):
    """A recurrent layer's chunked scan over the row blocks that hold a
    live row: each trip runs ``_scan_block`` on its block from the lane's
    carried float32 state. (o [K, T, ...] with the rows of blocks that
    never ran 0, the states [K, ...] after each lane's last live block:
    what masking the padding of the whole chunk gives too.)"""
    (o,), state = over_live_blocks(
        lambda lane, r0, blk, S: _scan_block(c, kind, lp, S, *blk),
        trips, rows, R, state)
    return o, state


def _write_chunks(c, params, ctx_kv, rows, kcs, states, slots, q_starts,
                  seq_lens, h, logits=None):
    """A prefill program's tail, after every read: the K chunks' rows
    (``rows``: {kind: [K, L, T, heads, width]}) as spans (a window kind's
    into its lane's modular buffer, whole), their compressed
    keys (``kcs``: [K, kvh, Sc, hd] a sparse layer) and recurrent leaves
    (``states``: (name, [K, ...] a layer) pairs) as whole lanes, and the
    logits of each chunk's last real position from ``h`` [K, T, H] (or
    [K T, H]) unless the caller has them (``logits``)."""
    K = slots.shape[0]

    def write_lane(i, out):
        out = dict(out)
        for name, r in rows.items():
            r = jax.lax.dynamic_index_in_dim(r, i, keepdims=False)
            if name in (WK, WV):
                # slot s takes the chunk's LAST real position that falls
                # on it, or keeps what it holds (an earlier position the
                # lane's next reader still sees)
                T, W = r.shape[1], out[name].shape[3]
                end = q_starts[i] + jnp.clip(seq_lens[i] - q_starts[i], 0, T)
                p = end - 1 - (end - 1 - jnp.arange(W, dtype=jnp.int32)) % W
                at = (0, 0, slots[i], 0, 0)
                old = jax.lax.dynamic_slice(
                    out[name], at, r.shape[:1] + (r.shape[2], 1, W)
                    + r.shape[3:])
                new = jnp.take(r, jnp.clip(p - q_starts[i], 0, T - 1),
                               axis=1).transpose(0, 2, 1, 3)[:, :, None]
                out[name] = jax.lax.dynamic_update_slice(
                    out[name], jnp.where(
                        (p >= q_starts[i])[None, None, None, :, None],
                        new.astype(old.dtype), old), at)
                continue
            out[name] = jax.lax.dynamic_update_slice(
                out[name], r.transpose(0, 2, 1, 3)[:, :, None],
                (0, 0, slots[i], q_starts[i], 0))
        if kcs:
            kc = jax.lax.dynamic_index_in_dim(
                jnp.stack(kcs, 1), i, keepdims=False)  # [L_sparse, kvh, Sc, hd]
            out[KC] = jax.lax.dynamic_update_slice(
                out[KC], kc[:, :, None].astype(out[KC].dtype),
                (0, 0, slots[i], 0, 0))
        for name, new in states:
            out[name] = [
                jax.lax.dynamic_update_slice(
                    buf, jax.lax.dynamic_index_in_dim(s, i, keepdims=True),
                    (slots[i],) + (0,) * (buf.ndim - 1))
                for buf, s in zip(out[name], new)]
        return out

    out_ctx = jax.lax.fori_loop(0, K, write_lane, dict(ctx_kv))
    if logits is not None:
        return out_ctx, logits
    last = jnp.maximum(seq_lens - q_starts - 1, 0)
    h_last = jnp.take_along_axis(
        h.reshape(K, -1, h.shape[-1]), last[:, None, None], axis=1)[:, 0]
    return out_ctx, _logits(c, params, h_last)


DIFF_SCOPES = {"attention": "full_diff_attn",
               "window_attention": "window_diff_attn",
               "cross_attention": "cross_diff_attn"}


def _rows_prefill(c, kind: str, ctx_kv, slots, q_starts, seq_lens, span: int,
                  q, own, rows, window_rows):
    """One window, full or cross attention layer over K chunks, in the
    differential form or the rotary GQA form (they differ in what happens
    to q, k and v before and to o after, not here), from its
    in-projection's q [K, T, heads, w] and ``own`` = (k, v) [K, T, kvh, w]
    (() for a cross layer): o [K, T, heads, w]. ``rows`` / ``window_rows``
    are the (ks, vs) lists of the layers that keep full / window rows so
    far in the program: the layer's own rows are appended to its pair, a
    cross layer reads the pair of the layer it names."""
    d = dims(c)
    window = kind == "window_attention"
    held = window_rows if window else rows
    row = d["rows_row"] if kind == "cross_attention" else len(held[0])
    for kept, new in zip(held, own):
        kept.append(new)
    if window:
        return prefill_attention(
            q, *own, q_starts, seq_lens,
            _window_prior(ctx_kv, row, slots, q_starts) if span else None,
            ctx_span=ctx_kv[WK].shape[3] if span else 0, window=d["window"])
    return prefill_attention(
        q, held[0][row], held[1][row], q_starts, seq_lens,
        _prior_rows(ctx_kv, row, slots, span), ctx_span=span)


def _climbs_at(c, l: int) -> bool:
    """Whether a prefill chunk's rows stop at layer ``l``: the layer whose
    rows the cross layers read, in a stack where nothing above it keeps a
    row or a state (and the skip is on)."""
    d = dims(c)
    return SKIP_ROWS and d.get("climbs", False) and l == d["rows_from"]


def _climb(c, params, ctx_kv, slots, q_starts, seq_lens, span: int, h, q,
           k, v, m):
    """The cross-decoder's share of a prefill chunk where only the chunk's
    last REAL row climbs it (``dims(c)["climbs"]``): the layers from the
    one whose rows the cross layers read up. ``h`` [K, T, H] the rows
    below that layer, ``q`` / ``k`` / ``v`` [K, T, ...] ITS in-projection
    of them (its K and V go to the region for every row; the caller keeps
    them), ``m`` [K, T, inner] the scan output the gated memory units
    read. The logits [K, vocab] of the row that climbed. Exact: nothing
    above that layer writes a row or a state, so no other row of the chunk
    is ever read there."""
    d = dims(c)
    K, T = h.shape[:2]
    last = jnp.clip(seq_lens - q_starts, 1, T) - 1
    at = lambda a: jnp.take_along_axis(  # noqa: E731
        a, last.reshape((K,) + (1,) * (a.ndim - 1)), axis=1)[:, 0]
    h, q, m = at(h), at(q), at(m)
    prior = _prior_rows(ctx_kv, d["rows_row"], slots, span)
    below = jnp.minimum(jnp.minimum(q_starts, seq_lens), span)
    stats = stats_zero(c)
    for l in range(d["rows_from"], c.num_layers):
        kind, lp = d["kinds"][l], params["layers"][l]
        x = _norm(c, lp, "ln1", h)
        if kind == "gmu":
            mix = _gmu(lp, x, m)
        else:
            with jax.named_scope(DIFF_SCOPES[kind]):
                if l > d["rows_from"]:
                    q = _diff_q(c, lp, x)
                mix = _diff_out(c, lp, _row_attention(q, k, v, last, prior,
                                                      below))
        h, stats = _layer_out(c, kind, lp, h, mix, None, stats)
    return _logits(c, params, h)


def batch_prefill_impl(config, params, ctx_kv, tokens, slots, q_starts,
                       seq_lens, ctx_span=0, adapter_ids=None, *,
                       attn=None):
    """K chunks [K, T] through the model in one program. The attention
    layers' rows land in each lane's region at [q_start, q_start + T) and
    the Mamba layers' states in the lane, in one tail pass after every
    read. ``ctx_span`` 0: every chunk is fresh, and neither the region
    nor a lane's state is read. Else a chunk with q_start > 0 starts
    from its lane's state as it attends its lane's rows; one with
    q_start 0 starts from zeros.

    Where ``live_row_block`` gives a block height, the program is
    ``_live_prefill``'s instead: the same layers with their per-row work
    over the lanes' LIVE row blocks. Here every layer runs all K x T
    bucket rows, padding masked: the form every narrow bucket and a stack
    outside ``LIVE_ROW_KINDS`` keep, and one whose lowered text must not
    move with the looped form's (a block-sparse stack's served tail latency
    follows its chunks' compiled schedule: PERF.md section 6, PR 49).
    ``attn`` (the protocol's: what the engine's programs are traced for)
    is not asked: every kind's prefill attention keeps the XLA loops or
    lowers by platform."""
    if live_row_block(config, tokens.shape[1]):
        return _live_prefill(config, params, ctx_kv, tokens, slots, q_starts,
                             seq_lens, ctx_span)
    c, d = config, dims(config)
    _refuse_adapters(params)
    K, T = tokens.shape
    cdt = _rows_of(ctx_kv).dtype
    positions = q_starts[:, None] + jnp.arange(T, dtype=jnp.int32)
    real = positions < seq_lens[:, None]                      # [K, T]
    valid = real.reshape(K * T)
    n_real = jnp.clip(seq_lens - q_starts, 0, T)
    span = min(ctx_span, _rows_of(ctx_kv).shape[3])
    continuing = q_starts > 0
    if span and d["n_latent"]:
        # continuing chunks' prior latent rows, expanded per head into a
        # workspace every latent layer rewrites (mla_moe._expand_prior)
        m = mla_moe.dims(c)
        below = jnp.minimum(jnp.minimum(q_starts, seq_lens), span)
        work = tuple(jnp.zeros((1, m["nh"], K, span, w), cdt)
                     for w in (m["nope"] + m["rope"], m["v"]))
    h = _embed(c, params, tokens.reshape(K * T), cdt)
    stats = stats_zero(c)
    R = _move_block(d, K * T)
    moved = jnp.int32(0)
    ks, vs, kcs, ssm_out, conv_out, lin_out = [], [], [], [], [], []
    lat, kda_out, kda_conv_out, m1_out, m1_conv_out = [], [], [], [], []
    lanes = lambda a: a.reshape(K, T, *a.shape[1:])  # noqa: E731
    A = lambda lp: -jnp.exp(lp["A_log"])  # noqa: E731
    wks, wvs = [], []     # the window layers' rows
    m = logits = None     # the scan output the gated memory units read
    for l, (kind, lp) in enumerate(zip(d["kinds"], params["layers"])):
        if kind == "gmu" or _form(d, kind) == "diff":
            # the row-wise halves as the looped form has them, around the
            # attention ops at pair-wide rows
            ins = _mix_in(c, kind, lp, h, None)
            if kind == "gmu":
                h, = _mix_out(c, kind, lp, h, ins[0], m)
                continue
            q, *own = map(lanes, ins)
            if _climbs_at(c, l):
                # the rows stop here: the chunk's last real row climbs
                # the rest of the stack alone
                ks.append(own[0])
                vs.append(own[1])
                logits = _climb(c, params, ctx_kv, slots, q_starts,
                                seq_lens, span, lanes(h), q, *own, lanes(m))
                break
            with jax.named_scope(DIFF_SCOPES[kind]):
                o = _rows_prefill(c, kind, ctx_kv, slots, q_starts, seq_lens,
                                  span, q, own, (ks, vs), (wks, wvs))
            h, = _mix_out(c, kind, lp, h, o.reshape(K * T, *o.shape[2:]))
            continue
        mixes, _ = _parts(c, kind)
        x = _norm(c, lp, "ln1", h) if mixes else None
        if not mixes:
            mix = None   # the layer is its feed-forward part
        elif _form(d, kind) == "gqa":
            with jax.named_scope(GQA_SCOPES[kind]):
                q, k, v, z = _gqa_in(c, kind, lp, x,
                                     positions.reshape(K * T))
                o = _rows_prefill(c, kind, ctx_kv, slots, q_starts, seq_lens,
                                  span, lanes(q), [lanes(k), lanes(v)],
                                  (ks, vs), (wks, wvs))
                mix = _gqa_out(lp, o.reshape(K * T, *o.shape[2:]), z)
        elif kind == "attention":
            with jax.named_scope("nope_attn"):
                q, k, v = (a.reshape(K, T, *a.shape[1:])
                           for a in _qkv(c, lp, x))
                prior = _prior_rows(ctx_kv, len(ks), slots, span)
                ks.append(k)
                vs.append(v)
                o = prefill_attention(q, k, v, q_starts, seq_lens, prior,
                                      ctx_span=span)
                mix = o.reshape(K * T, c.q_dim) @ lp["wo"]
        elif kind == "sparse_attention":
            mix, k, v, kc = _sparse_prefill(
                c, lp, x, ctx_kv, len(ks), len(kcs), slots, q_starts,
                seq_lens, span, K, T)
            ks.append(k)
            vs.append(v)
            kcs.append(kc)
        elif kind == "linear_attention":
            j = len(lin_out)
            q, k, v, z = _lin_in(c, lp, x, positions.reshape(K * T))
            if span:
                S0 = jnp.where(continuing[:, None, None, None],
                               ctx_kv[LIN][j][slots], 0.0)
            else:
                S0 = jnp.zeros((K, d["lin_heads"], d["lin_dim"],
                                d["lin_dim"]), jnp.float32)
            decay = jnp.asarray(lightning.log_decays(d["lin_heads"]))
            with jax.named_scope("lin_attn_scan"):
                o, S = jax.vmap(
                    lambda q, k, v, real, S0: lightning.chunk_scan(
                        q, k, v, decay, real, S0, LIN_CHUNK)
                )(*(a.reshape(K, T, *a.shape[1:]) for a in (q, k, v)),
                  real, S0)
            lin_out.append(S)
            mix = _lin_out(c, lp, o.reshape(K * T, *o.shape[2:]), z)
        elif kind == "kda":
            j = len(kda_out)
            qkv, g, b, z = _kda_in(c, lp, x)
            if span:
                keep = continuing[:, None, None]
                win0 = jnp.where(keep, ctx_kv[KDA_CONV][j][slots], 0)
                S0 = jnp.where(keep[..., None], ctx_kv[KDA][j][slots], 0.0)
            else:
                win0 = jnp.zeros((K, d["kda_W"] - 1, 3 * d["kda_inner"]), cdt)
                S0 = jnp.zeros((K, d["kda_heads"], d["kda_dim"],
                                d["kda_dim"]), jnp.float32)
            with jax.named_scope("kda_conv"):
                qkv, win = jax.vmap(
                    lambda a, w0, n: mamba2.causal_conv(
                        a, w0, lp["conv_w"], _no_bias(lp), n)
                )(qkv.reshape(K, T, -1), win0, n_real)
            with jax.named_scope("kda_scan"):
                o, S = jax.vmap(kda.chunk_scan)(
                    *_kda_qkv(c, qkv), lanes(g), lanes(b), real, S0)
            kda_out.append(S)
            kda_conv_out.append(win)
            mix = _kda_out(c, lp, o.reshape(K * T, *o.shape[2:]), z, cdt)
        elif kind == "mamba1":
            j = len(m1_out)
            z, xs = _m1_in(c, lp, x)
            if span:
                keep = continuing[:, None, None]
                win0 = jnp.where(keep, ctx_kv[M1_CONV][j][slots], 0)
                S0 = jnp.where(keep, ctx_kv[M1][j][slots], 0.0)
            else:
                win0 = jnp.zeros((K, d["m1_W"] - 1, d["m1_inner"]), cdt)
                S0 = jnp.zeros((K, d["m1_N"], d["m1_inner"]), jnp.float32)
            with jax.named_scope("m1_conv"):
                xs, win = jax.vmap(
                    lambda a, w0, n: mamba2.causal_conv(
                        a, w0, lp["conv_w"], lp["conv_b"], n)
                )(xs.reshape(K, T, -1), win0, n_real)
            dt, Bm, Cm = _m1_ssm(c, lp, xs.reshape(K * T, -1))
            with jax.named_scope("m1_scan"):
                # a lane a kernel call: K is the few chunks of one dispatch
                ys, Ss = zip(*(mamba1.chunk_scan(
                    xs[i], lanes(dt)[i], lanes(Bm)[i], lanes(Cm)[i], A(lp),
                    lp["D"], S0[i], n_real[i]) for i in range(K)))
            m1_out.append(jnp.stack(Ss))
            m1_conv_out.append(win)
            if l == d.get("scan_from"):
                m = jnp.concatenate(ys)   # before the gate
            mix = _m1_out(lp, jnp.concatenate(ys), z)
        elif kind == "latent_attention":
            with jax.named_scope("mla_attn"):
                q_nope, q_rope, row = mla_moe._attn_in(
                    c, lp, h, positions.reshape(K * T))
                k, v = mla_moe._expand_kv(c, lp, row)
                prior = None
                if span:
                    work = mla_moe._expand_prior(
                        c, work, ctx_kv[KV], lp["wkb"], lp["wvb"],
                        jnp.int32(len(lat)), slots, below)
                    prior = PriorContext(*work, jnp.int32(0),
                                         jnp.arange(K, dtype=jnp.int32))
                lat.append(row.reshape(K, T, 1, -1))
                o = fused_prefill_attention(
                    lanes(jnp.concatenate([q_nope, q_rope], -1)), lanes(k),
                    lanes(v), q_starts, seq_lens, prior, ctx_span=span)
                mix = o.reshape(K * T, -1) @ lp["wo"]
        else:
            j = len(ssm_out)
            z, xbc, dt = _ssm_in(c, lp, x)
            # padding neither decays nor feeds the state
            dt = jnp.where(real[..., None], dt.reshape(K, T, -1), 0.0)
            if span:
                keep = continuing[:, None, None]
                win0 = jnp.where(keep, ctx_kv[CONV][j][slots], 0)
                S0 = jnp.where(keep[..., None], ctx_kv[SSM][j][slots], 0.0)
            else:
                win0 = jnp.zeros((K, d["W"] - 1, d["conv"]), cdt)
                S0 = jnp.zeros((K, d["nh"], d["P"], d["N"]), jnp.float32)
            with jax.named_scope("ssm_conv"):
                xbc, win = jax.vmap(
                    lambda a, w0, n: mamba2.causal_conv(
                        a, w0, lp["conv_w"], lp["conv_b"], n)
                )(xbc.reshape(K, T, -1), win0, n_real)
            xs, Bm, Cm = _split_xbc(c, xbc)
            with jax.named_scope("ssm_scan"):
                y, S = jax.vmap(
                    lambda xs, dt, Bm, Cm, S0: mamba2.chunk_scan(
                        xs, dt, A(lp), Bm, Cm, S0, d["chunk"])
                )(xs, dt, Bm, Cm, S0)
            ssm_out.append(S)
            conv_out.append(win)
            mix = _ssm_out(c, lp, y.reshape(K * T, d["nh"], d["P"]),
                           xs.reshape(K * T, d["nh"], d["P"]), z)
        before = stats
        h, stats = _layer_out(c, kind, lp, h, mix, valid, stats)
        if R:   # the layer's held picks are its groups' total
            moved = moved + rows_moved(stats[1] - before[1], R)

    # tail: every read is done. Rows as spans, compressed keys and states
    # as whole lanes
    if lat:
        rows = {KV: jnp.stack(lat, 1).astype(cdt)}  # [K, L_latent, T, 1, row]
    else:
        rows = {"k": jnp.stack(ks, 1).astype(cdt),  # [K, L_attn, T, kvh, hd]
                "v": jnp.stack(vs, 1).astype(cdt)}
    if wks:
        rows.update({WK: jnp.stack(wks, 1).astype(cdt),
                     WV: jnp.stack(wvs, 1).astype(cdt)})
    states = [(name, new) for name, new in (
        (SSM, ssm_out), (CONV, conv_out), (LIN, lin_out), (KDA, kda_out),
        (KDA_CONV, kda_conv_out), (M1, m1_out), (M1_CONV, m1_conv_out))
        if new]

    out_ctx, logits = _write_chunks(c, params, ctx_kv, rows, kcs, states, slots,
                                    q_starts, seq_lens, h, logits)
    return out_ctx, logits, moved


def _live_prefill(config, params, ctx_kv, tokens, slots, q_starts, seq_lens,
                  ctx_span):
    """``batch_prefill_impl`` where ``live_row_block`` gives a block height
    R: everything a layer does per row follows the lanes' LIVE rows (a
    prefix of the chunk) in blocks: both row-wise halves (``_live_half``,
    R rows a block) and the recurrent kinds' chunked scans
    (``_live_scan``, ``SCAN_ROW_BLOCK`` rows a block, within the halves'
    blocks), as the attention's query blocks and the expert path's row
    movements do; rows of blocks that never ran are 0 from the first
    layer's second half on, and no reader passes a lane's live length.
    Left over all T rows: the short convolutions (elementwise, a 3-row
    halo) and a routing layer's last residual."""
    c, d = config, dims(config)
    _refuse_adapters(params)
    K, T = tokens.shape
    cdt = _rows_of(ctx_kv).dtype
    positions = q_starts[:, None] + jnp.arange(T, dtype=jnp.int32)
    real = positions < seq_lens[:, None]                      # [K, T]
    valid = real.reshape(K * T)
    n_real = jnp.clip(seq_lens - q_starts, 0, T)
    span = min(ctx_span, _rows_of(ctx_kv).shape[3])
    continuing = q_starts > 0
    if span and d["n_latent"]:
        # continuing chunks' prior latent rows, expanded per head into a
        # workspace every latent layer rewrites (mla_moe._expand_prior)
        m = mla_moe.dims(c)
        below = jnp.minimum(jnp.minimum(q_starts, seq_lens), span)
        work = tuple(jnp.zeros((1, m["nh"], K, span, w), cdt)
                     for w in (m["nope"] + m["rope"], m["v"]))
    h = _embed(c, params, tokens, cdt)                        # [K, T, H]
    stats = stats_zero(c)
    # the lanes' live blocks, of the halves' and of the scans' height
    R, Rs = live_row_block(c, T), SCAN_ROW_BLOCK
    trips, scan_trips = (live_row_trips(q_starts, seq_lens, T, height)
                         for height in (R, Rs))
    Rm = _move_block(d, K * T)
    moved = jnp.int32(0)
    r = jnp.asarray(c.hybrid_dict["residual_multiplier"], cdt)
    ks, vs, kcs, lat = [], [], [], []
    new = {name: [] for name in (SSM, CONV, LIN, KDA, KDA_CONV, M1, M1_CONV)}
    flat = lambda a: a.reshape(K * T, *a.shape[2:])  # noqa: E731

    def rowwise(half, kind, lp, *rows):
        return _live_half(half, kind, c, lp, trips, rows, R)

    def scan(kind, lp, S0, *rows):
        return _live_scan(kind, c, lp, scan_trips, rows + (real,), S0, Rs)

    def before(names, shapes):
        """The lanes' recurrent leaves a chunk starts from: their own
        where it continues, zeros where it is fresh."""
        if not span:
            return [jnp.zeros((K,) + shape, dtype) for shape, dtype in shapes]
        j = len(new[names[0]])
        return [jnp.where(continuing.reshape((K,) + (1,) * len(shape)),
                          ctx_kv[name][j][slots], 0)
                for name, (shape, _) in zip(names, shapes)]

    def conv(lp, a, win0, bias):
        return jax.vmap(lambda a, w0, n: mamba2.causal_conv(
            a, w0, lp["conv_w"], bias, n))(a, win0, n_real)

    wks, wvs = [], []     # the window layers' rows
    m = logits = None     # the scan output the gated memory units read
    for l, (kind, lp) in enumerate(zip(d["kinds"], params["layers"])):
        mixes, _ = _parts(c, kind)
        ins = rowwise(_mix_in, kind, lp, h, positions) if mixes else ()
        if not mixes:
            seq = ()   # the second half is the whole layer, from h
        elif kind == "gmu":
            seq = (ins[0], m)
        elif _form(d, kind) == "diff":
            q, *own = ins
            if _climbs_at(c, l):
                # the rows stop here: the chunk's last real row climbs
                # the rest of the stack alone
                ks.append(own[0])
                vs.append(own[1])
                logits = _climb(c, params, ctx_kv, slots, q_starts,
                                seq_lens, span, h, q, *own, m)
                break
            with jax.named_scope(DIFF_SCOPES[kind]):
                seq = (_rows_prefill(c, kind, ctx_kv, slots, q_starts,
                                     seq_lens, span, q, own, (ks, vs),
                                     (wks, wvs)),)
        elif _form(d, kind) == "gqa":
            q, k, v, z = ins
            with jax.named_scope(GQA_SCOPES[kind]):
                seq = (_rows_prefill(c, kind, ctx_kv, slots, q_starts,
                                     seq_lens, span, q, [k, v], (ks, vs),
                                     (wks, wvs)), z)
        elif kind == "attention":
            q, k, v = ins
            with jax.named_scope("nope_attn"):
                seq = (prefill_attention(
                    q, k, v, q_starts, seq_lens,
                    _prior_rows(ctx_kv, len(ks), slots, span),
                    ctx_span=span),)
            ks.append(k)
            vs.append(v)
        elif kind == "sparse_attention":
            q, k, v, z = ins
            o, kc = _sparse_attend(c, q, k, v, ctx_kv, len(ks), len(kcs),
                                   slots, q_starts, seq_lens, span)
            ks.append(k)
            vs.append(v)
            kcs.append(kc)
            seq = (o, z)
        elif kind == "linear_attention":
            q, k, v, z = ins
            S0, = before((LIN,), [((d["lin_heads"], d["lin_dim"],
                                    d["lin_dim"]), jnp.float32)])
            o, S = scan(kind, lp, S0, q, k, v)
            new[LIN].append(S)
            seq = (o, z)
        elif kind == "kda":
            qkv, g, b, z = ins
            S0, win0 = before((KDA, KDA_CONV), [
                ((d["kda_heads"], d["kda_dim"], d["kda_dim"]), jnp.float32),
                ((d["kda_W"] - 1, 3 * d["kda_inner"]), cdt)])
            with jax.named_scope("kda_conv"):
                qkv, win = conv(lp, qkv, win0, _no_bias(lp))
            o, S = scan(kind, lp, S0, qkv, g, b)
            new[KDA].append(S)
            new[KDA_CONV].append(win)
            seq = (o, z)
        elif kind == "mamba1":
            z, xs = ins
            S0, win0 = before((M1, M1_CONV), [
                ((d["m1_N"], d["m1_inner"]), jnp.float32),
                ((d["m1_W"] - 1, d["m1_inner"]), cdt)])
            with jax.named_scope("m1_conv"):
                xs, win = conv(lp, xs, win0, lp["conv_b"])
            y, S = scan(kind, lp, S0, xs)
            new[M1].append(S)
            new[M1_CONV].append(win)
            if l == d.get("scan_from"):
                m = y   # before the gate
            seq = (y, z)
        elif kind == "latent_attention":
            qq, k, v, row = ins
            with jax.named_scope("mla_attn"):
                prior = None
                if span:
                    work = mla_moe._expand_prior(
                        c, work, ctx_kv[KV], lp["wkb"], lp["wvb"],
                        jnp.int32(len(lat)), slots, below)
                    prior = PriorContext(*work, jnp.int32(0),
                                         jnp.arange(K, dtype=jnp.int32))
                seq = (fused_prefill_attention(
                    qq, k, v, q_starts, seq_lens, prior, ctx_span=span),)
            lat.append(row[:, :, None])
        else:
            z, xbc, dt = ins
            S0, win0 = before((SSM, CONV), [
                ((d["nh"], d["P"], d["N"]), jnp.float32),
                ((d["W"] - 1, d["conv"]), cdt)])
            with jax.named_scope("ssm_conv"):
                xbc, win = conv(lp, xbc, win0, lp["conv_b"])
            y, S = scan(kind, lp, S0, xbc, dt)
            new[SSM].append(S)
            new[CONV].append(win)
            seq = (y, xbc, z)
        out = rowwise(_mix_out, kind, lp, h, *seq)
        if "wr" not in lp:
            h, = out
            continue
        h, x, sel, w, shared, *here = out
        y, load = _experts(c, lp, flat(x), flat(sel), flat(w), valid)
        h = h + r * (y.reshape(h.shape) + shared)
        stats = _seen(c, load, flat(here[0]) if here else None, valid,
                      K * T, stats)
        if Rm:   # the layer's held picks are its groups' total
            moved = moved + rows_moved(load.sum(), Rm)

    # tail: every read is done. Rows as spans, compressed keys and states
    # as whole lanes
    if lat:
        rows = {KV: jnp.stack(lat, 1).astype(cdt)}  # [K, L_latent, T, 1, row]
    else:
        rows = {"k": jnp.stack(ks, 1).astype(cdt),  # [K, L_attn, T, kvh, hd]
                "v": jnp.stack(vs, 1).astype(cdt)}
    if wks:
        rows.update({WK: jnp.stack(wks, 1).astype(cdt),
                     WV: jnp.stack(wvs, 1).astype(cdt)})
    states = [(name, leaves) for name, leaves in new.items() if leaves]

    out_ctx, logits = _write_chunks(c, params, ctx_kv, rows, kcs, states, slots,
                                    q_starts, seq_lens, h, logits)
    return out_ctx, logits, moved


def prefill_impl(config, params, ctx_kv, tokens, slot, q_start, seq_len,
                 embeds=None, embeds_mask=None, adapter_id=None,
                 fresh=False, *, attn=None):
    """One chunk: the K = 1 case of the batched program."""
    if embeds is not None:
        raise ValueError("the state-space hybrid block takes no embedding "
                         "overrides (multimodal)")
    one = lambda x: jnp.asarray(x, jnp.int32)[None]  # noqa: E731
    ctx_kv, logits, moved = batch_prefill_impl(
        config, params, ctx_kv, tokens[None], one(slot), one(q_start),
        one(seq_len), 0 if fresh else _rows_of(ctx_kv).shape[3])
    return ctx_kv, logits[0], moved


# ---------------------------------------------------------------------------
# Decode

def decode_step_impl(config, params, ctx_kv, ring, state, tokens, ctx_lens,
                     ring_base, ring_pos, live, *, attn: DecodeAttention):
    """One decode step for all slots: (ring, state, logits [B, vocab],
    stats). The attention layers' new rows land in ring slot
    ``ring_pos`` and the region is read-only, as in the dense decoder;
    ``state`` (the region's leaves a step writes, ``stepped_kinds``:
    ``{SSM: [...], CONV: [...]}``, ``{LIN: [...], KC: rows}``, ``{KDA:
    [...], KDA_CONV: [...]}`` or ``{M1: [...], M1_CONV: [...]}``, lanes + 1
    wide) comes back moved on by one
    position for the lanes that are ``live`` and as it was for the others.
    ``attn`` also says which delta-rule, Mamba-1 or Mamba-2 step runs: the
    Pallas kernel over the live lanes where the decode attention is one,
    the XLA form over every lane beside the reference."""
    c, d = config, dims(config)
    _refuse_adapters(params)
    B = tokens.shape[0]
    cdt = _rows_of(ctx_kv).dtype
    h = _embed(c, params, tokens, cdt)
    stats = stats_zero(c)
    ring = dict(ring)
    state = {n: (list(v) if isinstance(v, (list, tuple)) else v)
             for n, v in state.items()}
    ssm, conv = state.get(SSM), state.get(CONV)
    # the scratch lane rides along as one more row that never moves
    pad = lambda a: jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
    a = j = n = sp = 0
    for layers, stepped in ((d["n_kda"], KDA_STEPPED),
                            (d["n_m1"] + d["n_ssm"], SSM_STEPPED)):
        if layers:
            # the step kernels' work list, the same for every such layer,
            # and what they step of it: the live lanes' states, a layer
            work = kda.work_list(live)
            stats = stats.at[stats_layout(c).index(stepped)].add(
                work[1][0] * layers)
    wl = 0        # a window layer's ordinal among its kind
    m = None      # the scan output the gated memory units read, [B, inner]
    for l, (kind, lp) in enumerate(zip(d["kinds"], params["layers"])):
        mixes, _ = _parts(c, kind)
        x = _norm(c, lp, "ln1", h) if mixes else None
        if not mixes:
            mix = None   # the layer is its feed-forward part
        elif kind == "gmu":
            mix = _gmu(lp, x, m)
        elif _form(d, kind):
            # the full layer's rows at its ordinal (a cross layer reads
            # the same rows, this step's in the ring already), a window
            # layer's in its lane's modular buffer; the differential and
            # the rotary GQA form differ before and after the one read
            diff = _form(d, kind) == "diff"
            win = kind == "window_attention"
            names = (WK, WV) if win else ("k", "v")
            row = wl if win else a if kind == "attention" else d["rows_row"]
            with jax.named_scope((DIFF_SCOPES if diff else GQA_SCOPES)[kind]):
                if diff:
                    own = () if kind == "cross_attention" else _diff_kv(
                        c, lp, x)
                else:
                    q, *own, z = _gqa_in(c, kind, lp, x,
                                         jnp.maximum(ctx_lens - 1, 0))
                _ring_write(ring, names, row, own, ring_pos)
                if diff:   # after the write, as the traced text has it
                    q = _diff_q(c, lp, x)
                o = ctx_decode_attention(
                    attn, q, ctx_kv[names[0]],
                    ctx_kv[names[1]], ring[names[0]], ring[names[1]],
                    jnp.int32(row), ctx_lens, ring_base, live=live,
                    window=d["window"] if win else 0,
                    name=DIFF_KERNEL if diff else GQA_KERNELS[kind])
                mix = _diff_out(c, lp, o) if diff else _gqa_out(lp, o, z)
            wl += win
            a += kind == "attention"
        elif kind == "attention":
            with jax.named_scope("nope_attn"):
                q, k, v = _qkv(c, lp, x)
                _ring_write(ring, ("k", "v"), a, (k, v), ring_pos)
                o = ctx_decode_attention(
                    attn, q, ctx_kv["k"], ctx_kv["v"], ring["k"], ring["v"],
                    jnp.int32(a), ctx_lens, ring_base, live=live)
                mix = o.reshape(B, c.q_dim) @ lp["wo"]
            a += 1
        elif kind == "sparse_attention":
            mix, ring, state[KC] = _sparse_decode(
                c, lp, x, ctx_kv, ring, state[KC], a, sp, ctx_lens,
                ring_base, ring_pos, live, attn)
            a += 1
            sp += 1
        elif kind == "linear_attention":
            q, k, v, z = _lin_in(c, lp, x, ctx_lens - 1)
            decay = jnp.asarray(lightning.log_decays(d["lin_heads"]))
            with jax.named_scope("lin_attn_scan"):
                # a lane that is not live: decay exp(0), key 0, the state
                # as it was
                o, state[LIN][n] = lightning.step(
                    pad(q), pad(jnp.where(live[:, None, None], k, 0)),
                    pad(v), pad(jnp.where(live[:, None], decay[None], 0.0)),
                    state[LIN][n])
            mix = _lin_out(c, lp, o[:B], z)
            n += 1
        elif kind == "kda":
            qkv, g, b, z = _kda_in(c, lp, x)
            with jax.named_scope("kda_conv"):
                qkv, win = mamba2.conv_step(
                    pad(qkv), state[KDA_CONV][n], lp["conv_w"], _no_bias(lp))
                state[KDA_CONV][n] = jnp.where(
                    pad(live)[:, None, None], win, state[KDA_CONV][n])
            q, k, v = _kda_qkv(c, qkv[:B])
            with jax.named_scope("kda_step"):
                if attn.impl == REFERENCE_IMPL:
                    # every lane steps; one that is not live: decay
                    # exp(0), step 0, key 0
                    o, state[KDA][n] = kda.step(
                        pad(q), pad(jnp.where(live[:, None, None], k, 0.0)),
                        pad(v), pad(jnp.where(live[:, None, None], g, 0.0)),
                        pad(jnp.where(live[:, None], b, 0.0)),
                        state[KDA][n])
                else:   # the live lanes' states in place, no other touched
                    o, state[KDA][n] = kda.step_pallas(
                        q, k, v, g, b, state[KDA][n], *work,
                        interpret=attn.impl == PALLAS_INTERPRET)
            mix = _kda_out(c, lp, o[:B], z, cdt)
            n += 1
        elif kind == "mamba1":
            z, xs = _m1_in(c, lp, x)
            with jax.named_scope("m1_conv"):
                xs, win = mamba2.conv_step(
                    pad(xs), state[M1_CONV][n], lp["conv_w"], lp["conv_b"])
                xs = xs[:B]
                state[M1_CONV][n] = jnp.where(
                    pad(live)[:, None, None], win, state[M1_CONV][n])
            dt, Bm, Cm = _m1_ssm(c, lp, xs)
            A = -jnp.exp(lp["A_log"])
            with jax.named_scope("m1_step"):
                if attn.impl == REFERENCE_IMPL:
                    # every lane steps; one that is not live with dt 0:
                    # exp(0) h + 0, the state as it was
                    y, state[M1][n] = mamba1.scan_step(
                        pad(xs), pad(jnp.where(live[:, None], dt, 0.0)),
                        pad(Bm), pad(Cm), A, lp["D"], state[M1][n])
                    y = y[:B]
                else:   # the live lanes' states in place, no other touched
                    y, state[M1][n] = mamba1.scan_step_pallas(
                        xs, dt, Bm, Cm, A, lp["D"], state[M1][n], *work,
                        interpret=attn.impl == PALLAS_INTERPRET)
            if l == d.get("scan_from"):
                m = y   # before the gate; a transient of the step
            mix = _m1_out(lp, y, z)
            n += 1
        elif kind == "latent_attention":
            with jax.named_scope("mla_attn"):
                q_nope, q_rope, row = mla_moe._attn_in(
                    c, lp, h, jnp.maximum(ctx_lens - 1, 0))
                ring[KV] = jax.lax.dynamic_update_slice(
                    ring[KV], row.astype(ring[KV].dtype)[None, None, :, None],
                    (a, 0, 0, ring_pos, 0))
                o_lat = latent_decode_attention(
                    attn, mla_moe._absorb_q(c, lp, q_nope, q_rope),
                    ctx_kv[KV], ring[KV], jnp.int32(a), ctx_lens, ring_base,
                    mla_moe.dims(c)["kv_rank"], live)
                mix = mla_moe._unabsorb_o(c, lp, o_lat) @ lp["wo"]
            a += 1
        else:
            z, xbc, dt = _ssm_in(c, lp, x)
            with jax.named_scope("ssm_conv"):
                # over all lanes + 1 rows, so that the window is
                # rewritten whole, in place
                xbc, win = mamba2.conv_step(
                    pad(xbc), conv[j], lp["conv_w"], lp["conv_b"])
                xbc = xbc[:B]
                conv[j] = jnp.where(pad(live)[:, None, None], win, conv[j])
            xs, Bm, Cm = _split_xbc(c, xbc)
            A = -jnp.exp(lp["A_log"])
            with jax.named_scope("ssm_scan"):
                if attn.impl == REFERENCE_IMPL:
                    # every lane steps; one that is not live with dt 0:
                    # exp(0) S + 0, the state as it was
                    y, ssm[j] = mamba2.scan_step(
                        pad(xs), pad(jnp.where(live[:, None], dt, 0.0)), A,
                        pad(Bm), pad(Cm), ssm[j])
                    y = y[:B]
                else:   # the live lanes' states in place, no other touched
                    y, ssm[j] = mamba2.scan_step_pallas(
                        xs, dt, A, Bm, Cm, ssm[j], *work,
                        interpret=attn.impl == PALLAS_INTERPRET)
            mix = _ssm_out(c, lp, y, xs, z)
            j += 1
        h, stats = _layer_out(c, kind, lp, h, mix, live, stats)
    return ring, state, _logits(c, params, h), stats


def round_step(config, params, ctx_kv, ring, stepped, tokens, ctx_lens,
               ring_base, s, live, adapter_ids, stats, *,
               attn: DecodeAttention):
    """``decode_step_impl`` under the round's one signature (llama.py: the
    block protocol): the step's counters come back merged into the
    round's."""
    ring, stepped, logits, st = decode_step_impl(
        config, params, ctx_kv, ring, stepped, tokens, ctx_lens, ring_base,
        s, live, attn=attn)
    return ring, stepped, logits, merge_stats(stats, st)


# ---------------------------------------------------------------------------
# What the engine is told of this state (llama.py: the block protocol)

def _rows_only(config: ModelConfig) -> bool:
    """A stack of full and window attention layers alone: no recurrent
    leaf, but rows of a second length that no page of the pool holds."""
    return set(dims(config)["kinds"]) <= set(GQA_KINDS) and bool(
        dims(config)["n_win"])


def state_called(config: ModelConfig) -> str:
    if sparse_layers(config)[0] is not None:
        return ("a recurrent (linear-attention) state and "
                "compressed-key rows (kc)")
    if _rows_only(config):
        return "window rows (a modular buffer a lane, outside the pool)"
    return " beside ".join(
        ([mla_moe.state_called(config)] if config.mla is not None else [])
        + ["a recurrent (state-space) state"])


def transfer_refusal(config: ModelConfig) -> str:
    if config.mla is not None:
        return mla_moe.transfer_refusal(config)
    if _rows_only(config):
        return ("kv_transfer / disaggregation cannot carry window rows "
                "yet: pages move K and V rows of the context's length, and "
                "a prompt cannot resume without its lane's window buffers")
    return ("kv_transfer / disaggregation cannot carry a recurrent "
            "(state-space or linear-attention) state"
            + (" or compressed-key rows (kc)"
               if sparse_layers(config)[0] is not None else "")
            + " yet: pages move K and V rows, and a "
            "prompt cannot resume from rows without the state at "
            "their boundary")


def page_multiple(config: ModelConfig) -> int:
    """A prefill chunk starts on a page and has to start on a block of
    the sparse attention's selection."""
    sparse, _ = sparse_layers(config)
    return sparse.block if sparse is not None else 1


def pages_resume(config: ModelConfig) -> bool:
    # not without the recurrent state (or the window buffers) at their
    # boundary
    return False


def layer_parts(config: ModelConfig) -> dict[str, int]:
    """The parts ONE decode step runs, by what they are: recurrent mixers,
    other mixers, expert parts (the layers that route), dense MLPs. A
    stack of two-part layers runs a mixer AND a feed-forward part a layer,
    one of one-part layers one of the four."""
    d = dims(config)
    mixers = [t for t in d["kinds"] if _parts(config, t)[0]]
    ffn = [routes for t, routes in zip(d["kinds"], d["routes"])
           if _parts(config, t)[1] is not None]
    return {"mixer_ssm": sum(t in STATE_KINDS for t in mixers),
            "mixer_attn": sum(t not in STATE_KINDS for t in mixers),
            "experts": sum(ffn), "mlp": len(ffn) - sum(ffn)}


def decode_mirror(config: ModelConfig, max_context: int, ring_len: int,
                  attn: DecodeAttention):
    """The host's mirrors of a dispatched round: the parts its steps ran
    (``layer_parts`` x the steps, one histogram a part), and what its
    attention layers read (``_rows_mirror``), where the stack has any."""
    parts = [(LAYER_PARTS_RUN[part][0], n)
             for part, n in layer_parts(config).items()]
    rows = _rows_mirror(config, max_context, ring_len, attn)

    def mirror(ctx_lens, live, n_steps: int):
        ran = tuple((name, n * n_steps) for name, n in parts)
        return ran + (rows(ctx_lens, live, n_steps) if rows else ())
    return mirror


def _rows_mirror(config: ModelConfig, max_context: int, ring_len: int,
                 attn: DecodeAttention):
    """The host's mirrors of what a dispatched round's attention layers
    read: the latent layers' (mla_moe's, a layer), the sparse layers'
    (``sparse_attention.round_rows``, all such layers), or the
    ``attention`` kind's dense read (a layer, into the latent layers'
    two histograms: a stack has one or the other); None for a stack with
    none of them."""
    d = dims(config)
    if d["n_latent"]:
        return mla_moe.decode_mirror(config, max_context, ring_len, attn)
    sparse, layers = sparse_layers(config)
    if sparse is None:
        if "attention" not in d["kinds"]:
            return None
        dense = mla_moe.rows_mirror(dense_round_rows, attn, max_context)
        if not d["n_win"]:
            return dense
        # rows of two lengths: the full layer's rows are read by it AND by
        # every cross layer; a window layer reads its lane's buffer in
        # whole chunks, against the window's own min(n, window) rows
        readers = 1 + d.get("n_cross", 0)
        W, window = d["window_rows"], d["window"]
        cb = dense_chunk_rows(W, attn.chunk)
        # the query heads of all layers of a kind, where they are a layer's
        # own (the rotary GQA form): what the score work follows
        q_heads = {kind: sum(n for n, t in zip(d["heads"], d["kinds"])
                             if t == kind) for kind in GQA_KINDS}

        def mirror(ctx_lens, live, n_steps: int):
            (_, read), own = dense(ctx_lens, live, n_steps)
            live = np.asarray(live, bool)
            n = np.asarray(ctx_lens)[live].astype(np.int64)
            steps = np.arange(n_steps)[:, None]
            in_buffer = (np.minimum(np.maximum(n - 1, 0), W)
                         if attn.impl != REFERENCE_IMPL
                         else np.full(len(ctx_lens), W))
            out = ((DECODE_ATTN_ROWS_READ[0], read), own,
                   (ATTN_WINDOW_ROWS_READ[0], d["n_win"] * n_steps * int(
                       region_trips(in_buffer, 1, cb).sum() * cb)),
                   (ATTN_WINDOW_ROWS_BOUND[0], d["n_win"] * int(
                       np.minimum(n[None, :] + steps, window).sum())))
            if "rows_from" in d:   # another layer's rows, read once a reader
                out += ((ATTN_SHARED_ROWS_READ[0], readers * read),)
            if d["rotary"]:
                lane_steps = int(live.sum()) * n_steps
                out += ((DECODE_ATTN_Q_ROWS_FULL[0],
                         lane_steps * q_heads["attention"]),
                        (DECODE_ATTN_Q_ROWS_WINDOW[0],
                         lane_steps * q_heads["window_attention"]))
            return out
        return mirror

    def mirror(ctx_lens, live, n_steps: int):
        read, rows = sparse_attention.round_rows(
            sparse, ctx_lens, live, n_steps, max_context, ring_len)
        return ((SPARSE_ATTN_ROWS_READ[0], layers * read),
                (SPARSE_ATTN_ROWS_LIVE[0], layers * rows))
    return mirror


def prefill_mirror(config: ModelConfig, attn: DecodeAttention,
                   kv_quant: str = "none"):
    """The query blocks the stack's attention layers of every kind ran,
    and those of its ``latent_attention`` layers that ran through the
    fused kernel (``mla_moe.blocks_mirror``); beside them, what the
    stack's own layers add (``_prefill_mirror_of_kinds``)."""
    d = dims(config)
    blocks = mla_moe.blocks_mirror(
        attn, sum(t in PREFILL_ATTENTION_KINDS for t in d["kinds"]),
        fused_layers=d["n_latent"], n_heads=config.num_heads)
    kinds = _prefill_mirror_of_kinds(config)

    def mirror(width: int, q_starts, seq_lens, scored: int, ctx_span: int):
        out = blocks(width, q_starts, seq_lens, scored, ctx_span)
        if kinds is not None:
            out += kinds(width, q_starts, seq_lens, scored)
        return out
    return mirror


def _prefill_mirror_of_kinds(config: ModelConfig):
    """The sparse layers score a chunk's whole causal context under the
    selection's mask (``scored``, what ``prefill_attention_pairs`` counts
    a layer): beside it, what a gathering prefill would score. A
    stack with Mamba-1 or Mamba-2 layers mirrors the positions their
    prefill scans run instead (it has no sparse layer)."""
    d = dims(config)
    n_scan = d["n_m1"] or d["n_ssm"]
    if n_scan:
        # the positions the state-space layers' prefill scans run: a lane's
        # live scan blocks where the program loops, every bucket row else
        def scanned(width: int, q_starts, seq_lens, scored: int):
            rows = len(q_starts) * width
            if live_row_block(config, width):
                rows = SCAN_ROW_BLOCK * int(live_row_trips(
                    np.asarray(q_starts, np.int64),
                    np.asarray(seq_lens, np.int64), width,
                    SCAN_ROW_BLOCK).sum())
            out = ((SSM_SCAN_POSITIONS[0], n_scan * rows),)
            if SKIP_ROWS and d.get("climbs"):
                # real prompt rows x layers, and of them the rows x layers
                # that never ran: all but one row a chunk in the layers
                # from the one whose rows the cross layers read up
                real = np.clip(np.asarray(seq_lens, np.int64)
                               - np.asarray(q_starts, np.int64), 0, width)
                above = config.num_layers - d["rows_from"]
                out += ((PREFILL_LAYER_ROWS[0],
                         int(real.sum()) * config.num_layers),
                        (PREFILL_LAYER_ROWS_SKIPPED[0],
                         int(np.maximum(real - 1, 0).sum()) * above))
            return out
        return scanned
    sparse, layers = sparse_layers(config)
    if sparse is None:
        return None

    def mirror(width: int, q_starts, seq_lens, scored: int):
        return ((SPARSE_PREFILL_SCORED[0], layers * scored),
                (SPARSE_PREFILL_SELECTED[0],
                 layers * sparse_attention.prefill_pairs(
                     sparse, q_starts, seq_lens, width)))
    return mirror
