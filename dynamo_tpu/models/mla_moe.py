"""The DeepSeek-V3 decoder block on the served path: latent (MLA)
attention, a leading run of dense SwiGLU layers, then layers of
sigmoid-routed experts with a shared one.

What differs from models/llama.py, and how the engine meets it:

  - CACHE SPEC. A token holds ONE row a layer: ``[c_kv | k_rope]`` after
    norm and RoPE (kv_lora_rank + qk_rope_head_dim values), read by every
    query head. The ctx region, the pool and the ring are therefore
    ``{"kv": [L, 1, lanes, S, row]}`` — one row kind, one "head". The
    movers (``llama.flush_ctx``, ``seal_blocks``, ``load_ctx_pages``)
    carry whatever row kinds a region holds (``llama.row_kinds``); the
    number of heads is in the array's shape.
  - TWO ATTENTION FORMS, one result. PREFILL, fresh or continuing a
    context already in the region, expands K and V from the latent
    (``c_kv W_kvb``) and attends at the heads' own width (nope + rope =
    192 for scores, v = 128 for values) through the shared blocked prefill
    attention: the chunk's own rows as they are computed, the prior rows
    of the region ONCE a (layer, lane, row) a dispatch into a workspace
    the attention's region loop reads (``_expand_prior``). DECODE absorbs
    ``W_kvb``: the query is taken into the latent space (``q_nope
    W_kvb^K``), scores and the weighted sum run over the cached rows
    themselves at their stored width, and ``W_kvb^V`` is applied to the
    result. That is algebra, not an approximation; which side pays is
    arithmetic. Expanding one cached row costs kv_rank x nh x (nope + v)
    x 2 = 8.4 MFLOP a layer (512, 32, 128 + 128); scoring it absorbed
    costs nh x (stored - 192) x 2 x 2 = 57 kFLOP more a QUERY row (640
    against 192 columns, scores and weighted sum): expansion pays from
    ~146 query rows a prior row up. A decode step has one query row a
    lane, a prefill chunk a bucket's worth (128 at the least).
  - TWO LAYER KINDS. Attention weights are stacked over all layers; the
    dense MLPs (``params["dense"]``) are a stack of their own and the
    expert layers (``params["experts"]``) a list, one entry a layer.
  - EXPERTS. Scores are sigmoid(x W_r) in float32; the top k of
    ``scores + bias`` are selected, weighted by their scores WITHOUT the
    bias, normalised, scaled; the grouped dropless product
    (models/moe.py: grouped_experts) reads only the experts some token
    picked; the shared expert is added ungated.
  - Two things a config may add to the block (``ModelConfig.hc``,
    ``.rope_scaling``). A RESIDUAL OF n STREAMS (manifold-constrained
    hyper-connections, ops/hyper_connection.py): ``h`` is then [N, n, H],
    the embedding opens n copies, each sublayer reads a mix of the
    streams and its output is spread over a remix of them (``_open`` /
    ``_close``), and the head closes them with their sum. YaRN rotary
    (``_rotary``): its own inverse frequencies, a factor on cos and sin,
    and ``mscale_all_dim``'s factor, squared, on the softmax scale of
    BOTH attention forms.

Every function here is reached through the ``llama`` names the engine and
the benchmark's launcher call (``llama.init_params``, ``init_ctx``,
``prefill``, ``batch_prefill`` ...), which dispatch on ``config.mla``.

The hybrid stack (models/ssm_moe.py) runs this block's ATTENTION as its
``latent_attention`` layer kind, beside recurrent layers: it imports
``_attn_in`` (whose ``q_lora_rank`` None arm is its model's), ``by_head``,
``_expand_kv``, ``_absorb_q``, ``_unabsorb_o``, ``_expand_prior`` and
``ROW``, with a config that sets ``mla`` beside ``hybrid`` and no
``routed`` (``dims`` then gives the attention's sizes only).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.moe import grouped_experts
from dynamo_tpu.ops import hyper_connection as hc
from dynamo_tpu.ops.attention import (
    PREFILL_BLOCK,
    REFERENCE_IMPL,
    DecodeAttention,
    PriorContext,
    fused_prefill_attention,
    prefill_fuses,
    prefill_query_blocks,
)
from dynamo_tpu.ops import latent_decode
from dynamo_tpu.ops.latent_decode import latent_decode_attention
from dynamo_tpu.ops.rope import rope_inv_freq, yarn_inv_freq, yarn_mscale
from dynamo_tpu.telemetry.metrics import (
    DECODE_ATTN_ROWS_LIVE,
    DECODE_ATTN_ROWS_READ,
    HC_SINKHORN_RESIDUAL,
    MOE_LOAD_MAX,
    MOE_ROUTED,
    MOE_TOUCHED,
    PREFILL_ATTN_BLOCKS,
    PREFILL_ATTN_FUSED_BLOCKS,
    Counter,
)

Params = dict[str, Any]
Cache = dict[str, jnp.ndarray]

ROW = "kv"   # the one row kind of this block's cache


def dims(c: ModelConfig) -> dict[str, int]:
    """The attention's sizes, and where the config routes experts as this
    block does (``c.routed``; the hybrid stack, which borrows the
    attention alone, has its own) the expert layers'."""
    m, r = c.mla_dict, c.routed_dict
    d = {
        "nh": c.num_heads, "q_rank": m["q_lora_rank"],
        "kv_rank": m["kv_lora_rank"], "nope": m["qk_nope_head_dim"],
        "rope": m["qk_rope_head_dim"], "v": m["v_head_dim"],
        "row": m["kv_lora_rank"] + m["qk_rope_head_dim"],
        # the row as STORED: zero-padded to whole 128-value lanes. The
        # device tiles the minor dimension in 128s anyway (the pad costs
        # no memory), and a minor dimension that is no multiple of 128
        # makes XLA:TPU store the region position-minor, which turns
        # every row-wise read and write into a relayout of the region
        "stored": -(-(m["kv_lora_rank"] + m["qk_rope_head_dim"]) // 128)
        * 128,
        # residual streams (1: the plain residual)
        "n": c.hc_dict["hc_mult"] if c.hc else 1,
    }
    if r is not None:
        d.update({
            "E": r["n_routed_experts"], "K": r["num_experts_per_tok"],
            "I_e": r["moe_intermediate_size"],
            "I_s": r["moe_intermediate_size"] * r["n_shared_experts"],
            "n_dense": min(r["first_k_dense_replace"], c.num_layers),
        })
    return d


def kv_row_bytes(c: ModelConfig, itemsize: int) -> int:
    """Bytes one token holds in the ctx region, all layers."""
    return c.num_layers * dims(c)["stored"] * itemsize


# ---------------------------------------------------------------------------
# Parameters

def init_params(config: ModelConfig, rng: jax.Array | int = 0) -> Params:
    """Random parameters, 1/sqrt(fan_in); the selection bias is drawn
    small and non-zero so that selecting and weighting differ."""
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    c, d = config, dims(config)
    if c.quant is not None:
        raise ValueError("the latent-attention block has no int8 weights")
    dtype = jnp.dtype(c.dtype)
    keys = iter(jax.random.split(rng, 16 + 8 * c.num_layers))

    def rnd(*shape, scale=None):
        scale = scale or 1.0 / np.sqrt(shape[-2])
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    L, H, V = c.num_layers, c.hidden_size, c.vocab_size
    Ld, Le = d["n_dense"], c.num_layers - d["n_dense"]
    nh, I = d["nh"], c.intermediate_size
    params = {
        "embed": rnd(V, H, scale=0.02),
        "norm_f": jnp.ones((H,), dtype),
        "lm_head": rnd(H, V, scale=0.02),
        "layers": {
            "ln1": jnp.ones((L, H), dtype),
            "ln2": jnp.ones((L, H), dtype),
            "wqa": rnd(L, H, d["q_rank"]),
            "q_norm": jnp.ones((L, d["q_rank"]), dtype),
            "wqb": rnd(L, d["q_rank"], nh * (d["nope"] + d["rope"])),
            "wkva": rnd(L, H, d["row"]),
            "kv_norm": jnp.ones((L, d["kv_rank"]), dtype),
            "wkvb": rnd(L, d["kv_rank"], nh * (d["nope"] + d["v"])),
            "wo": rnd(L, nh * d["v"], H),
        },
        "dense": {
            "wg": rnd(Ld, H, I), "wu": rnd(Ld, H, I), "wd": rnd(Ld, I, H),
        },
        # one entry per expert layer, NOT stacked: the grouped product is
        # a kernel call, and a kernel's operand sliced out of a stack is
        # a copy of the layer's experts (768 MB each, every step)
        "experts": [{
            "wr": rnd(H, d["E"]),
            # in SCORE units, where the 8th and 9th of 256 lie ~0.007
            # apart and a sigmoid near its top moves 0.1 a unit of
            # logit: 0.1 here would hand some experts to half the tokens
            # (a step then read 36 % of the experts independent lanes
            # touch 69 % of; PERF.md section 6, PR 31), where a trained
            # bias exists to BALANCE the load
            "bias": 0.01 * jax.random.normal(
                next(keys), (d["E"],), jnp.float32),
            "we_g": rnd(d["E"], H, d["I_e"]),
            "we_u": rnd(d["E"], H, d["I_e"]),
            "we_d": rnd(d["E"], d["I_e"], H),
            "ws_g": rnd(H, d["I_s"]),
            "ws_u": rnd(H, d["I_s"]),
            "ws_d": rnd(d["I_s"], H),
        } for _ in range(Le)],
    }
    if c.hc is not None:
        # drawn after everything else, so that a seed gives the block
        # without streams the weights it always gave. Two sublayers a
        # layer, each phi [n H, n + n + n n] and its 3 gains and n + n +
        # n n offsets. The gains are 1 and b_res is of order 1 so that
        # the token-dependent term and the Sinkhorn iterations both move
        # the logits (a trained model's small gates would hide both from
        # any check); b_pre and b_post are 0
        n = d["n"]
        m = 2 * n + n * n
        b_res = jax.random.normal(next(keys), (L, 2, n * n), jnp.float32)
        params["layers"].update(
            hc_phi=rnd(L, 2, n * H, m),
            hc_a=jnp.ones((L, 2, 3), jnp.float32),
            hc_b=jnp.concatenate(
                [jnp.zeros((L, 2, 2 * n), jnp.float32), b_res], axis=-1))
    return params


def param_shardings(config: ModelConfig, mesh: Mesh) -> Params:
    """One chip holds a whole layer: everything replicated. A mesh with
    tp or ep > 1 is refused — the expert-parallel share and its exchange
    are not built (ROADMAP M1)."""
    for axis in ("tp", "ep"):
        if mesh.shape.get(axis, 1) > 1:
            raise ValueError(
                f"the latent-attention block is not sharded over {axis!r} "
                f"(mesh {dict(mesh.shape)}): one chip holds a whole layer")
    shapes = jax.eval_shape(lambda: init_params(config, 0))
    return jax.tree.map(
        lambda x: NamedSharding(mesh, P(*([None] * x.ndim))), shapes)


# ---------------------------------------------------------------------------
# Cache spec: the region, the pool and the ring

def _rows(config: ModelConfig, lanes: int, length: int, dtype) -> Cache:
    dtype = dtype or jnp.dtype(config.dtype)
    return {ROW: jnp.zeros(
        (config.num_layers, 1, lanes, length, dims(config)["stored"]),
        dtype)}


def _refuse_quant(kv_quant: str) -> None:
    if kv_quant != "none":
        raise ValueError(
            f"kv_quant={kv_quant!r}: the int8 KV plane cannot carry a "
            "latent row (its scale grid is per K and V page)")


def init_cache(config, num_pages, page_size, dtype=None, kv_quant="none"):
    _refuse_quant(kv_quant)
    return _rows(config, num_pages, page_size, dtype)


def init_ctx(config, batch, ctx_len, dtype=None, kv_quant="none",
             group=128):
    _refuse_quant(kv_quant)
    return _rows(config, batch + 1, ctx_len, dtype)


def init_ring(config, batch, ring_len, dtype=None):
    return _rows(config, batch, ring_len, dtype)


def cache_shardings(config: ModelConfig, mesh: Mesh,
                    kv_quant: str = "none") -> Cache:
    _refuse_quant(kv_quant)
    return {ROW: NamedSharding(mesh, P(None, None, None, None, None))}


ctx_shardings = cache_shardings   # the region holds rows only


def ring_shardings(config: ModelConfig, mesh: Mesh) -> Cache:
    return cache_shardings(config, mesh)   # and the ring the same kind


def stepped_kinds(config: ModelConfig, state: Cache) -> tuple[str, ...]:
    return ()   # a decode step writes the ring only


# What the engine is told of this state (llama.py: the block protocol)

def state_called(config: ModelConfig) -> str:
    return "a latent (MLA) cache row"


def transfer_refusal(config: ModelConfig) -> str:
    return ("kv_transfer / disaggregation cannot carry a latent (MLA) "
            "cache row yet: pages move as a K and a V")


def page_multiple(config: ModelConfig) -> int:
    return 1


def pages_resume(config: ModelConfig) -> bool:
    return True   # a page of latent rows is all a prompt's prefix holds


def rows_mirror(round_rows, attn: DecodeAttention, max_context: int):
    """A decode mirror over ``round_rows(attn, ctx_lens, live, n_steps,
    max_context)`` -> (region rows the round's attention read a layer,
    rows that were a live lane's own): ``latent_decode.round_rows`` here,
    ``attention.dense_round_rows`` for K and V rows (the dense decoder and
    the hybrid block's ``attention`` kind), by the two histograms both
    feed."""
    def mirror(ctx_lens, live, n_steps: int):
        read, own = round_rows(attn, ctx_lens, live, n_steps, max_context)
        return ((DECODE_ATTN_ROWS_READ[0], read),
                (DECODE_ATTN_ROWS_LIVE[0], own))
    return mirror


def decode_mirror(config: ModelConfig, max_context: int, ring_len: int,
                  attn: DecodeAttention):
    """The host's mirror of the latent decode attention's reads, a layer
    (``latent_decode.round_rows``)."""
    return rows_mirror(latent_decode.round_rows, attn, max_context)


def blocks_mirror(attn: DecodeAttention, layers: int, fused_layers: int = 0,
                  n_heads: int = 0, kv_heads: int = 0,
                  int8_region: bool = False):
    """A block's ``prefill_mirror`` of the query blocks its ``layers``
    prefill attentions ran a dispatch, and those of them that ran through
    the fused kernel: ``fused_layers`` layers of ``n_heads`` query heads
    over ``kv_heads`` K/V heads (0: one each, an expanded latent layer),
    where the programs are traced for TPU devices (``attn`` names a
    kernel: ``fused_prefill_attention`` lowers by platform, as
    ``decode_attention_for`` chooses) at a geometry the kernel takes
    (``prefill_fuses``, which the call site asks too) and the region a
    continuing chunk reads is not int8 (``int8_region``: the call keeps
    the loops)."""
    def mirror(width: int, q_starts, seq_lens, scored: int, ctx_span: int):
        blocks = prefill_query_blocks(width, q_starts, seq_lens)
        fused = attn.impl != REFERENCE_IMPL and prefill_fuses(
            width, n_heads, kv_heads or n_heads, ctx_span) and not (
            int8_region and ctx_span)
        return ((PREFILL_ATTN_BLOCKS[0], layers * blocks),
                (PREFILL_ATTN_FUSED_BLOCKS[0], fused_layers * blocks * fused))
    return mirror


def prefill_mirror(config: ModelConfig, attn: DecodeAttention,
                   kv_quant: str = "none"):
    """Every layer's prefill attention is the expanded latent one."""
    return blocks_mirror(attn, config.num_layers,
                         fused_layers=config.num_layers,
                         n_heads=dims(config)["nh"])


# ---------------------------------------------------------------------------
# Forward pieces

def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rotary(c: ModelConfig):
    """(inverse frequencies [rope/2], the factor on cos and sin, the
    factor on the softmax scale): 1 and 1 without ``rope_scaling``, YaRN's
    with it (ops/rope.py)."""
    rope, s = dims(c)["rope"], c.rope_scaling_dict
    if s is None:
        return rope_inv_freq(rope, c.rope_theta, None), 1.0, 1.0
    m_all = yarn_mscale(s["factor"], s["mscale_all_dim"])
    return (yarn_inv_freq(rope, c.rope_theta, s),
            yarn_mscale(s["factor"], s["mscale"]) / m_all, m_all * m_all)


def _rope_pairs(x, positions, inv_freq, times: float = 1.0):
    """Interleaved rotary: values (2i, 2i+1) of the last axis are one
    pair, turned by positions * inv_freq[i]; cos and sin times ``times``.
    ``x`` [N, ..., r], ``positions`` [N]."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq   # [N, r/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if times != 1.0:
        cos, sin = cos * times, sin * times
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attn_in(c: ModelConfig, lp, h, positions):
    """Norm, the two low-rank projections, RoPE. ``h`` [N, H] ->
    q_nope [N, nh, nope], q_rope [N, nh, rope], and the row to cache
    [N, stored] = [c_kv | k_rope | 0...]. A config whose ``q_lora_rank``
    is None projects the query in one step (``wq``), with no query
    norm."""
    d = dims(c)
    N = h.shape[0]
    inv_freq, times, _ = _rotary(c)
    inv_freq = jnp.asarray(inv_freq)
    x = _rms(h, lp["ln1"], c.rms_norm_eps)
    if d["q_rank"] is None:
        q = x @ lp["wq"]
    else:
        q = _rms(x @ lp["wqa"], lp["q_norm"], c.rms_norm_eps) @ lp["wqb"]
    # The product ends HERE, as an [N, nh * (nope + rope)] array, as the
    # dense decoder's do (llama._layer_qkv; PERF.md section 6, PR 55, 57).
    # Left to fold the reshape to heads into it, XLA:TPU wants the weight
    # head-major with the contraction minor: it transposed the whole wqb
    # stack once a round, wrote every layer's slice of that out again
    # every decode step, and laid each layer's shard out anew in front of
    # every prefill call (tools/tpu_compile_check.py ``weight_copies``).
    # Behind the barrier the product reads the shard where it lies.
    q = jax.lax.optimization_barrier(q)
    q = q.reshape(N, d["nh"], d["nope"] + d["rope"])
    q_nope, q_rope = q[..., :d["nope"]], q[..., d["nope"]:]
    kv = x @ lp["wkva"]
    c_kv = _rms(kv[:, :d["kv_rank"]], lp["kv_norm"], c.rms_norm_eps)
    k_rope = _rope_pairs(kv[:, d["kv_rank"]:], positions, inv_freq, times)
    q_rope = _rope_pairs(q_rope, positions, inv_freq, times)
    pad = jnp.zeros((N, d["stored"] - d["row"]), c_kv.dtype)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope, pad], axis=-1)


@functools.partial(jax.jit, static_argnames=("c",))
def by_head(c: ModelConfig, wkvb):
    """``wkvb`` [..., kv_rank, nh * (nope + v)], as published, -> the two
    weights the programs read (``serving_params``): the keys' part
    ``wkb`` [..., nh, nope, kv_rank] and the values' part ``wvb`` [...,
    nh, v, kv_rank], head-major with the latent minor."""
    d = dims(c)
    w = wkvb.reshape(*wkvb.shape[:-1], d["nh"], d["nope"] + d["v"])
    w = jnp.moveaxis(w, -3, -1)
    return w[..., :d["nope"], :], w[..., d["nope"]:, :]


def serving_params(config: ModelConfig, params: Params) -> Params:
    """The published parameters plus ``wkb`` and ``wvb`` (``by_head``),
    made ONCE, where the engine takes its parameters. Every product over
    W_kvb is batched over the head (``_absorb_q``, ``_unabsorb_o``,
    ``_expand_kv``), so XLA:TPU wants the head major and the latent minor,
    and what it cannot read in place is a PART of a leaf that has a head
    dimension: fed the published ``wkvb`` (or one head-major leaf split in
    the program) it transposed the whole stack every round and wrote every
    layer's slice of it out every decode step (PERF.md section 6, PR 57).
    ``wkvb`` stays: the benchmark's references read it."""
    layers = params["layers"]
    wkb, wvb = by_head(config, layers["wkvb"])
    return dict(params, layers=dict(layers, wkb=wkb, wvb=wvb))


def _absorb_q(c: ModelConfig, lp, q_nope, q_rope):
    """The decode query in the latent space, scaled: its scores against
    cached rows are the expanded scores / sqrt(qk width), times the
    rotary rule's factor on the softmax scale."""
    d = dims(c)
    times = _rotary(c)[2]
    q_lat = jnp.einsum("nhd,hdc->nhc", q_nope, lp["wkb"])
    pad = jnp.zeros(q_rope.shape[:2] + (d["stored"] - d["row"],),
                    q_rope.dtype)
    q = jnp.concatenate([q_lat, q_rope, pad], axis=-1)
    return _scaled(c, q, times / np.sqrt(d["nope"] + d["rope"]))


def _scaled(c: ModelConfig, q, k: float):
    """``q`` x ``k``. Under ``rope_scaling``, whose factor on the softmax
    scale is part of ``k``, the product is taken in float32 and rounded
    once (bfloat16 holds YaRN's 2.0047 as 2.0: scores 0.23 % low);
    without it, the product the one-stream block has always lowered."""
    if c.rope_scaling_dict is None:
        return q * jnp.asarray(k, q.dtype)
    return (q.astype(jnp.float32) * k).astype(q.dtype)


def _unabsorb_o(c: ModelConfig, lp, o_lat):
    """[N, nh, kv_rank] weighted sums of latents -> [N, nh * v]."""
    o = jnp.einsum("nhc,hdc->nhd", o_lat, lp["wvb"])
    return o.reshape(o.shape[0], -1)


def _mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def route(c: ModelConfig, ep, x):
    """The published router: sigmoid scores in float32, top k of scores +
    bias, weights from the scores alone, normalised and scaled."""
    r = c.routed_dict
    with jax.named_scope("moe_route"):
        logits = jnp.matmul(x, ep["wr"], preferred_element_type=jnp.float32)
        s = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(s + ep["bias"], r["num_experts_per_tok"])
        w = jnp.take_along_axis(s, sel, axis=-1)
        if r["norm_topk_prob"]:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return sel, w * r["routed_scaling_factor"]


def expert_ffn(c: ModelConfig, ep, x, valid=None):
    """One expert layer [N, H] -> [N, H], and the tokens each expert
    received. ``valid`` False rows are routed nowhere."""
    sel, w = route(c, ep, x)
    y, load = grouped_experts(x, sel, w, ep["we_g"], ep["we_u"],
                              ep["we_d"], valid)
    with jax.named_scope("moe_shared"):
        y = y + _mlp(x, ep["ws_g"], ep["ws_u"], ep["ws_d"])
    return y, load


def stats_layout(c: ModelConfig) -> tuple[Counter, ...]:
    """A step's counters, in order: experts touched, tokens routed, most
    tokens on one expert, and with hyper-connections a fourth, the last
    layer's Sinkhorn residual (hc.row_sum_residual) as the bits of a
    float32 (not negative, so the bits order as it does)."""
    return ((Counter(MOE_TOUCHED[0]), Counter(MOE_ROUTED[0]),
             Counter(MOE_LOAD_MAX[0]))
            + ((Counter(HC_SINKHORN_RESIDUAL[0], f32_bits=True),)
               if c.hc is not None else ()))


def stats_zero(c: ModelConfig):
    """A step's counters (``stats_layout``) before any layer."""
    return jnp.zeros(len(stats_layout(c)), jnp.int32)


def merge_stats(a, b):
    """The counters of two steps or layers as one: sums, and maxima from
    the third on."""
    return jnp.stack([a[0] + b[0], a[1] + b[1]] + [
        jnp.maximum(a[i], b[i]) for i in range(2, a.shape[0])])


def _ffn(c: ModelConfig, params, l: int, x, valid, stats):
    """Layer l's MLP (l is static): dense below first_k_dense_replace,
    experts from there on. ``stats`` accumulates the routing counters
    (``stats_zero``)."""
    n_dense = dims(c)["n_dense"]
    if l < n_dense:
        dp = jax.tree.map(lambda a: a[l], params["dense"])
        return _mlp(x, dp["wg"], dp["wu"], dp["wd"]), stats
    ep = params["experts"][l - n_dense]
    y, load = expert_ffn(c, ep, x, valid)
    seen = [jnp.sum(load > 0), load.sum(), load.max()]
    return y, merge_stats(stats, jnp.stack(
        seen + [jnp.int32(0)] * (stats.shape[0] - len(seen))))


def _embed(c: ModelConfig, params, tokens, dtype):
    """[N] -> the residual state: [N, H], or n copies of it [N, n, H]."""
    h = params["embed"][tokens].astype(dtype)
    if c.hc is None:
        return h
    return jnp.broadcast_to(h[:, None], (h.shape[0], dims(c)["n"],
                                         h.shape[1]))


def _open(c: ModelConfig, lp, sub: int, h):
    """Sublayer ``sub``'s (0 attention, 1 MLP) input from the residual
    state, and the coefficients that will close it (None: one stream)."""
    if c.hc is None:
        return h, None
    k = c.hc_dict
    with jax.named_scope("hc_pre"):
        mix = hc.mix_coefficients(
            h, lp["hc_phi"][sub], lp["hc_a"][sub], lp["hc_b"][sub],
            n=k["hc_mult"], iters=k["hc_sinkhorn_iters"], eps=k["hc_eps"],
            clamp=(k["mhc_h_res_clamp_min"], k["mhc_h_res_clamp_max"]),
            norm_eps=c.rms_norm_eps)
        return hc.hc_pre(h, mix), mix


def _close(h, f, mix):
    """The residual state after a sublayer whose output is ``f``."""
    if mix is None:
        return h + f
    with jax.named_scope("hc_post"):
        return hc.hc_post(h, f, mix)


def _layer_out(c: ModelConfig, params, lp, l, h, attn, mix, valid, stats):
    h = _close(h, attn @ lp["wo"], mix)
    u, mix = _open(c, lp, 1, h)
    x2 = _rms(u, lp["ln2"], c.rms_norm_eps)
    y, stats = _ffn(c, params, l, x2, valid, stats)
    if mix is not None and l == c.num_layers - 1:
        stats = stats.at[3].set(jax.lax.bitcast_convert_type(
            hc.row_sum_residual(mix), jnp.int32))
    return _close(h, y, mix), stats


def _logits(c: ModelConfig, params, h):
    if c.hc is not None:   # the head closes the streams: their plain sum
        h = _rms(h.astype(jnp.float32).sum(axis=1), params["norm_f"],
                 c.rms_norm_eps).astype(h.dtype)
    else:
        h = _rms(h, params["norm_f"], c.rms_norm_eps)
    return jnp.matmul(h, params["lm_head"],
                      preferred_element_type=jnp.float32)


def _refuse_adapters(params):
    if params.get("adapters") is not None:
        raise ValueError("the latent-attention block carries no LoRA bank")


# ---------------------------------------------------------------------------
# Prefill

def _expand_kv(c: ModelConfig, lp, row):
    """K and V per head from cached rows [N, stored]: K [N, nh, nope +
    rope] = [c_kv W_kvb^K | k_rope, the same for every head] and V [N,
    nh, v] = c_kv W_kvb^V, in the rows' dtype."""
    d = dims(c)
    c_kv = row[:, :d["kv_rank"]]
    k_rope = row[:, d["kv_rank"]:d["row"]]
    k = jnp.concatenate([
        jnp.einsum("nc,hdc->nhd", c_kv, lp["wkb"]),
        jnp.broadcast_to(k_rope[:, None],
                         (row.shape[0], d["nh"], d["rope"]))], -1)
    return k, jnp.einsum("nc,hdc->nhd", c_kv, lp["wvb"])


@functools.partial(jax.jit, static_argnames=("c",))
def _expand_prior(c: ModelConfig, work, region, wkb, wvb, layer, slots,
                  below):
    """The prior rows of K continuing chunks, expanded per head ONCE a
    dispatch: rows [0, below[i]) of lane ``slots[i]`` of the region's
    layer ``layer`` -> ``work`` = (K [1, nh, K, span, nope + rope], V [1,
    nh, K, span, v]), which ``prefill_attention`` then reads as it reads
    a region (layer 0, lane i). One rolled loop over the lanes' LIVE
    blocks, the blocks ``prefill_attention``'s region loop will visit: a
    fresh or dummy lane (below 0) costs nothing, a 4096-row context in a
    16384-row span a quarter of it. Rows at or past ``below`` keep what
    ``work`` held, finite values the attention masks. Jitted with the
    layer a VALUE, so that a program's layers share one traced
    expansion. ``wkb`` / ``wvb`` are one layer's (the hybrid stack's
    leaves), or the STACK of every layer's, which the loop's body slices
    at ``layer`` itself by an index the compiler cannot see is the same
    at every trip: a layer's slice handed to the loop is written out in
    front of it (``weight_copies``: ten ``bf16[32,128,512]`` a call), and
    so is one the compiler can hoist (llama._live_rows; PR 44)."""
    span = work[0].shape[3]
    cb = min(PREFILL_BLOCK, span)
    i32 = jnp.int32
    below = below.astype(i32)
    nblk = (below + cb - 1) // cb
    ends = jnp.cumsum(nblk)

    def expand(w, work):
        lane = jnp.sum(w >= ends).astype(i32)
        # a span of whole blocks (every engine's) writes at j x cb, an
        # offset XLA:TPU can see is aligned to the workspace's tiles: the
        # write takes 23 us a block where the slid form below, whose
        # offset it cannot see through the minimum, took 83 (chip runs,
        # PR 39). Only a span that is no multiple of the block
        # slides its last block back, as the attention's loop does
        k0 = (w - (ends[lane] - nblk[lane])) * cb
        if span % cb:
            k0 = jnp.minimum(k0, span - cb)
        rows = jax.lax.dynamic_slice(
            region, (layer, 0, slots[lane].astype(i32), k0, 0),
            (1, 1, 1, cb, region.shape[4]))[0, 0, 0]
        at = (0, 0, lane, k0, 0)
        lp = {"wkb": wkb, "wvb": wvb}
        if wkb.ndim == 4:
            lb, _ = jax.lax.optimization_barrier((layer, w))
            lp = jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(
                x, lb, keepdims=False), lp)
        return tuple(
            jax.lax.dynamic_update_slice(
                buf, x.transpose(1, 0, 2)[None, :, None], at)
            for buf, x in zip(work, _expand_kv(c, lp, rows)))

    return jax.lax.fori_loop(0, ends[-1], expand, tuple(work))


def batch_prefill_impl(config, params, ctx_kv, tokens, slots, q_starts,
                       seq_lens, ctx_span=0, adapter_ids=None, *,
                       attn=None):
    """K chunks [K, T] through the model in one program; their rows land
    in each lane's region at [q_start, q_start + T) in one tail pass after
    every read. Attention is EXPANDED in both programs (K and V per head
    from the latent, scored at nope + rope through the shared blocked
    prefill attention): a chunk is T query rows, and expanding a cached
    row once pays from ~146 query rows up (the module's header has the
    arithmetic). ``ctx_span`` 0: every chunk is fresh and no region read
    is compiled. Else the chunks continue contexts already in the region:
    each layer first expands the lanes' live prior rows into a workspace
    of ``ctx_span`` rows a lane (``_expand_prior``; nh x (nope + rope +
    v) values a row: 336 MB for one lane of 16384 rows of 32 heads in
    bfloat16, allocated once a program and rewritten by every layer), and
    the attention reads that workspace where a dense model's reads its
    region. A fresh lane (q_start 0) in a continuing program expands and
    reads nothing. Only decode absorbs (``decode_step_impl``). ``attn``
    (the protocol's: what the engine's programs are traced for) is not
    asked: the block holds whole layers on every device and its fused
    attention lowers by platform."""
    c, d = config, dims(config)
    _refuse_adapters(params)
    K, T = tokens.shape
    cdt = ctx_kv[ROW].dtype
    positions = q_starts[:, None] + jnp.arange(T, dtype=jnp.int32)
    scale_times = _rotary(c)[2]
    valid = (positions < seq_lens[:, None]).reshape(K * T)
    pos = positions.reshape(K * T)
    h = _embed(c, params, tokens.reshape(K * T), cdt)
    stats = stats_zero(c)
    rows_out = []
    span, prior = min(ctx_span, ctx_kv[ROW].shape[3]), None
    if span:
        below = jnp.minimum(jnp.minimum(q_starts, seq_lens), span)
        work = tuple(jnp.zeros((1, d["nh"], K, span, w), cdt)
                     for w in (d["nope"] + d["rope"], d["v"]))
    for l in range(c.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        u, mix = _open(c, lp, 0, h)
        with jax.named_scope("mla_attn"):
            q_nope, q_rope, row = _attn_in(c, lp, u, pos)
            rows_out.append(row)
            lanes = lambda a: a.reshape(K, T, *a.shape[1:])  # noqa: E731
            k, v = _expand_kv(c, lp, row)
            if span:
                work = _expand_prior(
                    c, work, ctx_kv[ROW], params["layers"]["wkb"],
                    params["layers"]["wvb"], jnp.int32(l), slots, below)
                prior = PriorContext(*work, jnp.int32(0),
                                     jnp.arange(K, dtype=jnp.int32))
            q = jnp.concatenate([q_nope, q_rope], -1)
            if scale_times != 1.0:
                q = _scaled(c, q, scale_times)
            o = fused_prefill_attention(lanes(q), lanes(k), lanes(v),
                                        q_starts, seq_lens, prior,
                                        ctx_span=span)
            attn = o.reshape(K * T, -1)
        h, stats = _layer_out(c, params, lp, l, h, attn, mix, valid, stats)

    rows = jnp.stack(rows_out).reshape(
        c.num_layers, K, T, d["stored"]).astype(cdt)

    def write_lane(i, buf):
        span = jax.lax.dynamic_index_in_dim(rows, i, axis=1, keepdims=False)
        return jax.lax.dynamic_update_slice(
            buf, span[:, None, None], (0, 0, slots[i], q_starts[i], 0))

    out_ctx = {ROW: jax.lax.fori_loop(0, K, write_lane, ctx_kv[ROW])}
    last = jnp.maximum(seq_lens - q_starts - 1, 0)
    h_last = jnp.take_along_axis(
        h.reshape(K, T, -1), last[:, None, None], axis=1)[:, 0]
    if c.hc is not None:
        h_last = h_last.reshape(K, *h.shape[1:])
    return out_ctx, _logits(c, params, h_last)


def prefill_impl(config, params, ctx_kv, tokens, slot, q_start, seq_len,
                 embeds=None, embeds_mask=None, adapter_id=None,
                 fresh=False, *, attn=None):
    """One chunk: the K = 1 case of the batched program."""
    if embeds is not None:
        raise ValueError("the latent-attention block takes no embedding "
                         "overrides (multimodal)")
    one = lambda x: jnp.asarray(x, jnp.int32)[None]  # noqa: E731
    ctx_kv, logits = batch_prefill_impl(
        config, params, ctx_kv, tokens[None], one(slot), one(q_start),
        one(seq_len), 0 if fresh else ctx_kv[ROW].shape[3])
    return ctx_kv, logits[0]


# ---------------------------------------------------------------------------
# Decode

def decode_step_impl(config, params, ctx_kv, ring, tokens, ctx_lens,
                     ring_base, ring_pos, live=None, adapter_ids=None, *,
                     attn: DecodeAttention):
    """One decode step for all slots: (ring, logits [B, vocab], stats
    i32, ``stats_zero``'s layout). The new token's row lands in ring slot
    ``ring_pos``; attention is absorbed, over the region's rows below
    ring_base and the ring's above, by the implementation ``attn`` names
    (ops/latent_decode.py); a lane that is not ``live`` reads no region
    row. The region is read-only here (llama.init_ring)."""
    c, d = config, dims(config)
    _refuse_adapters(params)
    positions = jnp.maximum(ctx_lens - 1, 0)
    h = _embed(c, params, tokens, ctx_kv[ROW].dtype)
    buf = ring[ROW]
    stats = stats_zero(c)
    for l in range(c.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        u, mix = _open(c, lp, 0, h)
        with jax.named_scope("mla_attn"):
            q_nope, q_rope, row = _attn_in(c, lp, u, positions)
            buf = jax.lax.dynamic_update_slice(
                buf, row.astype(buf.dtype)[None, None, :, None, :],
                (l, 0, 0, ring_pos, 0))
            o_lat = latent_decode_attention(
                attn, _absorb_q(c, lp, q_nope, q_rope), ctx_kv[ROW], buf,
                jnp.int32(l), ctx_lens, ring_base, d["kv_rank"], live)
            o = _unabsorb_o(c, lp, o_lat)
        h, stats = _layer_out(c, params, lp, l, h, o, mix, live, stats)
    return {ROW: buf}, _logits(c, params, h), stats


def round_step(config, params, ctx_kv, ring, stepped, tokens, ctx_lens,
               ring_base, s, live, adapter_ids, stats, *,
               attn: DecodeAttention):
    """``decode_step_impl`` under the round's one signature (llama.py: the
    block protocol): no leaf of the region is stepped, and the step's
    counters come back merged into the round's."""
    ring, logits, st = decode_step_impl(
        config, params, ctx_kv, ring, tokens, ctx_lens, ring_base, s, live,
        attn=attn)
    return ring, stepped, logits, merge_stats(stats, st)
