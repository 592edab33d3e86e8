"""Llama-family forward pass (Llama-2/3/3.x, DeepSeek-R1-Distill-Llama).

Design notes (TPU-first, round-4 layout):
  - Parameters are a pytree whose per-layer leaves are STACKED on a leading
    layer axis; the decoder is an unrolled python loop with static layer
    indices (XLA's aliasing keeps donated KV updates in place, which a
    lax.scan carry defeats).
  - SERVING CONTEXT is contiguous per slot: ``ctx_kv [L, kv_heads, B+1,
    S_max, head_dim]`` — slot b's tokens live at [.., b, 0:ctx). Decode
    writes one row per slot per step into a small ring, flushed into the
    region once a round as one span a lane (flush_ctx), and attention
    streams dense slabs (ops/flash_decode.py); prefill writes a
    contiguous span. Writers of the region slice and update SPANS
    (dynamic_slice / dynamic_update_slice in a rolled loop): a scatter or
    a gather over (lane, position) indices makes XLA:TPU relayout the
    whole region and copy it back, whatever the donation (the dense
    cells' traces, PERF.md §6, PR 34). Lane B is a
    SCRATCH lane: freed slots' in-flight garbage steps are redirected
    there (``dest`` argument), so a slot being prefilled for a new request
    is never corrupted by a stale pipelined step.
  - The PAGED POOL ``[L, kv_heads, num_pages, page_size, head_dim]`` is
    prefix-cache STORAGE only: sealed blocks are copied ctx->pool
    (seal_blocks) and prefix hits are copied pool->ctx at admission
    (load_ctx_pages). Paging is thereby removed from the per-step hot path
    entirely — the round-3 paged decode kernel spent 15.9 ms/step on
    page-grid overhead. Page 0 stays reserved as scratch for padded
    pool I/O (gather/scatter/seal padding).
  - Tensor parallelism is pure GSPMD: `param_shardings`/`cache_shardings`/
    `ctx_shardings` put head/hidden dims on the ``tp`` mesh axis; XLA
    inserts the ICI collectives. No hand-written comm (contrast: reference
    engines use NCCL inside vLLM — SURVEY.md §2.5).
  - Prefill is B=1 over a padded token bucket (positions q_start..q_start+T);
    decode is a fixed-slot batch, one token per slot. Both are jittable with
    static shapes; the engine buckets prompt lengths to bound recompiles.
    Inside a wide bucket the work follows the live rows, not the bucket:
    the attention and the two row-wise halves of a layer each loop over
    the row blocks that hold a prompt token (prefill_attention,
    _live_rows), with trip counts that are values of the one program.

THE SEAM (ROADMAP D2): ONE FRONT DOOR, AND WHAT A BLOCK IS. The engine,
``spec/``, ``engine/multihost.py`` and the benchmark's launcher call the
names of THIS module and no other of ``models/``. A BLOCK builds one
family of decoder: the dense one of this file, the latent-attention +
routed-expert block (models/mla_moe.py, a ``ModelConfig`` with ``mla``
set), the state-space / linear-attention hybrid (models/ssm_moe.py,
``hybrid`` set). ``block_of(config)`` names the module (None: the dense
decoder), and every name marked ``@_hands_over`` below runs the block's
function of the SAME name and signature; the dense decoder's is the
marked function's own body. That is the whole mechanism, and the list
of marked names is the whole protocol (``PROTOCOL``, held by
tests/test_model_seam.py). A new block is a module with these names and
one line in ``block_of``; no line of engine/engine.py.

  parameters and state (``c`` a ModelConfig, first everywhere)
    init_params(c, rng=0)                          -> params
    param_shardings(c, mesh)                       -> shardings of params
    serving_params(c, params)                      -> params + the leaves
        a block makes from them ONCE where the engine takes them; what
        the programs below are handed
    init_cache(c, num_pages, page_size, dtype=None, kv_quant="none")
    cache_shardings(c, mesh, kv_quant="none")      the pool: ROW leaves only
    init_ctx(c, batch, ctx_len, dtype=None, kv_quant="none", group=128)
    ctx_shardings(c, mesh, kv_quant="none")        the region: rows + state
    init_ring(c, batch, ring_len, dtype=None)
    ring_shardings(c, mesh)
    stepped_kinds(c, state) -> names               the region's leaves a
        decode STEP writes (recurrent state, rows a step completes): they
        ride the round's carry, the other leaves are read-only until the
        round's flush. () where a step writes the ring only.
  programs
    prefill_impl(c, params, ctx_kv, tokens, slot, q_start, seq_len,
                 embeds=None, embeds_mask=None, adapter_id=None,
                 fresh=False, *, attn=None)
                                        -> (ctx_kv, logits[, rows_moved])
    batch_prefill_impl(c, params, ctx_kv, tokens, slots, q_starts,
                       seq_lens, ctx_span=0, adapter_ids=None, *,
                       attn=None)       -> (ctx_kv, logits[, rows_moved])
        (the front door's ``prefill_impl`` / ``batch_prefill_impl`` add
        ``counted=`` and drop the third result unless asked; ``attn`` is
        the round's: the engine's ``DecodeAttention``, whose kernel and
        mesh the dense decoder's prefill attention takes; None: a caller
        that hands none over runs the XLA loops)
    round_step(c, params, ctx_kv, ring, stepped, tokens, ctx_lens,
               ring_base, s, live, adapter_ids, stats, *, attn)
                                        -> (ring, stepped, logits, stats)
        ONE decode step of the engine's round, one signature for every
        block: ``stepped`` = {name: ctx_kv[name] for stepped_kinds},
        moved on by one position; ``stats`` the round's counter row so
        far, returned with this step's counters merged in.
  the round's counter row
    stats_layout(c) -> (Counter, ...)   the row's columns in order, each
        by the telemetry/metrics.py histogram it feeds (None: a column
        the program carries and nothing reads) and whether the column is
        an int or a float32's bits. () = the block counts nothing and no
        row rides the round's token fetch.
    stats_zero(c) -> int32 [len(stats_layout(c))]  the row before a step
        (the dense decoder's is three wide under an empty layout: the
        dead carry its round programs have always held, kept because a
        new shape would move the dense cells' program text).
  what the state can and cannot do (asked once, at engine start)
    state_called(c) -> str | None       what a plane that moves K and V
        rows only is told it cannot carry; None: it can carry all of it.
    transfer_refusal(c) -> str | None   why pages of this state cannot
        move between workers; None: they can.
    page_multiple(c) -> int             positions a page must be a
        multiple of (a prefill chunk starts on a page).
    pages_resume(c) -> bool             whether sealed pages of rows are
        all a prompt needs to resume (the prefix cache's premise).
  the host's mirrors of what the kernels read (``decode_mirror`` None: the
  block has none)
    decode_mirror(c, max_context, ring_len, attn)
        -> f(ctx_lens, live, n_steps) -> ((metric, value), ...)
        (the dense decoder's: the region rows its attention's work list
        reads a layer, beside the live lanes' own)
    prefill_mirror(c, attn, kv_quant="none")
        -> f(width, q_starts, seq_lens, scored, ctx_span)
           -> ((metric, value), ...)
        (every block's: the query blocks its attention layers ran, and
        those that ran through the fused prefill kernel)
  and two live-row rules that are the front door's own
  (``live_row_block``, ``moe_prefill_rows_sorted``).

The movers (flush_ctx, seal_blocks, load_ctx_pages) are no part of it:
they carry whatever ROW KINDS a region holds (``row_kinds``: ``k`` and
``v`` of [kvh, hd] here and in the hybrid, one ``kv`` row in the latent
block) and pass over a region's RECURRENT leaves (``state_kinds``).
Functions of planes that cannot carry a latent row or a recurrent state
(speculation, sequence-parallel prefill, embeddings, page transfer, the
dense ``decode_step``) refuse it by name (``_dense_only``).

THE LOOP (``ModelConfig.looped``: ``model_type`` ``ouro``). The dense
decoder of this file run ``loop_steps`` times over the SAME weights, as
static branches: a config that takes none of them traces the plain dense
programs, to the letter (tests/test_lowering_looped.py). WEIGHTS are
indexed by layer ``l`` (``num_layers`` of them, a LoRA factor a weight
layer); K/V by PLANE ``t * num_layers + l`` (``cache_planes``: the leading
axis of region, ring and pool, a page of the offload tiers and the wire),
the rows THIS pass of layer l wrote. Sandwich norms (``ln1b``, ``ln2b``)
norm each half's output ahead of its add; ``_step_end`` closes every pass
with the ONE final norm (``_logits`` then adds none) and, in the round,
the exit gate, whose CDFs ``round_step`` counts. ``_over_passes`` runs the
passes; a prefill's pass stores its own planes as it ends. Planes that run
each layer once refuse a looped stack by name (``_no_loop``, and
``state_called`` at engine start).

Parity: this is the TPU engine the reference delegates to vLLM for
(launch/dynamo-run subprocess engines; SURVEY.md §2.1 L3).
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.kv_quant import (
    SCALE_EPS,
    dequantize_groups,
    requantize_groups,
)
from dynamo_tpu.models import mla_moe, ssm_moe
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.live_rows import live_row_trips, over_live_blocks
from dynamo_tpu.ops.attention import (
    DecodeAttention,
    PriorContext,
    ctx_decode_attention,
    dense_head_fuses,
    dense_prefill_attention,
    dense_round_rows,
)
from dynamo_tpu.ops.rope import apply_rope, rope_cos_sin, rope_inv_freq
from dynamo_tpu.telemetry.metrics import (
    LOOP_EXIT_CDF,
    LOOP_STEPS_RUN,
    Counter,
)

Params = dict[str, Any]
Cache = dict[str, jnp.ndarray]


def block_of(config: ModelConfig):
    """The module that builds ``config``'s block where it is not the
    dense decoder of this file (None): every ``@_hands_over`` name below
    hands over to it."""
    if config.hybrid is not None:   # which may borrow the latent block's
        return ssm_moe              # attention (``mla`` set beside it)
    if config.mla is not None:
        return mla_moe
    return None


# the block protocol: name -> the front door's signature (the module doc
# has it in words); filled by ``_hands_over``
PROTOCOL: dict[str, inspect.Signature] = {}


def _hands_over(door: Callable) -> Callable:
    """Mark ``door`` a name of the block protocol: called with a config
    another block builds, it is that module's function of the same name,
    with the arguments as given; else its own body, the dense decoder's.
    A leading underscore is no part of the name (a front-door function
    that adds to the protocol's hands over through a private twin)."""
    name = door.__name__.lstrip("_")
    PROTOCOL[name] = inspect.signature(door)

    @functools.wraps(door)
    def front(config, *args, **kwargs):
        block = block_of(config)
        if block is None:
            return door(config, *args, **kwargs)
        return getattr(block, name)(config, *args, **kwargs)
    return front


def row_kinds(state: Cache) -> tuple[str, ...]:
    """The kinds of row a region, pool or ring holds (its leaves that
    are neither scale grids nor recurrent state), in a fixed order."""
    return tuple(sorted(n for n in state
                        if not n.endswith(("_scale", "_state"))))


def state_kinds(state: Cache) -> tuple[str, ...]:
    """A region's recurrent leaves (``*_state``: per lane, not
    addressable by position). No mover touches them."""
    return tuple(sorted(n for n in state if n.endswith("_state")))


def _any_row(state: Cache) -> jnp.ndarray:
    return state[row_kinds(state)[0]]


def _row_stride(state: Cache, name: str) -> int:
    """Positions a row of kind ``name`` stands for. A region's (or a
    pool's) row kinds cover one span of positions, so a kind with fewer
    rows than the longest holds one row every so many positions (a page
    of it is ``page_size / stride`` rows)."""
    return (max(state[n].shape[3] for n in row_kinds(state))
            // state[name].shape[3])


def _dense_only(config_or_state, plane: str) -> None:
    """Refuse a latent-row or recurrent-state model (or its state) in a
    plane that knows one row geometry only, naming the plane."""
    if isinstance(config_or_state, ModelConfig):
        latent = config_or_state.mla is not None
        recurrent = config_or_state.hybrid is not None
    else:
        latent = "k" not in config_or_state
        recurrent = bool(state_kinds(config_or_state))
    if latent:
        raise ValueError(
            f"{plane}: this plane cannot carry a latent (MLA) cache row; "
            "it moves a K and a V of [kv_heads, head_dim]")
    if recurrent:
        raise ValueError(
            f"{plane}: this plane cannot carry a recurrent state; it "
            "moves K and V rows that are addressable by position")


def _no_loop(config: ModelConfig, plane: str) -> None:
    """Refuse a looped stack (``config.looped``: the layers run several
    times, a K/V plane a step a layer, sandwich norms, the step's norm
    and gate) in a plane that runs the layers once, naming the plane."""
    if config.looped:
        raise ValueError(
            f"{plane}: this plane cannot carry a looped layer stack yet "
            f"({config.loop_steps} passes over {config.num_layers} layers, "
            f"{config.cache_planes} K/V planes a token); it runs each "
            "layer once")


# ---------------------------------------------------------------------------
# What the engine asks of a block's state and counters (the block protocol)

@_hands_over
def stats_layout(config: ModelConfig) -> tuple[Counter, ...]:
    """The columns of a round's counter row, in order. The dense decoder
    counts nothing: no row rides its round's token fetch. A looped stack
    counts the passes a decoded token ran and, with the exit gate, the
    live lanes' mean exit CDF after each step before the last (as many
    steps as the registry has histograms for)."""
    if not config.looped:
        return ()
    steps = min(config.loop_steps - 1, len(LOOP_EXIT_CDF))
    return (Counter(LOOP_STEPS_RUN[0]),) + tuple(
        Counter(LOOP_EXIT_CDF[t][0], f32_bits=True)
        for t in range(steps if config.exit_gate else 0))


@_hands_over
def stats_zero(config: ModelConfig) -> jnp.ndarray:
    """A round's counter row before any step. The dense decoder's is the
    three-wide zero its round has always carried and never written (a
    dead carry of the loop: another shape would move the program text of
    every dense round). A looped stack's is its layout's."""
    return jnp.zeros(len(stats_layout(config)) or 3, jnp.int32)


@_hands_over
def state_called(config: ModelConfig) -> Optional[str]:
    """What a plane that moves a K and a V row is told it cannot carry,
    or None where K and V rows are all a lane holds (here), a plane a
    layer: a looped stack's lanes hold a plane a (step, layer), which the
    planes that size or index a cache by the weight layers cannot carry."""
    if config.looped:
        return (f"a looped layer stack ({config.loop_steps} passes over "
                f"{config.num_layers} layers, {config.cache_planes} K/V "
                "planes a token)")
    return None


@_hands_over
def transfer_refusal(config: ModelConfig) -> Optional[str]:
    """Why pages of this block's state cannot move between workers, or
    None where they can (here: pages move as a K and a V)."""
    return None


@_hands_over
def page_multiple(config: ModelConfig) -> int:
    """Positions a page has to be a multiple of."""
    return 1


@_hands_over
def pages_resume(config: ModelConfig) -> bool:
    """Whether sealed pages of rows are all a prompt needs to resume."""
    return True


@_hands_over
def decode_mirror(config: ModelConfig, max_context: int, ring_len: int,
                  attn: DecodeAttention) -> Optional[Callable]:
    """The host's mirror of what a dispatched round's decode attention
    reads: ``f(ctx_lens [B], live [B] bool, n_steps) -> ((metric, value),
    ...)`` to observe, or None where nothing is mirrored. Here: the region
    rows the flash kernel's work list reads a layer, in whole chunks a
    live lane, beside the live lanes' own rows."""
    return mla_moe.rows_mirror(dense_round_rows, attn, max_context)


@_hands_over
def prefill_mirror(config: ModelConfig, attn: DecodeAttention,
                   kv_quant: str = "none") -> Callable:
    """The host's mirror of what a prefill dispatch's attention layers
    ran beyond ``prefill_attention_pairs``: ``f(width, q_starts,
    seq_lens, scored, ctx_span) -> ((metric, value), ...)``. Here: the
    query blocks of every layer's attention, and those that ran through
    the fused kernel: every layer's, a group of query heads a K/V head,
    (every pass of every layer of a looped stack: ``cache_planes``)
    where ``attn`` (what the engine's programs are traced for, which
    the prefill programs are handed too) names a kernel, at a geometry
    inside its shape rule and a head of whole 128-lane tiles; a
    continuing chunk over an int8 region (``kv_quant``) keeps the
    loops."""
    return mla_moe.blocks_mirror(
        attn, config.cache_planes,
        fused_layers=config.cache_planes * dense_head_fuses(config.head_dim),
        n_heads=config.num_heads, kv_heads=config.num_kv_heads,
        int8_region=kv_quant == "int8")


# ---------------------------------------------------------------------------
# Parameters

@_hands_over
def init_params(config: ModelConfig, rng: jax.Array | int = 0) -> Params:
    """Random-init parameters (bf16, or w8a16 when config.quant="int8").
    Weight values only matter for quality, not performance, so benchmarks
    use this; serving uses load_hf_params.

    With quant, int8 leaves are generated DIRECTLY (uniform int8 + a
    constant per-channel scale matched to the dense init's std) — an 8B's
    dense weights can never be materialized on a 16 GB chip, so there is
    no dense-then-quantize step here."""
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    c = config
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(rng, 12)
    quant8 = c.quant == "int8"

    def rnd(key, *shape, scale=None, qaxis=-2):
        scale = scale or (1.0 / np.sqrt(shape[-2] if len(shape) > 1 else shape[-1]))
        if quant8 and qaxis is not None:
            q = jax.random.randint(key, shape, -127, 128, jnp.int8)
            s_shape = tuple(np.delete(shape, len(shape) + qaxis))
            # uniform[-127,127] has std ~73.3; s recovers the dense std
            s = jnp.full(s_shape, scale / 73.3, jnp.float32)
            return {"q": q, "s": s}
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    L, H, I, V = c.num_layers, c.hidden_size, c.intermediate_size, c.vocab_size
    layers: dict[str, Any] = {
        "ln1": jnp.ones((L, H), dtype),
        "ln2": jnp.ones((L, H), dtype),
        "wq": rnd(keys[1], L, H, c.q_dim),
        "wk": rnd(keys[2], L, H, c.kv_dim),
        "wv": rnd(keys[3], L, H, c.kv_dim),
        "wo": rnd(keys[4], L, c.q_dim, H),
    }
    if c.sandwich_norms:
        # the gains on each half's OUTPUT, ahead of the residual add
        layers.update(ln1b=jnp.ones((L, H), dtype),
                      ln2b=jnp.ones((L, H), dtype))
    if c.moe is not None:
        E = c.moe_dict["num_experts"]
        layers.update(
            wr=rnd(keys[5], L, H, E, qaxis=None),  # router stays dense
            we_g=rnd(keys[6], L, E, H, I),
            we_u=rnd(keys[7], L, E, H, I),
            we_d=rnd(keys[9], L, E, I, H),
        )
    else:
        layers.update(
            wg=rnd(keys[5], L, H, I),
            wu=rnd(keys[6], L, H, I),
            wd=rnd(keys[7], L, I, H),
        )
    params: Params = {
        "embed": rnd(keys[0], V, H, scale=0.02, qaxis=-1),
        "layers": layers,
        "norm_f": jnp.ones((H,), dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = rnd(keys[8], H, V, scale=0.02)
    if c.exit_gate:
        # Linear(H, 1) on every step's output, drawn a tenth of a
        # projection's scale: exit probabilities near a half
        params["gate_w"] = rnd(keys[10], H, scale=0.1 / np.sqrt(H),
                               qaxis=None)
        params["gate_b"] = jnp.zeros((), dtype)
    return params


@_hands_over
def param_shardings(config: ModelConfig, mesh: Mesh) -> Params:
    """NamedSharding pytree: Megatron-style TP over the `tp` mesh axis.
    qkv/gate/up shard the output (head/hidden) dim; o/down shard the input
    dim; embedding + lm_head shard the vocab dim. Quantized leaves get the
    weight's spec on "q" and the spec minus the reduced axis on "s"."""
    quant8 = config.quant == "int8"

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    def w(name, *spec):
        if quant8 and name in _QUANT_AXIS:
            axis = len(spec) + _QUANT_AXIS[name]
            s_spec = tuple(p for i, p in enumerate(spec) if i != axis)
            return {"q": ns(*spec), "s": ns(*s_spec)}
        return ns(*spec)

    layers: Params = {
        "ln1": ns(None, None),
        "ln2": ns(None, None),
        "wq": w("wq", None, None, "tp"),
        "wk": w("wk", None, None, "tp"),
        "wv": w("wv", None, None, "tp"),
        "wo": w("wo", None, "tp", None),
    }
    if config.sandwich_norms:
        layers.update(ln1b=ns(None, None), ln2b=ns(None, None))
    if config.moe is not None:
        # experts over ep, expert hidden over tp (wide-EP shape §2.5)
        layers.update(
            wr=ns(None, None, None),
            we_g=w("we_g", None, "ep", None, "tp"),
            we_u=w("we_u", None, "ep", None, "tp"),
            we_d=w("we_d", None, "ep", "tp", None),
        )
    else:
        layers.update(
            wg=w("wg", None, None, "tp"),
            wu=w("wu", None, None, "tp"),
            wd=w("wd", None, "tp", None),
        )
    out: Params = {
        "embed": w("embed", "tp", None),
        "layers": layers,
        "norm_f": ns(None),
    }
    if not config.tie_word_embeddings:
        out["lm_head"] = w("lm_head", None, "tp")
    if config.exit_gate:
        out.update(gate_w=ns(None), gate_b=ns())
    return out


@_hands_over
def serving_params(config: ModelConfig, params: Params) -> Params:
    """``params`` (of ``init_params``' tree, or a checkpoint's, already on
    their devices) as the PROGRAMS read them: the same leaves, plus what
    a block lays out anew once at engine start so that no program does
    it every call (the latent attention's W_kvb by head,
    ``mla_moe.serving_params``). The dense decoder reads its published
    leaves where they lie. Every program of this module takes the result;
    ``param_shardings`` describes ``init_params``' tree."""
    return params


# ---------------------------------------------------------------------------
# KV cache

@_hands_over
def init_cache(
    config: ModelConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: str = "none",
) -> Cache:
    """Paged KV pool — prefix-cache STORAGE (see module doc). Page 0 is the
    reserved scratch page for padded pool I/O.

    With ``kv_quant="int8"`` the pool holds int8 pages plus
    per-block-per-layer absmax scales (``k_scale``/``v_scale``: f32
    [L, num_pages]) — half the HBM residency of a bf16 pool, so the same
    chip holds ~2x the hittable prefix corpus. The hot decode path is
    untouched: quantize fuses into seal_blocks (ctx->pool), dequantize
    into load_ctx_pages (pool->ctx)."""
    c = config
    shape = (c.cache_planes, c.num_kv_heads, num_pages, page_size, c.head_dim)
    if kv_quant == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros((c.cache_planes, num_pages), jnp.float32),
            "v_scale": jnp.zeros((c.cache_planes, num_pages), jnp.float32),
        }
    dtype = dtype or jnp.dtype(c.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@_hands_over
def cache_shardings(
    config: ModelConfig, mesh: Mesh, kv_quant: str = "none"
) -> Cache:
    s = NamedSharding(mesh, P(None, "tp", None, None, None))
    out = {"k": s, "v": s}
    if kv_quant == "int8":
        # per-(layer, page) scales: no head axis, replicated over tp
        sc = NamedSharding(mesh, P(None, None))
        out["k_scale"] = sc
        out["v_scale"] = sc
    return out


def cache_is_quantized(cache: Cache) -> bool:
    return "k_scale" in cache


@_hands_over
def init_ctx(
    config: ModelConfig, batch: int, ctx_len: int, dtype=None,
    kv_quant: str = "none", group: int = 128,
) -> Cache:
    """Contiguous per-slot serving context ``[L, kvh, batch+1, S, hd]``
    (L the config's ``cache_planes`` here, in the pool and in the ring:
    a plane a layer, a plane a (step, layer) of a looped stack).
    Lane `batch` is the scratch lane for freed slots' in-flight garbage
    steps (see module doc / engine dest redirection).

    With ``kv_quant="int8"`` the region is int8 plus per-(layer, lane,
    position-group) f32 absmax scales ``k_scale``/``v_scale``
    [L, batch+1, S/group] — the flash-decode kernel dequantizes each KV
    chunk in VMEM after the DMA, halving live-context HBM traffic.
    ``group`` must be the engine's page_size so pool<->ctx copies at
    seal/admission are raw int8 page moves (the scale grids coincide);
    S is padded up to a multiple of it (the engine's max_context is
    already page-aligned, so no padding in practice)."""
    c = config
    shape = (c.cache_planes, c.num_kv_heads, batch + 1, ctx_len, c.head_dim)
    if kv_quant == "int8":
        S = -(-ctx_len // group) * group
        shape = shape[:3] + (S,) + shape[4:]
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(
                (c.cache_planes, batch + 1, S // group), jnp.float32),
            "v_scale": jnp.zeros(
                (c.cache_planes, batch + 1, S // group), jnp.float32),
        }
    dtype = dtype or jnp.dtype(c.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@_hands_over
def ctx_shardings(config: ModelConfig, mesh: Mesh,
                  kv_quant: str = "none") -> Cache:
    s = NamedSharding(mesh, P(None, "tp", None, None, None))
    out = {"k": s, "v": s}
    if kv_quant == "int8":
        # scales have no head axis: replicated over tp
        sc = NamedSharding(mesh, P(None, None, None))
        out["k_scale"] = sc
        out["v_scale"] = sc
    return out


def ctx_is_quantized(ctx_kv: Cache) -> bool:
    return "k_scale" in ctx_kv


def ctx_group_size(ctx_kv: Cache) -> int:
    """Position-group width of the int8 ctx scale grid."""
    return ctx_kv["k"].shape[3] // ctx_kv["k_scale"].shape[2]


def _ctx_compute_dtype(config: ModelConfig, ctx_kv: Cache):
    """Dtype activations/attention run in. The dense ctx region doubles
    as the compute dtype carrier; an int8 region cannot, so quantized
    mode computes in the model dtype (engines pair cache_dtype with the
    model dtype, so this is the same grid either way)."""
    if ctx_is_quantized(ctx_kv):
        return jnp.dtype(config.dtype)
    return ctx_kv["k"].dtype


def _prior_context(ctx_kv: Cache, l: int,
                   slots: jnp.ndarray) -> PriorContext:
    """Layer ``l`` of the region as the prior context of the chunks in
    lanes ``slots`` — handed to ``prefill_attention`` whole, which reads
    it block by block below each q_start (dequantizing an int8 region
    per block); no per-lane slab is sliced out first."""
    return PriorContext(
        ctx_kv["k"], ctx_kv["v"], jnp.int32(l), slots,
        ctx_kv.get("k_scale"), ctx_kv.get("v_scale"),
    )


def _quant_store_span(
    buf: jnp.ndarray,      # int8 [L, kvh, lanes, S, hd]
    scale: jnp.ndarray,    # f32 [L, lanes, nG]
    slot: jnp.ndarray,     # scalar i32
    start: jnp.ndarray,    # scalar i32 — span start position
    span: jnp.ndarray,     # float [L, kvh, T, hd] — new KV rows
    group: int,
    valid_t: Optional[jnp.ndarray] = None,  # scalar i32 — leading span
                           # rows that are REAL (rest is bucket padding)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize-on-store of a contiguous span into one slot's int8 ctx.

    Works on the minimal group-aligned window covering [start, start+T):
    gather window -> dequant -> overlay span -> requantize with fresh
    absmax scales for the overlapped groups (absmax over the request's
    own prefix + the span ONLY — stale suffix bytes from a previous
    occupant never feed a scale, keeping quantization deterministic per
    request history; see kv_quant.requantize_groups)."""
    L, kvh, lanes, S, hd = buf.shape
    nG = scale.shape[2]
    T = span.shape[2]
    nW = min((T + group - 1) // group + 1, nG)
    W = nW * group
    start = start.astype(jnp.int32)
    g0 = jnp.clip(start // group, 0, nG - nW)
    off = start - g0 * group  # in [0, W - T] by the window choice
    flat = buf.reshape(L, kvh, lanes * S, hd)
    base = slot.astype(jnp.int32) * S + g0 * group
    win = jax.lax.dynamic_slice(
        flat, (jnp.int32(0), jnp.int32(0), base, jnp.int32(0)),
        (L, kvh, W, hd),
    )[:, :, None]  # [L, kvh, 1, W, hd]
    sw = jax.lax.dynamic_slice(
        scale, (jnp.int32(0), slot.astype(jnp.int32), g0), (L, 1, nW)
    )  # [L, 1, nW]
    wf = dequantize_groups(win, sw, group)
    wf = jax.lax.dynamic_update_slice(
        wf, span.astype(jnp.float32)[:, :, None],
        (0, 0, 0, off, 0),
    )
    vt = T if valid_t is None else jnp.clip(
        valid_t.astype(jnp.int32), 0, T)
    w_idx = jnp.arange(W, dtype=jnp.int32)
    valid = (w_idx < off + vt)[None]                    # [1, W]
    j = jnp.arange(nW, dtype=jnp.int32)
    written = (((j + 1) * group > off) & (j * group < off + vt))[None]
    q, s_new = requantize_groups(wf, sw, valid, written, group)
    flat = jax.lax.dynamic_update_slice(
        flat, q[:, :, 0], (jnp.int32(0), jnp.int32(0), base, jnp.int32(0))
    )
    scale = jax.lax.dynamic_update_slice(
        scale, s_new, (jnp.int32(0), slot.astype(jnp.int32), g0)
    )
    return flat.reshape(L, kvh, lanes, S, hd), scale


@_hands_over
def init_ring(
    config: ModelConfig, batch: int, ring_len: int, dtype=None
) -> Cache:
    """Per-slot decode write ring ``[L, kv_heads, B, R, head_dim]``.

    Decode steps write their token's KV here (a cheap small-buffer
    update); ``flush_ctx`` writes a full ring into the ctx region once
    per round, one span a lane. This keeps the GB-scale ctx region
    READ-ONLY inside the round program — per-layer writes interleaved
    with the attention custom calls force XLA to materialize full copies
    of it (measured: ~7 GB temps, 120 ms/step). Ring slot r of lane b
    holds the token at position ``ring_base[b] + r``.
    """
    c = config
    dtype = dtype or jnp.dtype(c.dtype)
    shape = (c.cache_planes, c.num_kv_heads, batch, ring_len, c.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@_hands_over
def ring_shardings(config: ModelConfig, mesh: Mesh) -> Cache:
    s = NamedSharding(mesh, P(None, "tp", None, None, None))
    return {"k": s, "v": s}


@_hands_over
def stepped_kinds(config: ModelConfig, state: Cache) -> tuple[str, ...]:
    """A region's leaves a decode STEP writes (they ride the round's
    carry). The dense step writes the ring only."""
    return ()


# ---------------------------------------------------------------------------
# Quantization (w8a16: per-output-channel symmetric int8 weights)
#
# A quantized weight is the leaf pair {"q": int8 [..., in, out],
# "s": f32 [..., out]}; every matmul site routes through _mm/_embed_rows
# so dense and quantized params are interchangeable. The int8 tensor is
# what streams from HBM (half the weight-pass bytes of bf16 — the decode
# roofline — and what fits an 8B on a 16 GB v5e); the
# dequantize (convert + per-channel scale) fuses into the matmul epilogue.
# Reference analogue: the FP8 serving recipes
# (examples/llm/benchmarks/README.md:28).

_QUANT_AXIS = {
    # reduction axis for the per-output-channel scale, per weight name
    # (all weights are stored [in, out]-style; embed is row-gathered)
    "wq": -2, "wk": -2, "wv": -2, "wo": -2,
    "wg": -2, "wu": -2, "wd": -2,
    "we_g": -2, "we_u": -2, "we_d": -2,
    "embed": -1, "lm_head": -2,
}


def _is_quant(w) -> bool:
    return isinstance(w, dict) and "q" in w


def _mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for a dense or quantized weight."""
    if _is_quant(w):
        return jnp.matmul(x, w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


# ---------------------------------------------------------------------------
# Resident LoRA adapters (dynamo_tpu/tenancy/adapters.py builds the bank)
#
# The bank rides inside `params` as params["adapters"] = {site: {"a":
# [N, L, d_in, r], "b": [N, L, r, d_out]}}; presence is a TRACE-TIME
# check, so engines without a bank trace the identical pre-tenancy
# programs. Adapter 0 is all-zeros — the delta is exactly 0.0 and the
# base model's outputs are bit-identical.

def _lora_delta(x: jnp.ndarray, a, b) -> jnp.ndarray:
    """Rank-r LoRA delta for x [T, d_in] (or [B, d_in] in decode).
    Shared-id factors are 2-D ([d_in, r] / [r, d_out]); per-row decode
    factors are 3-D ([B, d_in, r] / [B, r, d_out]) — one gathered row
    per batch lane, contracted with that lane's activation only."""
    a = a.astype(x.dtype)
    b = b.astype(x.dtype)
    if a.ndim == 2:
        return (x @ a) @ b
    t = jnp.einsum("nd,ndr->nr", x, a)
    return jnp.einsum("nr,nro->no", t, b)


def _mm_ad(x: jnp.ndarray, w, ab) -> jnp.ndarray:
    """x @ w plus the site's adapter delta (``ab`` = (a, b) or None)."""
    y = _mm(x, w)
    if ab is not None:
        y = y + _lora_delta(x, ab[0], ab[1])
    return y


def _gather_adapters(bank, ids):
    """Gather each site's factor rows by adapter id: a scalar id yields
    per-site [L, d, r]; a [B] id row yields [B, L, d, r] (the per-slot
    decode gather — ids are constant within a round, so XLA hoists the
    gather out of the fused step loop)."""
    if bank is None or ids is None:
        return None
    return jax.tree.map(lambda x: x[ids], bank)


def _adapter_layer(gathered, l: int, per_row: bool):
    """Layer-l (a, b) slices of a gathered bank, keyed by site — the
    ``ad`` argument of _layer_body. None stays None (no-LoRA trace)."""
    if gathered is None:
        return None
    sl = (lambda x: x[:, l]) if per_row else (lambda x: x[l])
    return {s: (sl(ab["a"]), sl(ab["b"])) for s, ab in gathered.items()}


def _embed_rows(params: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    """Embedding gather for dense or quantized embed tables."""
    e = params["embed"]
    if _is_quant(e):
        return (e["q"][tokens].astype(dtype)
                * e["s"][tokens][..., None].astype(dtype))
    return e[tokens].astype(dtype)


def quantize_tensor(w, axis: int):
    """Symmetric per-channel int8: scale = amax/127 over `axis`."""
    wf = jnp.asarray(w, jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=axis) / 127.0
    s = jnp.maximum(s, 1e-10)
    q = jnp.clip(
        jnp.round(wf / jnp.expand_dims(s, axis)), -127, 127
    ).astype(jnp.int8)
    return {"q": q, "s": s.astype(jnp.float32)}


def quantize_params(params: Params) -> Params:
    """Post-load transform: dense params -> w8a16. Norms and the MoE
    router stay dense (tiny, accuracy-sensitive)."""
    out = dict(params)
    layers = dict(params["layers"])
    for name, axis in _QUANT_AXIS.items():
        if name in layers:
            layers[name] = quantize_tensor(layers[name], axis)
    out["layers"] = layers
    out["embed"] = quantize_tensor(params["embed"], _QUANT_AXIS["embed"])
    if "lm_head" in params:
        out["lm_head"] = quantize_tensor(
            params["lm_head"], _QUANT_AXIS["lm_head"]
        )
    return out


# ---------------------------------------------------------------------------
# Forward pieces

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _mlp(h, wg, wu, wd, ad=None):
    ad = ad or {}
    gate = _mm_ad(h, wg, ad.get("wg"))
    up = _mm_ad(h, wu, ad.get("wu"))
    return _mm_ad(jax.nn.silu(gate) * up, wd, ad.get("wd"))


def _moe_ffn(c: ModelConfig, lp, x: jnp.ndarray,
             valid=None) -> jnp.ndarray:
    """GShard-style dense-dispatch MoE FFN ``[T, H] -> [T, H]``.

    Pure einsums with a static per-expert capacity — jittable with static
    shapes and GSPMD-shardable: experts shard over `ep`, the expert hidden
    dim over `tp`; XLA inserts the all_to_alls over ICI (idiomatic TPU
    replacement for the reference's DeepEP dispatch, SURVEY §2.5 EP row).
    Tokens beyond an expert's capacity are dropped (standard GShard
    semantics); top-k gate weights are renormalized. `valid` [T] masks
    tokens OUT of routing entirely — padding / garbage decode lanes must
    not steal expert capacity from live tokens (and masking makes output
    independent of the co-batched garbage, keeping decode bit-exact
    regardless of slot occupancy)."""
    from dynamo_tpu.models.moe import MoEConfig, grouped_experts

    md = c.moe_dict
    mcfg = MoEConfig(
        hidden_size=c.hidden_size,
        intermediate_size=c.intermediate_size,
        num_experts=md["num_experts"],
        top_k=md.get("top_k", 2),
        capacity_factor=md.get("capacity_factor", 1.25),
    )
    if mcfg.capacity_factor <= 0:
        # dropless (the serving default): the one grouped expert layer
        # (models/moe.py); the one-hot dispatch below is the
        # capacity-bounded approximation only
        logits = jnp.matmul(x, lp["wr"], preferred_element_type=jnp.float32)
        gate_w, sel = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                    mcfg.top_k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
        return grouped_experts(x, sel, gate_w, lp["we_g"], lp["we_u"],
                               lp["we_d"], valid)[0]
    T = x.shape[0]
    E, K = mcfg.num_experts, mcfg.top_k
    C = mcfg.capacity(T)

    logits = jnp.matmul(x, lp["wr"], preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)            # [T, E]
    gate_w, sel = jax.lax.top_k(gates, K)              # [T, K]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    mask = jax.nn.one_hot(sel, E, dtype=jnp.float32)   # [T, K, E]
    if valid is not None:
        mask = mask * valid.astype(jnp.float32)[:, None, None]
    mask_f = mask.reshape(T * K, E)
    # 1-based arrival order of each (token, pick) in its expert's buffer
    pos = jnp.cumsum(mask_f, axis=0) * mask_f
    keep = (pos > 0) & (pos <= C)
    slot = jax.nn.one_hot(pos - 1, C, dtype=jnp.float32) * keep[..., None]
    # dispatch: [T*K, E, C] x [T*K, H] -> [E, C, H]
    x_rep = jnp.broadcast_to(x[:, None], (T, K, c.hidden_size))
    x_rep = x_rep.reshape(T * K, c.hidden_size)
    buf = jnp.einsum("sec,sh->ech", slot, x_rep.astype(jnp.float32))
    buf = buf.astype(x.dtype)
    def emm(spec, a, w):
        # expert einsum, dense or quantized (scale is per [E, out])
        if _is_quant(w):
            return (jnp.einsum(spec, a, w["q"].astype(a.dtype))
                    * w["s"][:, None, :].astype(a.dtype))
        return jnp.einsum(spec, a, w)

    y = (jax.nn.silu(emm("ech,ehi->eci", buf, lp["we_g"]))
         * emm("ech,ehi->eci", buf, lp["we_u"]))
    y = emm("eci,eih->ech", y, lp["we_d"])             # [E, C, H]
    out = jnp.einsum("sec,ech->sh", slot, y.astype(jnp.float32))
    out = out.reshape(T, K, c.hidden_size) * gate_w[..., None]
    return out.sum(axis=1).astype(x.dtype)


def _ffn(c: ModelConfig, lp, x: jnp.ndarray, valid=None,
         ad=None) -> jnp.ndarray:
    if c.moe is not None:
        # MoE expert stacks are not adapted (tenancy/adapters.py)
        return _moe_ffn(c, lp, x, valid)
    return _mlp(x, lp["wg"], lp["wu"], lp["wd"], ad)


def _layer_qkv(c: ModelConfig, lp, h, cos, sin, ad=None):
    """First half of a decoder layer: norm, QKV projections, RoPE.
    ``h`` is [N, H]; returns q [N, heads, hd], k and v [N, kvh, hd]."""
    N = h.shape[0]
    ad = ad or {}
    x = rms_norm(h, lp["ln1"], c.rms_norm_eps)

    def heads(name, n):
        y = _mm_ad(x, lp[name], ad.get(name))
        if not _is_quant(lp[name]):
            # The product ends HERE, as an [N, out] array. Left to itself
            # XLA:TPU folds the reshape to heads (and what the rope and
            # the attention do to the heads after it) into a bf16 product,
            # which then wants its weight head-major with the contraction
            # minor, and writes every layer's wq / wk / wv shard out
            # twice, a slice of the stack and a transposed copy, in front
            # of every product of every call: each decode step and each
            # prefill (tools/tpu_compile_check.py ``weight_copies``;
            # PERF.md section 6, PR 55). Behind the barrier the product
            # reads the shard where it lies in the stack, as wo and the
            # MLP's do, and what is laid out anew is the [N, out]
            # activation. An int8 weight's dequantising product already
            # reads the stack in place: its programs stay as they are.
            y = jax.lax.optimization_barrier(y)
        return y.reshape(N, n, c.head_dim)

    q = heads("wq", c.num_heads)
    k = heads("wk", c.num_kv_heads)
    v = heads("wv", c.num_kv_heads)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _layer_out(c: ModelConfig, lp, h, attn, ffn_valid=None, ad=None):
    """Second half: output projection, residual, norm, FFN; with
    sandwich norms each half's output is normed ahead of its add."""
    ad = ad or {}
    a = _mm_ad(attn.reshape(h.shape[0], c.q_dim), lp["wo"], ad.get("wo"))
    if c.sandwich_norms:
        a = rms_norm(a, lp["ln1b"], c.rms_norm_eps)
    h = h + a
    x2 = rms_norm(h, lp["ln2"], c.rms_norm_eps)
    f = _ffn(c, lp, x2, ffn_valid, ad)
    if c.sandwich_norms:
        f = rms_norm(f, lp["ln2b"], c.rms_norm_eps)
    return h + f


def _layer_body(c: ModelConfig, lp, h, cos, sin, write_kv, attend,
                ffn_valid=None, ad=None):
    """Shared decoder-layer body for prefill and decode.

    `write_kv(k, v)` scatters new KV into the carried cache and returns it;
    `attend(q, cache)` runs attention over the updated cache. `h` is [N, H]
    (N = padded tokens for prefill, batch slots for decode). `ad` is the
    layer's adapter-factor slices (``_adapter_layer``) or None — the
    rank-r LoRA deltas fuse into the existing site matmuls.
    """
    q, k, v = _layer_qkv(c, lp, h, cos, sin, ad)
    new_cache = write_kv(k, v)
    attn = attend(q, new_cache)
    return _layer_out(c, lp, h, attn, ffn_valid, ad), new_cache


# Height R of the row blocks a dense prefill chunk's two row-wise halves
# (_layer_qkv, _layer_out) loop over; chosen on the chip
# (tools/prefill_rows_bench.py, PERF.md section 6, PR 44).
LIVE_ROW_BLOCK = 512


def live_row_block(c: ModelConfig, T: int, tree: bool = False) -> int:
    """Rows a block of the live-row loops for a prefill chunk of bucket
    width ``T``, or 0 where a chunk's per-row work runs straight-line over
    all T rows: a bucket of one or two blocks (nothing worth a loop), a
    packed token tree (live nodes are no prefix of the chunk), the
    capacity-bounded expert layer (rows compete for capacity, so no row
    block stands alone) and the latent block, whose halves are its own.
    The hybrid block has its own rule for its own halves and scans
    (ssm_moe.live_row_block). Decided by the shape and by what the
    program is given, never by a model's name."""
    if tree or c.moe is not None or block_of(c) is mla_moe:
        return 0
    if block_of(c) is ssm_moe:
        return ssm_moe.live_row_block(c, T)
    R = LIVE_ROW_BLOCK
    return R if T % R == 0 and T // R > 2 else 0


def prefill_positions_run(c: ModelConfig, T: int, q_starts,
                          seq_lens) -> int:
    """Token positions one prefill dispatch of ``len(q_starts)`` lanes at
    bucket width ``T`` runs its per-row work over: the host's mirror of
    the live-row loops' trip count (``_live_rows`` here, the hybrid
    block's halves and scans): live row blocks x block height where the
    program loops over them, lanes x width where it does not."""
    R = live_row_block(c, T)
    if not R:
        return len(q_starts) * T
    trips = live_row_trips(np.asarray(q_starts, np.int64),
                           np.asarray(seq_lens, np.int64), T, R)
    return int(trips.sum()) * R


def moe_prefill_rows_sorted(c: ModelConfig, n_tokens: int) -> int:
    """(token, pick) rows the expert layers of one prefill program sort
    over ``n_tokens`` positions (lanes x bucket width), where they move
    those rows in the looped form — the host's mirror of
    ``moe.move_block``, the program's own rule; else 0. Only a block that
    holds a share of its experts loops, and only the hybrid block holds
    one."""
    return ssm_moe.prefill_rows_sorted(c, n_tokens) if block_of(
        c) is ssm_moe else 0


@functools.partial(jax.jit, static_argnames=("half", "c", "R"))
def _live_rows(half, c: ModelConfig, layers, l, ad, trips, rows, R: int):
    """One row-wise half of decoder layer ``l`` (``half`` = _layer_qkv or
    _layer_out: no row of its output depends on another row) over the
    row blocks that hold a live row, instead of over all K x T bucket
    rows. ``rows`` are the half's per-row operands, each [K, T, ...];
    ``layers`` the STACKED weights of every layer and ``l`` the layer as
    a value; ``ad`` the layer's adapter factors with a leading lane axis
    ({site: (a [K, d, r], b [K, r, o])}) or None; ``trips`` [K] =
    live_row_trips. Returns the half's outputs as a tuple, each
    [K, T, ...]; rows of blocks that never ran are 0 (the attention, the
    KV scales, the logits and every reader of the region stop at the
    live length).

    ONE rolled loop whose trip count is a traced value, over the flat
    work list of (lane, row block) pairs — the list prefill_attention
    builds for its query blocks. The layer is a value and the weights
    are arguments, so the layers of a program, unrolled in the caller,
    share one traced and lowered loop body, and the lowered program has
    the same size at every bucket width and lane count.

    The body slices its layer's weights out of the stack ITSELF, by an
    index the compiler cannot see is the same at every trip
    (optimization_barrier): a slice that is loop-invariant is hoisted,
    and XLA:TPU then copies the layer's weights (218 MB of int8 at
    Mistral-7B's widths) in front of every loop. Sliced inside, the
    slice and the int8 -> bf16 convert fuse into the matmul, as in the
    straight-line code (compile-only, PERF.md section 6, PR 44)."""
    def block(lane, r0, blk):
        ad_lane = None if ad is None else jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, lane, keepdims=False),
            ad)
        lb, _ = jax.lax.optimization_barrier((l, r0))
        lp = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, lb, keepdims=False),
            layers)
        return jax.tree.leaves(half(c, lp, *blk, ad=ad_lane))

    return over_live_blocks(block, trips, rows, R)


def _over_passes(c: ModelConfig, one_pass: Callable, carry):
    """``carry = one_pass(t, carry)`` for every loop step ``t`` of the
    stack: ONE pass straight through for the plain dense decoder (t = 0,
    a plane a layer: its programs' text does not move), ``loop_steps``
    passes for a looped one, each under the scope ``loop_step_<t>`` (a
    looped stack's device ops carry their step in a trace). ``one_pass``
    runs every layer l with the layer's weights and the cache plane
    ``t * num_layers + l``. The steps are UNROLLED like the layers, the
    step a static index: as a ``fori_loop`` of ``loop_steps`` trips
    round the unrolled layers the round and the batched prefills compile
    for the v5e in a third to a half of the time and copy nothing, but
    the solo fresh prefill of a 1024 bucket then copies the region it
    carries (7.5 GB more than the chip has; compile-only, PERF.md
    section 6, PR 64)."""
    if not c.looped:
        return one_pass(0, carry)
    for t in range(c.loop_steps):
        with jax.named_scope(f"loop_step_{t}"):
            carry = one_pass(t, carry)
    return carry


def _step_end(c: ModelConfig, params: Params, h: jnp.ndarray, stay=None):
    """What closes a pass of a looped stack over ``h`` [..., H]: the ONE
    final norm (its output feeds the next pass, and the head after the
    last: ``_logits`` adds none) and, where ``stay`` is given (the round
    of a config with the gate), the exit gate on the normed rows.
    ``stay`` [...] f32 is the probability that a row has not left before
    this step (1 ahead of the first); returns (h, stay after this step):
    1 - stay is the exit CDF."""
    with jax.named_scope("loop_norm_gate"):
        h = rms_norm(h, params["norm_f"], c.rms_norm_eps)
        if stay is not None:
            lam = jax.nn.sigmoid(
                jnp.einsum("...h,h->...", h.astype(jnp.float32),
                           params["gate_w"].astype(jnp.float32))
                + params["gate_b"].astype(jnp.float32))
            stay = stay * (1.0 - lam)
    return h, stay


def _logits(config: ModelConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    if not config.looped:    # a looped stack's last pass has normed it
        h = rms_norm(h, params["norm_f"], config.rms_norm_eps)
    w = params["embed"] if config.tie_word_embeddings else params["lm_head"]
    if _is_quant(w):
        q = w["q"].T if config.tie_word_embeddings else w["q"]  # [H, V]
        y = jnp.matmul(
            h, q.astype(h.dtype), preferred_element_type=jnp.float32
        )
        return y * w["s"]  # s is [V] for both orientations
    if config.tie_word_embeddings:
        w = w.T
    # f32 accumulation without materializing an f32 copy of the [H, V] matrix
    return jnp.matmul(h, w, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Prefill

def prefill_impl(
    config: ModelConfig,
    params: Params,
    ctx_kv: Cache,
    tokens: jnp.ndarray,      # [T] int32, bucket-padded
    slot: jnp.ndarray,        # scalar int32 — destination slot lane
    q_start: jnp.ndarray,     # scalar int32: #tokens already in the region
    seq_len: jnp.ndarray,     # scalar int32: total valid context length
    embeds: Optional[jnp.ndarray] = None,       # [T, H] override rows
    embeds_mask: Optional[jnp.ndarray] = None,  # [T] bool — True: use
                              # `embeds` instead of the token embedding
                              # (multimodal image tokens; vision.py)
    adapter_id: Optional[jnp.ndarray] = None,   # scalar i32 — resident
                              # LoRA bank row (0 = identity base model);
                              # ignored when params carry no bank
    fresh: bool = False,      # STATIC: the caller knows q_start == 0 —
                              # no read of the region is compiled at all
    counted: bool = False,    # STATIC: the hybrid block's third output
                              # too, the rows its expert layers' looped
                              # gathers ran (scalar i32; the engine asks
                              # where moe_prefill_rows_sorted says they
                              # loop)
    attn: Optional[DecodeAttention] = None,   # STATIC: what the engine's
                              # programs are traced for, as the round is
                              # handed it (the dense decoder's prefill
                              # attention is a kernel where it names one,
                              # mapped over its mesh's tp axis; None: the
                              # XLA loops)
) -> tuple[Cache, jnp.ndarray]:
    """Run T new tokens through the model, writing their KV into the
    slot's contiguous context region at [q_start, q_start+T).

    Returns (ctx_kv, logits[vocab]) where logits are for the LAST VALID
    token (position seq_len-1). Supports prefix-cache continuation: with
    q_start>0 the first q_start tokens' KV is already in the region
    (loaded from the pool by load_ctx_pages) and is attended to but not
    recomputed.

    CALLER CONTRACT (checked host-side by the engine scheduler, not here —
    dynamic_update_slice silently clamps under jit): q_start+T must fit the
    region.
    """
    out = _prefill_impl(config, params, ctx_kv, tokens, slot, q_start,
                        seq_len, embeds, embeds_mask, adapter_id, fresh,
                        attn=attn)
    return out if counted else out[:2]


@_hands_over
def _prefill_impl(config, params, ctx_kv, tokens, slot, q_start, seq_len,
                  embeds=None, embeds_mask=None, adapter_id=None,
                  fresh=False, *, attn=None):
    """``prefill_impl`` as the block protocol has it: the dense decoder's
    chunk, (ctx_kv, logits)."""
    c = config
    T = tokens.shape[0]
    inv_freq = jnp.asarray(
        rope_inv_freq(c.head_dim, c.rope_theta, c.rope_scaling_dict)
    )
    positions = q_start + jnp.arange(T, dtype=jnp.int32)
    cos, sin = rope_cos_sin(positions, inv_freq)

    cdt = _ctx_compute_dtype(c, ctx_kv)
    h = _embed_rows(params, tokens, cdt)
    if embeds is not None:
        h = jnp.where(embeds_mask[:, None], embeds.astype(h.dtype), h)

    # Layers are UNROLLED (python loop, static layer index). The region is
    # READ-ONLY during the layer stack: each layer's chunk KV is carried in
    # values and attention takes it directly (dense_prefill_attention, which
    # reads the region only in blocks below q_start, and not at all when
    # `fresh`); ALL writes land in one tail pass after the last read, so
    # the donated update chain aliases in place (interleaved write/read of
    # the GB-scale buffer would force XLA to materialize copies of it).
    ag = _gather_adapters(params.get("adapters"), adapter_id)
    one = lambda x: jnp.asarray(x)[None]  # noqa: E731 — K = 1
    # a wide bucket runs the two row-wise halves of a layer over its live
    # row blocks only, as the one lane of _live_rows
    R = live_row_block(c, T)
    trips = live_row_trips(one(q_start), one(seq_len), T, R) if R else None

    def live(half, l, *rows):
        out = _live_rows(
            half, c, params["layers"], jnp.int32(l),
            jax.tree.map(one, _adapter_layer(ag, l, per_row=False)), trips,
            tuple(x[None] for x in rows), R)
        return tuple(x[0] for x in out)

    # A looped stack runs the layers ``loop_steps`` times over the same
    # weights (weights by layer l, K/V by plane t * L + l: the rows THIS
    # step's pass of layer l wrote), the final norm closing every pass,
    # and its chunk rows go into the region a PASS at a time, planes
    # [t * L, (t + 1) * L) after the pass's last read: carried to one tail
    # they are cache_planes x 2 rows a token of temporaries (1.5 MB a
    # token at Ouro-2.6B's widths, 3 GB a 1024-token chunk with its stack)
    # beside a region sized to fill the chip. No pass reads what an
    # earlier one wrote (other planes, and rows at and above q_start).
    def store(ctx_kv, new_ks, new_vs, plane0):
        # one contiguous span write per buffer
        upd_k = jnp.stack(new_ks).transpose(0, 2, 1, 3)  # [L, kvh, T, hd]
        upd_v = jnp.stack(new_vs).transpose(0, 2, 1, 3)
        if ctx_is_quantized(ctx_kv):
            _no_loop(c, "kv_quant=int8 (the int8 KV plane)")
            g = ctx_group_size(ctx_kv)
            ck, ksc = _quant_store_span(
                ctx_kv["k"], ctx_kv["k_scale"], slot, q_start, upd_k, g,
                valid_t=seq_len - q_start)
            cv, vsc = _quant_store_span(
                ctx_kv["v"], ctx_kv["v_scale"], slot, q_start, upd_v, g,
                valid_t=seq_len - q_start)
            return {"k": ck, "v": cv, "k_scale": ksc, "v_scale": vsc}
        ck, cv = ctx_kv["k"], ctx_kv["v"]
        ck = jax.lax.dynamic_update_slice(
            ck, upd_k[:, :, None].astype(ck.dtype),
            (plane0, 0, slot, q_start, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cv, upd_v[:, :, None].astype(cv.dtype),
            (plane0, 0, slot, q_start, 0)
        )
        return {"k": ck, "v": cv}

    new_ks: list[jnp.ndarray] = []
    new_vs: list[jnp.ndarray] = []

    def one_pass(t, carry):
        h, ctx_kv = carry
        plane0 = t * c.num_layers
        for l in range(c.num_layers):
            if R:
                q, k, v = live(_layer_qkv, l, h, cos, sin)
            else:
                lp = jax.tree.map(lambda x: x[l], params["layers"])
                ad = _adapter_layer(ag, l, per_row=False)
                q, k, v = _layer_qkv(c, lp, h, cos, sin, ad)
            new_ks.append(k)
            new_vs.append(v)
            o = dense_prefill_attention(
                attn, q[None], k[None], v[None], one(q_start), one(seq_len),
                None if fresh else _prior_context(ctx_kv, plane0 + l,
                                                  one(slot)),
            )[0]
            if R:
                h, = live(_layer_out, l, h, o)
            else:
                # padding tokens must not claim MoE expert capacity
                h = _layer_out(c, lp, h, o, positions < seq_len, ad)
        if c.looped:
            h, _ = _step_end(c, params, h)
            ctx_kv = store(ctx_kv, new_ks, new_vs, plane0)
            new_ks.clear()
            new_vs.clear()
            # the next pass starts behind this one's store: left free, the
            # scheduler of a FRESH program (no read orders the stores)
            # holds every pass's rows to the end (6.2 GB of temporaries
            # at two lanes of 1024, compile-only, PERF.md section 6, PR 64)
            h, ctx_kv = jax.lax.optimization_barrier((h, ctx_kv))
        return h, ctx_kv

    h, ctx_kv = _over_passes(c, one_pass, (h, ctx_kv))
    # tail (all reads are done); a looped stack's passes have stored theirs
    out_ctx = ctx_kv if c.looped else store(ctx_kv, new_ks, new_vs, 0)

    last = seq_len - q_start - 1  # index of last valid token within T
    logits = _logits(c, params, h[last])
    return out_ctx, logits


prefill = jax.jit(
    prefill_impl, static_argnums=(0,),
    static_argnames=("fresh", "counted", "attn"), donate_argnums=(2,),
)


def _batch_forward(
    config: ModelConfig,
    params: Params,
    ctx_kv: Cache,
    tokens: jnp.ndarray,    # [K, T] int32, bucket-padded per request
    slots: jnp.ndarray,     # [K] i32
    q_starts: jnp.ndarray,  # [K] i32
    seq_lens: jnp.ndarray,  # [K] i32
    ctx_span: int,
    adapter_ids: Optional[jnp.ndarray] = None,  # [K] i32 bank rows
    depths: Optional[jnp.ndarray] = None,       # [K, T] i32 tree depths
                            # (RoPE position = q_start + depth; -1 pad)
    chunk_masks: Optional[jnp.ndarray] = None,  # [K, T, T] bool tree-
                            # causal in-chunk visibility (spec tree)
    attn: Optional[DecodeAttention] = None,  # as prefill_impl's (spec's
                            # planes hand none over: the XLA loops)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Cache]:
    """Read-only layer stack shared by batch_prefill and batch_score: K
    chunks through the model in one program. Returns (ks, vs, h, ctx_kv)
    — stacked per-layer KV [K, L, T, kvh, hd] and final hidden states
    [K, T, H]; region writes happen after the stack, in the caller, and
    ``ctx_kv`` comes back as given. A looped stack's passes write their
    own planes as each ends (``_prefill_impl`` has why): ks and vs come
    back None beside the written region.

    Each layer is lane-batched end to end: its two halves (_layer_qkv,
    _layer_out) are vmapped over the K lanes — one [K, T, H] pipeline,
    so a tp-sharded layer keeps two all-reduces over [K, T, hidden] —
    and between them ONE dense_prefill_attention call takes all lanes with
    their q_starts and seq_lens, so a dummy lane or a short prompt costs
    no attention. ``ctx_span`` 0 compiles no read of the region. A
    bucket of more than two row blocks (live_row_block) runs the halves
    over its live (lane, row block) pairs instead (_live_rows: the
    all-reduces then sit in the loop, one a block), so a dummy lane or
    a short prompt costs no matmul either.

    Tree mode (``depths``/``chunk_masks`` given, always together): the
    chunk is a packed token TREE, not a linear run — node t's RoPE
    position is q_start + depths[t] (siblings at one depth share a
    position) and in-chunk attention follows the caller's ancestor mask
    instead of index order. Tree chunks never carry adapters (spec is
    confined to the base model)."""
    c = config
    _dense_only(c, "speculation (spec/: scoring, drafting)")
    if depths is not None:
        _no_loop(c, "speculation (spec/: tree scoring)")
    K, T = tokens.shape
    inv_freq = jnp.asarray(
        rope_inv_freq(c.head_dim, c.rope_theta, c.rope_scaling_dict)
    )

    cdt = _ctx_compute_dtype(c, ctx_kv)
    # gather bank rows once ([K, L, d, r] per site); each vmapped half
    # then sees its lane's own [L, d, r] factors
    ag = _gather_adapters(params.get("adapters"), adapter_ids)
    if depths is None:
        positions = q_starts[:, None] + jnp.arange(T, dtype=jnp.int32)
        node_valid = positions < seq_lens[:, None]
    else:
        assert ag is None, "tree chunks are base-model only"
        # padding nodes (depth -1) pin to position q_start and are
        # masked out of attention (chunk_masks) and MoE routing below
        positions = q_starts[:, None] + jnp.maximum(depths, 0)
        node_valid = (positions < seq_lens[:, None]) & (depths >= 0)
    cos, sin = jax.vmap(lambda p: rope_cos_sin(p, inv_freq))(positions)
    h = jax.vmap(lambda t: _embed_rows(params, t, cdt))(tokens)
    # wide linear chunks run each half over their live row blocks only
    R = live_row_block(c, T, tree=depths is not None)
    trips = live_row_trips(q_starts, seq_lens, T, R) if R else None

    def live(half, l, *rows):
        return _live_rows(half, c, params["layers"], jnp.int32(l),
                          _adapter_layer(ag, l, per_row=True), trips, rows, R)

    new_ks: list[jnp.ndarray] = []
    new_vs: list[jnp.ndarray] = []

    def one_pass(t, carry):     # as _prefill_impl's
        h, ctx_kv = carry
        plane0 = t * c.num_layers
        for l in range(c.num_layers):
            if R:
                q, k, v = live(_layer_qkv, l, h, cos, sin)
            else:
                lp = jax.tree.map(lambda x: x[l], params["layers"])

                def ad(ag_row, l=l):
                    return _adapter_layer(ag_row, l, per_row=False)

                q, k, v = jax.vmap(
                    lambda h, cos, sin, ag_row: _layer_qkv(
                        c, lp, h, cos, sin, ad(ag_row))
                )(h, cos, sin, ag)
            new_ks.append(k)
            new_vs.append(v)
            o = dense_prefill_attention(
                attn, q, k, v, q_starts, seq_lens,
                _prior_context(ctx_kv, plane0 + l, slots) if ctx_span > 0
                else None,
                chunk_masks, ctx_span=ctx_span,
            )
            if R:
                h, = live(_layer_out, l, h, o)
            else:
                h = jax.vmap(
                    lambda h, o, valid, ag_row: _layer_out(
                        c, lp, h, o, valid, ad(ag_row))
                )(h, o, node_valid, ag)
        if c.looped:
            h, _ = _step_end(c, params, h)
            ctx_kv = _write_chunks(
                ctx_kv, jnp.stack(new_ks, axis=1).astype(cdt),
                jnp.stack(new_vs, axis=1).astype(cdt), slots, q_starts,
                seq_lens, plane0=plane0)
            new_ks.clear()
            new_vs.clear()
            h, ctx_kv = jax.lax.optimization_barrier((h, ctx_kv))
        return h, ctx_kv

    h, ctx_kv = _over_passes(c, one_pass, (h, ctx_kv))
    if c.looped:
        return None, None, h, ctx_kv
    return (
        jnp.stack(new_ks, axis=1).astype(cdt),
        jnp.stack(new_vs, axis=1).astype(cdt),
        h,
        ctx_kv,
    )


def _write_chunks(
    ctx_kv: Cache,
    ks: jnp.ndarray,        # [K, L, T, kvh, hd]
    vs: jnp.ndarray,
    slots: jnp.ndarray,
    q_starts: jnp.ndarray,
    seq_lens: Optional[jnp.ndarray] = None,  # [K] i32 — bounds the rows
                            # feeding int8 scales (padding excluded)
    plane0: int = 0,        # STATIC: the region's plane ks[:, 0] goes to
                            # (a looped stack's pass writes its own L)
) -> Cache:
    """Tail pass: K span writes per buffer, after every read — one
    rolled loop over the lanes whose carried buffers update in place, so
    the donated chain still aliases and the program's size does not grow
    with K. Quantized regions route each span through the
    group-requantize window (_quant_store_span) instead of a raw DUS."""
    K = ks.shape[0]
    quant = ctx_is_quantized(ctx_kv)
    assert not (quant and plane0), "an int8 region takes whole stacks"
    g = ctx_group_size(ctx_kv) if quant else 0

    def write_lane(i, ctx_kv):
        # [L, T, kvh, hd] -> [L, kvh, T, hd]
        upd = {
            "k": jax.lax.dynamic_index_in_dim(
                ks, i, keepdims=False).transpose(0, 2, 1, 3),
            "v": jax.lax.dynamic_index_in_dim(
                vs, i, keepdims=False).transpose(0, 2, 1, 3),
        }
        out = dict(ctx_kv)
        vt = None if seq_lens is None else seq_lens[i] - q_starts[i]
        for name, span in upd.items():
            if quant:
                out[name], out[name + "_scale"] = _quant_store_span(
                    ctx_kv[name], ctx_kv[name + "_scale"], slots[i],
                    q_starts[i], span, g, valid_t=vt)
            else:
                out[name] = jax.lax.dynamic_update_slice(
                    ctx_kv[name], span[:, :, None],
                    (plane0, 0, slots[i], q_starts[i], 0))
        return out

    return jax.lax.fori_loop(0, K, write_lane, dict(ctx_kv))


def batch_prefill_impl(
    config: ModelConfig,
    params: Params,
    ctx_kv: Cache,
    tokens: jnp.ndarray,    # [K, T] int32, bucket-padded per request
    slots: jnp.ndarray,     # [K] i32 — destination slot lanes (distinct)
    q_starts: jnp.ndarray,  # [K] i32 — tokens already in each region
    seq_lens: jnp.ndarray,  # [K] i32 — total valid context per request
    ctx_span: int = 0,      # STATIC: prior-context window to attend
                            # (pow2 >= max(q_starts); 0 = fresh prefill,
                            # no context read compiled at all)
    adapter_ids: Optional[jnp.ndarray] = None,  # [K] i32 — resident LoRA
                            # bank rows (0 = identity; padding lanes 0)
    counted: bool = False,  # STATIC: a third output, as prefill_impl's
    attn: Optional[DecodeAttention] = None,   # STATIC, as prefill_impl's
) -> tuple[Cache, jnp.ndarray]:
    """Batched multi-request prefill: K chunks through the model in ONE
    program — the TTFT lever for concurrent arrivals (reference analogue:
    vLLM's max_num_batched_tokens prefill batching; the per-request
    `prefill` above keeps the multimodal-embeds and odd-shape paths).

    Matmuls see [K*T, H] rows (the MXU-utilization win over K separate
    [T, H] dispatches); attention is the one blocked scan over live rows
    (ops/attention.py prefill_attention), so no [T, S+T] score tensor
    materializes and dummy lanes cost no attention. Per-request KV lands
    in each slot's contiguous region at [q_start_k, q_start_k+T); all
    writes happen in one tail pass after the last read (the round-4
    no-interleave discipline — models/llama.py module doc). Returns (ctx_kv, logits[K, vocab]) with
    each row the last valid token's logits.

    Padding lanes (group smaller than the compiled K): point slot at the
    scratch lane (batch index B) with seq_len=0 — ffn_valid masks their
    tokens out of MoE routing and their region writes hit scratch.
    """
    out = _batch_prefill_impl(config, params, ctx_kv, tokens, slots,
                              q_starts, seq_lens, ctx_span, adapter_ids,
                              attn=attn)
    return out if counted else out[:2]


@_hands_over
def _batch_prefill_impl(config, params, ctx_kv, tokens, slots, q_starts,
                        seq_lens, ctx_span=0, adapter_ids=None, *,
                        attn=None):
    """``batch_prefill_impl`` as the block protocol has it: the dense
    decoder's K chunks, (ctx_kv, logits)."""
    ks, vs, h, ctx_kv = _batch_forward(
        config, params, ctx_kv, tokens, slots, q_starts, seq_lens, ctx_span,
        adapter_ids, attn=attn,
    )
    if ks is not None:      # a looped stack's passes have written theirs
        ctx_kv = _write_chunks(ctx_kv, ks, vs, slots, q_starts, seq_lens)
    last = jnp.maximum(seq_lens - q_starts - 1, 0)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    logits = _logits(config, params, h_last)
    return ctx_kv, logits


batch_prefill = jax.jit(
    batch_prefill_impl, static_argnums=(0, 7),
    static_argnames=("counted", "attn"), donate_argnums=(2,),
)


def batch_score_impl(
    config: ModelConfig,
    params: Params,
    ctx_kv: Cache,
    tokens: jnp.ndarray,    # [K, T] int32 — T = pending + proposed tokens
    slots: jnp.ndarray,     # [K] i32 (dummies -> scratch lane)
    q_starts: jnp.ndarray,  # [K] i32 — tokens already in each region
    seq_lens: jnp.ndarray,  # [K] i32 — q_start + T for live rows, 0 dummy
    ctx_span: int,          # STATIC prior-context window (always > 0 here)
) -> tuple[Cache, jnp.ndarray]:
    """Speculative-verification scorer: identical to batch_prefill — same
    chunked q_start>0 forward, same optimistic KV tail write — but
    returns logits for EVERY chunk position [K, T, V], not just the last.
    Row t of a chunk scores the target's distribution for the token
    FOLLOWING tokens[:, t] — the verifier (spec/verifier.py) compares
    those rows against the proposed tokens. The KV rows written for
    later-rejected tokens are dead weight past the committed length:
    attention masks by seq_len and the next write over the lane
    overwrites them, so rollback is pointer truncation, not a device op.
    """
    _no_loop(config, "speculation (spec/: scoring)")
    ks, vs, h, _ = _batch_forward(
        config, params, ctx_kv, tokens, slots, q_starts, seq_lens, ctx_span
    )
    ctx_kv = _write_chunks(ctx_kv, ks, vs, slots, q_starts, seq_lens)
    return ctx_kv, _logits(config, params, h)


def batch_score_tree_impl(
    config: ModelConfig,
    params: Params,
    ctx_kv: Cache,
    tokens: jnp.ndarray,       # [B, T] i32 packed tree (node 0 = pending)
    slots: jnp.ndarray,        # [B] i32 (dummies -> scratch lane)
    q_starts: jnp.ndarray,     # [B] i32 — tokens already in each region
    seq_lens: jnp.ndarray,     # [B] i32 — q_start + T live, 0 dummy
    depths: jnp.ndarray,       # [B, T] i32 node depths (-1 = padding)
    chunk_masks: jnp.ndarray,  # [B, T, T] bool ancestor-or-self
    ctx_span: int,             # STATIC prior-context window (> 0)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Tree-verification scorer: one q_start>0 batched forward over a
    packed token TREE per slot — RoPE by node depth, in-chunk attention
    by ancestor mask — returning logits for EVERY node [B, T, V]. Row t
    scores the target's distribution for the token FOLLOWING node t's
    root-to-node path.

    Unlike batch_score_impl this does NOT write ctx: a tree's rows are
    position-aliased (siblings share a RoPE position), so the optimistic
    linear tail write would land sibling KV in rows the accepted path
    must own. The caller runs acceptance on device, gathers exactly the
    accepted path's rows out of the returned (ks, vs), and commits them
    via commit_tree_path — rollback stays pointer-shaped."""
    ks, vs, h, _ = _batch_forward(
        config, params, ctx_kv, tokens, slots, q_starts, seq_lens,
        ctx_span, None, depths, chunk_masks,
    )
    return ks, vs, _logits(config, params, h)


def commit_tree_path(
    ctx_kv: Cache,
    ks: jnp.ndarray,          # [B, L, T, kvh, hd] from batch_score_tree_impl
    vs: jnp.ndarray,
    path: jnp.ndarray,        # [B, T] i32 — accepted node index per output
                              # position (path[:, 0] == 0, the pending
                              # token; entries past n_out are ignored)
    slots: jnp.ndarray,       # [B] i32
    q_starts: jnp.ndarray,    # [B] i32
    commit_lens: jnp.ndarray,  # [B] i32 — q_start + n_out (live), 0 dummy
) -> Cache:
    """Commit ONLY the accepted root-to-leaf path's KV rows: reorder the
    fresh-chunk KV by the path's node indices (sibling rows are simply
    never gathered) and span-write at [q_start, commit_len). Rows past
    n_out gather clamped garbage but stay dead — attention masks by
    seq_len, the quantized store bounds its scale window at
    commit_len - q_start, and the next round's write starts exactly at
    commit_len. This is what keeps tree rollback pointer truncation."""
    idx = jnp.clip(path, 0, ks.shape[2] - 1)[:, None, :, None, None]
    ks_path = jnp.take_along_axis(ks, idx, axis=2)
    vs_path = jnp.take_along_axis(vs, idx, axis=2)
    return _write_chunks(ctx_kv, ks_path, vs_path, slots, q_starts,
                         commit_lens)


def batch_draft_impl(
    config: ModelConfig,
    params: Params,
    ctx_kv: Cache,
    tokens: jnp.ndarray,    # [B, T] i32 — per-slot history catch-up chunk
    slots: jnp.ndarray,     # [B] i32 (dummies -> scratch lane)
    q_starts: jnp.ndarray,  # [B] i32 — draft KV already in each region
    seq_lens: jnp.ndarray,  # [B] i32 — q_start + chunk for live rows, 0 dummy
    ctx_span: int,          # STATIC prior-context window
    k: int,                 # STATIC draft depth
    m: int = 1,             # STATIC branches per level (comb tree; 1 =
                            # the original linear chain, bit-identical)
) -> tuple[Cache, jnp.ndarray]:
    """Draft ``k`` greedy continuation tokens for EVERY speculating slot
    in ONE program: the catch-up chunk (the tokens accepted since the
    slot's last draft) runs as a batch_prefill-shaped forward, then a
    ``lax.fori_loop`` runs k-1 single-token batched steps with argmax
    feedback entirely on device — one dispatch a round whatever the
    number of slots and k (DraftModelProposer.propose_batch).
    Returns (ctx_kv, drafted [B, k] i32); nothing touches the host.

    KV bookkeeping: the catch-up chunk lands at
    [q_start, seq_len), draft step s writes at seq_len + s, and the last
    drafted token's KV is never computed (it is never fed back). Rollback
    stays pointer truncation. Dummy rows (seq_len 0) write the scratch
    lane at position 0 and are masked out of attention and MoE routing.

    ``m > 1`` (tree drafts): each fori step records the top-m candidates
    instead of just the argmax, but ONLY the top-1 "spine" feeds back
    (and owns the KV written at seq_len + s) — a comb-shaped tree, depth
    k with m-way fan at every level, from the same program at the same
    dispatch cost. Returns drafted [B, k*m] in level-major node order
    (level s occupies columns [s*m, s*m + m), column s*m = the spine);
    spec/proposer.py comb_parents gives the matching parent pointers.
    """
    _no_loop(config, "speculation (spec/: drafting)")
    B, T = tokens.shape
    ks, vs, h, _ = _batch_forward(
        config, params, ctx_kv, tokens, slots, q_starts, seq_lens, ctx_span
    )
    ctx_kv = _write_chunks(ctx_kv, ks, vs, slots, q_starts, seq_lens)
    last = jnp.maximum(seq_lens - q_starts - 1, 0)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    logits = _logits(config, params, h_last)
    live = seq_lens > 0

    if m > 1:
        drafted = jnp.zeros((B, k * m), jnp.int32)
        _, top0 = jax.lax.top_k(logits, m)  # idx 0 == argmax (ties: low)
        drafted = jax.lax.dynamic_update_slice_in_dim(
            drafted, top0.astype(jnp.int32), 0, axis=1
        )
        if k == 1:
            return ctx_kv, drafted

        def body_m(s, carry):
            ctx_kv, drafted = carry
            # feed level s's spine (column s*m) back, as the m=1 path
            # feeds its single candidate
            toks_s = jax.lax.dynamic_slice_in_dim(drafted, s * m, 1, axis=1)
            pos = jnp.where(live, seq_lens + s, 0)
            sl = jnp.where(live, pos + 1, 0)
            ks, vs, h, _ = _batch_forward(
                config, params, ctx_kv, toks_s, slots, pos, sl, ctx_span
            )
            ctx_kv = _write_chunks(ctx_kv, ks, vs, slots, pos, sl)
            logits = _logits(config, params, h[:, 0])
            _, nxt = jax.lax.top_k(logits, m)
            drafted = jax.lax.dynamic_update_slice_in_dim(
                drafted, nxt.astype(jnp.int32), (s + 1) * m, axis=1
            )
            return ctx_kv, drafted

        return jax.lax.fori_loop(0, k - 1, body_m, (ctx_kv, drafted))

    drafted = jnp.zeros((B, k), jnp.int32)
    drafted = drafted.at[:, 0].set(
        jnp.argmax(logits, axis=-1).astype(jnp.int32)
    )
    if k == 1:
        return ctx_kv, drafted

    def body(s, carry):
        ctx_kv, drafted = carry
        toks_s = jax.lax.dynamic_slice_in_dim(drafted, s, 1, axis=1)
        # dummy rows stay pinned at (pos 0, seq_len 0): their garbage
        # writes target scratch row 0 and attention masks them entirely
        pos = jnp.where(live, seq_lens + s, 0)
        sl = jnp.where(live, pos + 1, 0)
        ks, vs, h, _ = _batch_forward(
            config, params, ctx_kv, toks_s, slots, pos, sl, ctx_span
        )
        ctx_kv = _write_chunks(ctx_kv, ks, vs, slots, pos, sl)
        logits = _logits(config, params, h[:, 0])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        drafted = jax.lax.dynamic_update_slice_in_dim(
            drafted, nxt[:, None], s + 1, axis=1
        )
        return ctx_kv, drafted

    ctx_kv, drafted = jax.lax.fori_loop(0, k - 1, body, (ctx_kv, drafted))
    return ctx_kv, drafted


batch_draft = jax.jit(
    batch_draft_impl, static_argnums=(0, 7, 8, 9), donate_argnums=(2,)
)


# ---------------------------------------------------------------------------
# Decode

def decode_step_impl(
    config: ModelConfig,
    params: Params,
    ctx_kv: Cache,             # [L, kvh, B+1, S, hd] — READ-ONLY here
    ring: Cache,               # [L, kvh, B, R, hd] write ring
    tokens: jnp.ndarray,       # [B] int32 — last sampled token per slot
    ctx_lens: jnp.ndarray,     # [B] int32 — context length INCLUDING this token
    ring_base: jnp.ndarray,    # [B] int32 — position held by ring slot 0
    ring_pos: jnp.ndarray,     # scalar int32 — ring slot receiving this token
    live: Optional[jnp.ndarray] = None,  # [B] bool — garbage lanes masked
                               # out of MoE expert routing
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] i32 — per-slot
                               # resident LoRA bank rows (0 = identity);
                               # mixed ids batch into ONE program via a
                               # row gather + rank-r einsum per site
    *,
    attn: DecodeAttention,     # which attention implementation to trace
                               # (ops/attention.py) — always the caller's
                               # explicit choice, no default
) -> tuple[Cache, jnp.ndarray]:
    """One decode step for all slots. Returns (ring, logits [B, vocab]).

    The new token's KV lands in ring slot `ring_pos` (its position is
    ``ctx-1 == ring_base + ring_pos`` for live slots); attention covers
    the ctx region for positions < ring_base plus ring entries
    [ring_base, ctx). The ctx region is immutable between `flush_ctx`
    calls — the write/read interleave on the GB-scale buffer is what
    forces XLA copies (see init_ring).
    """
    return _decode_step(config, params, ctx_kv, ring, tokens, ctx_lens,
                        ring_base, ring_pos, live, adapter_ids, attn=attn)[:2]


def _decode_step(config, params, ctx_kv, ring, tokens, ctx_lens, ring_base,
                 ring_pos, live=None, adapter_ids=None, *, attn):
    """``decode_step_impl`` and, third, a looped stack's exit CDFs: f32
    [loop_steps - 1, B], after each pass before the last, what
    ``round_step`` counts (None without the gate)."""
    c = config
    _dense_only(c, "llama.decode_step (the latent block's decode entry "
                "is mla_moe.decode_step_impl)")
    inv_freq = jnp.asarray(
        rope_inv_freq(c.head_dim, c.rope_theta, c.rope_scaling_dict)
    )
    positions = jnp.maximum(ctx_lens - 1, 0)
    cos, sin = rope_cos_sin(positions, inv_freq)  # [B, hd]

    h = _embed_rows(params, tokens, _ctx_compute_dtype(c, ctx_kv))  # [B, H]
    quant = ctx_is_quantized(ctx_kv)
    # [B, L, d, r] per site — ids are round-constant, so XLA hoists the
    # gather out of the fori_loop wrapping this step in the fused round
    ag = _gather_adapters(params.get("adapters"), adapter_ids)

    # unrolled layers — see prefill_impl for why not lax.scan; a looped
    # stack's passes (_over_passes): weights by layer, the ring's and the
    # region's K/V by plane
    cdfs: list[jnp.ndarray] = []

    def one_pass(t, carry):
        h, ring, stay = carry
        for l in range(c.num_layers):
            lp = jax.tree.map(lambda x: x[l], params["layers"])

            def write_kv(k, v, l=t * c.num_layers + l):
                # one DUS per layer: [B, kvh, hd] -> ring[l, :, :, ring_pos]
                def put(r, x):
                    upd = x.transpose(1, 0, 2)[None, :, :, None, :]
                    return jax.lax.dynamic_update_slice(
                        r, upd.astype(r.dtype), (l, 0, 0, ring_pos, 0)
                    )

                return {"k": put(ring["k"], k), "v": put(ring["v"], v)}

            def attend(q, new_ring, l=t * c.num_layers + l):
                return ctx_decode_attention(
                    attn, q, ctx_kv["k"], ctx_kv["v"],
                    new_ring["k"], new_ring["v"], jnp.int32(l),
                    ctx_lens, ring_base,
                    ctx_k_scale=ctx_kv["k_scale"] if quant else None,
                    ctx_v_scale=ctx_kv["v_scale"] if quant else None,
                    live=live,
                )

            h, ring = _layer_body(c, lp, h, cos, sin, write_kv, attend,
                                  ffn_valid=live,
                                  ad=_adapter_layer(ag, l, per_row=True))
        if c.looped:
            h, stay = _step_end(c, params, h, stay)
            if stay is not None and t < c.loop_steps - 1:
                cdfs.append(1.0 - stay)     # the last pass's is 1
        return h, ring, stay

    stay = jnp.ones(h.shape[0], jnp.float32) if c.exit_gate else None
    h, ring, _ = _over_passes(c, one_pass, (h, ring, stay))
    logits = _logits(c, params, h)
    return ring, logits, jnp.stack(cdfs) if cdfs else None


decode_step = jax.jit(
    decode_step_impl, static_argnums=(0,), static_argnames=("attn",),
    donate_argnums=(3,),
)


@_hands_over
def round_step(config, params, ctx_kv, ring, stepped, tokens, ctx_lens,
               ring_base, s, live, adapter_ids, stats, *,
               attn: DecodeAttention):
    """One decode step of the engine's round, under the one signature
    every block has (the module doc): (ring, stepped, logits, stats). The
    dense decoder steps no leaf of the region and counts nothing:
    ``stepped`` and ``stats`` come back as they were given. A looped
    stack's row (``stats_layout``) is this step's: the passes a token ran
    and, a column a step before the last, the live lanes' mean exit CDF."""
    ring, logits, cdfs = _decode_step(
        config, params, ctx_kv, ring, tokens, ctx_lens, ring_base, s, live,
        adapter_ids, attn=attn)
    if config.looped:
        row = [jnp.int32(config.loop_steps)]
        if cdfs is not None:
            lanes = live.astype(jnp.float32)
            mean = (cdfs * lanes).sum(-1) / jnp.maximum(lanes.sum(), 1.0)
            row += list(jax.lax.bitcast_convert_type(
                mean[:stats.shape[0] - 1], jnp.int32))
        stats = jnp.stack(row)
    return ring, stepped, logits, stats


def flush_ctx_impl(
    ctx_kv: Cache,
    ring: Cache,
    dest: jnp.ndarray,       # [B] int32 — live: own lane; freed: scratch B
    ring_base: jnp.ndarray,  # [B] int32
    valid_len: jnp.ndarray,  # [B] int32 — #real tokens in the ring per slot
) -> Cache:
    """Write a full ring into the ctx region, once per round AFTER all of
    the round's reads. Ring entry (b, r) holds position ring_base[b]+r and
    goes to lane dest[b]; entries at or past valid_len[b], or past the
    region's end, leave the region's rows as they were, and freed slots
    (dest == B) write the scratch lane.

    Every row kind the ring holds (``k`` and ``v`` of [kvh, hd], or the
    latent block's one ``kv`` row) moves as an in-place SPAN a lane: slice
    the R rows the lane's entries cover out of the region, overlay the
    valid entries, write the span back, in a loop rolled over the lanes.
    A lane's R entries are contiguous in the region whatever the number of
    heads, so nothing else is touched (a scatter over (lane, position)
    would relayout the whole region: module header). A kind whose region
    rows are FEWER than the longest kind's is a MODULAR buffer a lane (the
    window layers' rows, models/ssm_moe.py: position p lives in slot p mod
    its length): its entries go in as two such spans, one where the ring's
    first position falls and one at slot 0 for what wraps.

    Quantized regions (ctx_is_quantized) instead requantize the minimal
    group-aligned WINDOW around each lane's ring span: gather old int8
    window + scales, dequantize, overlay the valid ring entries, fresh
    absmax scales for the groups the span overlaps (absmax over the
    lane's own prefix + the new entries — never stale suffix bytes), and
    scatter int8 + scales back. Still one fused pass inside the round
    program — zero extra dispatches."""
    if ctx_is_quantized(ctx_kv):
        return _flush_ctx_quant(ctx_kv, ring, dest, ring_base, valid_len)
    B, R = _any_row(ring).shape[2:4]
    S = max(ctx_kv[n].shape[3] for n in row_kinds(ring))
    i = jnp.arange(R, dtype=jnp.int32)

    def span(buf, src, b):
        # the span never runs off the region's end: it starts at most at
        # S - R and the entries are shifted inside it
        size = src.shape[:2] + (1, R) + src.shape[4:]
        start = jnp.clip(ring_base[b], 0, S - R)
        at = (0, 0, dest[b], start, 0)
        old = jax.lax.dynamic_slice(buf, at, size)
        new = jax.lax.dynamic_slice(src, (0, 0, b, 0, 0), size)
        entry = i - (ring_base[b] - start)
        ok = (entry >= 0) & (entry < valid_len[b])
        new = jnp.take(new, jnp.clip(entry, 0, R - 1), axis=3)
        new = jnp.where(ok[None, None, None, :, None],
                        new.astype(buf.dtype), old)
        return jax.lax.dynamic_update_slice(buf, new, at)

    def wrapped(t, bufs):
        # a modular buffer, two trips a lane (ONE read-modify-write a trip:
        # two of a buffer in one loop body make XLA:TPU relayout the whole
        # leaf around the loop): slot s takes ring entry (s - ring_base)
        # mod its length where that is a valid entry, first in the span
        # where the ring's first position falls, then in the one at slot 0
        # for what wraps
        b = t % B
        out = {}
        for n, buf in bufs.items():
            W = buf.shape[3]
            size = ring[n].shape[:2] + (1, R) + ring[n].shape[4:]
            new = jax.lax.dynamic_slice(ring[n], (0, 0, b, 0, 0), size)
            start = jnp.where(t < B, jnp.minimum(ring_base[b] % W, W - R), 0)
            at = (0, 0, dest[b], start, 0)
            old = jax.lax.dynamic_slice(buf, at, size)
            entry = (start + i - ring_base[b]) % W
            rows = jnp.take(new, jnp.clip(entry, 0, R - 1), axis=3)
            out[n] = jax.lax.dynamic_update_slice(
                buf, jnp.where(
                    (entry < valid_len[b])[None, None, None, :, None],
                    rows.astype(buf.dtype), old), at)
        return out

    def lane(b, bufs):
        return {n: span(buf, ring[n], b) for n, buf in bufs.items()}

    kinds = row_kinds(ring)
    out = jax.lax.fori_loop(
        0, B, lane, {n: ctx_kv[n] for n in kinds if ctx_kv[n].shape[3] == S})
    modular = {n: ctx_kv[n] for n in kinds if ctx_kv[n].shape[3] != S}
    if modular:
        out.update(jax.lax.fori_loop(0, 2 * B, wrapped, modular))
    return out


def _flush_ctx_quant(
    ctx_kv: Cache,
    ring: Cache,
    dest: jnp.ndarray,       # [B] i32 (freed slots -> scratch lane)
    ring_base: jnp.ndarray,  # [B] i32
    valid_len: jnp.ndarray,  # [B] i32
) -> Cache:
    """Ring flush into an int8 ctx region (see flush_ctx_impl doc)."""
    L, kvh, B, R, hd = ring["k"].shape
    lanes, S = ctx_kv["k"].shape[2], ctx_kv["k"].shape[3]
    r_idx = jnp.arange(R, dtype=jnp.int32)[None, :]            # [1, R]
    valid = ((r_idx < valid_len[:, None])
             & (ring_base[:, None] + r_idx < S))               # [B, R]
    g = ctx_group_size(ctx_kv)
    nG = S // g
    # window: enough group slots to hold a ring span at any alignment
    nW = min(-(-R // g) + 1, nG)
    W = nW * g
    base = jnp.clip(ring_base.astype(jnp.int32), 0, S)
    g0 = jnp.clip(base // g, 0, nG - nW)                       # [B]
    lane = jnp.clip(dest.astype(jnp.int32), 0, lanes - 1)      # [B]
    off = base - g0 * g                                        # [B]
    # where each ring entry lands inside its lane's window; invalid
    # entries (past valid_len / region end / vacated lanes) index W and
    # are DROPPED from the overlay rather than redirected
    w_of_r = jnp.where(
        valid, off[:, None] + jnp.arange(R, dtype=jnp.int32)[None, :], W
    )                                                          # [B, R]
    # absmax inputs: the lane's own prefix + the new valid entries; the
    # suffix beyond the span (stale bytes) never feeds a scale
    w_idx = jnp.arange(W, dtype=jnp.int32)[None, :]            # [1, W]
    span_end = off + jnp.clip(valid_len.astype(jnp.int32), 0, R)
    valid_w = w_idx < span_end[:, None]                        # [B, W]
    j = jnp.arange(nW, dtype=jnp.int32)[None, :]
    written = ((j + 1) * g > off[:, None]) & (j * g < span_end[:, None])
    written &= (valid_len > 0)[:, None]                        # [B, nW]
    # per-lane flat gather/scatter indices for the int8 window
    widx = (lane * S + g0 * g)[:, None] + jnp.arange(W)[None, :]
    widx_f = widx.reshape(-1)                                  # [B*W]
    gidx = g0[:, None] + j                                     # [B, nW]
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]            # [B, 1]

    out = {}
    for name in ("k", "v"):
        flat = ctx_kv[name].reshape(L, kvh, lanes * S, hd)
        win = flat[:, :, widx_f].reshape(L, kvh, B, W, hd)
        sw = ctx_kv[name + "_scale"][:, lane[:, None], gidx]   # [L, B, nW]
        wf = dequantize_groups(win, sw, g)
        overlay = ring[name].astype(jnp.float32)               # [L,kvh,B,R,hd]
        wf = wf.at[:, :, b_idx, w_of_r].set(overlay, mode="drop")
        q, s_new = requantize_groups(wf, sw, valid_w, written, g)
        # vacated lanes all alias the scratch lane: overlapping windows
        # write garbage over garbage (scratch is garbage by contract)
        flat = flat.at[:, :, widx_f].set(q.reshape(L, kvh, B * W, hd))
        out[name] = flat.reshape(L, kvh, lanes, S, hd)
        out[name + "_scale"] = ctx_kv[name + "_scale"].at[
            :, lane[:, None], gidx
        ].set(s_new)
    return out


flush_ctx = jax.jit(flush_ctx_impl, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# prefix-cache <-> context copies (admission / block seal)

def load_ctx_pages_impl(
    ctx_kv: Cache,
    cache: Cache,
    slot: jnp.ndarray,      # scalar int32 — destination lane
    page_ids: jnp.ndarray,  # [n] int32 — pow2-padded; padding = scratch 0
) -> Cache:
    """Copy a matched prefix run of pool pages into the slot's context
    region at [0, n*ps). The admission-side half of prefix reuse: padding
    pages write scratch-page garbage BEYOND the valid prefix (the engine
    passes q_start = real_blocks*ps, so garbage is never attended).

    The page list is pow2-padded by the caller, so n*ps can EXCEED the
    region length (e.g. 46 matched pages pad to 64 while the region holds
    52 — a dynamic_update_slice whose update outgrows the operand is a
    trace-time TypeError that kills the whole engine round). The load is
    clamped to the region statically: overflow pages are dropped, which
    is always safe because real matched runs fit the region by admission
    contract — only padding can overflow."""
    n = page_ids.shape[0]
    ps = _any_row(cache).shape[3]
    S = _any_row(ctx_kv).shape[3]
    usable = min(n, S // ps)
    if usable <= 0:
        return dict(ctx_kv)
    page_ids = page_ids[:usable]
    pool_q = cache_is_quantized(cache)
    ctx_q = ctx_is_quantized(ctx_kv)
    if ctx_q:
        # int8 ctx: the scale grids coincide (group == page_size by
        # init_ctx contract), so a quantized pool admits as a RAW int8
        # page copy + scale copy — no dequantize pass at all; the
        # decode kernel dequantizes per chunk in VMEM. A dense pool
        # (cross-mode peer) quantizes per page on the way in.
        g = ctx_group_size(ctx_kv)
        assert g == ps, (
            f"int8 ctx group ({g}) must equal pool page_size ({ps}) — "
            "init_ctx(group=page_size) is the engine contract"
        )
        out = dict(ctx_kv)
        for name in ("k", "v"):
            pages = cache[name][:, :, page_ids]  # [L, kvh, u, ps, hd]
            L, kvh, _, _, hd = pages.shape
            if pool_q:
                q = pages
                s = cache[name + "_scale"][:, page_ids]   # [L, u]
            else:
                pf = pages.astype(jnp.float32)
                s = jnp.maximum(
                    jnp.max(jnp.abs(pf), axis=(1, 3, 4)) / 127.0,
                    SCALE_EPS)
                q = jnp.clip(
                    jnp.round(pf / s[:, None, :, None, None]), -127, 127
                ).astype(jnp.int8)
            span = q.reshape(L, kvh, usable * ps, hd)
            out[name] = jax.lax.dynamic_update_slice(
                ctx_kv[name], span[:, :, None], (0, 0, slot, 0, 0)
            )
            out[name + "_scale"] = jax.lax.dynamic_update_slice(
                ctx_kv[name + "_scale"], s[:, None], (0, slot, 0)
            )
        return out
    # (a region's recurrent leaves are no rows, and a window layer's
    # modular buffer is no kind the pool holds: they pass through)
    held = row_kinds(cache)
    out = {n: ctx_kv[n] for n in ctx_kv if n not in held}
    for name in held:
        pages = cache[name][:, :, page_ids]      # [L, kvh, usable, ps, hd]
        if pool_q:
            # fused dequant: int8 pages * per-(layer, page) scale, in the
            # same admission-copy program — never a separate dispatch
            s = cache[name + "_scale"][:, page_ids]       # [L, usable]
            pages = (pages.astype(jnp.float32)
                     * s[:, None, :, None, None])
        L, kvh, _, rows, hd = pages.shape     # ps rows a page, or ps /
        span = pages.reshape(L, kvh, usable * rows, hd)  # stride of them
        out[name] = jax.lax.dynamic_update_slice(
            ctx_kv[name], span[:, :, None].astype(ctx_kv[name].dtype),
            (0, 0, slot, 0, 0),
        )
    return out


load_ctx_pages = jax.jit(load_ctx_pages_impl, donate_argnums=(0,))


def write_ctx_span_impl(
    ctx_kv: Cache,
    slot: jnp.ndarray,  # scalar int32
    kv: Cache,          # {"k","v"}: [L, kvh, T, hd] (e.g. sp_prefill output)
) -> Cache:
    """Write a whole computed KV span into a slot's region at [0, T) —
    how sp_prefill's ring-computed prompt KV enters the serving context
    (GSPMD gathers the sp-sharded span into the replicated region).
    Int8 ctx quantizes on store (fresh absmax scales for the covered
    groups — same grid as the in-round writes)."""
    _dense_only(ctx_kv, "sequence-parallel prefill (write_ctx_span)")
    if ctx_is_quantized(ctx_kv):
        out = dict(ctx_kv)
        g = ctx_group_size(ctx_kv)
        T = kv["k"].shape[2]
        zero = jnp.int32(0)
        for name in ("k", "v"):
            out[name], out[name + "_scale"] = _quant_store_span(
                ctx_kv[name], ctx_kv[name + "_scale"], slot, zero,
                kv[name], g, valid_t=jnp.int32(T),
            )
        return out
    out = {}
    for name in ("k", "v"):
        upd = kv[name][:, :, None]  # [L, kvh, 1, T, hd]
        out[name] = jax.lax.dynamic_update_slice(
            ctx_kv[name], upd.astype(ctx_kv[name].dtype),
            (0, 0, slot, 0, 0),
        )
    return out


write_ctx_span = jax.jit(write_ctx_span_impl, donate_argnums=(0,))


def seal_blocks_impl(
    cache: Cache,
    ctx_kv: Cache,
    slots: jnp.ndarray,   # [n] int32 — source lanes (pow2-padded)
    starts: jnp.ndarray,  # [n] int32 — block start positions
    pages: jnp.ndarray,   # [n] int32 — destination pool pages
                          # (padding entries -> scratch page 0)
    page_size: int,
) -> Cache:
    """Copy sealed blocks ctx->pool (the storage half of commit). Each
    entry copies ctx_kv[:, :, slots[i], starts[i]:+ps] into pool page
    pages[i]. Padding rows target scratch page 0 (garbage by contract).

    An unquantised region seals into an unquantised pool as in-place span
    writes, one ``dynamic_slice`` of the region and one
    ``dynamic_update_slice`` of the pool an entry and row kind, in a loop
    rolled over the entries: a block's rows are contiguous in both (a
    gather over a flat view would copy the whole region: module header).

    Quantized pools (cache_is_quantized) quantize in ONE fused gather:
    per-(layer, page) absmax scales over the block's [kvh, ps, hd]
    elements, int8 payload + scale scattered together. When the ctx
    region is int8 too (same group == page_size grid) the seal
    degenerates to a RAW int8 copy: blocks and their scales move
    verbatim, no requantize pass at the boundary at all."""
    ps = page_size
    pool_q = cache_is_quantized(cache)
    ctx_q = ctx_is_quantized(ctx_kv)
    if not (pool_q or ctx_q):
        def block(pool, src, i, stride):
            # a kind with one row every ``stride`` positions moves its
            # ``ps / stride`` rows of the block
            lane, start = slots[i], starts[i]
            if stride != 1:
                start = start // stride
            rows = jax.lax.dynamic_slice(
                src, (0, 0, lane, start, 0),
                src.shape[:2] + (1, ps // stride) + src.shape[4:])
            return jax.lax.dynamic_update_slice(
                pool, rows.astype(pool.dtype), (0, 0, pages[i], 0, 0))

        def one(i, pools):
            return {n: block(pool, ctx_kv[n], i, _row_stride(ctx_kv, n))
                    for n, pool in pools.items()}

        return jax.lax.fori_loop(
            0, slots.shape[0], one, {n: cache[n] for n in row_kinds(cache)})
    if ctx_q:
        g = ctx_group_size(ctx_kv)
        assert g == ps, (
            f"int8 ctx group ({g}) must equal pool page_size ({ps})"
        )
    out = {}
    for name in ("k", "v"):
        # ONE gather over the (lane, position)-flattened axis (a
        # vmap(dynamic_index + dynamic_slice) materialized the full
        # [L, kvh, S, hd] LANE per entry before slicing)
        src = ctx_kv[name]
        L, kvh, lanes, S, hd = src.shape
        flat = src.reshape(L, kvh, lanes * S, hd)
        idx = (slots * S + starts)[:, None] + jnp.arange(ps)[None, :]
        blocks = flat[:, :, idx]                 # [L, kvh, n, ps, hd]
        if ctx_q:
            # blocks are already int8; their ctx scales are page-aligned
            # (starts are block starts, ps == group), so the pool entry
            # is the ctx entry moved verbatim
            sc = ctx_kv[name + "_scale"][
                :, slots, starts // ps
            ]                                    # [L, n]
            if pool_q:
                out[name] = cache[name].at[:, :, pages].set(blocks)
                out[name + "_scale"] = (
                    cache[name + "_scale"].at[:, pages].set(sc)
                )
            else:
                # cross-mode pool (dense): dequantize the blocks in the
                # same fused gather before the dense scatter
                dense = (blocks.astype(jnp.float32)
                         * sc[:, None, :, None, None])
                out[name] = cache[name].at[:, :, pages].set(
                    dense.astype(cache[name].dtype))
            continue
        # dense region, int8 pool: quantize a page on the way in
        bf = blocks.astype(jnp.float32)
        s = jnp.max(jnp.abs(bf), axis=(1, 3, 4)) / 127.0   # [L, n]
        s = jnp.maximum(s, 1e-8)
        q = jnp.clip(
            jnp.round(bf / s[:, None, :, None, None]), -127, 127
        ).astype(jnp.int8)
        out[name] = cache[name].at[:, :, pages].set(q)
        out[name + "_scale"] = (
            cache[name + "_scale"].at[:, pages].set(s)
        )
    return out


seal_blocks = jax.jit(
    seal_blocks_impl, static_argnames=("page_size",), donate_argnums=(0,)
)


# ---------------------------------------------------------------------------
# Sequence-parallel (ring) prefill — long-context path (SURVEY §2.5 SP
# row / §7.11: the reference has no sequence parallelism; this is the
# TPU-native long-context answer). The prompt is sharded over the `sp`
# mesh axis; every layer's attention runs as ring attention (KV blocks
# rotate over ICI via ppermute) so per-device memory is O(T/sp).

def sp_prefill(
    config: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,    # [T] int32, sp-sharded, T % sp == 0
    seq_len: jnp.ndarray,   # scalar int32 — valid length
    mesh: Mesh,
    axis: str = "sp",
) -> tuple[Cache, jnp.ndarray]:
    """Returns (kv, logits[vocab]) where kv = {"k","v"}: [L, kvh, T, hd]
    sp-sharded on the T axis (callers page/commit it as needed) and the
    logits are for position seq_len-1. Weights are replicated over sp;
    only KV blocks move (one ICI hop per ring step)."""
    from dynamo_tpu.ops.ring_attention import ring_attention

    c = config
    _dense_only(c, "sequence-parallel prefill (sp_prefill)")
    _no_loop(c, "sequence-parallel prefill (sp_prefill)")
    T = int(tokens.shape[0])
    inv_freq = jnp.asarray(
        rope_inv_freq(c.head_dim, c.rope_theta, c.rope_scaling_dict)
    )
    positions = jnp.arange(T, dtype=jnp.int32)
    cos, sin = rope_cos_sin(positions, inv_freq)
    h = _embed_rows(params, tokens, jnp.dtype(c.dtype))

    ks, vs = [], []
    rep = c.num_heads // c.num_kv_heads
    for l in range(c.num_layers):
        lp = jax.tree.map(lambda x: x[l], params["layers"])

        def write_kv(k, v):
            ks.append(k)
            vs.append(v)
            return (k, v)

        def attend(q, kv):
            k, v = kv
            return ring_attention(
                q, jnp.repeat(k, rep, axis=1),
                jnp.repeat(v, rep, axis=1), mesh, axis,
            )

        h, _ = _layer_body(c, lp, h, cos, sin, write_kv, attend)

    logits = _logits(c, params, h[seq_len - 1])
    kv = {
        "k": jnp.stack(ks).transpose(0, 2, 1, 3),  # [L, kvh, T, hd]
        "v": jnp.stack(vs).transpose(0, 2, 1, 3),
    }
    return kv, logits


# ---------------------------------------------------------------------------
# Encoder path (embeddings API): full self-attention over the prompt with
# no KV cache — the /v1/embeddings endpoint pools the final hidden states
# (reference protocols/openai embeddings surface; the reference delegates
# embedding models to its engines)

def encode_impl(
    config: ModelConfig,
    params: Params,
    tokens: jnp.ndarray,   # [T] int32, padded
    seq_len: jnp.ndarray,  # scalar int32: valid length
) -> jnp.ndarray:
    """Mean-pooled, L2-normalized final hidden state [H] over the valid
    tokens. Cache-free causal attention (prompt-sized, one shot)."""
    c = config
    _dense_only(c, "embeddings (encode)")
    _no_loop(c, "embeddings (encode)")
    T = tokens.shape[0]
    inv_freq = jnp.asarray(
        rope_inv_freq(c.head_dim, c.rope_theta, c.rope_scaling_dict)
    )
    positions = jnp.arange(T, dtype=jnp.int32)
    cos, sin = rope_cos_sin(positions, inv_freq)
    h = _embed_rows(params, tokens, jnp.dtype(c.dtype))
    valid = positions < seq_len                                   # [T]
    causal = (positions[None, :] <= positions[:, None]) & valid[None, :]

    def attend(q, kv):
        k, v = kv
        # GQA: repeat kv heads to match q heads
        rep = c.num_heads // c.num_kv_heads
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(c.head_dim)
        scores = jnp.where(causal[None], scores.astype(jnp.float32),
                           -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", w, v)

    for l in range(c.num_layers):
        lp = jax.tree.map(lambda x: x[l], params["layers"])
        h, _ = _layer_body(
            c, lp, h, cos, sin,
            write_kv=lambda k, v: (k, v),
            attend=attend,
        )
    h = rms_norm(h, params["norm_f"], c.rms_norm_eps)
    maskf = valid.astype(jnp.float32)[:, None]
    pooled = (h.astype(jnp.float32) * maskf).sum(0) / jnp.maximum(
        maskf.sum(), 1.0
    )
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


encode = jax.jit(encode_impl, static_argnums=(0,))


# ---------------------------------------------------------------------------
# KV page export/import (the block-transfer data plane's device ops;
# reference analogue: NIXL block read/write, block_manager/block/transfer.rs)

def gather_pages_impl(cache: Cache, page_ids: jnp.ndarray) -> jnp.ndarray:
    """Pull whole pages out of the pool: [2, L, kvh, n, ps, hd] (k then v).
    Callers bucket n to a pow2 (padding with scratch page 0) to bound
    recompiles; the host slices the padding off after fetch."""
    _dense_only(cache, "KV page transfer / offload tiers (gather_pages)")
    return jnp.stack(
        [cache["k"][:, :, page_ids], cache["v"][:, :, page_ids]]
    )


def scatter_pages_impl(
    cache: Cache, page_ids: jnp.ndarray, data: jnp.ndarray
) -> Cache:
    """Write whole pages into the pool (inverse of gather_pages). Padding
    entries must point at scratch page 0 — it is garbage by contract."""
    _dense_only(cache, "KV page transfer / offload tiers (scatter_pages)")
    return {
        "k": cache["k"].at[:, :, page_ids].set(data[0].astype(cache["k"].dtype)),
        "v": cache["v"].at[:, :, page_ids].set(data[1].astype(cache["v"].dtype)),
    }


def gather_pages_q_impl(
    cache: Cache, page_ids: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """gather_pages for a quantized pool: (int8 pages [2, L, kvh, n, ps,
    hd], scales [2, L, n]) — the int8 payload plus its scale sidecar is
    what every downstream tier/transfer consumer moves."""
    data = jnp.stack(
        [cache["k"][:, :, page_ids], cache["v"][:, :, page_ids]]
    )
    scales = jnp.stack(
        [cache["k_scale"][:, page_ids], cache["v_scale"][:, page_ids]]
    )
    return data, scales


def scatter_pages_q_impl(
    cache: Cache, page_ids: jnp.ndarray,
    data: jnp.ndarray, scales: jnp.ndarray,
) -> Cache:
    """Inverse of gather_pages_q: int8 pages + scales into the pool."""
    return {
        "k": cache["k"].at[:, :, page_ids].set(data[0]),
        "v": cache["v"].at[:, :, page_ids].set(data[1]),
        "k_scale": cache["k_scale"].at[:, page_ids].set(scales[0]),
        "v_scale": cache["v_scale"].at[:, page_ids].set(scales[1]),
    }


gather_pages = jax.jit(gather_pages_impl)
scatter_pages = jax.jit(scatter_pages_impl, donate_argnums=(0,))
gather_pages_q = jax.jit(gather_pages_q_impl)
scatter_pages_q = jax.jit(scatter_pages_q_impl, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# HF weight loading

_HF_LAYER_MAP = {
    "input_layernorm.weight": ("ln1", False),
    "post_attention_layernorm.weight": ("ln2", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "mlp.gate_proj.weight": ("wg", True),
    "mlp.up_proj.weight": ("wu", True),
    "mlp.down_proj.weight": ("wd", True),
}


def params_from_state_dict(
    config: ModelConfig, raw: dict[str, jnp.ndarray], dtype=None
) -> Params:
    """Build our param pytree from HF-named tensors (torch state_dict names).

    Torch linear weights are [out, in]; ours are [in, out] — transposed here.
    Per-layer tensors are stacked on the leading layer axis.
    """
    dtype = jnp.dtype(config.dtype) if dtype is None else jnp.dtype(dtype)
    L = config.num_layers
    layers: dict[str, list] = {k: [None] * L for (k, _) in _HF_LAYER_MAP.values()}
    for hf_suffix, (ours, transpose) in _HF_LAYER_MAP.items():
        for l in range(L):
            t = jnp.asarray(raw[f"model.layers.{l}.{hf_suffix}"])
            layers[ours][l] = t.T if transpose else t

    params: Params = {
        "embed": jnp.asarray(raw["model.embed_tokens.weight"], dtype),
        "layers": {
            k: jnp.stack(v).astype(dtype) for k, v in layers.items()
        },
        "norm_f": jnp.asarray(raw["model.norm.weight"], dtype),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(raw["lm_head.weight"]).T.astype(dtype)
    return params


def load_hf_params(
    config: ModelConfig, model_dir: str, dtype=None, shardings: Params | None = None
) -> Params:
    """Load llama safetensors weights from a local HF model directory.

    Tensors are read and stacked on the host CPU (never staged through an
    accelerator); with `shardings` each stacked leaf is device_put straight
    to its target sharding, so peak accelerator memory is one sharded copy.
    """
    import glob
    import os

    from safetensors import safe_open

    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no safetensors in {model_dir}")
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        raw: dict[str, jnp.ndarray] = {}
        for fp in files:
            with safe_open(fp, framework="flax") as f:
                for name in f.keys():
                    raw[name] = f.get_tensor(name)
        params = params_from_state_dict(config, raw, dtype)
        del raw
        if config.quant == "int8":
            # quantize on the host: the dense 8B never touches the chip
            params = quantize_params(params)
    if shardings is not None:
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, shardings
        )
    return params
