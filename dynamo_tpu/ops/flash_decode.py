"""Pallas TPU kernel: flash decode attention over CONTIGUOUS per-slot KV
plus a small per-round write ring, for the lanes of a WORK LIST.

What the file is. Decode context lives in a contiguous per-slot region
``ctx_kv [L, kvh, B+1, S, hd]`` (the paged pool is prefix-cache
*storage*; the engine copies pages in and out at admission and seal), and
a step writes its K and V to a tiny per-slot RING ``[L, kvh, B, R, hd]``
that the engine flushes into the region once a round, AFTER all reads,
where the update aliases in place (a scatter into the multi-GB region
beside the kernel's reads made XLA copy it: 119 ms a step, round 4).
Attention therefore streams big dense blocks and never gathers.

ONE call a layer (PR 53) walks a flat list of work items built by the
caller (``ops/attention.py: ctx_decode_attention``): for every lane that
holds a request, its region chunks of ``DEFAULT_CHUNK`` rows, ascending,
then its ring, lane after lane. The list rides in as scalar prefetch and
its LENGTH, a traced value, is the grid's bound (the pattern of
``ops/kda.py: step_pallas``): a grid step is one item, every block's index
map reads the item's lane and chunk, so Mosaic's own pipeline fetches the
next item's ``[kvh, chunk, hd]`` K and V blocks, of this lane or the next
one, while this one is scored. The running max / denominator /
accumulator are the lane's being walked; its ring item (which names the
chunk before it again, so nothing is fetched) normalises and writes the
lane's output block. A lane that is not on the list costs nothing, not
even an empty grid step, and its output row is never written (the caller
makes it 0; a freed lane's device length keeps counting up: the kernel
never sees it). The cost follows the live lanes' own chunks, not lanes x
the region's capacity.

Why (chip numbers: PERF.md section 6, PR 53). Until PR 53 the grid was
``(B, S / chunk + 1)``: 72 steps a layer at 8 lanes of 4096 rows, 864 at
96, whatever was live, each ~0.2 us when it did nothing, and every lane's
first chunk was DMA'd because a block index cannot be "none".
On the v5e (``tools/latent_decode_bench.py --kind dense``, a layer-call,
old -> this): 8 lanes x 8 K/V heads with 2 live at 300 rows 44 -> 10 us
(0 live: 5; all 8 live: 34), at 3000 rows 153 -> 38 (all live 158 ->
157: never slower); a 2-head shard of 16 lanes, 13 live, 47 -> 23; 96
lanes x 1 head, 48 live, 249 -> 87; 16 lanes of 32768 rows, 5 live at
6000, 348 -> 67. About 2 us an item at 1-2 MB and 5 us fixed. In the
chat cell the kernel fell from 0.40 to 0.10 s of a 3 s span and the
decode step from 11.77 to 10.46 ms. A live lane's output differs from the
old grid's by at most one last bit of bfloat16 where a shard holds more
than one K/V head (the same ``accumulate``; Mosaic schedules it anew).
A first form of the list kernel left K and V in HBM (``memory_space=ANY``)
and double-buffered the blocks by hand, as ``ops/latent_decode.py`` does
for 640-wide rows: Mosaic slices no minor dim below its 128-wide tiling
in a hand-made DMA, so a head of 64 could not compile. Blocks fetched by
the pipeline can hold any head size.

Partitioning: GSPMD cannot split a Mosaic call, so under a mesh the
caller maps this function per shard over ``tp`` (``ops/attention.py``):
heads are independent, each shard walks the same (replicated) list over
its own kv heads and needs no collective.

Int8 ctx (scales given): the int8 payload widens exactly to the compute
dtype in VMEM and the per-group f32 scales multiply the score /
probability COLUMNS (q.(s k) == s (q.k)), never the [chunk, hd] tiles —
Mosaic refuses the sublane-splitting reshape a tile-wise dequant needs
("infer-vector-layout: unsupported shape cast"). The layer's scales ride
in as one VMEM block ``[1, lanes, n_chunks, chunk/group]`` (a block's last
two dims must be (8, 128)-divisible or equal to the array's, and a DMA
cannot slice a minor dim below its tiling, so an item cannot fetch its
own 32 bytes); an item reads its lane's and chunk's row of it.

Position semantics: ctx_kv[l, :, b, p] holds position p of slot b, valid
while p < ring_base[b]; ring[l, :, b, r] holds position ring_base[b]+r,
valid while < ctx_lens[b] (the current token INCLUDED — the decode step
writes its KV to the ring before attending).

This replaces what vLLM's paged-attention CUDA kernel does for the
reference (SURVEY.md §7 "Paged attention on TPU" hard part); paging moved
out of the per-step critical path entirely.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

NEG_INF = -1e30

# region rows a work item holds. benchmarks/peaks.py counts a live lane's
# context in whole chunks of this many rows
DEFAULT_CHUNK = 512

# chunk floor for the divisor fallback: below this an item's fixed cost
# (its DMAs' latency, the loop's bookkeeping) outweighs its rows
CHUNK_FLOOR = 128

_chunk_warned: set = set()


def _pick_chunk(S: int, want: int, step: int = 1) -> int:
    """Chunk size for a context of S positions: ``want`` itself when it
    already tiles S (and is a multiple of ``step`` — the int8 scale
    group), else the largest divisor of S ≤ want that is a multiple of
    step, promoted to the smallest divisor ≥ CHUNK_FLOOR if the best
    candidate falls below it. The old ``gcd(want, S)`` fallback could
    silently pick a tiny divisor (S=520 → chunk 8 → 66 items a lane);
    log once per config when the request is adjusted."""
    want = max(1, min(want, S))
    if S % want == 0 and want % step == 0:
        return want
    divs = [d for d in range(1, S + 1) if S % d == 0 and d % step == 0]
    below = [d for d in divs if d <= want]
    best = max(below) if below else min(divs)
    floor = min(CHUNK_FLOOR, S)
    if best < floor:
        above = [d for d in divs if d >= floor]
        if above:
            best = min(above)
    key = (S, want, step)
    if key not in _chunk_warned:
        _chunk_warned.add(key)
        logger.info(
            "flash_decode: chunk %d does not tile S=%d (group %d); "
            "using %d", want, S, step, best,
        )
    return best


def chunk_rows(S: int, chunk: int = 0, group: int = 1) -> int:
    """Region rows one work item holds: ``DEFAULT_CHUNK`` (or the one a
    ``DecodeAttention`` names), fitted to a region of S rows and to whole
    scale groups of an int8 region (``_pick_chunk``)."""
    return _pick_chunk(S, chunk if chunk > 0 else DEFAULT_CHUNK, group)


def _kernel(
    # scalar prefetch
    layer_ref,   # [1] i32
    total_ref,   # [1] i32 — work items: every live lane's chunks + 1
    lane_ref,    # [W] i32 — work item -> lane
    chunk_ref,   # [W] i32 — work item -> chunk of that lane; n_chunks =
                 #           the lane's ring, its last item
    fetch_ref,   # [W] i32 — work item -> the chunk its K / V blocks hold
    ctx_sm,      # [B] i32
    base_sm,     # [B] i32 — ring base positions
    # blocks, the item's
    q_ref,       # [1, nkv, G, HD]
    k_ref,       # [1, nkv, 1, CHUNK, HD] — int8 when quantized
    v_ref,
    # quantized only: ksc_ref/vsc_ref [1, B(+1), n_chunks, CHUNK//group]
    # f32, the layer's; then:
    # rk_ref,    # [1, nkv, 1, R, HD]   the lane's ring (compute dtype)
    # rv_ref,
    # o_ref,     # [1, nkv, G, HD]
    # scratch:
    # m_ref,     # [nkv, G, 128] f32 running max of the lane being walked
    # l_ref,     # [nkv, G, 128] f32 running denom
    # acc_ref,   # [nkv, G, HD] f32 running numerator
    *refs,
    scale: float,
    chunk: int,
    n_chunks: int,
    quantized: bool,
    window: int = 0,
):
    if quantized:
        ksc_ref, vsc_ref = refs[:2]
        refs = refs[2:]
    rk_ref, rv_ref, o_ref, m_ref, l_ref, acc_ref = refs
    w = pl.program_id(0)
    lane, j = lane_ref[w], chunk_ref[w]
    ctx, base = ctx_sm[lane], base_sm[lane]
    # an empty list still runs one grid step: it does nothing
    listed = total_ref[0] > 0

    def reset():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(w == 0)
    def _():
        reset()

    def accumulate(k, v, start, limit, length, k_sc=None, v_sc=None,
                   wrap=None, lower=None):
        # k/v [nkv, length, HD]; positions start + iota valid below limit
        # (under a window: at or above ``lower`` too, and a region row's
        # position is the last one below ``wrap`` + 1 that its slot holds).
        # k_sc/v_sc [1, length] f32: per-position dequant scales of an
        # int8 chunk, applied to the score / probability COLUMNS —
        # q.(s_c k_c) == s_c (q.k_c) and sum_c p_c (s_c v_c) ==
        # sum_c (p_c s_c) v_c — so the [nkv, length, HD] tiles are never
        # rescaled element-wise (and never reshaped: Mosaic's layout
        # inference refuses the sublane-splitting cast that needs)
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, length), 2)
        if wrap is not None:
            pos = wrap - ((wrap - pos) & (n_chunks * chunk - 1))
        valid = pos < limit
        if lower is not None:
            valid = valid & (pos >= lower)
        q = q_ref[0]                                       # [nkv, G, HD]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                          # [nkv, G, length]
        if k_sc is not None:
            s = s * k_sc[None]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :, :1]
        row_max = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :, :1] * alpha + jnp.sum(
            p, axis=2, keepdims=True)
        if v_sc is not None:
            p = p * v_sc[None]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def per_position(sc):
        # [1, chunk//grp] group scales -> [1, chunk] along the lane axis:
        # position c takes sc[c // grp] (ascending overwrite, one compare
        # per group — no integer divide, no lane gather)
        grp = chunk // sc.shape[1]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        out = jnp.broadcast_to(sc[:, :1], (1, chunk))
        for g in range(1, sc.shape[1]):
            out = jnp.where(pos >= g * grp, sc[:, g:g + 1], out)
        return out

    # ctx chunk: positions [j*chunk, +chunk), valid below ring_base
    @pl.when(jnp.logical_and(listed, j < n_chunks))
    def _():
        k, v = k_ref[0, :, 0], v_ref[0, :, 0]      # [nkv, chunk, HD]
        k_sc = v_sc = None
        if quantized:
            # dequantize in VMEM, right after the DMA: the HBM stream
            # was the int8 bytes. The payload widens to the compute
            # dtype exactly (|int8| <= 127 fits bf16's mantissa); the
            # f32 scales ride the score columns
            k = k.astype(jnp.float32).astype(q_ref.dtype)
            v = v.astype(jnp.float32).astype(q_ref.dtype)
            k_sc = per_position(ksc_ref[0, lane, pl.ds(j, 1), :])
            v_sc = per_position(vsc_ref[0, lane, pl.ds(j, 1), :])
        if window:
            # the region is a lane's MODULAR buffer of its last rows
            # (position p in slot p mod its length): a slot holds the last
            # position below the ring base that falls on it, read where
            # that is inside the window
            accumulate(k, v, j * chunk, jnp.minimum(base, ctx), chunk,
                       wrap=base - 1, lower=jnp.maximum(ctx - window, 0))
        else:
            accumulate(k, v, j * chunk, jnp.minimum(base, ctx), chunk,
                       k_sc, v_sc)

    # the lane's last item, its ring: slot r holds position base + r,
    # valid below ctx
    @pl.when(jnp.logical_and(listed, j == n_chunks))
    def _():
        accumulate(rk_ref[0, :, 0], rv_ref[0, :, 0], base, ctx,
                   rk_ref.shape[3],
                   lower=ctx - window if window else None)
        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        reset()


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "window", "name"))
def flash_decode_attention(
    q: jnp.ndarray,          # [B, n_heads, HD]
    ctx_k: jnp.ndarray,      # [L, kvh, B(+1), S, HD] contiguous per-slot KV
    ctx_v: jnp.ndarray,
    ring_k: jnp.ndarray,     # [L, kvh, B, R, HD] current-round writes
    ring_v: jnp.ndarray,
    layer: jnp.ndarray,      # scalar i32
    ctx_lens: jnp.ndarray,   # [B] i32 — context length INCL. current token
    ring_base: jnp.ndarray,  # [B] i32 — position held by ring slot 0
    work: tuple,             # the work list: (lane_of, chunk_of, fetch_of,
                             # total)
    chunk: int = 0,
    interpret: bool = False,
    ctx_k_scale: jnp.ndarray | None = None,  # f32 [L, B(+1), S//group]
    ctx_v_scale: jnp.ndarray | None = None,  # (int8 ctx_k/ctx_v)
    window: int = 0,         # > 0: a query reads the ``window`` positions
                             # up to its own, and the region is a MODULAR
                             # buffer a lane (S a power of two >= window -
                             # 1: position p in slot p mod S)
    name: str = "flash_decode_attention",   # the Mosaic call's, in a trace
) -> jnp.ndarray:
    """Flash decode attention over contiguous KV + ring, for the lanes of
    a work list. Returns [B, n_heads, HD]: a listed lane's attention; the
    row of a lane that is not on the list is NEVER WRITTEN (the caller,
    ``ops/attention.py: ctx_decode_attention``, which also builds the
    list, makes it 0). The current token's KV must already be in the ring
    (position ctx-1 == ring_base + r for the step's ring slot r).
    ``chunk`` is the chunk the list was built for. With ctx scales given,
    ctx_k/ctx_v are int8 and each chunk dequantizes in VMEM after its DMA
    (half the live-context HBM bytes)."""
    B, n_heads, hd = q.shape
    L, nkv, _, S, _ = ctx_k.shape
    R = ring_k.shape[3]
    g = n_heads // nkv
    quantized = ctx_k_scale is not None
    if window and (quantized or S & (S - 1) or S < window - 1):
        raise ValueError(
            f"a window of {window} over a buffer of {S} rows a lane: the "
            "buffer holds a power of two of rows, the window less one at "
            "least, unquantised")
    # chunk must tile S exactly (and whole scale groups when quantized)
    group = S // ctx_k_scale.shape[2] if quantized else 1
    chunk = chunk_rows(S, chunk, group)
    n_chunks = S // chunk
    lane_of, chunk_of, fetch_of, total = work
    if lane_of.shape[0] != B * (n_chunks + 1):
        raise ValueError(
            f"a work list of {lane_of.shape[0]} items was not built for "
            f"{B} lanes of {n_chunks} chunks and a ring")
    scale = float(1.0 / (hd ** 0.5))
    i32 = jnp.int32

    def of_lane(w, layer, total, lane_of, *_):
        return (lane_of[w], 0, 0, 0)

    def kv_map(w, layer, total, lane_of, chunk_of, fetch_of, *_):
        # a ring item names the chunk before it again: no DMA
        return (layer[0], 0, lane_of[w], fetch_of[w], 0)

    def sc_map(w, layer, *_):
        return (layer[0], 0, 0, 0)

    def ring_map(w, layer, total, lane_of, *_):
        return (layer[0], 0, lane_of[w], 0, 0)

    in_specs = [
        pl.BlockSpec((1, nkv, g, hd), of_lane),
        pl.BlockSpec((1, nkv, 1, chunk, hd), kv_map),
        pl.BlockSpec((1, nkv, 1, chunk, hd), kv_map),
    ]
    inputs = [q.reshape(B, nkv, g, hd), ctx_k, ctx_v]
    if quantized:
        # a block's last two dims must be (8, 128)-divisible or equal to
        # the array's: the layer's scales, viewed [lanes, n_chunks,
        # chunk/group], ride in once a call and an item reads its lane's
        # and chunk's row
        ngc = chunk // group
        lanes = ctx_k_scale.shape[1]
        in_specs += [pl.BlockSpec((1, lanes, n_chunks, ngc), sc_map)] * 2
        inputs += [ctx_k_scale.reshape(L, lanes, n_chunks, ngc),
                   ctx_v_scale.reshape(L, lanes, n_chunks, ngc)]
    in_specs += [pl.BlockSpec((1, nkv, 1, R, hd), ring_map)] * 2
    inputs += [ring_k, ring_v]

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, chunk=chunk, n_chunks=n_chunks,
            quantized=quantized, window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            # the list's length, a traced value, is the grid's bound: an
            # item past the list costs nothing, not even an empty step
            # (never an empty grid: what a pipeline with no step writes
            # back is nobody's promise)
            grid=(jnp.maximum(total[0], 1),),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, nkv, g, hd), of_lane),
            scratch_shapes=[
                pltpu.VMEM((nkv, g, 128), jnp.float32),
                pltpu.VMEM((nkv, g, 128), jnp.float32),
                pltpu.VMEM((nkv, g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # generous scoped-vmem budget for the chunked block pipeline
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name=name,
    )(
        jnp.asarray(layer, i32).reshape(1), total.astype(i32).reshape(1),
        lane_of.astype(i32), chunk_of.astype(i32), fetch_of.astype(i32),
        ctx_lens.astype(i32), ring_base.astype(i32), *inputs,
    )
    return out.reshape(B, n_heads, hd)


def flash_decode_attention_reference(
    q: jnp.ndarray,
    ctx_k: jnp.ndarray,
    ctx_v: jnp.ndarray,
    ring_k: jnp.ndarray,
    ring_v: jnp.ndarray,
    layer: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    ring_base: jnp.ndarray,
    ctx_k_scale: jnp.ndarray | None = None,
    ctx_v_scale: jnp.ndarray | None = None,
    live: jnp.ndarray | None = None,   # [B] bool; None = every lane
    window: int = 0,                   # as the kernel's
) -> jnp.ndarray:
    """Pure-jnp equivalent (CPU tests / kernel parity checks). With ctx
    scales given, ctx_k/ctx_v are int8 per-group quantized — dequantize
    them to the query dtype first (matching the kernel's in-VMEM
    dequant, so parity tests cover the quantized math too). A lane that
    is not ``live`` comes back 0, as from the kernel; every lane's whole
    region is scored all the same."""
    B, n_heads, hd = q.shape
    L, nkv, _, S, _ = ctx_k.shape
    R = ring_k.shape[3]
    n_rep = n_heads // nkv
    kl, vl = ctx_k[layer][:, :B], ctx_v[layer][:, :B]  # [nkv, B, S, hd]
    if ctx_k_scale is not None:
        g = S // ctx_k_scale.shape[2]
        ks = jnp.repeat(ctx_k_scale[layer][:B], g, axis=1)  # [B, S]
        vs = jnp.repeat(ctx_v_scale[layer][:B], g, axis=1)
        kl = (kl.astype(jnp.float32) * ks[None, :, :, None]
              ).astype(q.dtype)
        vl = (vl.astype(jnp.float32) * vs[None, :, :, None]
              ).astype(q.dtype)
    k = jnp.repeat(kl, n_rep, axis=0)                   # [nh, B, S, hd]
    v = jnp.repeat(vl, n_rep, axis=0)
    rk = jnp.repeat(ring_k[layer], n_rep, axis=0)       # [nh, B, R, hd]
    rv = jnp.repeat(ring_v[layer], n_rep, axis=0)
    k = jnp.concatenate([k, rk], axis=2)                # [nh, B, S+R, hd]
    v = jnp.concatenate([v, rv], axis=2)
    scores = jnp.einsum(
        "bnh,nbsh->bns", q, k, preferred_element_type=jnp.float32
    ) / (hd ** 0.5)
    ctx_pos = jnp.arange(S)[None, :]                    # [1, S]
    if window:   # a slot's position: the last below the ring base on it
        last = ring_base[:, None] - 1
        ctx_pos = last - ((last - ctx_pos) & (S - 1))
    ctx_ok = ctx_pos < jnp.minimum(ring_base, ctx_lens)[:, None]
    ring_pos = ring_base[:, None] + jnp.arange(R)[None, :]
    ring_ok = ring_pos < ctx_lens[:, None]
    if window:
        lower = (ctx_lens - window)[:, None]
        ctx_ok = ctx_ok & (ctx_pos >= jnp.maximum(lower, 0))
        ring_ok = ring_ok & (ring_pos >= lower)
    mask = jnp.concatenate([ctx_ok, ring_ok], axis=1)   # [B, S+R]
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bns,nbsh->bnh", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    if live is not None:
        out = jnp.where(live[:, None, None], out, 0.0)
    return out.astype(q.dtype)
