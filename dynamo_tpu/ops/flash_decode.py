"""Pallas TPU kernel: flash decode attention over CONTIGUOUS per-slot KV
plus a small per-round write ring.

Round-4 redesign of the decode hot path. Two lessons drive the design
(from the round-3/4 development runs; not re-measured on the v5e host
since — PERF.md):

  1. The round-3 kernel walked the paged pool with grid (slot, kv-head,
     page): 36k kernel invocations per step at ~0.4 µs each — 15.9 ms/step
     of pure grid overhead. Fix: decode context lives in a contiguous
     per-slot region ``ctx_kv [L, kvh, B+1, S, hd]`` (the paged pool
     remains prefix-cache *storage*; engine copies pages in/out at
     admission/seal), so attention streams big dense blocks:
     grid (B, S/CHUNK + 1) — ~32-130 invocations per layer.
  2. Writing the multi-GB ctx buffer per layer (scatter) while custom
     calls read it forces XLA to materialize copies (~7 GB temps,
     119 ms/step). Fix: steps write a tiny per-slot RING
     ``[L, kvh, B, R, hd]`` instead; the engine flushes ring->ctx once
     per round, AFTER all reads, where the update aliases in place.

Round-5 knob: ``slot_block`` processes SB slots per grid invocation
(grid (B/SB, chunks)) — measured per-invocation cost is dominated by
fixed overhead (grid sequencing + DMA setup + Mosaic's serialization of
small batched dots), so fewer, fatter invocations close the gap to the
bandwidth roofline. The DMA-skip index then clamps to the LONGEST live
context in the slot group (short slots ride along). ``chunk`` and
``slot_block`` are static arguments (``ops.attention.DecodeAttention``
carries them for sweeps); nothing is read from the environment.

Partitioning: GSPMD cannot split a Mosaic call, so under a mesh the
caller maps this function per shard over ``tp`` (``ops/attention.py``):
heads are independent, each shard sees its own kv heads and needs no
collective.

Int8 ctx (scales given): the int8 payload widens exactly to the compute
dtype in VMEM and the per-group f32 scales multiply the score /
probability COLUMNS (q.(s k) == s (q.k)), never the [chunk, hd] tiles —
Mosaic refuses the sublane-splitting reshape a tile-wise dequant needs
("infer-vector-layout: unsupported shape cast"). Scale blocks are
[.., 1, chunk/group]: a block's last two dims must be (8, 128)-divisible
or equal to the array's.

Position semantics: ctx_kv[l, :, b, p] holds position p of slot b, valid
while p < ring_base[b]; ring[l, :, b, r] holds position ring_base[b]+r,
valid while < ctx_lens[b] (the current token INCLUDED — the decode step
writes its KV to the ring before attending). Chunks beyond a slot
group's live context repeat the previous block index, so their DMA is
elided — cost tracks the LIVE context, not the padded capacity.

This replaces what vLLM's paged-attention CUDA kernel does for the
reference (SURVEY.md §7 "Paged attention on TPU" hard part); paging moved
out of the per-step critical path entirely.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

NEG_INF = -1e30

DEFAULT_CHUNK = 512
DEFAULT_SLOT_BLOCK = 1

# chunk floor for the divisor fallback: below this the grid degenerates
# into the per-invocation-overhead regime the kernel exists to avoid
CHUNK_FLOOR = 128

_chunk_warned: set = set()


def _pick_chunk(S: int, want: int, step: int = 1) -> int:
    """Chunk size for a context of S positions: ``want`` itself when it
    already tiles S (and is a multiple of ``step`` — the int8 scale
    group), else the largest divisor of S ≤ want that is a multiple of
    step, promoted to the smallest divisor ≥ CHUNK_FLOOR if the best
    candidate falls below it. The old ``gcd(want, S)`` fallback could
    silently pick a tiny divisor (S=520 → chunk 8 → 66 grid invocations
    per layer — the round-3 overhead cliff); log once per config when
    the request is adjusted."""
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


def _kernel(
    # scalar prefetch
    layer_ref,   # [1] i32
    ctx_sm,      # [B] i32
    base_sm,     # [B] i32 — ring base positions
    # blocks
    q_ref,       # [SB, nkv, G, HD]
    k_ref,       # [1, nkv, SB, CHUNK, HD] — int8 when quantized
    v_ref,
    # quantized only: ksc_ref/vsc_ref [1, SB, 1, 1, CHUNK//group] f32
    # then:
    # rk_ref,    # [1, nkv, SB, R, HD]   ring lanes (compute dtype)
    # rv_ref,
    # o_ref,     # [SB, nkv, G, HD]
    # scratch:
    # m_ref,     # [SB, nkv, G, 128] f32 running max
    # l_ref,     # [SB, nkv, G, 128] f32 running denom
    # acc_ref,   # [SB, nkv, G, HD] f32 running numerator
    *refs,
    scale: float,
    chunk: int,
    sb: int,
    quantized: bool,
):
    if quantized:
        ksc_ref, vsc_ref = refs[:2]
        rk_ref, rv_ref, o_ref, m_ref, l_ref, acc_ref = refs[2:]
    else:
        ksc_ref = vsc_ref = None
        rk_ref, rv_ref, o_ref, m_ref, l_ref, acc_ref = refs
    s_idx = pl.program_id(0)
    i = pl.program_id(1)
    n_chunks = pl.num_programs(1)  # ctx chunks + 1 ring chunk
    is_ring = i == n_chunks - 1

    @pl.when(i == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def accumulate(j, k, v, start, limit, length, k_sc=None, v_sc=None):
        # k/v [nkv, length, HD]; positions start + iota valid below limit.
        # k_sc/v_sc [1, length] f32: per-position dequant scales of an
        # int8 chunk, applied to the score / probability COLUMNS —
        # q.(s_c k_c) == s_c (q.k_c) and sum_c p_c (s_c v_c) ==
        # sum_c (p_c s_c) v_c — so the [nkv, length, HD] tiles are never
        # rescaled element-wise (and never reshaped: Mosaic's layout
        # inference refuses the sublane-splitting cast that needs)
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, length), 2)
        valid = pos < limit
        q = q_ref[j]                                       # [nkv, G, HD]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                          # [nkv, G, length]
        if k_sc is not None:
            s = s * k_sc[None]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[j, :, :, :1]
        row_max = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[j, :, :, :1] * alpha + jnp.sum(
            p, axis=2, keepdims=True)
        if v_sc is not None:
            p = p * v_sc[None]
        acc_ref[j] = acc_ref[j] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[j] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[j] = jnp.broadcast_to(l_new, l_ref.shape[1:])

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

    for j in range(sb):
        b = s_idx * sb + j
        ctx = ctx_sm[b]
        base = base_sm[b]

        # ctx chunk: positions [i*chunk, +chunk), valid below ring_base
        @pl.when(jnp.logical_and(
            jnp.logical_not(is_ring), i * chunk < base))
        def _(j=j, ctx=ctx, base=base):
            k = k_ref[0, :, j]                  # [nkv, chunk, HD]
            v = v_ref[0, :, j]
            k_sc = v_sc = None
            if quantized:
                # dequantize in VMEM, right after the DMA: the HBM
                # stream was the int8 bytes. The payload widens to the
                # compute dtype exactly (|int8| <= 127 fits bf16's
                # mantissa); the f32 scales ride the score columns
                k = k.astype(jnp.float32).astype(q_ref.dtype)
                v = v.astype(jnp.float32).astype(q_ref.dtype)
                k_sc = per_position(ksc_ref[0, j, 0])
                v_sc = per_position(vsc_ref[0, j, 0])
            accumulate(
                j, k, v, i * chunk, jnp.minimum(base, ctx), chunk,
                k_sc, v_sc,
            )

        # ring chunk: slot r holds position base + r, valid below ctx
        @pl.when(is_ring)
        def _(j=j, ctx=ctx, base=base):
            accumulate(j, rk_ref[0, :, j], rv_ref[0, :, j], base, ctx,
                       rk_ref.shape[3])

    @pl.when(i == n_chunks - 1)
    def _():
        denom = jnp.maximum(l_ref[:, :, :, :1], 1e-30)
        o_ref[:] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret", "slot_block")
)
def flash_decode_attention(
    q: jnp.ndarray,          # [B, n_heads, HD]
    ctx_k: jnp.ndarray,      # [L, kvh, B(+1), S, HD] contiguous per-slot KV
    ctx_v: jnp.ndarray,
    ring_k: jnp.ndarray,     # [L, kvh, B, R, HD] current-round writes
    ring_v: jnp.ndarray,
    layer: jnp.ndarray,      # scalar i32
    ctx_lens: jnp.ndarray,   # [B] i32 — context length INCL. current token
    ring_base: jnp.ndarray,  # [B] i32 — position held by ring slot 0
    chunk: int = 0,
    interpret: bool = False,
    slot_block: int = 0,
    ctx_k_scale: jnp.ndarray | None = None,  # f32 [L, B(+1), S//group]
    ctx_v_scale: jnp.ndarray | None = None,  # (int8 ctx_k/ctx_v)
) -> jnp.ndarray:
    """Flash decode attention over contiguous KV + ring. Returns
    [B, n_heads, HD]. The current token's KV must already be in the ring
    (position ctx-1 == ring_base + r for the step's ring slot r).
    chunk/slot_block of 0 pick the defaults. With ctx scales given,
    ctx_k/ctx_v are int8 and each chunk dequantizes in VMEM after its
    DMA (half the live-context HBM bytes)."""
    B, n_heads, hd = q.shape
    L, nkv, _, S, _ = ctx_k.shape
    R = ring_k.shape[3]
    g = n_heads // nkv
    quantized = ctx_k_scale is not None
    if chunk <= 0:
        chunk = DEFAULT_CHUNK
    if slot_block <= 0:
        slot_block = DEFAULT_SLOT_BLOCK
    # chunk must tile S exactly (and whole scale groups when quantized)
    group = S // ctx_k_scale.shape[2] if quantized else 1
    chunk = _pick_chunk(S, chunk, group)
    sb = math.gcd(slot_block, B)
    scale = float(1.0 / (hd ** 0.5))
    qg = q.reshape(B, nkv, g, hd)
    n_chunks = S // chunk
    ctx_i32 = ctx_lens.astype(jnp.int32)
    base_i32 = ring_base.astype(jnp.int32)

    def q_map(s, i, layer, ctx, base):
        return (s, 0, 0, 0)

    def _grp_live(s, base):
        # chunks beyond the slot GROUP's longest live context repeat the
        # previous index so the pipeline skips the (unused) DMA
        # scalar loads only in index maps (SMEM): unrolled group max
        grp_max = base[s * sb]
        for j in range(1, sb):
            grp_max = jnp.maximum(grp_max, base[s * sb + j])
        return jnp.maximum((grp_max + chunk - 1) // chunk - 1, 0)

    def kv_map(s, i, layer, ctx, base):
        return (layer[0], 0, s, jnp.minimum(i, _grp_live(s, base)), 0)

    def sc_map(s, i, layer, ctx, base):
        return (layer[0], s, jnp.minimum(i, _grp_live(s, base)), 0, 0)

    def ring_map(s, i, layer, ctx, base):
        return (layer[0], 0, s, 0, 0)

    in_specs = [
        pl.BlockSpec((sb, nkv, g, hd), q_map),
        pl.BlockSpec((1, nkv, sb, chunk, hd), kv_map),
        pl.BlockSpec((1, nkv, sb, chunk, hd), kv_map),
    ]
    inputs = [qg, ctx_k, ctx_v]
    if quantized:
        # Mosaic wants a block's last two dims (8, 128)-divisible or equal
        # to the array's: view the scale row as [n_chunks, 1, chunk/group]
        # so one chunk's scales are a whole (1, chunk/group) minor tile
        ngc = chunk // group
        sc_shape = (L, ctx_k_scale.shape[1], n_chunks, 1, ngc)
        in_specs += [
            pl.BlockSpec((1, sb, 1, 1, ngc), sc_map),
            pl.BlockSpec((1, sb, 1, 1, ngc), sc_map),
        ]
        inputs += [ctx_k_scale.reshape(sc_shape),
                   ctx_v_scale.reshape(sc_shape)]
    in_specs += [
        pl.BlockSpec((1, nkv, sb, R, hd), ring_map),
        pl.BlockSpec((1, nkv, sb, R, hd), ring_map),
    ]
    inputs += [ring_k, ring_v]

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, chunk=chunk, sb=sb, quantized=quantized
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B // sb, n_chunks + 1),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((sb, nkv, g, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((sb, nkv, g, 128), jnp.float32),
                pltpu.VMEM((sb, nkv, g, 128), jnp.float32),
                pltpu.VMEM((sb, nkv, g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nkv, g, hd), q.dtype),
        # generous scoped-vmem budget for the chunked block pipeline
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        ctx_i32,
        base_i32,
        *inputs,
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
) -> jnp.ndarray:
    """Pure-jnp equivalent (CPU tests / kernel parity checks). With ctx
    scales given, ctx_k/ctx_v are int8 per-group quantized — dequantize
    them to the query dtype first (matching the kernel's in-VMEM
    dequant, so parity tests cover the quantized math too)."""
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
    ctx_ok = ctx_pos < jnp.minimum(ring_base, ctx_lens)[:, None]
    ring_pos = ring_base[:, None] + jnp.arange(R)[None, :]
    ring_ok = ring_pos < ctx_lens[:, None]
    mask = jnp.concatenate([ctx_ok, ring_ok], axis=1)   # [B, S+R]
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bns,nbsh->bnh", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)
