"""Decode attention over latent (MLA) cache rows, absorbed form.

Every query head of a lane reads the SAME cached row per position
(``[c_kv | k_rope]``): the row is the key, and its first ``v_width``
values are the value. One query token per lane; context = the lane's rows
of the ctx region below ``ring_base`` plus the current round's rows in the
write ring (models/llama.py: init_ring).

A running softmax over chunks of the region, then over the ring. The
region part has two implementations behind one ``DecodeAttention``
(ops/attention.py), and that module's ``region_trips`` is the one place
that says how many chunks a lane reads in either, as in the dense
kernel: ``ceil(rows below its ring base / chunk)`` for a lane that holds
a request, none for one that does not (a freed lane's device length
keeps counting up, engine.py's round body).

  - the Pallas TPU kernel (``_region_kernel``): ONE invocation a layer
    walks a flat work list of (lane, chunk) pairs, so its cost follows
    the live lanes' own rows, not lanes x the longest context and not the
    region's capacity. The region stays in HBM where it is
    (``memory_space=ANY``, sliced by layer, lane and chunk start in the
    DMA descriptor: no per-layer slab, no relayout); ``[chunk, row]``
    blocks are double-buffered into VMEM across lane boundaries, scored
    on the MXU against the lane's ``[heads, row]`` queries, and the
    running max / denominator / accumulator of every lane live in the
    kernel's VMEM outputs. A grid of lanes x chunks would pay ~0.4 us a
    dead step, 1024 steps a layer at 16 lanes of 16384 rows (the dense
    kernel of ops/flash_decode.py had such a grid until PR 53, and walks
    a list like this one since);
  - the pure-XLA loop (``_region_reference``): what the kernel is tested
    against and what the CPU test meshes run. Its trip count is a traced
    value too, but ONE for the batch: every lane is read to the longest
    live lane's chunk and masked past its own length.

The ring's few rows, the merge, the normalisation and the "no visible
row" guard are XLA in both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.attention import (
    PALLAS_INTERPRET,
    REFERENCE_IMPL,
    DecodeAttention,
    flat_items,
    region_trips,
    round_rows as _round_rows,
)

NEG_INF = -1e30
# region rows a step of either loop reads at once. Chosen on the chip at
# both latent cells' shapes (PERF.md section 6, PR 42)
CHUNK = 512


def chunk_rows(S: int, chunk: int = 0) -> int:
    """Region rows a chunk holds: ``CHUNK`` (or the one a
    ``DecodeAttention`` names) in a region long enough for it."""
    return min(chunk or CHUNK, S)


def region_rows_read(impl: str, trips, cb: int) -> int:
    """Region rows one decode step's attention reads, from the host's
    ``region_trips``: the kernel reads each lane's own chunks, the XLA
    loop every lane to the longest."""
    if impl == REFERENCE_IMPL:
        return len(trips) * int(trips.max(initial=0)) * cb
    return int(trips.sum()) * cb


def round_rows(attn: DecodeAttention, ctx_lens, live, n_steps: int,
               max_context: int) -> tuple[int, int]:
    """The host's mirror of one dispatched round, a layer
    (``attention.round_rows``): each live lane's rows in whole chunks
    under the kernel, every lane to the longest of them under the XLA
    loop."""
    cb = chunk_rows(max_context, attn.chunk)
    return _round_rows(ctx_lens, live, n_steps, cb,
                       lambda trips: region_rows_read(attn.impl, trips, cb))


def _score(carry, q, rows, ok, v_width):
    """One running-softmax step: q [B, nh, row] against rows [B, n, row]
    under ok [B, n]."""
    m, l, acc = carry
    s = jnp.einsum("bhd,bnd->bhn", q, rows,
                   preferred_element_type=jnp.float32)
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    acc = acc * alpha[..., None] + jnp.einsum(
        "bhn,bnd->bhd", p.astype(rows.dtype), rows[..., :v_width],
        preferred_element_type=jnp.float32)
    return m_new, l * alpha + p.sum(axis=-1), acc


def _chunk_start(j, cb: int, S: int):
    """Where chunk ``j`` starts. A region that is no multiple of the chunk
    slides its last chunk back; the rows it has already seen are masked
    (``pos >= j * cb``)."""
    return j * cb if S % cb == 0 else jnp.minimum(j * cb, S - cb)


def _region_reference(q, ctx, layer, below, trips, v_width, cb):
    B, nh, row = q.shape
    S = ctx.shape[3]
    i32 = jnp.int32

    def region_chunk(j, carry):
        k0 = _chunk_start(j, cb, S)
        pos = k0 + jnp.arange(cb, dtype=i32)
        rows = jax.lax.dynamic_slice(
            ctx, (layer, 0, 0, k0, 0), (1, 1, B, cb, row))[0, 0]
        # (a lane without a request has no trips, whatever its length)
        ok = ((pos[None, :] >= j * cb) & (pos[None, :] < below[:, None])
              & (j < trips[:, None]))
        return _score(carry, q, rows.astype(q.dtype), ok, v_width)

    carry = (jnp.full((B, nh), NEG_INF, jnp.float32),
             jnp.zeros((B, nh), jnp.float32),
             jnp.zeros((B, nh, v_width), jnp.float32))
    return jax.lax.fori_loop(0, jnp.max(trips), region_chunk, carry)


def _region_kernel(
    # SMEM
    layer_ref,   # [1] i32
    total_ref,   # [1] i32 — work items: the sum of the lanes' trips
    lane_ref,    # [W] i32 — work item -> lane
    chunk_ref,   # [W] i32 — work item -> chunk of that lane
    below_ref,   # [B] i32 — region rows of each lane
    # VMEM
    q_ref,       # [B, nh, row]
    # HBM, where it is
    ctx_ref,     # [L, 1, B(+1), S, row]
    # VMEM outputs, the running state of every lane
    m_ref,       # [B, nh, 128] f32 (lane-broadcast)
    l_ref,       # [B, nh, 128] f32
    acc_ref,     # [B, nh, v_width] f32
    # scratch
    buf,         # [2, cb, row] — double-buffered row blocks
    sem,         # DMA semaphores [2]
    *,
    cb: int,
    S: int,
    v_width: int,
):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    layer, total = layer_ref[0], total_ref[0]

    def fetch(w, slot):
        start = _chunk_start(chunk_ref[w], cb, S)
        if S % cb == 0:
            start = pl.multiple_of(start, cb)
        return pltpu.make_async_copy(
            ctx_ref.at[layer, 0, lane_ref[w], pl.ds(start, cb), :],
            buf.at[slot], sem.at[slot])

    @pl.when(total > 0)
    def _():
        fetch(0, 0).start()

    def item(w, _):
        slot = jax.lax.rem(w, 2)

        # the next block, of this lane or the next one, flies while this
        # one is scored
        @pl.when(w + 1 < total)
        def _():
            fetch(w + 1, 1 - slot).start()

        fetch(w, slot).wait()
        lane, j = lane_ref[w], chunk_ref[w]
        rows = buf[slot].astype(q_ref.dtype)               # [cb, row]
        pos = _chunk_start(j, cb, S) + jax.lax.broadcasted_iota(
            jnp.int32, (1, cb), 1)
        ok = pos < below_ref[lane]
        if S % cb:
            ok = ok & (pos >= j * cb)
        s = jax.lax.dot_general(
            q_ref[lane], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [nh, cb]
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[lane][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_ref[lane][:, :1] * alpha + jnp.sum(
            p, axis=1, keepdims=True)
        acc_ref[lane] = acc_ref[lane] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[lane] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[lane] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    jax.lax.fori_loop(0, total, item, None)


def _region_pallas(q, ctx, layer, below, trips, v_width, cb, interpret):
    B, nh, row = q.shape
    S = ctx.shape[3]
    i32 = jnp.int32
    # the flat work list: lane b's chunks 0..trips[b]-1, lane after lane
    lane_of, chunk_of, ends = flat_items(trips, B * -(-S // cb))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    stat = jax.ShapeDtypeStruct((B, nh, 128), jnp.float32)
    m, l, acc = pl.pallas_call(
        functools.partial(_region_kernel, cb=cb, S=S, v_width=v_width),
        in_specs=[smem] * 5 + [vmem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[vmem] * 3,
        out_shape=[stat, stat,
                   jax.ShapeDtypeStruct((B, nh, v_width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2, cb, row), ctx.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_decode_region",
    )(layer.reshape(1), ends[-1:].astype(i32), lane_of, chunk_of, below, q,
      ctx)
    return m[..., 0], l[..., 0], acc


def latent_decode_attention(
    attn: DecodeAttention,   # which region implementation to trace
    q: jnp.ndarray,          # [B, nh, row] — absorbed, already scaled
    ctx: jnp.ndarray,        # [L, 1, B(+1), S, row]
    ring: jnp.ndarray,       # [L, 1, B, R, row]
    layer: jnp.ndarray,      # scalar i32
    ctx_lens: jnp.ndarray,   # [B] i32 — context length INCL. current token
    ring_base: jnp.ndarray,  # [B] i32 — position held by ring slot 0
    v_width: int,            # leading values of a row that are the value
    live: jnp.ndarray | None = None,  # [B] bool — lanes that hold a
                             # request; None = all. The others read no
                             # region row
) -> jnp.ndarray:
    """Returns [B, nh, v_width]: softmax(q . rows) weighted sum of the
    rows' value parts, float32 scores and accumulation. ``attn.chunk``
    names another chunk than ``CHUNK`` (tests, sweeps)."""
    B, nh, row = q.shape
    S, R = ctx.shape[3], ring.shape[3]
    i32 = jnp.int32
    layer = jnp.asarray(layer, i32)
    below = jnp.minimum(ring_base, ctx_lens).astype(i32)   # region rows
    cb = chunk_rows(S, attn.chunk)
    trips = region_trips(
        below, True if live is None else live, cb).astype(i32)
    if attn.impl == REFERENCE_IMPL:
        carry = _region_reference(q, ctx, layer, below, trips, v_width, cb)
    else:
        carry = _region_pallas(q, ctx, layer, below, trips, v_width, cb,
                               attn.impl == PALLAS_INTERPRET)

    rows = jax.lax.dynamic_slice(
        ring, (layer, 0, 0, 0, 0), (1, 1, B, R, row))[0, 0]
    rpos = ring_base[:, None] + jnp.arange(R, dtype=i32)[None, :]
    m, l, acc = _score(carry, q, rows.astype(q.dtype),
                       rpos < ctx_lens[:, None], v_width)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    # a lane with no visible row (ctx_len 0) holds exp(0) per masked key
    o = jnp.where((m > NEG_INF / 2)[..., None], o, 0.0)
    return o.astype(q.dtype)
