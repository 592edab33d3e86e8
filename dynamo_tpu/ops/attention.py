"""Attention over the contiguous per-slot decode context.

Round-4 layout (see ops/flash_decode.py and models/llama.py): each decode
slot owns a contiguous KV region ``ctx_kv [L, kvh, B(+1), S, hd]``; the
paged pool exists only as prefix-cache storage, copied in/out at
admission/seal. Attention in the hot path therefore reads dense slabs —
no gathers, no page tables:

  - decode: the implementation the CALLER names with a ``DecodeAttention``
    — a compiled Pallas kernel on TPU devices, the pure-jnp reference on
    the CPU test meshes. Two kernels stand behind the one name, by what
    the region holds: K and V per kv head (``ctx_decode_attention`` here:
    the flash kernel of ops/flash_decode.py, mapped per shard over the
    mesh's ``tp`` axis) or one latent row a position
    (ops/latent_decode.py: ``latent_decode_attention``, a work list of
    each lane's own chunks). Nothing here looks at the process's default
    backend, and there is no fall-through from one to the other: a kernel
    that fails to compile fails the program. Both kernels walk a flat
    WORK LIST of the live lanes' chunks in one invocation a layer, and
    this module is the one place that says how many chunks a lane reads
    (``region_trips``) and how the list is laid out (``flat_items``);
  - prefill: ONE blocked running-softmax attention in pure XLA
    (``prefill_attention``) for every prefill-family program, solo and
    batched: two rolled loops whose trip counts follow the live rows, so
    padding rows, dummy lanes, blocks above the causal diagonal and — for
    a fresh prompt — the whole region are never scored, and the lowered
    program has the same size at every bucket width and lane count. The
    calls that hold no tree mask, selection, window or int8 region run
    the same work list as ONE Mosaic call a layer on a TPU
    (``fused_prefill_attention``, ops/flash_prefill.py): the expanded
    latent chunks and, through ``dense_prefill_attention``, the dense
    decoder's GQA layers, mapped per shard over the mesh's ``tp`` axis.

This replaces the round-3 paged-attention kernel whose (slot, head, page)
grid cost 15.9 ms/step in pure invocation overhead (SURVEY.md §7 "Paged
attention on TPU" hard part; reference analogue is vLLM's paged-attention
CUDA kernel).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.ops.flash_decode import (
    chunk_rows as dense_chunk_rows,
    flash_decode_attention,
    flash_decode_attention_reference,
)
from dynamo_tpu.ops.flash_prefill import flash_prefill_attention
from dynamo_tpu.parallel.mesh import AXIS_TENSOR

NEG_INF = -1e30

PALLAS = "pallas"                      # the compiled Mosaic kernel
PALLAS_INTERPRET = "pallas_interpret"  # same kernel, interpreted: tests only
REFERENCE_IMPL = "reference"           # pure jnp


@dataclasses.dataclass(frozen=True)
class DecodeAttention:
    """The decode-attention implementation a program is traced with.
    Hashable: it is a static argument of the jitted model functions."""

    impl: str
    # kernel impls: the mesh whose ``tp`` axis the flash kernel is mapped
    # over (Mosaic calls cannot be partitioned by GSPMD). None = unsharded
    # operands (single-device kernel tests). The latent block holds whole
    # layers on every device and refuses tp / ep > 1: its kernel is called
    # bare, as its grouped expert product is.
    mesh: Optional[Mesh] = None
    # region rows a work item of either decode attention holds (a step
    # of the latent XLA loop too); 0 = the kernel's default (tools sweep)
    chunk: int = 0

    def __post_init__(self):
        if self.impl not in (PALLAS, PALLAS_INTERPRET, REFERENCE_IMPL):
            raise ValueError(f"unknown decode attention impl {self.impl!r}")


REFERENCE = DecodeAttention(REFERENCE_IMPL)


def decode_attention_for(mesh: Mesh) -> DecodeAttention:
    """The implementation an engine placed on ``mesh`` runs: the compiled
    kernel on TPU devices — the only path there — and the jnp reference
    on CPU devices (the test meshes; interpret-mode Pallas is far too
    slow to serve from). Decided from the devices the engine's arrays
    live on, once, and reported by the engine at start."""
    platform = mesh.devices.flat[0].platform
    if platform == "tpu":
        return DecodeAttention(PALLAS, mesh)
    if platform == "cpu":
        return REFERENCE
    raise ValueError(
        f"no decode attention implementation for platform {platform!r}"
    )


def region_trips(below, live, cb: int):
    """Chunks of the region each lane's decode attention reads: ``below``
    [B] rows under the lane's ring base, ``live`` [B] whether the lane
    holds a request, ``cb`` rows a chunk. numpy in (the engine's mirror),
    numpy out; traced in, traced out."""
    return (below + (cb - 1)) // cb * live


def flat_items(counts, width: int):
    """The flat work list a decode kernel walks, from ``counts`` [B] items
    a lane: (``lane_of`` [width] int32, item -> lane; ``item_of`` [width],
    item -> which of its lane's items, ascending; ``ends`` [B], the
    running sum of ``counts``: ``ends[-1]`` items are on the list). Lane
    b's items 0..counts[b]-1, lane after lane; what lies past the list's
    end is never read. ``width`` is a static bound on the sum of
    ``counts`` (the same list serves every layer of a step: XLA keeps
    one)."""
    B = counts.shape[0]
    i32 = jnp.int32
    ends = jnp.cumsum(counts)
    w = jnp.arange(width, dtype=i32)
    lane_of = jnp.minimum(
        jnp.sum(w[:, None] >= ends[None, :], axis=1), B - 1).astype(i32)
    item_of = w - (ends - counts)[lane_of]
    return lane_of, item_of.astype(i32), ends


def round_rows(ctx_lens, live, n_steps: int, cb: int,
               rows_read) -> tuple[int, int]:
    """The host's mirror of one dispatched round, a layer: (region rows
    its steps' attention read, rows that were some live lane's own).
    ``ctx_lens`` [B] the lanes' lengths at dispatch (the region's rows lie
    below the round's ring base, ctx - 1), ``live`` [B] bool the lanes
    dispatched, ``cb`` rows a chunk, ``rows_read(trips)`` the rows a step
    of the traced implementation reads given the lanes' ``region_trips``
    (which the attention's wrapper calls too): a kernel reads each live
    lane's own chunks, ``trips.sum() * cb``."""
    base = np.maximum(np.asarray(ctx_lens) - 1, 0)
    live = np.asarray(live, bool)
    trips = region_trips(base, live, cb)
    return n_steps * int(rows_read(trips)), n_steps * int(base[live].sum())


def dense_round_rows(attn: DecodeAttention, ctx_lens, live, n_steps: int,
                     max_context: int) -> tuple[int, int]:
    """``round_rows`` of ``ctx_decode_attention``: each live lane's rows
    in whole chunks under the kernel; the jnp reference of the CPU meshes
    scores every lane's whole region."""
    cb = dense_chunk_rows(max_context, attn.chunk)
    return round_rows(
        ctx_lens, live, n_steps, cb,
        (lambda trips: len(trips) * max_context)
        if attn.impl == REFERENCE_IMPL else (lambda trips: trips.sum() * cb))


def ctx_decode_attention(
    attn: DecodeAttention,
    q: jnp.ndarray,          # [B, n_heads, hd] — one new token per slot
    ctx_k: jnp.ndarray,      # [L, kvh, B(+1), S, hd]
    ctx_v: jnp.ndarray,
    ring_k: jnp.ndarray,     # [L, kvh, B, R, hd] current-round writes
    ring_v: jnp.ndarray,
    layer: jnp.ndarray,      # scalar i32
    ctx_lens: jnp.ndarray,   # [B] i32 — context length INCL. current token
    ring_base: jnp.ndarray,  # [B] i32 — position held by ring slot 0
    ctx_k_scale: Optional[jnp.ndarray] = None,  # f32 [L, B(+1), S//g]
    ctx_v_scale: Optional[jnp.ndarray] = None,  # when ctx is int8
    live: Optional[jnp.ndarray] = None,  # [B] bool — lanes that hold a
                             # request; None = all. The others read
                             # nothing and come back 0
    window: int = 0,         # > 0: the ``window`` positions up to the
                             # query's own are visible, and ``ctx_k`` /
                             # ``ctx_v`` hold a MODULAR buffer a lane of
                             # its last S rows (ops/flash_decode.py)
    name: str = "flash_decode_attention",   # the kernel's, in a trace
) -> jnp.ndarray:
    """Decode attention over the two-tier context (ctx region below
    ring_base + ring above). The current token's KV must already be in the
    ring. Returns [B, n_heads, hd]. When the ctx region is int8
    (scales given), each KV chunk dequantizes in VMEM right after the
    DMA — the HBM stream is the int8 bytes.

    The kernel walks a work list: each live lane's ``region_trips`` chunks
    of the region, ascending, then its ring. A freed lane's device length
    keeps counting up (engine.py's round body); it is not on the list."""
    scales = () if ctx_k_scale is None else (ctx_k_scale, ctx_v_scale)
    if attn.impl == REFERENCE_IMPL:
        return flash_decode_attention_reference(
            q, ctx_k, ctx_v, ring_k, ring_v, layer, ctx_lens, ring_base,
            *scales, live=live, window=window,
        )
    B, S = q.shape[0], ctx_k.shape[3]
    i32 = jnp.int32
    if live is None:
        live = jnp.ones(B, bool)
    cb = dense_chunk_rows(
        S, attn.chunk, S // ctx_k_scale.shape[2] if scales else 1)
    n_chunks = S // cb
    below = jnp.minimum(ring_base, ctx_lens).astype(i32)
    if window:   # a lane's buffer holds its last S rows and no more
        below = jnp.minimum(below, S)
    trips = region_trips(below, live, cb).astype(i32)
    # a live lane's items: its chunks, then (item ``trips``) its ring,
    # which the kernel knows as chunk ``n_chunks`` and whose K / V blocks
    # are the chunk's before it once more (a block index cannot be "none")
    lane_of, item_of, ends = flat_items(
        (trips + 1) * live, B * (n_chunks + 1))
    last = trips[lane_of]
    work = (lane_of, jnp.where(item_of == last, n_chunks, item_of),
            jnp.minimum(item_of, jnp.maximum(last - 1, 0)),
            ends[-1:].astype(i32))

    def kernel(q, ctx_k, ctx_v, ring_k, ring_v, layer, ctx_lens, ring_base,
               lane_of, chunk_of, fetch_of, total, *scales):
        k_scale, v_scale = scales or (None, None)
        return flash_decode_attention(
            q, ctx_k, ctx_v, ring_k, ring_v, layer, ctx_lens, ring_base,
            (lane_of, chunk_of, fetch_of, total), chunk=cb,
            interpret=attn.impl == PALLAS_INTERPRET,
            ctx_k_scale=k_scale, ctx_v_scale=v_scale, window=window,
            name=name,
        )

    if attn.mesh is not None:
        # heads are independent: q/out shard on heads, ctx/ring on kv
        # heads (the layouts llama.param/ctx/ring_shardings already give
        # them), each shard runs the kernel on its own heads — no
        # collective. The work list and the scales carry no head axis:
        # replicated, every shard walks the same list over its own heads.
        tp = attn.mesh.shape[AXIS_TENSOR]
        if ctx_k.shape[1] % tp:
            raise ValueError(
                f"{ctx_k.shape[1]} kv heads do not divide over tp={tp}"
            )
        heads = P(None, AXIS_TENSOR, None)
        kv = P(None, AXIS_TENSOR, None, None, None)
        kernel = jax.shard_map(
            kernel, mesh=attn.mesh,
            in_specs=(heads, kv, kv, kv, kv, P(), P(), P())
            + (P(),) * (len(work) + len(scales)),
            out_specs=heads,
            # pallas_call has no replication rule; the other mesh axes
            # see replicated operands and produce replicated outputs
            check_vma=False,
        )
    out = kernel(
        q, ctx_k, ctx_v, ring_k, ring_v, jnp.asarray(layer, jnp.int32),
        ctx_lens, ring_base, *work, *scales,
    )
    # no item wrote the row of a lane that is not on the list
    return jnp.where(live[:, None, None], out, 0)


PREFILL_BLOCK = 256  # query/key rows per block of prefill_attention


class PriorContext(NamedTuple):
    """Where a prefill chunk's prior context lives, read block by block
    inside the attention loop (never sliced into a per-lane slab first).
    A dense model hands over the engine's ctx region itself. A latent-row
    model hands over a WORKSPACE of the same geometry holding its chunks'
    prior rows expanded per head, written once a dispatch before the call
    (one layer, lane i = chunk i; models/mla_moe.py:_expand_prior, whose
    header has the arithmetic)."""

    k: jnp.ndarray           # [L, kvh, lanes, S, hd] — the whole region
    v: jnp.ndarray           # [L, kvh, lanes, S, hd_v]
    layer: jnp.ndarray       # scalar i32 — a VALUE, so that every layer
                             # of a program shares one traced attention
    slots: jnp.ndarray       # [K] i32 — each chunk's lane of the region
    k_scale: Optional[jnp.ndarray] = None  # f32 [L, lanes, S//g] when the
    v_scale: Optional[jnp.ndarray] = None  # region is int8


@functools.partial(jax.jit,
                   static_argnames=("block", "ctx_span", "mask_block",
                                    "window"))
def prefill_attention(
    q: jnp.ndarray,          # [K, T, n_heads, hd] — K chunks of T new tokens
    k_new: jnp.ndarray,      # [K, T, kvh, hd] — the chunks' own keys
    v_new: jnp.ndarray,      # [K, T, kvh, hd_v] — values, at their width
    q_starts: jnp.ndarray,   # [K] i32 — tokens already in each region
    seq_lens: jnp.ndarray,   # [K] i32 — total valid context (0 = dummy lane)
    ctx: Optional[PriorContext] = None,  # None = fresh chunks (every
                             # q_start 0): no region read is compiled
    chunk_masks: Optional[jnp.ndarray] = None,  # [K, T, T] bool in-chunk
                             # visibility (tree-causal); None = causal
    block: int = PREFILL_BLOCK,
    ctx_span: int = 0,       # STATIC bound on the region rows read (0 =
                             # the whole region)
    block_masks: Optional[jnp.ndarray] = None,  # [K, kvh, T, NB] bool: may
                             # query row i of K/V group g read the keys at
                             # ABSOLUTE positions [b mask_block, (b + 1)
                             # mask_block)? (a block-sparse SELECTION, ops/
                             # sparse_attention.py); None = every block
    mask_block: int = 0,
    window: int = 0,         # > 0: a query reads the ``window`` positions
                             # up to its own only, and ``ctx`` holds each
                             # chunk's LAST ``ctx_span`` prior positions
                             # (row i = position q_start - ctx_span + i)
) -> jnp.ndarray:
    """The one prefill attention: blocked, running-softmax, causal, in
    pure XLA, scoring only (query block, key block) pairs that can hold
    a live pair. Chunk k's T tokens sit at positions q_start..q_start+T
    and attend the prior context [0, q_start) plus the chunk itself.
    Returns [K, T, n_heads, hd_v]; rows of a query block with no live row
    (padding past seq_len, dummy lanes) and rows that see no key are 0.

    Two rolled loops whose trip counts are traced values:

      - outer, over the WORK LIST: the (lane, query block) pairs with a
        row below the lane's live length seq_len - q_start. A dummy lane
        contributes none, a 300-token prompt in a 1024 bucket two of four;
      - inner, over key blocks: first the prior context's blocks below
        q_start (read from the region in place, dequantized per block
        when it is int8), then the chunk's blocks up to the causal
        diagonal and the live length. With a ``chunk_mask`` (a packed
        token TREE: node i sees its ancestor chain, not its index
        predecessors; spec/verifier.py) only the live length bounds them.

    So the number of operations in the lowered program depends neither
    on T nor on K, and the work done follows the live rows. It is a
    jitted function called from inside the model's jits: the layers of a
    program, unrolled there, share ONE traced and lowered attention (the
    layer index is a value), which is what keeps the tracing and
    lowering that set-up pays per program from growing with depth. Scores, max,
    sum and accumulator are float32; probabilities are cast to the value
    dtype before the PV product. A width that is no multiple of the
    block slides its last block back (start = width - block) and masks
    the rows it has already seen, instead of padding the source.

    Under a ``window`` the bound is a LOWER loop bound too: the key blocks
    wholly below a query block's window are never scored, in the chunk and
    in the prior context, which is then a workspace of the lane's last
    ``ctx_span`` prior rows (a window layer keeps no more), not a region
    from position 0.

    With ``block_masks`` every pair is still SCORED (the loops' bounds
    are causality's) and the selection masks the scores: the same
    mathematics as an attention that reads only the chosen blocks, at the
    cost of the whole causal context.
    """
    K, T, n_heads, hd = q.shape
    kvh, hd_v = k_new.shape[2], v_new.shape[3]
    rep = n_heads // kvh
    blk = min(block, T)
    nq = -(-T // blk)
    i32 = jnp.int32
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    q_starts = q_starts.astype(i32)
    seq_lens = seq_lens.astype(i32)
    n_live = jnp.clip(seq_lens - q_starts, 0, T)     # [K] live chunk rows
    nblk_live = (n_live + blk - 1) // blk            # [K] live blocks
    ends = jnp.cumsum(nblk_live)                     # work list offsets

    # kv-head-major operands: a block is then one [kvh, rep*blk, hd] x
    # [kvh, blk, hd] product, K/V never repeated across the GQA group
    qt = q.reshape(K, T, kvh, rep, hd).transpose(0, 2, 3, 1, 4)
    kt = k_new.transpose(0, 2, 1, 3).astype(q.dtype)  # [K, kvh, T, hd]
    vt = v_new.transpose(0, 2, 1, 3).astype(q.dtype)

    if block_masks is not None:
        # one spare block, so that a slice that starts in the last one
        # never slides back
        block_masks = jnp.pad(block_masks, ((0, 0),) * 3 + ((0, 1),))

    def selected(lane, q0, k0, n):
        """The selection for query rows q0.. of ``lane`` against the n
        keys at absolute positions k0..: [kvh, 1, blk, n]."""
        nb = -(-n // mask_block) + 1
        m = jax.lax.dynamic_slice(
            block_masks, (lane, 0, q0, k0 // mask_block),
            (1, kvh, blk, nb))[0]
        m = jnp.repeat(m, mask_block, axis=-1)
        return jax.lax.dynamic_slice(
            m, (0, 0, k0 % mask_block), (kvh, blk, n))[:, None]

    def score(carry, q_blk, k_blk, v_blk, ok):
        """One running-softmax step: q_blk [kvh, rep, blk, hd] against
        k_blk/v_blk [kvh, n, hd] under ok [blk, n] (or [kvh, 1, blk, n]
        under a selection)."""
        m, l, acc = carry
        s = jnp.einsum("grqh,gkh->grqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "grqk,gkh->grqh", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1), acc

    def query_block(w, out):
        lane = jnp.sum(w >= ends).astype(i32)
        qb = w - (ends[lane] - nblk_live[lane])
        q0 = jnp.minimum(qb * blk, T - blk)
        rows = q0 + jnp.arange(blk, dtype=i32)       # chunk-relative
        q_start, live = q_starts[lane], n_live[lane]
        q_blk = jax.lax.dynamic_slice(
            qt, (lane, 0, 0, q0, 0), (1, kvh, rep, blk, hd))[0]
        carry = (jnp.full((kvh, rep, blk), NEG_INF, jnp.float32),
                 jnp.zeros((kvh, rep, blk), jnp.float32),
                 jnp.zeros((kvh, rep, blk, hd_v), jnp.float32))

        if ctx is not None:
            span = ctx_span or ctx.k.shape[3]
            cb = min(block, span)
            layer = jnp.asarray(ctx.layer, i32)
            below = jnp.minimum(jnp.minimum(q_start, seq_lens[lane]), span)
            slot = ctx.slots[lane].astype(i32)

            def ctx_block(j, carry):
                k0 = jnp.minimum(j * cb, span - cb)
                kp = k0 + jnp.arange(cb, dtype=i32)  # absolute position
                if window:
                    return windowed_ctx_block(j, k0, kp, carry)
                at = (layer, 0, slot, k0, 0)
                k_blk = jax.lax.dynamic_slice(
                    ctx.k, at, (1, kvh, 1, cb, hd))[0, :, 0]
                v_blk = jax.lax.dynamic_slice(
                    ctx.v, at, (1, kvh, 1, cb, hd_v))[0, :, 0]
                if ctx.k_scale is not None:
                    g = ctx.k.shape[3] // ctx.k_scale.shape[2]

                    def dequant(x, scale_grid):
                        sc = scale_grid[layer, slot][kp // g]
                        return x.astype(jnp.float32) * sc[None, :, None]

                    k_blk = dequant(k_blk, ctx.k_scale)
                    v_blk = dequant(v_blk, ctx.v_scale)
                ok = ((kp >= j * cb) & (kp < below))[None, :]
                if block_masks is not None:
                    ok = ok & selected(lane, q0, k0, cb)
                return score(carry, q_blk, k_blk.astype(q.dtype),
                             v_blk.astype(q.dtype), ok)

            def windowed_ctx_block(j, k0, kp, carry):
                # row ``kp`` of the workspace holds position q_start -
                # span + kp
                at = (layer, 0, slot, k0, 0)
                k_blk = jax.lax.dynamic_slice(
                    ctx.k, at, (1, kvh, 1, cb, hd))[0, :, 0]
                v_blk = jax.lax.dynamic_slice(
                    ctx.v, at, (1, kvh, 1, cb, hd_v))[0, :, 0]
                pos = q_start - span + kp
                ok = ((kp >= j * cb) & (pos >= 0))[None, :] & (
                    pos[None, :] > (q_start + rows - window)[:, None])
                return score(carry, q_blk, k_blk.astype(q.dtype),
                             v_blk.astype(q.dtype), ok)

            if window:
                # the first workspace row the block's first query sees,
                # and the first that holds a position at all
                first = jnp.maximum(q0 - window + 1, -q_start) + span
                carry = jax.lax.fori_loop(
                    jnp.clip(first, 0, span) // cb, (span + cb - 1) // cb,
                    ctx_block, carry)
            else:
                carry = jax.lax.fori_loop(
                    0, (below + cb - 1) // cb, ctx_block, carry)

        def chunk_block(j, carry):
            k0 = jnp.minimum(j * blk, T - blk)
            kp = k0 + jnp.arange(blk, dtype=i32)     # chunk-relative
            at = (lane, 0, k0, 0)
            ok = ((kp >= j * blk) & (kp < live))[None, :]
            if chunk_masks is None:
                ok = ok & (kp[None, :] <= rows[:, None])
                if window:
                    ok = ok & (kp[None, :] > rows[:, None] - window)
            else:
                ok = ok & jax.lax.dynamic_slice(
                    chunk_masks, (lane, q0, k0), (1, blk, blk))[0]
            if block_masks is not None:
                ok = ok & selected(lane, q0, q_start + k0, blk)
            return score(
                carry, q_blk,
                jax.lax.dynamic_slice(kt, at, (1, kvh, blk, hd))[0],
                jax.lax.dynamic_slice(vt, at, (1, kvh, blk, hd_v))[0], ok)

        n_keys = nblk_live[lane]
        if chunk_masks is None:
            n_keys = jnp.minimum(qb + 1, n_keys)     # the causal diagonal
        m, l, acc = jax.lax.fori_loop(
            jnp.maximum(q0 - window + 1, 0) // blk if window else 0,
            n_keys, chunk_block, carry)
        # a row that met no unmasked score holds p = exp(0) per masked
        # key (NEG_INF is finite): gate on the running max, emit zeros
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        o = jnp.where((m > NEG_INF / 2)[..., None], o, 0.0)
        return jax.lax.dynamic_update_slice(
            out, o.astype(q.dtype)[None], (lane, 0, 0, q0, 0))

    out = jax.lax.fori_loop(
        0, ends[-1], query_block,
        jnp.zeros(qt.shape[:-1] + (hd_v,), qt.dtype))
    return out.transpose(0, 3, 1, 2, 4).reshape(K, T, n_heads, hd_v)


def prefill_fuses(width: int, n_heads: int, kv_heads: int,
                  ctx_span: int = 0, block: int = PREFILL_BLOCK) -> bool:
    """The SHAPE RULE of ``fused_prefill_attention``: does a call of this
    geometry run the fused kernel (ops/flash_prefill.py) on a TPU? Whole
    groups of query heads a K/V head (one each: an expanded latent chunk;
    more: a dense GQA layer, the group sharing its K/V tile) in whole
    blocks: the kernel's tiles never slide back over rows they have
    seen."""
    blk = min(block, width)
    return (n_heads % kv_heads == 0 and width % blk == 0
            and ctx_span % min(block, ctx_span or block) == 0)


def prefill_query_blocks(width: int, q_starts, seq_lens,
                         block: int = PREFILL_BLOCK) -> int:
    """The (lane, query block) pairs with a live row of one prefill
    dispatch, a layer: the items of ``prefill_attention``'s work list and
    of the fused kernel's (the host's count, beside
    ``prefill_attention_pairs``)."""
    blk = min(block, width)
    return sum(-(-min(max(int(n) - int(q), 0), width) // blk)
               for q, n in zip(q_starts, seq_lens))


def prefill_steps(n_live, below, width: int, ctx_span: int, block: int):
    """The flat list of STEPS the fused kernel walks, from the lanes'
    live chunk rows ``n_live`` [K] and prior rows ``below`` [K]: for every
    (lane, query block) pair with a live row, lane after lane and block
    after block, the lane's prior blocks, then the chunk's blocks up to
    the causal diagonal and the live length: what ``prefill_attention``'s
    loops run, trip for trip, and ``prefill_attention_pairs`` counts.
    Returns ((lane_of, qb_of, j_of, last_of, total), block_live [K, nq],
    pblk [K])."""
    K = n_live.shape[0]
    blk = min(block, width)
    nq = width // blk
    i32 = jnp.int32
    cb = min(block, ctx_span) if ctx_span else blk
    pblk = (below.astype(i32) + cb - 1) // cb
    nblk_live = (n_live.astype(i32) + blk - 1) // blk
    qbs = jnp.arange(nq, dtype=i32)[None, :]
    block_live = qbs < nblk_live[:, None]
    counts = jnp.where(
        block_live, pblk[:, None] + jnp.minimum(qbs + 1, nblk_live[:, None]),
        0).reshape(K * nq)
    item_of, j_of, ends = flat_items(
        counts, K * (nq * (ctx_span // cb) + nq * (nq + 1) // 2))
    return ((item_of // nq, item_of % nq, j_of,
             j_of == counts[item_of] - 1, ends[-1]), block_live, pblk)


@functools.partial(jax.jit,
                   static_argnames=("block", "ctx_span", "interpret",
                                    "heads", "key_rows", "mesh"))
def fused_prefill_attention(
    q: jnp.ndarray,          # [K, T, n_heads, hd]
    k_new: jnp.ndarray,      # [K, T, kvh, hd] — a K and a V head a group
    v_new: jnp.ndarray,      # [K, T, kvh, hd_v]   of n_heads / kvh heads
    q_starts: jnp.ndarray,   # [K] i32
    seq_lens: jnp.ndarray,   # [K] i32
    ctx: Optional[PriorContext] = None,   # an int8 region: the loops
    block: int = PREFILL_BLOCK,
    ctx_span: int = 0,
    interpret: Optional[bool] = None,   # tests, tools: the kernel itself,
                             # interpreted or not, whatever the platform
    heads: int = 0,          # query heads a grid step (tools sweep); 0:
                             # the kernel's own
    key_rows: bool = False,  # the kernel reads keys as ROWS [.., rows,
                             # hd]: the engine's own region, in place (a
                             # head of whole 128-lane tiles lies row-major
                             # on the chip). False: as COLUMNS, a latent
                             # model's workspace as XLA lays it out
    mesh: Optional[Mesh] = None,   # the kernel is mapped over this mesh's
                             # ``tp`` axis, a shard's K/V heads and their
                             # groups a device (heads are independent: no
                             # collective); None: unsharded operands
) -> jnp.ndarray:
    """``prefill_attention`` for the calls that hold no tree mask, no
    selection, no window and no int8 region: an EXPANDED latent chunk, K
    and V per head (models/mla_moe.py, the ``latent_attention`` kind of
    models/ssm_moe.py), and the dense decoder's GQA layers, a group of
    query heads a K/V head (models/llama.py). On a TPU it is ONE Mosaic
    call a layer (ops/flash_prefill.py) that walks the same (lane, query
    block) work list over the same key blocks, scores, probabilities and
    accumulator in VMEM; elsewhere (the CPU test meshes), and at a
    geometry outside ``prefill_fuses``, the XLA loops themselves. Same
    results to float32 rounding: rows of a query block with no live row
    and rows that see no key are 0. Jitted with the layer a VALUE, like
    the loops: a program's layers share one lowered body."""
    K, T, n_heads, _ = q.shape
    span = (ctx_span or ctx.k.shape[3]) if ctx is not None else 0

    def loops(q, k_new, v_new, q_starts, seq_lens, ctx):
        return prefill_attention(q, k_new, v_new, q_starts, seq_lens, ctx,
                                 block=block, ctx_span=ctx_span)

    if not prefill_fuses(T, n_heads, k_new.shape[2], span, block) or (
            ctx is not None and ctx.k_scale is not None):
        return loops(q, k_new, v_new, q_starts, seq_lens, ctx)

    def kernel(interpret, q, k_new, v_new, q_starts, seq_lens, ctx):
        i32 = jnp.int32
        blk, (nh, hd), kvh = min(block, T), q.shape[2:], k_new.shape[2]
        q_starts, seq_lens = q_starts.astype(i32), seq_lens.astype(i32)
        n_live = jnp.clip(seq_lens - q_starts, 0, T)
        below = jnp.minimum(jnp.minimum(q_starts, seq_lens), span)
        steps, block_live, pblk = prefill_steps(
            n_live, below, T, span, block)
        # keys as COLUMNS, the chunk's and the region's: [.., hd, rows]
        # is the layout XLA:TPU gives a [.., rows, 192] array by itself
        # (192 is no whole number of 128-lane tiles), so the transposed
        # view of the workspace is the buffer ``_expand_prior`` wrote,
        # where the row-major one was a copy of it a layer. The engine's
        # own region, rows of 128, lies row-major: ``key_rows`` reads it
        # as it lies, where the transposed view would be the copy
        region = None if ctx is None else (
            ctx.k if key_rows else ctx.k.swapaxes(3, 4), ctx.v, ctx.layer,
            ctx.slots, pblk, below)
        if nh == kvh:
            qt = q.transpose(0, 2, 1, 3)
        else:
            # a query block's group of heads one under the other: the
            # kernel's one [rep x blk, hd] operand a K/V head
            qt = q.reshape(K, T // blk, blk, kvh, nh // kvh, hd).transpose(
                0, 3, 1, 4, 2, 5).reshape(K, kvh, T * (nh // kvh), hd)
        out = flash_prefill_attention(
            qt,
            k_new.transpose((0, 2, 1, 3) if key_rows
                            else (0, 2, 3, 1)).astype(q.dtype),
            v_new.transpose(0, 2, 1, 3).astype(q.dtype),
            steps, n_live, region, block=blk,
            ctx_block=min(block, span), heads=heads, key_rows=key_rows,
            interpret=interpret)
        # no step wrote the tile of a query block with no live row
        rows = jnp.repeat(block_live, blk, axis=1)
        return jnp.where(rows[:, :, None], out, 0).reshape(
            K, T, nh, v_new.shape[3])

    kernel = functools.partial(kernel, bool(interpret))
    if mesh is not None:
        # q / out shard on heads, the chunks' K and V and the region on
        # K/V heads (the layouts llama.param / ctx_shardings already give
        # them); the step list is built from replicated values: every
        # shard walks the same list over its own heads
        by_head, kv = P(None, None, AXIS_TENSOR, None), P(
            None, AXIS_TENSOR, None, None, None)
        kernel = jax.shard_map(
            kernel, mesh=mesh,
            in_specs=(by_head, by_head, by_head, P(), P(),
                      None if ctx is None else PriorContext(
                          kv, kv, P(), P())),
            # pallas_call has no replication rule (ctx_decode_attention)
            out_specs=by_head, check_vma=False)
    if interpret is not None:
        return kernel(q, k_new, v_new, q_starts, seq_lens, ctx)
    return jax.lax.platform_dependent(
        q, k_new, v_new, q_starts, seq_lens, ctx, tpu=kernel, default=loops)


def dense_head_fuses(head_dim: int) -> bool:
    """Whether a dense layer's head lets ``dense_prefill_attention`` read
    the region's key rows in place: whole 128-lane tiles (the call site's
    rule and the host mirror's, ``llama.prefill_mirror``)."""
    return head_dim % 128 == 0


def dense_prefill_attention(
    attn: Optional[DecodeAttention],   # what the caller's programs are
                             # traced for, handed over as the round's is;
                             # None: a caller that says nothing of where
                             # its arrays live
    q: jnp.ndarray,          # [K, T, n_heads, hd]
    k_new: jnp.ndarray,      # [K, T, kvh, hd]
    v_new: jnp.ndarray,
    q_starts: jnp.ndarray,
    seq_lens: jnp.ndarray,
    ctx: Optional[PriorContext] = None,   # the engine's own region
    chunk_masks: Optional[jnp.ndarray] = None,
    ctx_span: int = 0,
) -> jnp.ndarray:
    """The prefill attention of a layer whose region holds a K and a V
    row a K/V head (the dense decoder's two call sites), by what the call
    holds and nothing else: the fused kernel where ``attn`` names one,
    reading the region's keys as the rows they are and mapped over the
    ``tp`` axis of ``attn.mesh`` where that shards the heads; the XLA
    loops for a tree mask (the speculative verifier), for the jnp
    reference of the CPU meshes, and for a caller that hands no ``attn``
    over (GSPMD partitions the loops wherever the arrays live; a bare
    Mosaic call it cannot), and for a head that is no whole 128-lane
    tiles (XLA holds such a region rows-minor: rows read in place are
    whole lanes). An int8 region and a width of no whole blocks keep the
    loops inside ``fused_prefill_attention``."""
    if (attn is None or attn.impl == REFERENCE_IMPL
            or chunk_masks is not None or not dense_head_fuses(q.shape[3])):
        return prefill_attention(q, k_new, v_new, q_starts, seq_lens, ctx,
                                 chunk_masks, ctx_span=ctx_span)
    sharded = attn.mesh is not None and attn.mesh.shape[AXIS_TENSOR] > 1
    return fused_prefill_attention(
        q, k_new, v_new, q_starts, seq_lens, ctx, ctx_span=ctx_span,
        interpret=True if attn.impl == PALLAS_INTERPRET else None,
        key_rows=True, mesh=attn.mesh if sharded else None)


def prefill_attention_pairs(
    width: int,               # T, the bucket
    q_starts,                 # per lane (dummies included)
    seq_lens,
    ctx_span: int = 0,        # region rows the program may read (the
                              # whole region for the solo program); 0 =
                              # the fresh program, which reads none
    block: int = PREFILL_BLOCK,
    causal: bool = True,
) -> tuple[int, int]:
    """Host-side count for one prefill dispatch, mirroring the loop
    bounds of ``prefill_attention``: (live, scored) (query, key) pairs —
    the pairs the mask admits for real prompt rows, and the pairs the
    program computes a score for (whole blocks)."""
    blk = min(block, width)
    live = scored = 0
    for q_start, seq_len in zip(q_starts, seq_lens):
        q_start, seq_len = int(q_start), int(seq_len)
        n = min(max(seq_len - q_start, 0), width)
        live += n * q_start + n * (n + 1) // 2
        nb = -(-n // blk)
        ctx_keys = 0
        if ctx_span:
            cb = min(block, ctx_span)
            ctx_keys = -(-min(q_start, seq_len, ctx_span) // cb) * cb
        for qb in range(nb):
            keys = min(qb + 1, nb) if causal else nb
            scored += blk * (ctx_keys + keys * blk)
    return live, scored
