"""Pallas TPU kernel: the blocked running-softmax PREFILL attention of
``ops/attention.py: prefill_attention`` as ONE call a layer, under plain
causality: no tree mask, no selection, no window, no int8 region. For
chunks whose K and V come per head (``rep`` 1: an expanded latent chunk,
32 heads of 192 / 128 values, keys as COLUMNS) and for the dense decoder's
GQA layers (PR 63): a GROUP of ``rep = n_heads / kv_heads`` query heads
shares its K/V head's tile in VMEM, the group's score ONE ``[rep x blk,
hd] x [hd, blk]`` product and its probabilities ONE ``[rep x blk, blk] x
[blk, hd_v]`` (the loops' own form: K and V fetched and pushed to the MXU
once a group, never repeated), the prior context read from the engine's
own region ``[L, kvh, lanes, S, hd]`` in place, keys as the ROWS they are
(``key_rows``: a score contracts both operands on hd; a head of 128 is
whole lanes and lies row-major on the chip, so the transposed view a
column read needs would be a copy of the region a layer).

Why. The XLA form's ``score`` step is two fusions a key block, and the
``[heads, 256, 256]`` float32 scores leave the first and come back into
the second through HBM: 8.4 MB each way a step of 1.3 GFLOP, 26 us where
the products take 6.8 at the MXU's peak (PERF.md section 6, PR 61: one
layer of a 4096-row chunk over 4096 prior rows took 10.2 ms at ~26 % of
the peak). Here a (lane, query block)'s scores, probabilities, running
max / sum and float32 accumulator never leave VMEM, and its result is
the kernel's own out block: an aligned tile, where the loop's
``dynamic_update_slice`` landed at an offset XLA could not see is one.

How. The SAME work the loop does, as a flat list of STEPS built by the
caller (``attention.fused_prefill_attention``): for every (lane, query
block) pair with a live row, first the lane's prior blocks below
``min(q_start, seq_len, span)``, read from the region (a latent model's
expanded workspace) in place, then the chunk's own blocks up to the causal
diagonal and the live length. The list rides in as scalar prefetch and its
LENGTH, a traced value, bounds the grid (the pattern of
``ops/flash_decode.py``): a grid step is one (query block, key block) pair
for a block of heads, every BlockSpec's index map reads the step's lane
and blocks, so Mosaic's own pipeline fetches the next step's K and V tiles
while this one is scored (a block of K/V heads with their groups where
heads share them: the running max / sum / accumulator stay per query head,
a group's heads one under the other). A step of the prior names the
chunk's first block (which the item needs next) and a step of the chunk
names the prior's last block again: neither is fetched twice. The step
that ends an item normalises and writes its out tile; a query block with
no live row is never on the list, costs nothing, and its tile is never
written (the caller makes it 0).

The mathematics is ``prefill_attention``'s ``score``, to the operation:
float32 scores x 1/sqrt(hd), ``NEG_INF`` masking, float32 max / sum /
accumulator, probabilities cast to the value dtype before P.V,
``acc / max(l, 1e-30)`` at the end, a row that saw no key emits 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# heads a grid step scores (the outer grid axis walks the head blocks, each
# over the whole list). Chosen on the chip (PERF.md section 6, PR 61): a
# grid step costs ~0.42 us of its own beside ~0.28 us a head of 256 x 256
# pairs, so a 32-head step of the long-context cell's 4096-row chunk over
# 4096 prior rows took 12.4 / 10.7 / 9.9 us at 4 / 8 / 16 heads (23.0 in
# the XLA loops); 16 heads hold ~21 MiB of VMEM in tiles and statistics
HEAD_BLOCK = 16
# ... and where a group of query heads shares its K/V head (the dense
# decoder's layers, heads of 128): a step pushes a K/V tile to the MXU once
# a group, and a 32-head step of a 4096-row bucket with 2560 live rows took
# 12.2 / 11.0 us at 16 / 32 query heads a step, 9.3 / 8.2 over 2048 prior
# rows, against 20.5 / 16.9 in the XLA loops (PERF.md section 6, PR 63);
# 32 heads of 128 hold ~26 MiB
GROUP_HEAD_BLOCK = 32


def head_block(kv_heads: int, want: int = 0, rep: int = 1) -> int:
    """K/V heads a grid step holds, each with its ``rep`` query heads:
    ``HEAD_BLOCK`` query heads (``GROUP_HEAD_BLOCK`` where groups share a
    K/V head; or as many as a tool names), fitted to a divisor of the K/V
    heads."""
    want = want or (HEAD_BLOCK if rep == 1 else GROUP_HEAD_BLOCK)
    want = max(1, min(want // rep, kv_heads))
    return next(h for h in range(want, 0, -1) if kv_heads % h == 0)


def _kernel(
    # scalar prefetch
    layer_ref,   # [1] i32
    total_ref,   # [1] i32 — steps on the list
    lane_ref,    # [W] i32 — step -> lane (chunk)
    qb_ref,      # [W] i32 — step -> query block of that lane
    j_ref,       # [W] i32 — step -> which of its item's steps, ascending
    last_ref,    # [W] i32 — 1: the item's last step
    slot_ref,    # [K] i32 — lane -> its lane of the region (the index
                 #           maps')
    pblk_ref,    # [K] i32 — lane -> its prior blocks
    below_ref,   # [K] i32 — lane -> its prior rows
    live_ref,    # [K] i32 — lane -> its live chunk rows
    # blocks, the step's: H K/V heads, each with the ``rep`` query heads
    # of its group (rep 1: K and V per head)
    q_ref,       # [1, H, rep x blk, hd] — a group's query heads one under
                 # the other: row r blk + i is row i of its r-th head
    *refs,       # with a region: pk_ref [1, H, 1, hd, cb] (keys as
                 # COLUMNS; ``key_rows``: [1, H, 1, cb, hd], as the
                 # engine's region holds them), pv_ref [1, H, 1, cb,
                 # hd_v]; then k_ref [1, H, hd, blk] ([1, H, blk, hd]),
                 # v_ref [1, H, blk, hd_v], o_ref [1, blk, H x rep x hd_v]
                 # (query head h's values at columns [h hd_v, +hd_v));
                 # scratch m_ref, l_ref [H, rep x blk, W] f32 (W = 128
                 # lanes; 1 at widths that are no whole lanes: tests),
                 # acc_ref [H, rep x blk, hd_v] f32
    scale: float,
    with_ctx: bool,
    key_rows: bool,
):
    if with_ctx:
        pk_ref, pv_ref = refs[:2]
        refs = refs[2:]
    k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    H, blk = q_ref.shape[1], v_ref.shape[2]
    rep = q_ref.shape[2] // blk
    hd_v, W = v_ref.shape[3], m_ref.shape[2]
    w = pl.program_id(1)
    lane, qb, j = lane_ref[w], qb_ref[w], j_ref[w]
    # an empty list still runs one grid step: it does nothing
    listed = total_ref[0] > 0
    pblk = pblk_ref[lane] if with_ctx else 0

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def score(k_of, v_of, ok):
        # one running-softmax step a K/V head: its group's q [rep x blk,
        # hd] against k [hd, n] / v [n, hd_v] under ok ([rep x blk, n] or
        # [1, n]), the heads' one mask: ONE Q.K and ONE P.V product a
        # group, the K and V tile held once for its ``rep`` query heads.
        #
        # The running max rides REPLICATED over its 128 lanes and the
        # running sum as 128 lane-wise PARTIAL sums a row (their one
        # cross-lane sum waits for the item's last step): a [blk, 1]
        # column read back from VMEM is a lane broadcast a use, and the
        # cross-lane unit, not the MXU, was what a step waited for.
        #
        # In program order head h + 1's Q.K product comes BEFORE head h is
        # normalised: the MXU's operations keep their order, so it scores
        # the next head while the vector units take this one's max / exp /
        # sum, where head after head left each unit waiting for the other
        # (658 -> 402 bundles a head in the compiler's static schedule,
        # against 384 of MXU passes; 13.6 -> 10.7 us a 32-head step on the
        # chip at 8 heads a step)
        def qk(h):
            if key_rows:   # keys as the region holds them: contract on hd
                return jax.lax.dot_general(
                    q_ref[0, h], k_of(h), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return jnp.dot(q_ref[0, h], k_of(h),
                           preferred_element_type=jnp.float32)

        s_next = qk(0)
        for h in range(H):
            s = jnp.where(ok, s_next * scale, NEG_INF)
            if h + 1 < H:
                s_next = qk(h + 1)
            n = s.shape[1]
            m_prev = m_ref[h]                              # [blk, W]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - jnp.tile(m_new, (1, n // W)))
            l_ref[h] = l_ref[h] * alpha + sum(
                p[:, i:i + W] for i in range(0, n, W))
            v = v_of(h)
            acc_ref[h] = acc_ref[h] * jnp.tile(alpha, (1, hd_v // W)) + (
                jnp.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32))
            m_ref[h] = m_new

    if with_ctx:
        # a prior block: region rows [j cb, +cb), valid below the lane's
        # prior rows
        @pl.when(jnp.logical_and(listed, j < pblk))
        def _():
            cb = pv_ref.shape[3]
            pos = j * cb + jax.lax.broadcasted_iota(jnp.int32, (1, cb), 1)
            score(lambda h: pk_ref[0, h, 0], lambda h: pv_ref[0, h, 0],
                  pos < below_ref[lane])

    # a block of the chunk itself: keys below the live length, at or below
    # the query's own row
    @pl.when(jnp.logical_and(listed, j >= pblk))
    def _():
        kp = (j - pblk) * blk + jax.lax.broadcasted_iota(
            jnp.int32, (rep * blk, blk), 1)
        q0 = qb * blk
        rows = jax.lax.broadcasted_iota(jnp.int32, (rep * blk, blk), 0)
        if rep > 1:   # row r blk + i of a group is row i of a head
            rows = jax.lax.rem(rows, blk)
        rows = q0 + rows
        score(lambda h: k_ref[0, h], lambda h: v_ref[0, h],
              (kp < live_ref[lane]) & (kp <= rows))

    @pl.when(jnp.logical_and(listed, last_ref[w] == 1))
    def _():
        for h in range(H):
            l = jnp.sum(l_ref[h], axis=1, keepdims=True)
            o = acc_ref[h] / jnp.maximum(l, 1e-30)
            # a row that met no unmasked score holds p = exp(0) per masked
            # key (NEG_INF is finite): gate on the running max, emit zeros
            o = jnp.where(
                jnp.tile(m_ref[h], (1, hd_v // W)) > NEG_INF / 2, o,
                0.0).astype(o_ref.dtype)
            for r in range(rep):
                at = (h * rep + r) * hd_v
                o_ref[0, :, at:at + hd_v] = (
                    o if rep == 1 else o[r * blk:(r + 1) * blk])


def flash_prefill_attention(
    qt: jnp.ndarray,         # [K, kvh, rep x T, hd] — K/V-head-major
                             # queries, a query block's ``rep`` heads one
                             # under the other: row (qb rep + r) blk + i is
                             # row qb blk + i of the group's r-th head
                             # (rep 1: [K, nh, T, hd], head-major)
    kt: jnp.ndarray,         # [K, kvh, hd, T] — the chunks' own keys, as
                             # COLUMNS: a score is q . k with no transpose
                             # (``key_rows``: [K, kvh, T, hd])
    vt: jnp.ndarray,         # [K, kvh, T, hd_v]
    steps: tuple,            # the step list: (lane_of, qb_of, j_of,
                             # last_of, total)
    live: jnp.ndarray,       # [K] i32 — live rows of each chunk
    region: tuple | None = None,   # (k [L, kvh, lanes, hd, S], v [L,
                             # kvh, lanes, S, hd_v], layer, slots [K],
                             # pblk [K], below [K]); None: fresh chunks,
                             # no region read (``key_rows``: k [L, kvh,
                             # lanes, S, hd], the engine's own region)
    *,
    block: int,              # query / key rows a block (divides T)
    ctx_block: int = 0,      # region rows a block (divides the rows read)
    heads: int = 0,          # query heads a grid step; 0: HEAD_BLOCK
    key_rows: bool = False,  # keys lie as ROWS [.., rows, hd], the chunks'
                             # and the region's, and a score contracts
                             # both operands on hd
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [K, T, nh x hd_v], the query heads' values side by side as
    the caller's next product wants them: the tiles of the listed (lane,
    query block) pairs; the tile of a pair that is not on the list is
    NEVER WRITTEN (``attention.fused_prefill_attention``, which also
    builds the list, makes it 0)."""
    K, kvh, _, hd = qt.shape
    T, hd_v = vt.shape[2], vt.shape[3]
    rep = qt.shape[2] // T
    H = head_block(kvh, heads, rep)
    lane_of, qb_of, j_of, last_of, total = steps
    i32 = jnp.int32
    total = jnp.asarray(total, i32).reshape(())
    zeros = jnp.zeros(K, i32)
    # the running statistics' width: the lanes of a vector register where
    # every width they meet is whole lanes, a column else (toy widths)
    lanes = 128 if all(n % 128 == 0 for n in (
        block, ctx_block or block, hd_v)) else 1
    with_ctx = region is not None
    if with_ctx:
        pk, pv, layer, slots, pblk, below = region
    else:
        layer, slots, pblk, below = jnp.int32(0), zeros, zeros, zeros

    def of_item(hb, w, layer, total, lane_of, qb_of, *_):
        return (lane_of[w], hb, qb_of[w], 0)

    def out_map(hb, w, layer, total, lane_of, qb_of, *_):
        return (lane_of[w], qb_of[w], hb)

    def prior_block(w, lane_of, j_of, pblk):
        # a step of the chunk names the prior's last block again: no DMA
        return jnp.minimum(j_of[w], jnp.maximum(pblk[lane_of[w]] - 1, 0))

    def chunk_block(w, lane_of, j_of, pblk):
        # a step of the prior names the chunk's first block: the item's
        # next fetch, in flight early
        return jnp.maximum(j_of[w] - pblk[lane_of[w]], 0)

    def prior_k(hb, w, layer, total, lane_of, qb_of, j_of, last_of, slots,
                pblk, *_):
        return (layer[0], hb, slots[lane_of[w]], 0,
                prior_block(w, lane_of, j_of, pblk))

    def prior_v(hb, w, layer, total, lane_of, qb_of, j_of, last_of, slots,
                pblk, *_):
        return (layer[0], hb, slots[lane_of[w]],
                prior_block(w, lane_of, j_of, pblk), 0)

    def chunk_k(hb, w, layer, total, lane_of, qb_of, j_of, last_of, slots,
                pblk, *_):
        return (lane_of[w], hb, 0, chunk_block(w, lane_of, j_of, pblk))

    def chunk_v(hb, w, layer, total, lane_of, qb_of, j_of, last_of, slots,
                pblk, *_):
        return (lane_of[w], hb, chunk_block(w, lane_of, j_of, pblk), 0)

    in_specs = [pl.BlockSpec((1, H, rep * block, hd), of_item)]
    inputs = [qt]
    if key_rows:   # a key tile has a value tile's shape, at its place
        prior_keys = pl.BlockSpec((1, H, 1, ctx_block, hd), prior_v)
        chunk_keys = pl.BlockSpec((1, H, block, hd), chunk_v)
    else:
        prior_keys = pl.BlockSpec((1, H, 1, hd, ctx_block), prior_k)
        chunk_keys = pl.BlockSpec((1, H, hd, block), chunk_k)
    if with_ctx:
        in_specs += [prior_keys,
                     pl.BlockSpec((1, H, 1, ctx_block, hd_v), prior_v)]
        inputs += [pk, pv]
    in_specs += [chunk_keys, pl.BlockSpec((1, H, block, hd_v), chunk_v)]
    inputs += [kt, vt]

    return pl.pallas_call(
        functools.partial(_kernel, scale=float(1.0 / (hd ** 0.5)),
                          with_ctx=with_ctx, key_rows=key_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            # the list's length, a traced value, bounds the grid: a step
            # past the list costs nothing (never an empty grid: what a
            # pipeline with no step writes back is nobody's promise)
            grid=(kvh // H, jnp.maximum(total, 1)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block, H * rep * hd_v), out_map),
            scratch_shapes=[
                pltpu.VMEM((H, rep * block, lanes), jnp.float32),
                pltpu.VMEM((H, rep * block, lanes), jnp.float32),
                pltpu.VMEM((H, rep * block, hd_v), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((K, T, kvh * rep * hd_v), qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="flash_prefill_attention",
    )(
        jnp.asarray(layer, i32).reshape(1), total.reshape(1),
        lane_of.astype(i32), qb_of.astype(i32), j_of.astype(i32),
        last_of.astype(i32), slots.astype(i32), pblk.astype(i32),
        below.astype(i32), live.astype(i32), *inputs,
    )
