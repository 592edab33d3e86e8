"""Block-sparse attention by SELECTION over compressed keys (InfLLM-V2,
the trainable sparse attention of arXiv:2506.07900), in plain XLA.

A K/V head keeps, beside its K and V rows, one COMPRESSED key every
``stride`` positions: ``kc_j = mean(k[stride j : stride j + kernel])``,
visible to a query at position t once its last row is (``stride j +
kernel - 1 <= t``). A query at t >= ``dense_len`` attends a SELECTION of
``block``-row blocks, shared by the query heads of its K/V group:

  * a head's ``p_h = softmax_j(q_h . kc_j / sqrt(hd))`` over the visible
    j; the group's ``s[j] = sum_h p_h[j]``; a block's score the max of
    ``s`` over the compressed keys whose rows overlap it;
  * chosen: the first ``init_blocks`` blocks, every block that overlaps
    the last ``window`` positions, and the best-scored others until
    ``topk`` blocks in all;
  * one softmax over the rows u <= t of the chosen blocks.

A query below ``dense_len`` attends every u <= t. The rule is per query
POSITION, so a chunked prefill followed by token-by-token decode computes
one function.

What is here: the geometry (``Geometry``), the compressed keys of a
prefill chunk (``compress_chunk``) and of a decode step
(``compress_step``), block scores from the ``s`` above
(``block_scores``), what the choice sorts by (``keyed``), the selection MASK of a
prefill chunk (``prefill_block_mask``, which ``prefill_attention`` then
scores under: the whole causal context is scored and masked, the host's
``prefill_pairs`` says what that leaves on the table) and the decode read
(``decode_attention``: the chosen blocks' rows GATHERED from the region
where they lie, never the whole context under a mask), with the host's
mirror of what it reads (``decode_rows``).

``kernel == 2 * stride`` and ``block % stride == 0`` (models/config.py
refuses anything else): a compressed key is the mean of two adjacent
``stride``-row group means, and a block is overlapped by the keys ``r b -
1 .. r b + r - 1`` with ``r = block / stride``. A prefill chunk starts at a
multiple of ``block`` (the engine's chunks are whole pages, and a page is
whole blocks).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
FORCED = 1e9     # above any score (a score is a sum of probabilities)


class Geometry(NamedTuple):
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    @classmethod
    def of(cls, sparse: dict) -> "Geometry":
        return cls(sparse["kernel_size"], sparse["kernel_stride"],
                   sparse["block_size"], sparse["topk"],
                   sparse["init_blocks"], sparse["window_size"],
                   sparse["dense_len"])


def compress_chunk(g: Geometry, k_new, tail):
    """The compressed keys a chunk completes. ``k_new`` [T, kvh, hd] the
    chunk's keys at positions q_start .. q_start + T (q_start a multiple
    of ``stride``), ``tail`` [stride, kvh, hd] the ``stride`` keys before
    them. Returns [T / stride, kvh, hd] for j = q_start / stride - 1
    onward (the first is no key when q_start is 0: the caller drops it)."""
    T, kvh, hd = k_new.shape
    f32 = jnp.float32
    rows = jnp.concatenate([tail.astype(k_new.dtype), k_new], axis=0)
    m = rows.astype(f32).reshape(T // g.stride + 1, g.stride, kvh, hd).mean(1)
    return (0.5 * (m[:-1] + m[1:])).astype(k_new.dtype)


def overlay(kc_lane, kc_new, j0):
    """``kc_lane`` [kvh, Sc, hd] with ``kc_new`` [n, kvh, hd] written at
    rows j0 .. j0 + n; j0 may be -1 (that row falls off)."""
    buf = jnp.pad(kc_lane, ((0, 0), (1, 0), (0, 0)))
    buf = jax.lax.dynamic_update_slice(
        buf, kc_new.transpose(1, 0, 2).astype(buf.dtype), (0, j0 + 1, 0))
    return buf[:, 1:]


def block_scores(g: Geometry, s):
    """``s`` [..., Sc] (a group's summed probabilities a compressed key)
    -> [..., NB]: a block's score, the max over the keys that overlap
    it."""
    r = g.block // g.stride
    nb = s.shape[-1] // r
    grouped = s[..., :nb * r].reshape(*s.shape[:-1], nb, r)
    before = jnp.pad(grouped[..., :-1, r - 1],
                     [(0, 0)] * (s.ndim - 1) + [(1, 0)])
    return jnp.maximum(grouped.max(-1), before)


def keyed(g: Geometry, score, t):
    """``score`` [..., NB] and the query positions ``t`` (broadcast
    against score[..., 0]) -> the value the choice sorts by: FORCED for
    the leading and the window's blocks, -1 for a block that starts after
    t, else the score."""
    nb = score.shape[-1]
    b0 = jnp.arange(nb, dtype=jnp.int32) * g.block
    t = t[..., None]
    forced = (b0 < g.init_blocks * g.block) | (
        (b0 + g.block - 1 >= t - g.window + 1))
    key = jnp.where(forced, FORCED, score)
    return jnp.where(b0 <= t, key, -1.0)


def group_probs(g: Geometry, q, kc, t):
    """``q`` [..., rep, hd] of one K/V group, ``kc`` [..., Sc, hd] its
    compressed keys, ``t`` [...] the query positions -> s [..., Sc]
    float32: the heads' softmax over the visible keys, summed."""
    hd = q.shape[-1]
    logits = jnp.einsum("...rh,...jh->...rj", q, kc,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    j = jnp.arange(kc.shape[-2], dtype=jnp.int32)
    visible = (j * g.stride + g.kernel - 1 <= t[..., None])[..., None, :]
    logits = jnp.where(visible, logits, NEG_INF)
    p = jnp.exp(logits - logits.max(-1, keepdims=True))
    p = jnp.where(visible, p, 0.0)
    return (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(-2)


def prefill_block_mask(g: Geometry, q, kc_lane, q_start, n_live,
                       q_block: int = 256):
    """The selection of one lane's chunk as a mask. ``q`` [T, kvh, rep,
    hd], ``kc_lane`` [kvh, Sc, hd] (the lane's compressed keys, this
    chunk's included), ``q_start`` and ``n_live`` scalars. Returns [kvh,
    T, NB] bool: may query row i read block b. Rows below ``dense_len``
    and rows past the live length read every block (causality is the
    attention's own mask). Query blocks are walked in a loop whose trip
    count follows the live rows at or past ``dense_len``."""
    T, kvh, rep, hd = q.shape
    nb = kc_lane.shape[1] * g.stride // g.block
    blk = min(q_block, T)
    qt = q.transpose(1, 0, 2, 3)                       # [kvh, T, rep, hd]

    def one(i, mask):
        q0 = jnp.minimum(i * blk, T - blk)
        t = q_start + q0 + jnp.arange(blk, dtype=jnp.int32)
        q_blk = jax.lax.dynamic_slice(qt, (0, q0, 0, 0), (kvh, blk, rep, hd))
        s = group_probs(g, q_blk, kc_lane[:, None], t[None, :])
        key = keyed(g, block_scores(g, s), t[None, :])   # [kvh, blk, NB]
        # the chosen ids, not a threshold: adjacent blocks share the
        # compressed key between them, so equal scores are common, and
        # top_k breaks a tie by the lower index (as a stable argsort does)
        _, ids = jax.lax.top_k(key, g.topk)              # [kvh, blk, topk]
        sel = (ids[..., None] == jnp.arange(nb, dtype=ids.dtype)).any(-2)
        sel = sel | (t < g.dense_len)[None, :, None]
        return jax.lax.dynamic_update_slice(mask, sel, (0, q0, 0))

    first = jnp.clip(g.dense_len - q_start, 0, T) // blk
    last = (jnp.clip(n_live, 0, T) + blk - 1) // blk
    return jax.lax.fori_loop(first, last, one,
                             jnp.ones((kvh, T, nb), bool))


def prefill_pairs(g: Geometry, q_starts, seq_lens, width: int) -> int:
    """Host-side: the (query, key) pairs the selection ADMITS for the
    real rows of a prefill dispatch, one layer (what a prefill that
    gathered the chosen blocks would score, against the pairs
    ``prefill_attention`` scores under the mask)."""
    total = 0
    for q_start, seq_len in zip(q_starts, seq_lens):
        lo, hi = int(q_start), min(int(seq_len), int(q_start) + width)
        if hi <= lo:
            continue
        t = np.arange(lo, hi, dtype=np.int64)
        chosen = (g.topk - 1) * g.block + t % g.block + 1
        total += int(np.where(t < g.dense_len, t + 1, chosen).sum())
    return total


def compress_step(g: Geometry, ctx_k, ring_k, kc, layer: int,
                  kc_layer: int, t, ring_base, live):
    """The compressed key a decode step may complete, written where it
    belongs: for every LIVE lane whose newest position ``t`` [B] (already
    in the ring) ends a key, the mean of the ``kernel`` keys that end
    there, read from the ring where they lie at or past ``ring_base`` [B]
    and from the region ``ctx_k`` below it, goes into row ``(t + 1 -
    kernel) / stride`` of the lane in ``kc`` [L_sparse, kvh, lanes, Sc,
    hd]; every other row comes back bit for bit. One slice of the region
    and one in-place row a lane, unrolled over the lanes (a gather over
    (lane, position) or a rolled loop makes XLA:TPU relayout the K
    region every step)."""
    B = t.shape[0]
    kvh, hd = ctx_k.shape[1], ctx_k.shape[4]
    R = ring_k.shape[3]
    done = live & (t >= g.kernel - 1) & ((t + 1 - g.kernel) % g.stride == 0)
    row = jnp.maximum(t + 1 - g.kernel, 0) // g.stride
    first = jnp.maximum(t - g.kernel + 1, 0)

    for b in range(B):
        old = jax.lax.dynamic_slice(
            ctx_k, (layer, 0, b, first[b], 0),
            (1, kvh, 1, g.kernel, hd))[0, :, 0]           # [kvh, kernel, hd]
        pos = first[b] + jnp.arange(g.kernel, dtype=jnp.int32)
        new = jax.lax.dynamic_slice(
            ring_k, (layer, 0, b, 0, 0), (1, kvh, 1, R, hd))[0, :, 0]
        new = jnp.take(new, jnp.clip(pos - ring_base[b], 0, R - 1), axis=1)
        rows = jnp.where((pos >= ring_base[b])[None, :, None],
                         new.astype(old.dtype), old)
        mean = rows.astype(jnp.float32).mean(1).astype(kc.dtype)
        at = (kc_layer, 0, b, row[b], 0)
        was = jax.lax.dynamic_slice(kc, at, (1, kvh, 1, 1, hd))
        kc = jax.lax.dynamic_update_slice(
            kc, jnp.where(done[b], mean[None, :, None, None, :], was), at)
    return kc


def decode_attention(g: Geometry, q, ctx_k, ctx_v, ctx_kc, ring_k, ring_v,
                     layer: int, kc_layer: int, ctx_lens, ring_base, on):
    """One new token a lane over its SELECTED blocks, for the lanes that
    are ``on`` [B] (live, and at or past ``dense_len``: the caller keeps
    the dense read for the others). ``q`` [B, nh, hd]; ``ctx_k`` /
    ``ctx_v`` [L, kvh, lanes, S, hd] and ``ctx_kc`` [L, kvh, lanes, S /
    stride, hd] the region (the step's own compressed key already in it);
    the ring [L, kvh, B, R, hd] with the current token in it; ``layer`` /
    ``kc_layer`` this layer's index in the K/V leaves / in ``ctx_kc``;
    ``ctx_lens`` [B] the context INCLUDING the current token. Returns
    ([B, nh, hd], the chosen block ids [B, kvh, topk]); zeros for a lane
    that is not ``on``.

    A lane at a time, each behind a ``cond`` that skips a lane that is not
    ``on``: such a lane's compressed keys are not scored and no row of it
    is read. For a lane that is, the chosen blocks' rows are gathered from
    the region in place (``topk x block`` rows a group, whatever the
    context); the region's rows at or past ``ring_base`` are stale and
    masked, the ring's rows stand for them (the window's blocks are always
    chosen, and the ring lies inside the window).

    Unrolled over the lanes with a ``cond`` each because that is the XLA
    form that reads nothing for a lane that is not ``on`` and relayouts no
    region leaf; each executed branch is still fed a copy of ``ctx_kc``
    (PERF.md section 6, PR 45, has the forms measured, section 7 the
    kernel over a (lane, group, block) work list that would replace it)."""
    B, nh, hd = q.shape
    L, kvh, lanes, S, _ = ctx_k.shape
    rep = nh // kvh
    nb = S // g.block
    qg = q.reshape(B, kvh, rep, hd)
    head = jnp.arange(kvh, dtype=jnp.int32)
    i32 = jnp.int32

    outs = []
    for b in range(B):
        def read(b=b):
            t = ctx_lens[b:b + 1] - 1                               # [1]
            q_b = qg[b:b + 1]
            with jax.named_scope("sparse_select"):
                # a gather of the lane's rows (a static slice of the
                # leaf inside a branch is a copy of the whole leaf)
                kc = ctx_kc[kc_layer, head[None, :], jnp.full((1, 1), b)]
                s = group_probs(g, q_b, kc.astype(q.dtype), t[:, None])
                key = keyed(g, block_scores(g, s), t[:, None])
                _, chosen = jax.lax.top_k(key, g.topk)       # [1, kvh, topk]

            def rows(buf):
                blocks = buf.reshape(L, kvh, lanes, nb, g.block,
                                     buf.shape[4])
                return blocks[layer, head[None, :, None], b, chosen]

            with jax.named_scope("sparse_attn"):
                o_b = _attend(g, q_b, rows(ctx_k), rows(ctx_v), chosen,
                              ring_k[layer, :, b:b + 1],
                              ring_v[layer, :, b:b + 1], t + 1,
                              ring_base[b:b + 1])
            return o_b, chosen.astype(i32)

        def skip():
            return (jnp.zeros((1, nh, ctx_v.shape[4]), q.dtype),
                    jnp.zeros((1, kvh, g.topk), i32))

        outs.append(jax.lax.cond(on[b], read, skip))
    return (jnp.concatenate([o for o, _ in outs]),
            jnp.concatenate([ids for _, ids in outs]))


def _attend(g: Geometry, qg, k_sel, v_sel, ids, ring_k, ring_v, ctx_lens,
            ring_base):
    """``qg`` [B, kvh, rep, hd] over the gathered rows ``k_sel`` /
    ``v_sel`` [B, kvh, topk, block, hd] of blocks ``ids`` and one layer's
    ring [kvh, B, R, hd]: one softmax, [B, nh, hd]."""
    B, kvh, rep, hd = qg.shape
    q = qg
    R = ring_k.shape[2]
    pos = ids[..., None] * g.block + jnp.arange(g.block, dtype=jnp.int32)
    ok = pos < jnp.minimum(ring_base, ctx_lens)[:, None, None, None]
    scale = 1.0 / np.sqrt(hd)
    f32 = jnp.float32
    s_sel = jnp.einsum("bgrh,bgnkh->bgrnk", qg, k_sel.astype(q.dtype),
                       preferred_element_type=f32) * scale
    s_sel = jnp.where(ok[:, :, None], s_sel, NEG_INF).reshape(
        B, kvh, rep, g.topk * g.block)
    rk = ring_k.transpose(1, 0, 2, 3).astype(q.dtype)    # [B, kvh, R, hd]
    rv = ring_v.transpose(1, 0, 2, 3).astype(q.dtype)
    s_ring = jnp.einsum("bgrh,bgkh->bgrk", qg, rk,
                        preferred_element_type=f32) * scale
    ring_ok = (ring_base[:, None] + jnp.arange(R, dtype=jnp.int32)
               < ctx_lens[:, None])
    s_ring = jnp.where(ring_ok[:, None, None, :], s_ring, NEG_INF)
    s_all = jnp.concatenate([s_sel, s_ring], axis=-1)
    p = jax.nn.softmax(s_all, axis=-1).astype(q.dtype)
    v_all = jnp.concatenate(
        [v_sel.astype(q.dtype).reshape(B, kvh, g.topk * g.block, -1), rv],
        axis=2)
    o = jnp.einsum("bgrk,bgkh->bgrh", p, v_all, preferred_element_type=f32)
    return o.astype(q.dtype).reshape(B, kvh * rep, -1)


def decode_rows(g: Geometry, ctx_lens, live, region_rows: int,
                ring_rows: int) -> tuple[int, int]:
    """Host-side mirror of one decode step of one sparse layer: (rows
    read, rows live). Read: a live lane at or past ``dense_len`` takes its
    compressed keys (the whole ``region_rows / stride`` of them are
    scored, in their own rows), ``topk x block`` region rows and the ring;
    a live lane below it its own rows (the dense read); a lane that is not
    live nothing (``decode_attention`` skips it). Live: what a dense read
    of the live lanes would take."""
    read = live_rows = 0
    for n, on in zip(ctx_lens, live):
        if not on:
            continue
        n = int(n)
        live_rows += n
        read += (region_rows // g.stride + g.topk * g.block + ring_rows
                 if n - 1 >= g.dense_len else n)
    return read, live_rows


def round_rows(g: Geometry, ctx_lens, live, n_steps: int, region_rows: int,
               ring_rows: int) -> tuple[int, int]:
    """``decode_rows`` over the ``n_steps`` of one dispatched round, one
    sparse layer: the lanes' lengths move on by one a step."""
    read = rows = 0
    for s in range(n_steps):
        a, b = decode_rows(g, ctx_lens + s, live, region_rows, ring_rows)
        read, rows = read + a, rows + b
    return read, rows
