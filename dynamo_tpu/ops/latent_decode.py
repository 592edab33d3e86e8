"""Decode attention over latent (MLA) cache rows, absorbed form.

Every query head of a lane reads the SAME cached row per position
(``[c_kv | k_rope]``): the row is the key, and its first ``v_width``
values are the value. One query token per lane; context = the lane's rows
of the ctx region below ``ring_base`` plus the current round's rows in the
write ring (models/llama.py: init_ring).

Pure XLA, running softmax over chunks of the region. The chunk loop's
trip count is a traced value, the longest live context of the batch over
the chunk, so the step reads the region up to the longest live lane and
not its whole length; a lane's rows past its own length are masked. The
region is sliced in place per chunk (layer index and chunk start are
values): no per-layer slab and no relayout of the region is made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
CHUNK = 256


def latent_decode_attention(
    q: jnp.ndarray,          # [B, nh, row] — absorbed, already scaled
    ctx: jnp.ndarray,        # [L, 1, B(+1), S, row]
    ring: jnp.ndarray,       # [L, 1, B, R, row]
    layer: jnp.ndarray,      # scalar i32
    ctx_lens: jnp.ndarray,   # [B] i32 — context length INCL. current token
    ring_base: jnp.ndarray,  # [B] i32 — position held by ring slot 0
    v_width: int,            # leading values of a row that are the value
    chunk: int = CHUNK,
) -> jnp.ndarray:
    """Returns [B, nh, v_width]: softmax(q . rows) weighted sum of the
    rows' value parts, float32 scores and accumulation."""
    B, nh, row = q.shape
    S, R = ctx.shape[3], ring.shape[3]
    cb = min(chunk, S)
    i32 = jnp.int32
    layer = jnp.asarray(layer, i32)
    below = jnp.minimum(ring_base, ctx_lens).astype(i32)   # region rows

    def score(carry, rows, ok):
        """rows [B, n, row] under ok [B, n]."""
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

    def region_chunk(j, carry):
        k0 = jnp.minimum(j * cb, S - cb)
        pos = k0 + jnp.arange(cb, dtype=i32)
        rows = jax.lax.dynamic_slice(
            ctx, (layer, 0, 0, k0, 0), (1, 1, B, cb, row))[0, 0]
        ok = (pos[None, :] >= j * cb) & (pos[None, :] < below[:, None])
        return score(carry, rows.astype(q.dtype), ok)

    carry = (jnp.full((B, nh), NEG_INF, jnp.float32),
             jnp.zeros((B, nh), jnp.float32),
             jnp.zeros((B, nh, v_width), jnp.float32))
    carry = jax.lax.fori_loop(
        0, (jnp.max(below) + cb - 1) // cb, region_chunk, carry)

    rows = jax.lax.dynamic_slice(
        ring, (layer, 0, 0, 0, 0), (1, 1, B, R, row))[0, 0]
    rpos = ring_base[:, None] + jnp.arange(R, dtype=i32)[None, :]
    m, l, acc = score(carry, rows.astype(q.dtype), rpos < ctx_lens[:, None])
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    # a lane with no visible row (ctx_len 0) holds exp(0) per masked key
    o = jnp.where((m > NEG_INF / 2)[..., None], o, 0.0)
    return o.astype(q.dtype)
