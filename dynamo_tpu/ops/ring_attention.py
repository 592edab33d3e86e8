"""Ring attention: sequence-parallel causal attention over the `sp` mesh
axis for long-context prefill.

The reference has NO sequence/context parallelism (SURVEY §2.5 SP row:
grep found no ring/Ulysses code — its long-context story is engine-side
chunked prefill plus disaggregation). This module is the TPU-native
answer promised in SURVEY §7.11: shard the prompt across the `sp` axis,
keep Q resident, and rotate KV blocks around the ring with `ppermute`
(one ICI hop per step) while accumulating attention with the
log-sum-exp (flash) trick — O(T) memory per device, full-precision
equivalent to single-device causal attention.

Layout inside shard_map (per device): q/k/v are [Tl, heads, hd] where
Tl = T / sp. Device i owns global positions [i*Tl, (i+1)*Tl). At ring
step s it holds the KV block originally owned by device (i - s) mod sp;
block-level causality (owner <= mine, triangular when equal) masks the
contribution. bf16 inputs accumulate in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_off, k_off, scale):
    """Flash-style partial attention of one Q block against one KV block.
    Returns (scores_max [H, Tq], exp-sum [H, Tq], weighted values
    [H, Tq, hd]) for log-sum-exp accumulation. Masks by GLOBAL causal
    positions."""
    Tq = q.shape[0]
    Tk = k.shape[0]
    qt = q.transpose(1, 0, 2)                     # [H, Tq, hd]
    kt = k.transpose(1, 0, 2)
    vt = v.transpose(1, 0, 2)
    s = jnp.einsum("htd,hsd->hts", qt, kt,
                   preferred_element_type=jnp.float32) * scale
    q_pos = q_off + jnp.arange(Tq)[:, None]        # [Tq, 1]
    k_pos = k_off + jnp.arange(Tk)[None, :]        # [1, Tk]
    mask = k_pos <= q_pos
    s = jnp.where(mask[None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                        # [H, Tq]
    # fully-masked rows: keep m finite so exp() stays 0, not NaN
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m_safe[..., None])             # [H, Tq, Tk]
    l = jnp.sum(p, axis=-1)                        # [H, Tq]
    o = jnp.einsum("hts,hsd->htd", p, vt.astype(jnp.float32))
    return m_safe, l, o


def _ring_body(sp_size: int, axis: str, q, k, v, my_idx, Tl, scale):
    """The per-device ring loop (runs inside shard_map)."""
    H = q.shape[1]
    hd = q.shape[2]
    Tq = q.shape[0]
    q_off = my_idx * Tl

    # accumulators are per-device (sp-varying) state: mark them so the
    # fori_loop carry type matches the sharded outputs
    def _vary(x):
        return jax.lax.pcast(x, axis, to="varying")

    m0 = _vary(jnp.full((H, Tq), -1e29, jnp.float32))
    l0 = _vary(jnp.zeros((H, Tq), jnp.float32))
    o0 = _vary(jnp.zeros((H, Tq, hd), jnp.float32))
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

    def step(s, carry):
        m, l, o, k_blk, v_blk = carry
        owner = (my_idx - s) % sp_size
        bm, bl, bo = _block_attend(
            q, k_blk, v_blk, q_off, owner * Tl, scale
        )
        new_m = jnp.maximum(m, bm)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(bm - new_m)
        l = l * alpha + bl * beta
        o = o * alpha[..., None] + bo * beta[..., None]
        # rotate KV one hop around the ring (ICI neighbour exchange)
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return new_m, l, o, k_blk, v_blk

    m, l, o, _, _ = jax.lax.fori_loop(
        0, sp_size, step, (m0, l0, o0, k, v)
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]     # [H, Tq, hd]
    return out.transpose(1, 0, 2).astype(q.dtype)  # [Tq, H, hd]


@functools.lru_cache(maxsize=64)
def _build_ring(mesh: Mesh, axis: str, sp_size: int, Tl: int,
                scale: float):
    """Cached shard_map program per (mesh, axis, geometry) — rebuilding
    the closure per call would re-trace every layer of every prefill."""
    spec = P(axis, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def sharded(ql, kl, vl):
        my_idx = jax.lax.axis_index(axis)
        return _ring_body(sp_size, axis, ql, kl, vl, my_idx, Tl, scale)

    return sharded


def ring_attention(
    q: jnp.ndarray,   # [T, heads, hd] — sp-sharded on T
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
) -> jnp.ndarray:
    """Causal self-attention with the sequence sharded over `axis`.
    Numerically equivalent to single-device causal attention; each device
    keeps O(T/sp) KV and exchanges one block per ring step over ICI."""
    sp_size = mesh.shape[axis]
    T = q.shape[0]
    if T % sp_size:
        raise ValueError(f"sequence {T} not divisible by sp={sp_size}")
    Tl = T // sp_size
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    return _build_ring(mesh, axis, sp_size, Tl, scale)(q, k, v)


def sp_shard(x: jnp.ndarray, mesh: Mesh, axis: str = "sp") -> jnp.ndarray:
    """Place a [T, ...] array sharded over the sp axis."""
    return jax.device_put(
        x, NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))
    )
