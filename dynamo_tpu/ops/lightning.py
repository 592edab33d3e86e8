"""Linear ("lightning") attention with a per-head decay, in plain XLA: a
chunked form for prefill and a one-step form for decode (Lightning
Attention-2, arXiv:2401.04658).

Per head h (D values wide) with a matrix state ``S`` [D, D], over time::

    S_t = lam_h S_{t-1} + k_t^T v_t            o_t = q_t S_t

no softmax and no normaliser; ``q`` arrives scaled. ``lam_h`` < 1 is a
constant of the head (``log_decay``: its logarithm, negative), so a head
remembers over ``1 / (1 - lam_h)`` positions. The projections, the norms,
the rotary and the gate are the model's (models/ssm_moe.py).

PREFILL (``chunk_scan``) cuts the T positions into chunks of Q; inside a
chunk the outputs are one masked [Q, Q] product a head (every pair (t, s
<= t) weighted by the decay between them) plus what the carried state
adds, and the state crosses chunks through a ``lax.scan``. The decay a
position applies is a VALUE a position (``log_decay`` where the row is
real, 0 on padding) and a padding row's key is zeroed: padding neither
decays nor feeds the state, so the state that comes out is the state after
the last REAL position, and every exponent is <= 0 (no division by a
decay, nothing overflows). The state, the decays and every accumulator are
float32; the [Q, Q] products take q and k as they come.

DECODE (``step``) is the recurrence as written, one position a lane; a
lane whose ``log_decay`` is 0 and whose key is 0 gets its state back bit
for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def log_decays(heads: int) -> np.ndarray:
    """The per-head slopes of Lightning Attention-2: ``lam_h = exp(-2^(-8
    (h + 1) / heads))``; returned as ``log lam_h`` [heads] float32."""
    h = np.arange(1, heads + 1, dtype=np.float64)
    return (-(2.0 ** (-8.0 * h / heads))).astype(np.float32)


def chunk_scan(q, k, v, log_decay, real, state, chunk: int):
    """One lane's T positions from ``state`` to the state after its last
    real one.

    ``q`` (scaled), ``k``, ``v`` [T, H, D]; ``log_decay`` [H] float32
    (negative); ``real`` [T] bool; ``state`` [H, D, D] float32 (key
    dimension first). Returns (o [T, H, D] float32, the state). T that is
    no multiple of ``chunk`` is padded here as not real."""
    T, H, D = q.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        real = jnp.pad(real, (0, pad))
    n = (T + pad) // Q
    f32 = jnp.float32
    k = jnp.where(real[:, None, None], k, jnp.zeros((), k.dtype))
    g = jnp.where(real[:, None], log_decay[None, :], 0.0).astype(f32)
    xs = (q.reshape(n, Q, H, D), k.reshape(n, Q, H, D),
          v.reshape(n, Q, H, D), g.reshape(n, Q, H))
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(S, c):
        qc, kc, vc, gc = c
        cum = jnp.cumsum(gc, axis=0)                           # [Q, H]
        # masked BEFORE the exponential (s > t would be a growth)
        between = jnp.exp(jnp.where(
            causal[:, :, None], cum[:, None, :] - cum[None, :, :], -jnp.inf))
        A = jnp.einsum("thd,shd->tsh", qc, kc,
                       preferred_element_type=f32) * between
        o = jnp.einsum("tsh,she->the", A.astype(vc.dtype), vc,
                       preferred_element_type=f32)
        # what the state carried into the chunk adds at t
        o = o + jnp.einsum("thd,hde->the", qc.astype(f32), S) * jnp.exp(
            cum)[:, :, None]
        to_end = jnp.exp(cum[-1][None, :] - cum)               # [Q, H]
        S = (jnp.exp(cum[-1])[:, None, None] * S
             + jnp.einsum("shd,she->hde",
                          kc.astype(f32) * to_end[:, :, None],
                          vc.astype(f32)))
        return S, o

    state, o = jax.lax.scan(one, state.astype(f32), xs)
    return o.reshape(n * Q, H, D)[:T], state


def step(q, k, v, log_decay, state):
    """One position a lane, the recurrence as written. ``q`` (scaled),
    ``k``, ``v`` [L, H, D]; ``log_decay`` [L, H] float32 (0 for a lane
    that must not move, whose ``k`` the caller zeroes); ``state`` [L, H,
    D, D] float32 -> (o [L, H, D] float32, the new state)."""
    f32 = jnp.float32
    state = (jnp.exp(log_decay)[:, :, None, None] * state
             + k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :])
    return jnp.sum(q.astype(f32)[..., :, None] * state, axis=-2), state
