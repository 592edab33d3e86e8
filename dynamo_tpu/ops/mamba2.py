"""The Mamba-2 state-space mixer's recurrence, in plain XLA: a chunked
scan for prefill and a one-step update for decode (arXiv:2405.21060).

Per head h (P values wide) with a state ``S`` [P, N], over time::

    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer B_t)      y_t = S_t C_t

``A`` < 0 a head, ``dt`` > 0 a head and position, ``B`` and ``C`` [N]
shared by all heads (one group). ``D x_t``, the gate and the norm are
the model's (models/ssm_moe.py); so is the short causal convolution's
weight, applied here (``causal_conv`` / ``conv_step``) because its
window is the other half of a lane's recurrent state.

PREFILL (``chunk_scan``) is the chunked form of the same recurrence
("SSD"): the T positions are cut into chunks of Q; inside a chunk the
outputs are one masked [Q, Q] product a head (every pair (t, s <= t)
weighted by the decay between them), the state crosses chunks through a
``lax.scan`` whose carry is the state itself, so a chunk's temporaries
([Q, Q, heads] float32: 33.5 MB at Q 256, 128 heads) exist once, not T / Q
times. ``dt`` 0 at a position makes it neither decay nor feed the state:
that is how the caller masks the padding of a bucket, so that the state
that comes out is the state after the last REAL position. ``dt``, ``A``,
the decays and the state are float32; the products take their operands
as they come (float32 here, which the TPU's default matmul precision
rounds to bfloat16 at the unit's input, as it does the model's other
matmuls).

DECODE (``scan_step``) is the recurrence as written, one position a
lane: multiply, add, and a reduction over N, fused by XLA into one pass
that reads the lane's state once and writes it once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(xbc, window, w, b, n_real):
    """The depthwise causal convolution of one lane's chunk.

    ``xbc`` [T, C] the chunk's inputs, ``window`` [W - 1, C] the W - 1
    inputs before it (zeros when the lane is fresh), ``w`` [W, C] with
    ``w[W - 1]`` on the current position, ``b`` [C], ``n_real`` the
    chunk's real rows. Returns (silu(conv + b) [T, C], the window after
    the last real row [W - 1, C]: gathered at ``n_real``, not at T, and
    the old window itself where the chunk has no real row)."""
    W = w.shape[0]
    T = xbc.shape[0]
    padded = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=0)
    out = sum(padded[j:j + T].astype(jnp.float32) * w[j].astype(jnp.float32)
              for j in range(W))
    out = jax.nn.silu(out + b.astype(jnp.float32)).astype(xbc.dtype)
    new_window = jax.lax.dynamic_slice_in_dim(padded, n_real, W - 1, axis=0)
    return out, new_window.astype(window.dtype)


def conv_step(xbc, window, w, b):
    """One position a lane: ``xbc`` [B, C], ``window`` [B, W - 1, C] ->
    (silu(conv + b) [B, C], the window moved on by one)."""
    full = jnp.concatenate([window.astype(xbc.dtype), xbc[:, None]], axis=1)
    out = jnp.einsum("bwc,wc->bc", full.astype(jnp.float32),
                     w.astype(jnp.float32))
    out = jax.nn.silu(out + b.astype(jnp.float32)).astype(xbc.dtype)
    return out, full[:, 1:].astype(window.dtype)


def chunk_scan(x, dt, A, B, C, state, chunk: int):
    """One lane's T positions from ``state`` to the state after them.

    ``x`` [T, H, P], ``dt`` [T, H] float32 (0 on padding), ``A`` [H]
    float32 (negative), ``B`` and ``C`` [T, N], ``state`` [H, P, N]
    float32. Returns (y [T, H, P] float32, the final state). T that is
    no multiple of ``chunk`` is padded here with ``dt`` 0."""
    T, H, P = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n = (T + pad) // Q
    f32 = jnp.float32
    xs = (x.reshape(n, Q, H, P), dt.reshape(n, Q, H).astype(f32),
          B.reshape(n, Q, -1), C.reshape(n, Q, -1))
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(S, c):
        xc, dtc, Bc, Cc = c
        xc, Bc, Cc = xc.astype(f32), Bc.astype(f32), Cc.astype(f32)
        cum = jnp.cumsum(dtc * A, axis=0)                      # [Q, H]
        # (t, s) pairs inside the chunk: C_t . B_s, the decay from s to
        # t, and dt_s; masked BEFORE the exponential (s > t would be a
        # growth, an overflow)
        between = jnp.where(causal[:, :, None],
                            cum[:, None, :] - cum[None, :, :], -jnp.inf)
        M = (Cc @ Bc.T)[:, :, None] * jnp.exp(between) * dtc[None, :, :]
        y = jnp.einsum("tsh,shp->thp", M, xc)
        # what the state carried into the chunk adds at t
        y = y + jnp.einsum("tn,hpn->thp", Cc, S) * jnp.exp(cum)[:, :, None]
        to_end = jnp.exp(cum[-1][None, :] - cum) * dtc          # [Q, H]
        S = (jnp.exp(cum[-1])[:, None, None] * S
             + jnp.einsum("shp,sn->hpn", xc * to_end[:, :, None], Bc))
        return S, y

    state, y = jax.lax.scan(one, state.astype(f32), xs)
    return y.reshape(n * Q, H, P)[:T], state


def scan_step(x, dt, A, B, C, state):
    """One position a lane, the recurrence as written. ``x`` [L, H, P],
    ``dt`` [L, H] float32, ``A`` [H], ``B`` and ``C`` [L, N], ``state``
    [L, H, P, N] float32 -> (y [L, H, P] float32, the new state)."""
    f32 = jnp.float32
    decay = jnp.exp(dt * A)[:, :, None, None]
    fed = (dt[:, :, None] * x.astype(f32))[..., None] * B.astype(f32)[
        :, None, None, :]
    state = decay * state + fed
    return jnp.sum(state * C.astype(f32)[:, None, None, :], axis=-1), state
