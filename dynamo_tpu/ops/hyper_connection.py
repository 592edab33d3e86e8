"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections arXiv:2409.19606): the residual state of a token is n
streams ``X [n, C]`` and every sublayer F is wrapped

    u  = H_pre X                      the sublayer's input, [C]
    X' = H_res X + H_post^T F(u)      the streams mixed, F's output spread

with coefficients computed from the token's own state, ``x = vec(X) /
rms(vec(X))``:

    H_pre  = sigmoid(a_pre (x phi_pre) + b_pre)                 [n]
    H_post = 2 sigmoid(a_post (x phi_post) + b_post)            [n]
    H_res  = SK(clip(a_res mat(x phi_res) + b_res, lo, hi))     [n, n]

``SK`` = exp, then ``iters`` times: rows divided by (their sums + eps),
columns by (theirs + eps) — towards a doubly stochastic matrix.

Pure XLA. The layout is the point: a ``[N, n, n]`` tensor with n = 4 as
the minor dimension pads every lane tile 32x, and a reduction over it is
an op of its own, 2 x iters times. Here each of the n + n + n*n
coefficients of N tokens is a SLAB ``[N]`` float32 of its own (a Python
list of them, the tokens minor), a row or column sum is n - 1 adds of
slabs and a normalisation one reciprocal and n multiplies: nothing but
elementwise ops on equal shapes, which XLA:TPU fuses into a handful of
ops an unrolled stretch (5 for 20 unrolled iterations; the same
arithmetic on one ``[n, n, N]`` array with sliced sums or ``reduce``
became 78-100, compile-only, PR 37).

The product itself needs no float32 copy of the state: ``x phi = (vec(X)
phi) / rms``, the streams are held in the model's dtype and so is phi, and
a bf16 x bf16 product accumulated in float32 is exact in its terms.
"""
from __future__ import annotations

import functools
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp

Slab = jnp.ndarray   # [N] float32: one coefficient of every token


class Mix(NamedTuple):
    """One sublayer's coefficients for N tokens, as slabs."""

    pre: list[Slab]          # [n]
    post: list[Slab]         # [n]
    res: list[list[Slab]]    # [n][n] — res[i][j] weighs stream j in X'[i]


def _sum(slabs) -> Slab:
    return functools.reduce(operator.add, slabs)


SINKHORN_UNROLL = 5   # iterations a trip of the rolled loop


def sinkhorn(logits: list[list[Slab]], iters: int, eps: float
             ) -> list[list[Slab]]:
    """exp, then ``iters`` x (rows, then columns) divided by their sums +
    eps. ``logits[i][j]`` is row i, column j.

    A rolled loop of SINKHORN_UNROLL iterations a trip: fully unrolled,
    the 20 iterations of a layer's two sublayers are ~2200 elementwise ops
    a layer, and XLA:TPU took 31 of a whole-model prefill program's 47 s
    to compile them (compile-only, PR 37: 16.6 s at one iteration); rolled
    one iteration a trip, a decode step would launch 14 x 20 tiny loop
    bodies. Four trips of five keep both small."""
    n = len(logits)

    def iteration(_, m):
        m = [list(row) for row in m]
        for i in range(n):
            r = 1.0 / (_sum(m[i]) + eps)
            m[i] = [x * r for x in m[i]]
        for j in range(n):
            r = 1.0 / (_sum(m[i][j] for i in range(n)) + eps)
            for i in range(n):
                m[i][j] = m[i][j] * r
        return m

    return jax.lax.fori_loop(
        0, iters, iteration, [[jnp.exp(x) for x in row] for row in logits],
        unroll=min(SINKHORN_UNROLL, iters))


def mix_coefficients(x, phi, a, b, *, n: int, iters: int, eps: float,
                     clamp: tuple[float, float], norm_eps: float) -> Mix:
    """``x`` [N, n, C] (any float dtype), ``phi`` [n*C, 2n + n*n],
    ``a`` [3], ``b`` [2n + n*n]."""
    N = x.shape[0]
    f32 = jnp.float32
    # [2n + n*n, N]: the tokens come out minor, no transpose of a result
    raw = jnp.einsum("nk,km->mn", x.reshape(N, -1), phi,
                     preferred_element_type=f32)
    xf = x.astype(f32)
    inv_rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(1, 2)) + norm_eps)
    a, b = a.astype(f32), b.astype(f32)

    def logit(gain: int, k: int) -> Slab:
        return a[gain] * (raw[k] * inv_rms) + b[k]

    pre = [jax.nn.sigmoid(logit(0, k)) for k in range(n)]
    post = [2.0 * jax.nn.sigmoid(logit(1, n + k)) for k in range(n)]
    res = [[jnp.clip(logit(2, 2 * n + i * n + j), clamp[0], clamp[1])
            for j in range(n)] for i in range(n)]
    return Mix(pre, post, sinkhorn(res, iters, eps))


def _weighted(w: list[Slab], streams: list[jnp.ndarray]) -> jnp.ndarray:
    """sum_j w[j] [N] * streams[j] [N, C], as multiplies and adds in
    float32 (a contraction over n = 4 is no work for the matrix unit, and
    as a dot it would round its float32 operands)."""
    return _sum(wj[:, None] * s for wj, s in zip(w, streams))


def _streams(x: jnp.ndarray) -> list[jnp.ndarray]:
    return [x[:, j].astype(jnp.float32) for j in range(x.shape[1])]


def hc_pre(x: jnp.ndarray, mix: Mix) -> jnp.ndarray:
    """The sublayer's input ``u = H_pre X``: [N, n, C] -> [N, C]."""
    return _weighted(mix.pre, _streams(x)).astype(x.dtype)


def hc_post(x: jnp.ndarray, f: jnp.ndarray, mix: Mix) -> jnp.ndarray:
    """``X' = H_res X + H_post^T f``: x [N, n, C], f [N, C] -> [N, n, C]."""
    xs, ff = _streams(x), f.astype(jnp.float32)
    return jnp.stack(
        [_weighted(mix.res[i], xs) + mix.post[i][:, None] * ff
         for i in range(x.shape[1])], axis=1).astype(x.dtype)


def row_sum_residual(mix: Mix) -> jnp.ndarray:
    """max over tokens and rows of |rowsum(H_res) - 1|: what the last
    column normalisation left of the rows' — how far from converged the
    iterations stopped (a program that cuts them shows here)."""
    return jnp.max(jnp.stack(
        [jnp.abs(_sum(row) - 1.0) for row in mix.res]))
