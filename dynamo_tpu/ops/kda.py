"""Delta-rule linear attention with a gate per CHANNEL (Kimi Delta
Attention, arXiv:2510.26692): a chunked form for prefill in plain XLA, and
the one-step form for decode as plain XLA and as ONE Pallas TPU kernel a
layer.

Per head (keys and values ``D`` wide) with a matrix state ``S`` [D, D]
(key dimension first), over time::

    S'  = Diag(a_t) S_{t-1}                      a_t = exp(g_t) in (0, 1]^D
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T        b_t in [0, 1], a scalar
    o_t = S_t^T q_t

i.e. ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``: the
state first forgets channel by channel, then REPLACES what it held under
the key ``k_t`` by a step ``b_t`` towards ``v_t`` (the delta rule). ``q``
arrives scaled, ``q`` and ``k`` L2-normalised; the projections, the short
convolutions, the gate's form and the output norm are the model's
(models/ssm_moe.py).

PREFILL (``chunk_scan``) is the WY / UT-transform form over chunks of C
positions. With ``G_t`` the running sum of ``g`` inside the chunk and
``u_t = b_t (v_t - S'^T k_t)`` the step the state takes at ``t``::

    A_ij = b_i <k_i e^{G_i}, k_j e^{-G_j}>  (i > j)    T = (I + A)^-1 Diag(b)
    W = T (K e^G)        U = T V - W S_0
    O = (Q e^G) S_0 + tril(Q e^G (K e^-G)^T) U
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

Everything that does not read the carried state (``A``, ``T``, ``W``,
``T V``, the [C, C] query-key products) is computed for all chunks at
once; the state then crosses the chunks in a ``lax.scan`` of three small
products. ``e^{-G}`` alone would overflow (a gate of -5 a step is e^320
over 64 positions), so a pair's decay is taken relative to the MIDDLE of
the ROW's sub-block of ``SUB`` positions: the row factor ``e^{G_i -
G_mid}`` and, inside the sub-block, the column factor ``e^{G_mid - G_j}``
stay within ``e^{+-SUB / 2 x bound}`` (e^40 at 16 x 5; relative to the
sub-block's START a small key times e^-80 is a float32 denormal, and the
e^80 beside it returns a product that has lost its bits), and a column
before the sub-block has a factor below 1 that may underflow with the
pair's true decay. ``(I +
A)^-1`` is forward substitution on the ``SUB`` x ``SUB`` diagonal blocks
(a loop of SUB steps over tiny operands) and block merges above them: the
Neumann product ``(I - A)(I + A^2)...`` cancels catastrophically when
keys repeat. Padding runs with ``g`` = 0, ``b`` = 0, ``k`` = 0: the state
that comes out is the state after the last REAL position. ``G``, ``T``
and the state are float32; products take float32 operands (which the
TPU's default matmul precision rounds to bfloat16 at the unit's input, as
it does the model's other matmuls), the inverse's merges at full
precision.

DECODE (``step``) is the recurrence as written, one position a lane, in
float32; a lane with ``g`` = 0, ``b`` = 0 and ``k`` = 0 gets its state
back as it was. ``step_pallas`` is the same step as one kernel a layer
that reads and writes a lane's [H, D, D] state once, IN PLACE (the
XLA form is a scale, a mat-vec, an outer product and a second mat-vec over
a 2.1 MB operand a lane: two passes over the state), and only the lanes
of a WORK LIST (``work_list``: the lanes that hold a request, scalar
prefetch): a lane the list does not hold is neither read nor written, so
a step costs what its live lanes' state costs. The kernel wants
``a``, ``k`` and ``q`` along the state's KEY axis, i.e. as columns: each
rides in as one [heads, D] tile a grid step that the kernel pads to [D, D]
and transposes once, head h in column h.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64   # positions a chunk of the prefill form
SUB = 16     # positions a sub-block: SUB / 2 x |lower bound of g| must
             # stay well inside float32's exponent (8 x 5 = 40 of 87)
HEADS_PER_STEP = 8   # heads a grid step of the decode kernel ([8, D, D]
                     # float32 = 512 KB at D 128; in + out, double-buffered)

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` [..., C, C], C a
    multiple of SUB (or below it): forward substitution on the diagonal
    blocks, then ``[[X, 0], [Y, Z]]^-1 = [[X^-1, 0], [-Z^-1 Y X^-1,
    Z^-1]]`` block size by block size."""
    C = A.shape[-1]
    s = min(SUB, C)
    nb = C // s
    lead = A.shape[:-2]
    blocks = A.reshape(*lead, nb, s, nb, s)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)
    eye = jnp.eye(s, dtype=_F32)

    def row(i, inv):
        # row i of the inverse: e_i - A[i, :i] inv[:i] (rows >= i of
        # ``inv`` are still zero)
        a = jax.lax.dynamic_index_in_dim(diag, i, axis=-2, keepdims=True)
        new = jax.lax.dynamic_index_in_dim(eye, i, axis=0, keepdims=True) - (
            jnp.matmul(a, inv, precision=_HI))
        return jax.lax.dynamic_update_index_in_dim(inv, new[..., 0, :], i,
                                                   axis=-2)

    inv = jax.lax.fori_loop(0, s, row, jnp.zeros_like(diag))
    # inv [..., nb, s, s]: the diagonal blocks' inverses
    parts = [inv[..., i, :, :] for i in range(nb)]
    size = s
    while len(parts) > 1:
        merged = []
        for i in range(0, len(parts), 2):
            X, Z = parts[i], parts[i + 1]
            r0 = (i + 1) * size
            Y = A[..., r0:r0 + size, r0 - size:r0]
            low = -jnp.matmul(Z, jnp.matmul(Y, X, precision=_HI),
                              precision=_HI)
            top = jnp.concatenate([X, jnp.zeros_like(X)], axis=-1)
            merged.append(jnp.concatenate(
                [top, jnp.concatenate([low, Z], axis=-1)], axis=-2))
        parts, size = merged, 2 * size
    return parts[0]


def _pair_products(q, k, G):
    """The decayed products inside every chunk: (``<k_i e^{G_i}, k_j
    e^{-G_j}>``, ``<q_i e^{G_i}, k_j e^{-G_j}>``), each [n, H, C, C], a
    row's decay taken relative to the middle of its sub-block. Entries
    above the diagonal are finite garbage the caller masks. ``q``, ``k``,
    ``G`` [n, C, H, D] float32."""
    n, C, H, D = k.shape
    s = min(SUB, C)
    kk, qk = [], []
    for i in range(C // s):
        lo, hi = i * s, (i + 1) * s
        mid = G[:, lo + s // 2 - 1][:, None]   # G in the sub-block's middle
        rows = jnp.exp(G[:, lo:hi] - mid)
        cols = k[:, :hi] * jnp.exp(mid - G[:, :hi])
        pad = ((0, 0), (0, 0), (0, 0), (0, C - hi))
        for out, x in ((kk, k), (qk, q)):
            out.append(jnp.pad(jnp.einsum(
                "nthd,nshd->nhts", x[:, lo:hi] * rows, cols,
                preferred_element_type=_F32), pad))
    return jnp.concatenate(kk, axis=2), jnp.concatenate(qk, axis=2)


def chunk_scan(q, k, v, g, b, real, state, chunk: int = CHUNK):
    """One lane's T positions from ``state`` to the state after its last
    real one.

    ``q`` (scaled), ``k``, ``v`` [T, H, D]; ``g`` [T, H, D] float32 (the
    log of the per-channel decay, <= 0); ``b`` [T, H] float32; ``real``
    [T] bool; ``state`` [H, D, D] float32 (key dimension first). Returns
    (o [T, H, D] float32, the state). T that is no multiple of ``chunk``
    is padded here as not real."""
    T, H, D = q.shape
    C = min(chunk, T)
    if C > SUB and C % SUB:
        raise ValueError(f"chunk {C} is no multiple of the sub-block {SUB}")
    pad = -T % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        b = jnp.pad(b, ((0, pad), (0, 0)))
        real = jnp.pad(real, (0, pad))
    n = (T + pad) // C
    live = real[:, None, None]
    q, v = q.astype(_F32), v.astype(_F32)
    k = jnp.where(live, k.astype(_F32), 0.0)
    g = jnp.where(live, g.astype(_F32), 0.0)
    b = jnp.where(real[:, None], b.astype(_F32), 0.0)
    q, k, v, g = (a.reshape(n, C, H, D) for a in (q, k, v, g))
    b = b.reshape(n, C, H).transpose(0, 2, 1)                 # [n, H, C]
    G = jnp.cumsum(g, axis=1)                                 # [n, C, H, D]

    kk, qk = _pair_products(q, k, G)
    t = jnp.arange(C)
    A = jnp.where(t[:, None] > t[None, :], kk * b[..., :, None], 0.0)
    Aqk = jnp.where(t[:, None] >= t[None, :], qk, 0.0)
    Tm = _unit_lower_inverse(A) * b[..., None, :]             # [n, H, C, C]
    eG = jnp.exp(G)
    W = jnp.einsum("nhts,nshd->nhtd", Tm, k * eG,
                   preferred_element_type=_F32)
    U0 = jnp.einsum("nhts,nshd->nhtd", Tm, v, preferred_element_type=_F32)
    last = G[:, -1]                                           # [n, H, D]
    xs = (W, U0, Aqk, (q * eG).transpose(0, 2, 1, 3),
          (k * jnp.exp(last[:, None] - G)).transpose(0, 2, 1, 3),
          jnp.exp(last))

    def one(S, c):
        W, U0, Aqk, qe, ke, decay = c
        U = U0 - jnp.einsum("htd,hde->hte", W, S,
                            preferred_element_type=_F32)
        o = (jnp.einsum("htd,hde->hte", qe, S, preferred_element_type=_F32)
             + jnp.einsum("hts,hse->hte", Aqk, U,
                          preferred_element_type=_F32))
        S = decay[:, :, None] * S + jnp.einsum(
            "hsd,hse->hde", ke, U, preferred_element_type=_F32)
        return S, o

    state, o = jax.lax.scan(one, state.astype(_F32), xs)
    return o.transpose(0, 2, 1, 3).reshape(n * C, H, D)[:T], state


def step(q, k, v, g, b, state):
    """One position a lane, the recurrence as written. ``q`` (scaled),
    ``k``, ``v`` [L, H, D]; ``g`` [L, H, D] float32; ``b`` [L, H]
    float32; ``state`` [L, H, D, D] float32 -> (o [L, H, D] float32, the
    new state). A lane that must not move comes with ``g`` 0, ``b`` 0 and
    ``k`` 0."""
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    S = jnp.exp(g.astype(_F32))[..., None] * state
    u = b.astype(_F32)[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def _step_kernel(lanes_ref, n_ref, a_ref, k_ref, q_ref, v_ref, b_ref, s_ref,
                 o_ref, out_ref):
    """Work item ``i`` of the grid's first axis: ``hb`` heads of lane
    ``lanes[i]``. ``a`` (the decay, exp g), ``k``, ``q``, ``v`` and ``b``
    (broadcast over D) as [1, hb, D] tiles, ``s_ref`` / ``out_ref`` [1,
    hb, D, D] the state before and after (one buffer), ``o_ref`` [1, hb,
    D] the outputs."""
    hb, D = s_ref.shape[1], s_ref.shape[2]

    def columns(ref):
        # the heads' vectors along the state's KEY axis: the tile padded
        # to [D, D] and transposed once, head h in column h
        x = ref[0]
        return jnp.concatenate(
            [x, jnp.zeros((D - hb, D), x.dtype)], axis=0).T

    @pl.when(n_ref[0] > 0)
    def _():
        a_c, k_c, q_c = columns(a_ref), columns(k_ref), columns(q_ref)
        for h in range(hb):
            a, k, q = (c[:, h:h + 1] for c in (a_c, k_c, q_c))     # [D, 1]
            v, b = v_ref[0, h:h + 1, :], b_ref[0, h:h + 1, :]      # [1, D]
            S = a * s_ref[0, h]
            u = b * (v - jnp.sum(S * k, axis=0, keepdims=True))
            S = S + k * u
            out_ref[0, h] = S
            o_ref[0, h:h + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)

    @pl.when(n_ref[0] == 0)
    def _():
        # an empty list: the grid's one lane (lane 0) goes back as it came
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def work_list(live):
    """The lanes a step visits, from ``live`` [B] bool: (``lanes`` [B]
    int32, the live lanes' indices first and 0 after them, ``n_live`` [1]
    int32)."""
    lanes, = jnp.nonzero(live, size=live.shape[0], fill_value=0)
    return lanes.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)[None]


def visited(lanes, n_live):
    """[B] bool: the lanes a step kernel's items visited, ``lanes[:n_live]``
    of a work list. The output rows of the others were never written."""
    B = lanes.shape[0]
    return jnp.any((lanes[None, :] == jnp.arange(B)[:, None])
                   & (jnp.arange(B)[None, :] < n_live[0]), axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def step_pallas(q, k, v, g, b, state, lanes, n_live, interpret: bool = False):
    """``step`` as one kernel over the lanes of a work list
    (``work_list``): ``q``, ``k``, ``v``, ``g`` [B, H, D], ``b`` [B, H];
    ``state`` [L >= B, H, D, D] float32; ``lanes`` [B] and ``n_live`` [1]
    int32 ride in as scalar prefetch, and ``n_live`` is the grid's bound:
    a lane past the list costs nothing, not even an empty grid step. The
    lanes ``lanes[:n_live]`` are stepped IN PLACE; no other lane's state is
    read or written, and its ``o`` comes back 0."""
    B, H, D = q.shape
    hb = next(n for n in (HEADS_PER_STEP, 4, 2, 1) if H % n == 0)
    vectors = (jnp.exp(g.astype(_F32)), k.astype(_F32), q.astype(_F32),
               v.astype(_F32),
               jnp.broadcast_to(b.astype(_F32)[..., None], (B, H, D)))
    tile = pl.BlockSpec((1, hb, D), lambda i, j, lanes, n: (lanes[i], j, 0))
    lane = pl.BlockSpec((1, hb, D, D),
                        lambda i, j, lanes, n: (lanes[i], j, 0, 0))
    o, state = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # never an empty grid: what a pipeline with no step writes
            # back is nobody's promise
            grid=(jnp.maximum(n_live[0], 1), H // hb),
            in_specs=[tile] * 5 + [lane],
            out_specs=[tile, lane]),
        out_shape=[jax.ShapeDtypeStruct((B, H, D), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operand indices count the two prefetched scalars
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_step",
    )(lanes, n_live, *vectors, state)
    return jnp.where(visited(lanes, n_live)[:, None, None], o, 0.0), state
