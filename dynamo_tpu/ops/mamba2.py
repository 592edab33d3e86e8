"""The Mamba-2 state-space mixer's recurrence, in plain XLA: a chunked
scan for prefill and a one-step update for decode (arXiv:2405.21060).

Per head h (P values wide) with a state ``S`` [P, N], over time::

    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer B_t)      y_t = S_t C_t

``A`` < 0 a head, ``dt`` > 0 a head and position, ``B`` and ``C`` [N]
shared by all heads (one group: ``B`` / ``C`` of [.., N]) or by the heads
of a GROUP (``B`` / ``C`` of [.., G, N]: head h reads group h // (H / G);
every function here takes either, and one group given as [.., N] traces
as it did before groups were built). ``D x_t``, the gate and the norm are
the model's (models/ssm_moe.py); so is the short causal convolution's
weight, applied here (``causal_conv`` / ``conv_step``) because its
window is the other half of a lane's recurrent state.

PREFILL (``chunk_scan``) is the chunked form of the same recurrence
("SSD"): the T positions are cut into chunks of Q; inside a chunk the
outputs are one masked [Q, Q] product a head (every pair (t, s <= t)
weighted by the decay between them; ``C_t . B_s`` is one [Q, Q] map a
GROUP, which each head of the group reads), the state crosses chunks through a
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
that reads the lane's state once and writes it once; a lane with ``dt``
= 0 gets its state back bit for bit. ``scan_step_pallas`` is the same
step as one kernel a layer over the lanes of a WORK LIST
(``kda.work_list``: the lanes that hold a request, scalar prefetch),
their [H, P, N] states rewritten IN PLACE: a lane the list does not hold
is neither read nor written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops import kda

HEADS_PER_STEP = 32   # heads a grid step of the decode kernel: a [32, 64,
                      # 128] float32 state block is 1 MB

_F32 = jnp.float32


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


def _heads_of(a, H: int):
    """A group's row for each of its heads: ``a`` [.., G, N] -> [.., H, N],
    head h reading group h // (H / G)."""
    return jnp.repeat(a, H // a.shape[-2], axis=-2)


def chunk_scan(x, dt, A, B, C, state, chunk: int):
    """One lane's T positions from ``state`` to the state after them.

    ``x`` [T, H, P], ``dt`` [T, H] float32 (0 on padding), ``A`` [H]
    float32 (negative), ``B`` and ``C`` [T, N] (one group) or [T, G, N]
    (head h reads group h // (H / G)), ``state`` [H, P, N] float32.
    Returns (y [T, H, P] float32, the final state). T that is no multiple
    of ``chunk`` is padded here with ``dt`` 0."""
    T, H, P = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n = (T + pad) // Q
    f32 = jnp.float32
    grouped = B.ndim == 3
    xs = (x.reshape(n, Q, H, P), dt.reshape(n, Q, H).astype(f32),
          B.reshape(n, Q, *B.shape[1:-1], -1),
          C.reshape(n, Q, *C.shape[1:-1], -1))
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    if grouped:
        G = B.shape[1]
        per = H // G
        # the [Q, Q] map ``C_t . B_s`` a GROUP, read by each of its heads;
        # the state in and out through its group's rows
        pairs = lambda Cc, Bc: jnp.repeat(  # noqa: E731
            jnp.einsum("tgn,sgn->tsg", Cc, Bc), per, axis=2)
        carried = lambda Cc, S: jnp.einsum(  # noqa: E731
            "tgn,gipn->tgip", Cc, S.reshape(G, per, P, -1)).reshape(Q, H, P)
        fed = lambda xw, Bc: jnp.einsum(  # noqa: E731
            "sgip,sgn->gipn", xw.reshape(Q, G, per, P), Bc).reshape(H, P, -1)
    else:
        pairs = lambda Cc, Bc: (Cc @ Bc.T)[:, :, None]  # noqa: E731
        carried = lambda Cc, S: jnp.einsum("tn,hpn->thp", Cc, S)  # noqa: E731
        fed = lambda xw, Bc: jnp.einsum("shp,sn->hpn", xw, Bc)  # noqa: E731

    def one(S, c):
        xc, dtc, Bc, Cc = c
        xc, Bc, Cc = xc.astype(f32), Bc.astype(f32), Cc.astype(f32)
        cum = jnp.cumsum(dtc * A, axis=0)                      # [Q, H]
        # (t, s) pairs inside the chunk: C_t . B_s, the decay from s to
        # t, and dt_s; masked BEFORE the exponential (s > t would be a
        # growth, an overflow)
        between = jnp.where(causal[:, :, None],
                            cum[:, None, :] - cum[None, :, :], -jnp.inf)
        M = pairs(Cc, Bc) * jnp.exp(between) * dtc[None, :, :]
        y = jnp.einsum("tsh,shp->thp", M, xc)
        # what the state carried into the chunk adds at t
        y = y + carried(Cc, S) * jnp.exp(cum)[:, :, None]
        to_end = jnp.exp(cum[-1][None, :] - cum) * dtc          # [Q, H]
        S = (jnp.exp(cum[-1])[:, None, None] * S
             + fed(xc * to_end[:, :, None], Bc))
        return S, y

    state, y = jax.lax.scan(one, state.astype(f32), xs)
    return y.reshape(n * Q, H, P)[:T], state


def scan_step(x, dt, A, B, C, state):
    """One position a lane, the recurrence as written. ``x`` [L, H, P],
    ``dt`` [L, H] float32, ``A`` [H], ``B`` and ``C`` [L, N] or [L, G, N],
    ``state`` [L, H, P, N] float32 -> (y [L, H, P] float32, the new
    state)."""
    f32 = jnp.float32
    if B.ndim == 3:   # a group's row for each of its heads
        Bh, Ch = (_heads_of(a.astype(f32), x.shape[1])[:, :, None, :]
                  for a in (B, C))
    else:
        Bh, Ch = (a.astype(f32)[:, None, None, :] for a in (B, C))
    decay = jnp.exp(dt * A)[:, :, None, None]
    fed = (dt[:, :, None] * x.astype(f32))[..., None] * Bh
    state = decay * state + fed
    return jnp.sum(state * Ch, axis=-1), state


def _step_kernel(lanes_ref, n_ref, decay_ref, fed_ref, b_ref, c_ref, s_ref,
                 y_ref, out_ref, heads_a_group: int = 0):
    """Work item ``(i, j)``: the ``hb`` heads from ``j * hb`` of lane
    ``lanes[i]``, in blocks of ``per`` heads = R rows of the state.
    ``decay_ref`` [B, H] (exp(dt A)) sits in SMEM: a head's decay is a
    scalar. ``fed_ref`` (dt x) and ``y_ref`` [B, H / per, R], ``b_ref`` /
    ``c_ref`` [B, N] hold every lane's rows, resident for the whole call:
    the item reads and writes its own. ``s_ref`` / ``out_ref`` [1, hb, P,
    N] the state before and after (one buffer). With GROUPS
    (``heads_a_group`` > 0) ``b_ref`` / ``c_ref`` are [B, G, N] and a block
    reads the row of ITS group (a block of ``per`` heads never straddles
    two: ``per`` divides a group's heads).

    ``dt x`` is wanted down the state's P axis and ``y`` comes out down
    it, while both live ALONG the lanes of their rows: the item's [blocks,
    R] tile of ``fed`` is transposed once (block k in column k), and a
    block's [R, N] products ``S C`` are transposed once and summed over
    the sublanes, which gives its R values of ``y`` as a row. (Reducing
    every register over its 128 lanes instead, and broadcasting a column
    of decays beside the column of ``dt x``, held this kernel to 55 % of
    the HBM's pace on the v5e; this form runs at the pace of its DMAs.)"""
    hb, P, N = s_ref.shape[1:]
    R = fed_ref.shape[2]
    per, blocks = R // P, hb * P // R
    lane, j = lanes_ref[pl.program_id(0)], pl.program_id(1)
    row = pl.ds(lane, 1)
    tile = pl.ds(pl.multiple_of(j * blocks, blocks), blocks)
    side = max(blocks, R)

    @pl.when(n_ref[0] > 0)
    def _():
        if not heads_a_group:
            b, c = b_ref[row, :], c_ref[row, :]                    # [1, N]
        fed = jnp.pad(fed_ref[row, tile, :][0],
                      ((0, side - blocks), (0, side - R))).T       # [R.., k..]
        ys = []
        for k in range(blocks):
            if heads_a_group:
                g = pl.ds((j * hb + k * per) // heads_a_group, 1)
                b, c = b_ref[row, g, :][0], c_ref[row, g, :][0]    # [1, N]
            products = []
            for i in range(per):
                h = k * per + i
                S = (decay_ref[lane, j * hb + h] * s_ref[0, h]
                     + fed[i * P:(i + 1) * P, k:k + 1] * b)
                out_ref[0, h] = S
                products.append(S * c)
            t = jnp.concatenate(products, axis=0)                  # [R, N]
            ys.append(jnp.sum(t.T, axis=0, keepdims=True))
        y_ref[row, tile, :] = jnp.concatenate(ys, axis=0)[None]

    @pl.when(n_ref[0] == 0)
    def _():
        # an empty list: the grid's one lane (lane 0) goes back as it came
        out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_step_pallas(x, dt, A, B, C, state, lanes, n_live,
                     interpret: bool = False):
    """``scan_step`` as one kernel over the lanes of a work list
    (``kda.work_list``): ``x`` [B, H, P], ``dt`` [B, H] float32, ``A``
    [H], ``B`` and ``C`` [B, N] or, by group, [B, G, N]; ``state`` [L >= B,
    H, P, N] float32;
    ``lanes`` [B] and ``n_live`` [1] int32 ride in as scalar prefetch, and
    ``n_live`` is the grid's bound. The lanes ``lanes[:n_live]`` are
    stepped IN PLACE; no other lane's state is read or written, and its
    ``y`` comes back 0. A state element is computed as ``scan_step``
    computes it; ``y`` sums the same products in another order."""
    nB, H, P = x.shape
    N = B.shape[-1]
    hb = next(n for n in (HEADS_PER_STEP, 16, 8, 4, 2, 1) if H % n == 0)
    # with groups, [B, G, N]: the heads that read one row of B and C
    group = H // B.shape[1] if B.ndim == 3 else 0
    # heads a block: as many as fill a register's 128 lanes with their P
    # (and lie in ONE group: a block reads one row of B and of C)
    per = next(n for n in range(min(hb, max(1, 128 // P)), 0, -1)
               if hb % n == 0 and not group % n)
    G, R = H // per, per * P
    kernel = (functools.partial(_step_kernel, heads_a_group=group)
              if group else _step_kernel)
    dt = dt.astype(_F32)
    fed = (dt[:, :, None] * x.astype(_F32)).reshape(nB, G, R)
    whole = lambda *s: pl.BlockSpec(s, lambda i, j, lanes, n: (0,) * len(s))  # noqa: E731
    lane = pl.BlockSpec((1, hb, P, N),
                        lambda i, j, lanes, n: (lanes[i], j, 0, 0))
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # never an empty grid: what a pipeline with no step writes
            # back is nobody's promise
            grid=(jnp.maximum(n_live[0], 1), H // hb),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      whole(nB, G, R), whole(*B.shape), whole(*C.shape),
                      lane],
            out_specs=[whole(nB, G, R), lane]),
        out_shape=[jax.ShapeDtypeStruct((nB, G, R), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operand indices count the two prefetched scalars
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="m2_step",
    )(lanes, n_live, jnp.exp(dt * A), fed, B.astype(_F32), C.astype(_F32),
      state)
    return jnp.where(kda.visited(lanes, n_live)[:, None, None],
                     y.reshape(nB, H, P), 0.0), state
