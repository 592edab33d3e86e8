"""The Mamba-1 selective scan (arXiv:2312.00752): a chunk form for prefill
and a one-step form for decode, each as plain XLA and as ONE Pallas TPU
kernel.

Per channel c of ``I`` (the mixer's inner width) with a state column
``h[:, c]`` of ``N`` values, over time::

    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) outer B_t     [N, I]
    y_t = sum_n h_t[n] C_t[n] + D x_t                      [I]

``A`` [N, I] < 0 (one decay a channel AND state column, where Mamba-2's
is one a head: there is no [Q, Q] matrix form of a chunk, the recurrence
is N x I multiply-adds and as many ``exp`` a position on the vector
unit), ``dt`` [T, I] > 0 a channel and position, ``B`` and ``C`` [T, N]
shared by all channels. The projections, the inner norms on dt / B / C,
the gate and the short convolution's weight are the model's
(models/ssm_moe.py); the convolution itself is ``mamba2.causal_conv`` /
``conv_step``.

THE STATE IS HELD CHANNELS-MINOR, ``[N, I]`` (the published layout is
[I, N]): a minor dimension of N = 16 would be tiled to 128 lanes on the
chip, eight times the bytes and a relayout a step. ``A`` comes the same
way.

PREFILL (``chunk_scan``). On the TPU one kernel over (channel tiles x
position blocks): a tile's [N, TILE] state stays in VMEM (the output
block, revisited along the position axis) while the positions stream
through, so HBM sees ``x``, ``dt`` and ``y`` once each and nothing of
size [T, I, N]. In plain XLA the same recurrence materialises [T, I, N]
float32 several times over. A position's ``B_t`` and ``C_t`` are wanted
as COLUMNS (along the state's N axis): they ride in as [T / 8, N, 8]
slabs, one [N, 8] tile a group of 8 positions, whose columns the kernel
slices statically. ``dt`` 0 at a position makes it neither decay nor feed
the state: ``chunk_scan`` masks the rows at and past ``n_real`` so, and
the state that comes out is the state after the last REAL position.
Elsewhere (the CPU meshes) and as the kernel's test oracle: a
``lax.scan`` over positions.

DECODE (``scan_step``) is the recurrence as written, one position a lane;
a lane with ``dt`` = 0 gets its state back bit for bit.
``scan_step_pallas`` is the same step as one kernel a layer over the
lanes of a WORK LIST (``kda.work_list``: the lanes that hold a request,
scalar prefetch), their [N, I] states rewritten IN PLACE: a lane the list
does not hold is neither read nor written.

``dt``, ``A``, the decays and the state are float32 everywhere; ``x``,
``B`` and ``C`` are taken as they come and widened.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops import kda

TILE = 512    # channels a grid step of the prefill kernel: an [N, TILE]
              # float32 state is 8 vector registers at N = 16
ROWS = 256    # positions a block of the prefill kernel
GROUP = 8     # positions a group: one float32 tile of sublanes
STEP_TILE = 512   # channels a slice of the decode kernel's inner loop
STEP_VMEM_BYTES = 48 << 20   # of the v5e's 128 MiB; the default is 16

_F32 = jnp.float32


def _tile(I: int, want: int) -> int:
    """Channels a tile: ``want`` where it divides ``I``, else 128-lane
    multiples down to 128, else all of them (a toy width)."""
    for t in (want, 256, 128):
        if t <= want and I % t == 0:
            return t
    return I


def scan_xla(x, dt, B, C, A, D, state):
    """The recurrence as written, a ``lax.scan`` over the T positions of
    one lane: ``x``, ``dt`` [T, I], ``B``, ``C`` [T, N], ``A`` [N, I],
    ``D`` [I], ``state`` [N, I] -> (y [T, I] float32, the state after)."""
    def one(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = jnp.exp(dt_t[None, :] * A) * h + (dt_t * x_t)[None, :] * B_t[:, None]
        return h, jnp.sum(h * C_t[:, None], axis=0) + D * x_t

    state, y = jax.lax.scan(
        one, state.astype(_F32),
        (x.astype(_F32), dt.astype(_F32), B.astype(_F32), C.astype(_F32)))
    return y, state


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s_ref, y_ref,
                 out_ref):
    """Grid step (i, t): channel tile ``i``, position block ``t``.
    ``x_ref`` / ``dt_ref`` / ``y_ref`` [rows, tile]; ``b_ref`` / ``c_ref``
    [rows / 8, N, 8]; ``a_ref`` [N, tile]; ``d_ref`` [1, tile]; ``s_ref``
    [N, tile] the state before the first block, ``out_ref`` [N, tile] the
    state as the blocks so far left it (one block index along ``t``: it
    stays in VMEM until the tile's last block)."""
    rows, tile = x_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = s_ref[...]

    A, D = a_ref[...], d_ref[...]
    at = jax.lax.broadcasted_iota(jnp.int32, (GROUP, tile), 0)

    def group(g, h):
        r0 = pl.multiple_of(g * GROUP, GROUP)
        xg, dtg = x_ref[pl.ds(r0, GROUP), :], dt_ref[pl.ds(r0, GROUP), :]
        bg, cg = b_ref[g], c_ref[g]                            # [N, 8]
        yg = jnp.zeros((GROUP, tile), _F32)
        for j in range(GROUP):
            x_t, dt_t = xg[j:j + 1], dtg[j:j + 1]              # [1, tile]
            h = jnp.exp(dt_t * A) * h + (dt_t * x_t) * bg[:, j:j + 1]
            y_t = jnp.sum(h * cg[:, j:j + 1], axis=0, keepdims=True) + D * x_t
            yg = jnp.where(at == j, y_t, yg)
        y_ref[pl.ds(r0, GROUP), :] = yg
        return h

    out_ref[...] = jax.lax.fori_loop(0, rows // GROUP, group, out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_pallas(x, dt, B, C, A, D, state, interpret: bool = False):
    """``scan_xla`` as one kernel. T is padded here to whole position
    blocks with ``dt`` 0 (which moves no state)."""
    T, I = x.shape
    N = A.shape[0]
    rows = min(ROWS, -(-T // GROUP) * GROUP)
    pad = -T % rows
    x, dt, B, C = (jnp.pad(a.astype(_F32), ((0, pad), (0, 0)))
                   for a in (x, dt, B, C))
    Tp = T + pad
    # a position's B and C as columns: [T / 8, N, 8]
    cols = lambda a: a.reshape(Tp // GROUP, GROUP, N).transpose(0, 2, 1)  # noqa: E731
    tile = _tile(I, TILE)
    seq = pl.BlockSpec((rows, tile), lambda i, t: (t, i))
    col = pl.BlockSpec((rows // GROUP, N, GROUP), lambda i, t: (t, 0, 0))
    chan = pl.BlockSpec((N, tile), lambda i, t: (0, i))
    y, state = pl.pallas_call(
        _scan_kernel,
        grid=(I // tile, Tp // rows),
        in_specs=[seq, seq, col, col, chan,
                  pl.BlockSpec((1, tile), lambda i, t: (0, i)), chan],
        out_specs=[seq, chan],
        out_shape=[jax.ShapeDtypeStruct((Tp, I), _F32),
                   jax.ShapeDtypeStruct((N, I), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="m1_scan",
    )(x, dt, cols(B), cols(C), A.astype(_F32), D.astype(_F32)[None],
      state.astype(_F32))
    return y[:T], state


def chunk_scan(x, dt, B, C, A, D, state, n_real, interpret=None):
    """One lane's T positions from ``state`` to the state after its last
    real one: ``x`` [T, I], ``dt`` [T, I] float32, ``B``, ``C`` [T, N],
    ``A`` [N, I] float32 (negative), ``D`` [I], ``state`` [N, I] float32,
    ``n_real`` the real rows (a prefix). Returns (y [T, I] float32, the
    state). The Pallas kernel on TPU devices, the ``lax.scan`` elsewhere;
    ``interpret`` (tests) picks the kernel, interpreted or not, whatever
    the platform."""
    real = jnp.arange(x.shape[0]) < n_real
    dt = jnp.where(real[:, None], dt.astype(_F32), 0.0)
    if interpret is not None:
        return scan_pallas(x, dt, B, C, A, D, state, interpret=interpret)
    return jax.lax.platform_dependent(
        x, dt, B, C, A, D, state, tpu=scan_pallas, default=scan_xla)


def scan_step(x, dt, B, C, A, D, state):
    """One position a lane: ``x``, ``dt`` [L, I], ``B``, ``C`` [L, N],
    ``A`` [N, I], ``D`` [I], ``state`` [L, N, I] float32 -> (y [L, I]
    float32, the new state). A lane that must not move comes with ``dt``
    0: exp(0) h + 0, its state bit for bit."""
    x, dt = x.astype(_F32), dt.astype(_F32)
    state = (jnp.exp(dt[:, None, :] * A) * state
             + (dt * x)[:, None, :] * B.astype(_F32)[:, :, None])
    y = jnp.sum(state * C.astype(_F32)[:, :, None], axis=1) + D * x
    return y, state


def _step_kernel(lanes_ref, n_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 s_ref, y_ref, out_ref):
    """Work item ``i``: lane ``lanes[i]``. ``x_ref`` / ``dt_ref`` /
    ``y_ref`` [B, I], every lane's row, resident for the whole call (a
    [1, I] block of a [B, I] array is no whole tile, and a [B, 1, I]
    operand is a relayout of it a call: the item reads and writes its own
    row); ``b_ref`` / ``c_ref`` [1, N, 1] (columns); ``a_ref`` [N, I];
    ``d_ref`` [1, I]; ``s_ref`` / ``out_ref`` [1, N, I] the lane's state
    before and after (one buffer). The channels go through in slices of
    ``STEP_TILE``, a slice's state in registers."""
    I = s_ref.shape[2]
    tile = _tile(I, STEP_TILE)
    row = pl.ds(lanes_ref[pl.program_id(0)], 1)

    @pl.when(n_ref[0] > 0)
    def _():
        b, c = b_ref[0], c_ref[0]                              # [N, 1]
        for k in range(I // tile):
            at = pl.ds(k * tile, tile)
            x, dt = x_ref[row, at], dt_ref[row, at]            # [1, tile]
            h = jnp.exp(dt * a_ref[:, at]) * s_ref[0, :, at] + (dt * x) * b
            out_ref[0, :, at] = h
            y_ref[row, at] = (jnp.sum(h * c, axis=0, keepdims=True)
                              + d_ref[:, at] * x)

    @pl.when(n_ref[0] == 0)
    def _():
        # an empty list: the grid's one lane (lane 0) goes back as it came
        out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_step_pallas(x, dt, B, C, A, D, state, lanes, n_live,
                     interpret: bool = False):
    """``scan_step`` as one kernel over the lanes of a work list
    (``kda.work_list``): ``x``, ``dt`` [B, I], ``B``, ``C`` [B, N];
    ``state`` [L >= B, N, I] float32; ``lanes`` [B] and ``n_live`` [1]
    int32 ride in as scalar prefetch, and ``n_live`` is the grid's bound.
    The lanes ``lanes[:n_live]`` are stepped IN PLACE; no other lane's
    state is read or written, and its ``y`` comes back 0."""
    nB, I = x.shape
    N = A.shape[0]
    col = pl.BlockSpec((1, N, 1), lambda i, lanes, n: (lanes[i], 0, 0))
    lane = pl.BlockSpec((1, N, I), lambda i, lanes, n: (lanes[i], 0, 0))
    whole = lambda r: pl.BlockSpec((r, I), lambda i, lanes, n: (0, 0))  # noqa: E731
    y, state = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # never an empty grid: what a pipeline with no step writes
            # back is nobody's promise
            grid=(jnp.maximum(n_live[0], 1),),
            in_specs=[whole(nB), whole(nB), col, col, whole(N), whole(1),
                      lane],
            out_specs=[whole(nB), lane]),
        out_shape=[jax.ShapeDtypeStruct((nB, I), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operand indices count the two prefetched scalars
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # x, dt and y whole (double-buffered) + a state block in and
            # out: 14 MB at 96 lanes of 5120 channels
            vmem_limit_bytes=STEP_VMEM_BYTES),
        interpret=interpret,
        name="m1_step",
    )(lanes, n_live, x.astype(_F32), dt.astype(_F32),
      B.astype(_F32)[:, :, None], C.astype(_F32)[:, :, None],
      A.astype(_F32), D.astype(_F32)[None], state)
    return jnp.where(kda.visited(lanes, n_live)[:, None], y, 0.0), state
