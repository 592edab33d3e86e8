"""A prefill chunk's per-row work over the row blocks that hold a live
row: the one loop the dense decoder's halves (models/llama.py:
``_live_rows``) and the hybrid block's halves and chunked scans
(models/ssm_moe.py) share.

A chunk is ``K`` lanes of ``T`` bucket rows of which lane ``i``'s first
``n_i = clip(seq_len_i - q_start_i, 0, T)`` are live (a prefix). Cut into
blocks of ``R`` rows, ``ceil(n_i / R)`` blocks of a lane hold a live row
(``live_row_trips``); ``over_live_blocks`` runs a function of one block
over exactly those, lane by lane and front to back, in ONE rolled loop
whose trip count is a traced value of the program: a dummy lane or a short
prompt costs no trip, and the lowered program has the same size at every
bucket width and lane count. The host's mirrors of what a program ran
(``llama.prefill_positions_run``) call ``live_row_trips`` too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def live_row_trips(q_starts, seq_lens, T: int, R: int):
    """Row blocks of each lane that hold a live row: ``ceil(n / R)`` of
    its ``n = seq_len - q_start`` live rows, none for a dummy lane.
    numpy in (the engine's mirror), numpy out; traced in, traced out."""
    return ((seq_lens - q_starts).clip(0, T) + (R - 1)) // R


def over_live_blocks(block, trips, rows, R: int, state=None):
    """``block`` over the (lane, row block) pairs that hold a live row.

    ``rows`` are the per-row operands, each [K, T, ...]; ``trips`` [K] =
    ``live_row_trips``. Without ``state``, ``block(lane, r0, blk)`` takes
    the operands' rows [r0, r0 + R) of ``lane`` and returns a list of
    arrays [R, ...]; the result is a tuple of [K, T, ...] arrays born
    zero, so rows of blocks that never ran are 0. With ``state`` (a pytree
    of [K, ...] leaves, a lane's recurrent state each) ``block(lane, r0,
    blk, s)`` also takes the lane's state as the blocks before left it and
    returns (the list, the state after the block); the result is (the
    tuple, the states after each lane's last live block: a lane with no
    live block keeps its own).

    The flat work list is the one prefill_attention builds for its query
    blocks: pair ``w`` belongs to the lane whose running total of trips
    first passes ``w``."""
    i32 = jnp.int32
    trips = trips.astype(i32)
    ends = jnp.cumsum(trips)

    def of(tree, lane):
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, lane, keepdims=False),
            tree)

    def one(lane, r0, s):
        blk = [jax.lax.dynamic_slice(
            x, (lane, r0) + (0,) * (x.ndim - 2), (1, R) + x.shape[2:])[0]
            for x in rows]
        if state is None:
            return block(lane, r0, blk), None
        return block(lane, r0, blk, of(s, lane))

    def run(w, carry):
        outs, s = carry
        lane = jnp.sum(w >= ends).astype(i32)
        r0 = (w - (ends[lane] - trips[lane])) * R
        ys, new = one(lane, r0, s)
        outs = tuple(
            jax.lax.dynamic_update_slice(
                o, y[None], (lane, r0) + (0,) * (y.ndim - 1))
            for o, y in zip(outs, ys))
        if state is not None:
            s = jax.tree.map(
                lambda x, y: jax.lax.dynamic_update_index_in_dim(
                    x, y, lane, 0), s, new)
        return outs, s

    K, T = rows[0].shape[:2]
    shapes, _ = jax.eval_shape(one, i32(0), i32(0), state)
    outs = tuple(jnp.zeros((K, T) + s.shape[1:], s.dtype) for s in shapes)
    outs, state = jax.lax.fori_loop(0, ends[-1], run, (outs, state))
    return outs if state is None else (outs, state)
