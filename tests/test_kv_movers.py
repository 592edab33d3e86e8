"""The two K/V movers of the decode round — ring -> ctx region
(``llama.flush_ctx``) and ctx region -> pool (``llama.seal_blocks``) —
held to a plain numpy statement of what they move, byte for byte.

Both move unquantised rows as in-place span writes in a loop rolled over
lanes / entries (PR 34; the scatter and flat-gather forms made XLA:TPU
copy the whole region, tests/test_lowering_*.py guards that). One pair
serves every row kind: ``k`` and ``v`` of [kvh, hd] (dense models, kvh
sharded over ``tp``) and the latent block's one ``kv`` row (kvh == 1).
The scratch lane (index B) and scratch page 0 hold garbage by contract
and are not compared.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.models import llama
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

L, HD, S, R, PS = 3, 8, 32, 4, 8


def np_flush(ctx, ring, dest, base, valid):
    """Ring entry (b, r) holds position base[b] + r and goes to lane
    dest[b] if r < valid[b] and the position is inside the region."""
    out = ctx.copy()
    for b in range(ring.shape[2]):
        for r in range(ring.shape[3]):
            p = base[b] + r
            if r < valid[b] and p < ctx.shape[3]:
                out[:, :, dest[b], p] = ring[:, :, b, r]
    return out


def np_seal(pool, ctx, slots, starts, pages, ps):
    out = pool.copy()
    for s, t, p in zip(slots, starts, pages):
        out[:, :, p] = ctx[:, :, s, t:t + ps]
    return out


def _state(kinds, kvh, lanes, length, seed):
    rng = np.random.RandomState(seed)
    return {n: rng.randn(L, kvh, lanes, length, HD).astype(np.float32)
            for n in kinds}


def _put(state, tp):
    """On the device(s): kvh sharded over a tp-wide mesh as the program's
    own ``ctx_shardings`` / ``ring_shardings`` / ``cache_shardings`` do."""
    if tp == 1:
        return {n: jnp.asarray(a) for n, a in state.items()}
    mesh = make_mesh(MeshConfig(tp=tp), jax.devices()[:tp])
    s = NamedSharding(mesh, P(None, "tp", None, None, None))
    return {n: jax.device_put(a, s) for n, a in state.items()}


def i32(*xs):
    return jnp.asarray(xs, jnp.int32)


KV, LATENT = ("k", "v"), ("kv",)

# (kinds, kvh, tp, B, dest, ring_base, valid_len)
FLUSH = {
    "mid_region": (KV, 2, 1, 3, (0, 1, 2), (5, 17, 9), (4, 4, 4)),
    # a span that would run off the end starts at S - R and its entries
    # shift inside it: ring_base S-1, S-2, S-4 (the last start unclipped)
    "clipped_at_region_end": (KV, 2, 1, 3, (0, 1, 2),
                              (S - 1, S - 2, S - R), (4, 4, 4)),
    "clipped_and_partial": (KV, 2, 1, 2, (0, 1), (S - 3, S - 1), (2, 1)),
    "ring_base_past_the_end": (KV, 2, 1, 2, (0, 1), (S, S + 3), (4, 4)),
    "valid_len_zero_and_partial": (KV, 2, 1, 4, (0, 1, 2, 3),
                                   (3, 8, 0, 20), (0, 1, 3, 4)),
    "one_freed_lane": (KV, 2, 1, 3, (0, 3, 2), (4, 6, 11), (4, 4, 4)),
    # both park on the scratch lane, at overlapping spans
    "two_freed_lanes": (KV, 2, 1, 4, (4, 1, 4, 3), (6, 2, 7, 13),
                        (4, 4, 4, 2)),
    "dest_is_not_the_lane": (KV, 2, 1, 3, (2, 0, 1), (1, 12, 28),
                             (4, 3, 4)),
    "one_kv_head": (KV, 1, 1, 2, (0, 1), (7, 30), (4, 4)),
    # the dense cells' head count: clipped, partial, empty and freed at once
    "eight_kv_heads": (KV, 8, 1, 4, (0, 4, 2, 3), (S - 2, 3, 11, 24),
                       (4, 4, 0, 3)),
    "eight_kv_heads_over_tp4": (KV, 8, 4, 3, (2, 3, 0), (S - R, S - 1, 6),
                                (4, 2, 1)),
    "kvh_sharded_over_tp4": (KV, 4, 4, 4, (1, 0, 4, 3),
                             (S - 2, 5, 9, 16), (4, 2, 4, 4)),
    "latent_row": (LATENT, 1, 1, 3, (0, 3, 1), (S - 1, 4, 10), (4, 4, 3)),
}

# (kinds, kvh, tp, lanes, slots, starts, pages): entries (0, 0, 0) pad
SEAL = {
    "two_blocks": (KV, 2, 1, 3, (0, 2), (0, PS), (3, 5)),
    "padding_on_page_0": (KV, 2, 1, 3, (1, 0, 0, 0), (2 * PS, 0, 0, 0),
                          (4, 0, 0, 0)),
    "all_padding": (KV, 2, 1, 2, (0, 0), (0, 0), (0, 0)),
    # every lane seals every block of its context in one call
    "widest_pow2_batch": (KV, 2, 1, 4,
                          tuple(b for b in range(4) for _ in range(4)),
                          tuple(PS * j for _ in range(4) for j in range(4)),
                          tuple(range(1, 17))),
    "kvh_sharded_over_tp4": (KV, 4, 4, 3, (2, 0, 0, 1), (PS, 3 * PS, 0, 0),
                             (6, 2, 0, 7)),
    "one_kv_head": (KV, 1, 1, 2, (1, 0, 0, 0), (3 * PS, PS, 0, 0),
                    (2, 16, 0, 0)),
    "eight_kv_heads": (KV, 8, 1, 3, (0, 2, 1, 0), (2 * PS, 0, 3 * PS, 0),
                       (5, 1, 12, 0)),
    "latent_row": (LATENT, 1, 1, 3, (0, 2, 0, 0), (PS, 0, 0, 0),
                   (1, 9, 0, 0)),
}


@pytest.mark.parametrize("case", list(FLUSH))
def test_flush_spans_match_numpy(case):
    kinds, kvh, tp, B, dest, base, valid = FLUSH[case]
    ctx = _state(kinds, kvh, B + 1, S, 1)
    ring = _state(kinds, kvh, B, R, 2)
    got = llama.flush_ctx(_put(ctx, tp), _put(ring, tp), i32(*dest),
                          i32(*base), i32(*valid))
    assert sorted(got) == sorted(kinds)
    for n in kinds:
        want = np_flush(ctx[n], ring[n], dest, base, valid)
        # live lanes: exactly the valid entries moved, nothing else
        np.testing.assert_array_equal(np.asarray(got[n])[:, :, :B],
                                      want[:, :, :B])
        if tp > 1:
            assert got[n].sharding.spec == P(None, "tp", None, None, None)


@pytest.mark.parametrize("case", list(SEAL))
def test_seal_spans_match_numpy(case):
    kinds, kvh, tp, lanes, slots, starts, pages = SEAL[case]
    ctx = _state(kinds, kvh, lanes, S, 3)
    pool = _state(kinds, kvh, 17, PS, 4)
    got = llama.seal_blocks(_put(pool, tp), _put(ctx, tp), i32(*slots),
                            i32(*starts), i32(*pages), page_size=PS)
    assert sorted(got) == sorted(kinds)
    for n in kinds:
        want = np_seal(pool[n], ctx[n], slots, starts, pages, PS)
        # page 0 is scratch; every other page is either sealed or as it was
        np.testing.assert_array_equal(np.asarray(got[n])[:, :, 1:],
                                      want[:, :, 1:])
        if tp > 1:
            assert got[n].sharding.spec == P(None, "tp", None, None, None)


@pytest.mark.parametrize("kinds,kvh", [(KV, 2), (KV, 8), (LATENT, 1)],
                         ids=["kv_2_heads", "kv_8_heads", "latent_row"])
def test_seal_casts_to_the_pool_dtype(kinds, kvh):
    """A float32 region (CPU test engines) seals into a bf16 pool."""
    ctx = _state(kinds, kvh, 2, S, 5)
    pool = {n: jnp.zeros((L, kvh, 4, PS, HD), jnp.bfloat16) for n in kinds}
    got = llama.seal_blocks(pool, _put(ctx, 1), i32(1), i32(PS), i32(2),
                            page_size=PS)
    for n in kinds:
        assert got[n].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got[n][:, :, 2].astype(jnp.float32)),
            np.asarray(jnp.asarray(ctx[n][:, :, 1, PS:2 * PS])
                       .astype(jnp.bfloat16).astype(jnp.float32)))


def test_flush_casts_to_the_region_dtype():
    """A float32 ring flushes into a bf16 region; rows it does not cover
    keep their bits."""
    def bf16(a):   # rounded to bf16, held as float32
        return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))

    ctx = {n: bf16(a) for n, a in _state(KV, 2, 3, S, 6).items()}
    ring = _state(KV, 2, 2, R, 7)
    got = llama.flush_ctx(
        {n: jnp.asarray(a, jnp.bfloat16) for n, a in ctx.items()},
        _put(ring, 1), i32(1, 0), i32(S - 2, 9), i32(2, 3))
    for n in KV:
        assert got[n].dtype == jnp.bfloat16
        want = np_flush(ctx[n], bf16(ring[n]), (1, 0), (S - 2, 9), (2, 3))
        np.testing.assert_array_equal(
            np.asarray(got[n].astype(jnp.float32))[:, :, :2], want[:, :, :2])
