"""Flash decode kernel parity: the Pallas TPU kernel (interpret mode on
CPU) vs the pure-jnp reference, across context lengths, chunking, ring
occupancy, and the scratch-lane layout (reference analogue: vLLM's
paged-attention kernel tests; ours covers the round-4 two-tier
ctx+ring design, ops/flash_decode.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.attention import (
    PALLAS_INTERPRET,
    REFERENCE,
    DecodeAttention,
    ctx_decode_attention,
    dense_round_rows,
    flat_items,
)
from dynamo_tpu.ops.flash_decode import (
    _pick_chunk,
    flash_decode_attention,
    flash_decode_attention_reference,
)

L, NKV, NH, HD = 3, 2, 4, 16
B, S, R = 4, 64, 4


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    ck = jnp.asarray(rng.randn(L, NKV, B + 1, S, HD) * 0.3, jnp.float32)
    cv = jnp.asarray(rng.randn(L, NKV, B + 1, S, HD) * 0.3, jnp.float32)
    rk = jnp.asarray(rng.randn(L, NKV, B, R, HD) * 0.3, jnp.float32)
    rv = jnp.asarray(rng.randn(L, NKV, B, R, HD) * 0.3, jnp.float32)
    q = jnp.asarray(rng.randn(B, NH, HD), jnp.float32)
    return q, ck, cv, rk, rv


def kernel(q, ck, cv, rk, rv, layer, ctx, base, *scales, chunk, live=None):
    """The kernel, interpreted, as every caller reaches it: through the
    wrapper that builds its work list."""
    return ctx_decode_attention(
        DecodeAttention(PALLAS_INTERPRET, chunk=chunk), q, ck, cv, rk, rv,
        jnp.int32(layer), ctx, base, *scales, live=live)


def both(data, ctx, base, chunk, layer=0, live=None):
    q, ck, cv, rk, rv = data
    got = kernel(q, ck, cv, rk, rv, layer, ctx, base, chunk=chunk, live=live)
    want = flash_decode_attention_reference(
        q, ck, cv, rk, rv, jnp.int32(layer), ctx, base, live=live
    )
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_kernel_matches_reference(data, chunk):
    # mid-round state: ring holds 2 tokens beyond each slot's ctx base
    base = jnp.asarray([1, 15, 31, 60], jnp.int32)
    ctx = base + 2
    for layer in (0, L - 1):
        got, want = both(data, ctx, base, chunk, layer)
        # interpret mode emulates the MXU's bf16 passes -> ~1e-3 tolerance
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)


def test_ring_only_context(data):
    """Fresh slots: base=0, everything lives in the ring."""
    base = jnp.asarray([0, 0, 0, 0], jnp.int32)
    ctx = jnp.asarray([1, 2, 3, 4], jnp.int32)
    got, want = both(data, ctx, base, 16)
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)


def test_single_token_context_is_v_row(data):
    """base=0, ctx=1: softmax over one ring position — output must be
    (approximately, interpret-mode bf16 dots) the ring v row 0."""
    q, ck, cv, rk, rv = data
    base = jnp.zeros(B, jnp.int32)
    ctx = jnp.ones(B, jnp.int32)
    got = kernel(q, ck, cv, rk, rv, 1, ctx, base, chunk=32)
    for b in range(B):
        for n in range(NH):
            h = n // (NH // NKV)
            np.testing.assert_allclose(
                np.asarray(got)[b, n], np.asarray(rv)[1, h, b, 0],
                rtol=5e-3, atol=5e-3,
            )


def test_chunk_boundary_contexts(data):
    """Ring bases straddling chunk boundaries agree with the reference
    (the per-slot DMA-skip index math)."""
    for bases in ([15, 16, 17, 31], [32, 33, 48, 60]):
        base = jnp.asarray(bases, jnp.int32)
        ctx = base + 3
        got, want = both(data, ctx, base, 16)
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)


# --- in-kernel int8 decode ctx (PR 14) -------------------------------

def _quantize_ctx(x, group):
    """Per-(layer, slot-lane, group) absmax int8 — the ctx scale grid
    models/llama.init_ctx uses (no kvh axis, group == page_size)."""
    lyr, kvh, lanes, s, hd = x.shape
    ng = s // group
    grouped = np.asarray(x).reshape(lyr, kvh, lanes, ng, group, hd)
    absmax = np.abs(grouped).max(axis=(1, 4, 5))        # [L, lanes, nG]
    scale = np.maximum(absmax / 127.0, 1e-8).astype(np.float32)
    q = np.clip(
        np.rint(grouped / scale[:, None, :, :, None, None]), -127, 127
    ).astype(np.int8).reshape(x.shape)
    return jnp.asarray(q), jnp.asarray(scale)


def _quant_args(data, group):
    q, ck, cv, rk, rv = data
    ck_q, ks = _quantize_ctx(ck, group)
    cv_q, vs = _quantize_ctx(cv, group)
    return q, ck_q, cv_q, rk, rv, ks, vs


@pytest.mark.parametrize("group", [16, 64])   # nG in {4, 1}
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_int8_kernel_matches_reference(data, group, chunk):
    """Quantized kernel (in-VMEM dequant after the chunk DMA) vs the
    quantized pure-jnp reference, across scale-group widths, chunking,
    and odd ctx/ring_base straddles. Pinned at 1e-2 abs by ISSUE 14
    (interpret mode lands ~1e-6)."""
    q, ck_q, cv_q, rk, rv, ks, vs = _quant_args(data, group)
    for bases in ([1, 15, 31, 60], [15, 16, 17, 33]):
        base = jnp.asarray(bases, jnp.int32)
        ctx = base + 2
        for layer in (0, L - 1):
            got = kernel(q, ck_q, cv_q, rk, rv, layer, ctx, base, ks, vs,
                         chunk=chunk)
            want = flash_decode_attention_reference(
                q, ck_q, cv_q, rk, rv, jnp.int32(layer), ctx, base,
                ctx_k_scale=ks, ctx_v_scale=vs,
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-2, rtol=0)


# --- the work list (PR 53) --------------------------------------------
# contexts at 0 / 1 / chunk - 1 / chunk / chunk + 1 / S rows of the region
# (chunk 16, S 64) and what is live of the four lanes. A lane that is not
# live holds a stale length, as a freed lane of the engine does.
_C = 16
WORK_LISTS = {
    "no_lane_live": ([40, 63, 17, 5], [0, 0, 0, 0]),
    "one_live_among_stale_lengths": ([60, 64, 3, 64], [0, 0, 1, 0]),
    "every_lane_live": ([1, 15, 31, 60], [1, 1, 1, 1]),
    "rows_0_1_chunk-1_chunk": ([0, 1, _C - 1, _C], [1, 1, 1, 1]),
    "rows_chunk+1_S_and_holes": ([_C + 1, S, 2 * _C, S], [1, 1, 0, 1]),
    "ring_only_contexts": ([0, 0, 0, 0], [1, 0, 1, 1]),
    "the_last_lane_alone": ([64, 64, 64, 33], [0, 0, 0, 1]),
    "a_whole_region_every_lane": ([S, S, S, S], [1, 1, 1, 1]),
}


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("case", sorted(WORK_LISTS))
def test_the_kernel_follows_its_work_list(data, case, quant):
    """One invocation a layer walks the LIVE lanes' chunks, ascending,
    then each one's ring: a live lane reads what the reference reads, a
    lane that is not on the list comes back exactly 0 whatever its stale
    length, and an empty list starts nothing and returns zeros."""
    below, live = (np.asarray(x) for x in WORK_LISTS[case])
    live = live.astype(bool)
    base = jnp.asarray(below, jnp.int32)
    ctx = base + jnp.asarray([1, 2, 3, 4], jnp.int32)   # ring occupancy
    args, tol = data, dict(rtol=5e-3, atol=5e-3)
    if quant:
        args, tol = _quant_args(data, 16), dict(atol=1e-2, rtol=0)
    got = np.asarray(kernel(*args[:5], 1, ctx, base, *args[5:], chunk=_C,
                            live=jnp.asarray(live)))
    want = np.asarray(flash_decode_attention_reference(
        *args[:5], jnp.int32(1), ctx, base, *args[5:]))
    np.testing.assert_allclose(got[live], want[live], **tol)
    assert not got[~live].any()
    if live.all():
        # None = every lane (the kernel tests' form)
        np.testing.assert_array_equal(
            got, np.asarray(kernel(*args[:5], 1, ctx, base, *args[5:],
                                   chunk=_C)))


def test_the_work_list_by_hand():
    """``flat_items``: lane after lane, a lane's items ascending; the
    kernel's wrapper marks a live lane's last item (its ring) with the
    region's chunk count. And the host's mirror of what the list reads:
    whole chunks a live lane under the kernel, every lane's whole region
    under the jnp reference."""
    lane_of, item_of, ends = flat_items(jnp.asarray([2, 0, 3, 1]), 12)
    assert np.asarray(ends).tolist() == [2, 2, 5, 6]    # 6 items in all
    assert np.asarray(lane_of)[:6].tolist() == [0, 0, 2, 2, 2, 3]
    assert np.asarray(item_of)[:6].tolist() == [0, 1, 0, 1, 2, 0]
    assert not np.asarray(flat_items(jnp.zeros(4, jnp.int32), 12)[2]).any()
    with pytest.raises(ValueError, match="work list of 7 items"):
        flash_decode_attention(
            jnp.zeros((B, NH, HD)), jnp.zeros((L, NKV, B + 1, S, HD)),
            jnp.zeros((L, NKV, B + 1, S, HD)), jnp.zeros((L, NKV, B, R, HD)),
            jnp.zeros((L, NKV, B, R, HD)), jnp.int32(0),
            jnp.ones(B, jnp.int32), jnp.zeros(B, jnp.int32),
            (jnp.zeros(7, jnp.int32),) * 3 + (jnp.zeros(1, jnp.int32),),
            chunk=_C, interpret=True)
    lens = np.array([1301, 1, 513, 2000], np.int32)
    live = np.array([True, False, True, False])
    assert dense_round_rows(DecodeAttention(PALLAS_INTERPRET), lens, live, 4,
                            2048) == (4 * (3 + 1) * 512, 4 * (1300 + 512))
    assert dense_round_rows(REFERENCE, lens, live, 4, 2048) == (
        4 * 4 * 2048, 4 * (1300 + 512))


def test_int8_dequant_error_bound(data):
    """Per-element dequantization error of the ctx payload is bounded by
    absmax/127 per (layer, lane, group) — the int8 quantizer invariant
    every upstream writer (prefill store, ring flush, seal) relies on."""
    _, ck, _, _, _ = data
    for group in (16, 64):
        ck_q, ks = _quantize_ctx(ck, group)
        ng = S // group
        deq = (np.asarray(ck_q, np.float32)
               .reshape(L, NKV, B + 1, ng, group, HD)
               * np.asarray(ks)[:, None, :, :, None, None])
        orig = np.asarray(ck).reshape(L, NKV, B + 1, ng, group, HD)
        bound = np.asarray(ks) * 0.5 + 1e-6   # scale = absmax/127
        err = np.abs(deq - orig).max(axis=(1, 4, 5))
        assert (err <= bound).all()


def test_int8_output_close_to_dense(data):
    """Quantized attention stays close to the bf16/f32 dense path: the
    quant noise per KV element is <= absmax/127 (~0.01 for this data),
    so the attention output — a convex combination of V rows — moves by
    the same order."""
    q, ck, cv, rk, rv = data
    _, ck_q, cv_q, _, _, ks, vs = _quant_args(data, 16)
    base = jnp.asarray([7, 21, 40, 61], jnp.int32)
    ctx = base + 2
    dense = flash_decode_attention_reference(
        q, ck, cv, rk, rv, jnp.int32(0), ctx, base)
    quant = flash_decode_attention_reference(
        q, ck_q, cv_q, rk, rv, jnp.int32(0), ctx, base,
        ctx_k_scale=ks, ctx_v_scale=vs)
    np.testing.assert_allclose(
        np.asarray(quant), np.asarray(dense), atol=0.08, rtol=0)


def test_pick_chunk():
    """_pick_chunk replaces the old gcd() fallback: honor exact
    requests, else the largest divisor <= want that is a multiple of
    the scale group, promoted past the grid-overhead floor."""
    assert _pick_chunk(64, 512) == 64        # clamp to S
    assert _pick_chunk(64, 16) == 16         # exact tile honored
    assert _pick_chunk(512, 512) == 512
    # non-tiling want: largest divisor <= want, floored at 128 (the old
    # gcd(512, 520) == 8 cliff)
    assert _pick_chunk(520, 512) == 260
    assert _pick_chunk(520, 512, 8) == 520   # group forces whole-S
    assert _pick_chunk(64, 16, 64) == 64     # group > want promotes
    # result always tiles S and the group
    for s, want, step in ((520, 512, 8), (192, 100, 16), (96, 64, 32)):
        c = _pick_chunk(s, want, step)
        assert s % c == 0 and c % step == 0
