"""The one prefill attention (ops/attention.py prefill_attention).

Equivalence against a plain dense float32 softmax written here, over the
axes the blocked loop bounds depend on (prior context, live length, GQA
ratio, width vs block, tree masks, dtype, an int8 region); the host-side
pair count against a brute-force count; the model-level guarantee that
fresh and continued prefill agree, solo and batched; and the size of the
lowered programs, which must not depend on the bucket width or the lane
count (the CPU stand-in for what set-up pays on the chip)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import (
    PALLAS,
    PALLAS_INTERPRET,
    DecodeAttention,
    PriorContext,
    dense_prefill_attention,
    fused_prefill_attention,
    prefill_attention,
    prefill_attention_pairs,
    prefill_fuses,
    prefill_steps,
)
from dynamo_tpu.ops.flash_prefill import flash_prefill_attention

HD = 16
S = 48          # region rows per lane
BLOCK = 8


def dense_reference(q, k_new, v_new, q_starts, seq_lens, region=None,
                    slots=None, chunk_masks=None):
    """Plain float32 softmax attention, one lane and one head at a time.
    Row i of lane k sits at position q_start + i and sees region rows
    below min(q_start, seq_len) plus chunk keys j with position below
    seq_len and j <= i (or chunk_mask[i, j])."""
    q, k_new, v_new = (np.asarray(x, np.float32) for x in (q, k_new, v_new))
    K, T, nh, hd = q.shape
    kvh = k_new.shape[2]
    out = np.zeros((K, T, nh, v_new.shape[3]), np.float32)
    for lane in range(K):
        qs, sl = int(q_starts[lane]), int(seq_lens[lane])
        n_ctx = min(qs, sl) if region is not None else 0
        for h in range(nh):
            g = h // (nh // kvh)
            keys, vals = k_new[lane, :, g], v_new[lane, :, g]
            if n_ctx:
                rk, rv = region
                keys = np.concatenate(
                    [np.asarray(rk, np.float32)[0, g, slots[lane], :n_ctx],
                     keys])
                vals = np.concatenate(
                    [np.asarray(rv, np.float32)[0, g, slots[lane], :n_ctx],
                     vals])
            j = np.arange(T)
            for i in range(T):
                if chunk_masks is None:
                    ok = (j <= i) & (qs + j < sl)
                else:
                    ok = np.asarray(chunk_masks[lane, i]) & (qs + j < sl)
                ok = np.concatenate([np.ones(n_ctx, bool), ok])
                if not ok.any():
                    continue
                s = keys[ok] @ q[lane, i, h] / np.sqrt(hd)
                p = np.exp(s - s.max())
                out[lane, i, h] = (p / p.sum()) @ vals[ok]
    return out


def make_case(seed, K, T, nh, kvh, dtype, lanes=5):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q = draw(K, T, nh, HD)
    k_new, v_new = draw(K, T, kvh, HD), draw(K, T, kvh, HD)
    region = (draw(1, kvh, lanes, S, HD), draw(1, kvh, lanes, S, HD))
    slots = np.asarray(rng.permutation(lanes)[:K], np.int32)
    return q, k_new, v_new, region, slots


def live_rows(T, q_starts, seq_lens):
    """[K, T] bool: rows the caller reads (a real prompt token). Rows of
    a dead query block are zeros, other padding rows are unspecified."""
    n = np.clip(np.asarray(seq_lens) - np.asarray(q_starts), 0, T)
    return np.arange(T)[None, :] < n[:, None]


def check(out, ref, rows, dtype):
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[rows], ref[rows], atol=tol, rtol=tol)


# (T, q_start, live rows of the chunk): q_start 0 / mid-block / aligned,
# chunks shorter than T by less and by more than a block, T no multiple
# of the block, T below the block
GEOMETRY = [
    pytest.param(24, 0, 24, id="fresh-full"),
    pytest.param(24, 0, 19, id="fresh-short-by-less-than-a-block"),
    pytest.param(24, 0, 5, id="fresh-short-by-more-than-a-block"),
    pytest.param(24, 13, 24, id="ctx-mid-block"),
    pytest.param(24, 16, 20, id="ctx-block-aligned"),
    pytest.param(24, 21, 3, id="ctx-mid-block-short"),
    pytest.param(20, 0, 20, id="fresh-width-not-a-block-multiple"),
    pytest.param(20, 9, 17, id="ctx-width-not-a-block-multiple"),
    pytest.param(5, 11, 4, id="ctx-width-below-the-block"),
    pytest.param(1, 30, 1, id="ctx-single-token"),
]


@pytest.mark.parametrize("T,q_start,n_live", GEOMETRY)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_matches_dense_softmax(T, q_start, n_live, dtype):
    """Two lanes: the parametrised one and a full one at another start."""
    q, k_new, v_new, region, slots = make_case(T * 100 + q_start, 2, T, 8,
                                               2, dtype)
    q_starts = np.asarray([q_start, 8], np.int32)
    seq_lens = np.asarray([q_start + n_live, 8 + T], np.int32)
    ref = dense_reference(q, k_new, v_new, q_starts, seq_lens, region, slots)
    ctx = PriorContext(*region, 0, jnp.asarray(slots))
    out = prefill_attention(q, k_new, v_new, jnp.asarray(q_starts),
                            jnp.asarray(seq_lens), ctx, block=BLOCK)
    check(out, ref, live_rows(T, q_starts, seq_lens), dtype)
    if q_start == 0:
        # the fresh program (no region operand at all) agrees on the lane
        fresh = prefill_attention(
            q[:1], k_new[:1], v_new[:1], jnp.asarray(q_starts[:1]),
            jnp.asarray(seq_lens[:1]), block=BLOCK)
        check(fresh, ref[:1], live_rows(T, q_starts[:1], seq_lens[:1]),
              dtype)


@pytest.mark.parametrize("n_rep", [1, 4, 8])
def test_gqa_ratios(n_rep):
    kvh, T = 2, 24
    q, k_new, v_new, region, slots = make_case(n_rep, 2, T, kvh * n_rep,
                                               kvh, jnp.float32)
    q_starts = np.asarray([0, 10], np.int32)
    seq_lens = np.asarray([17, 10 + T], np.int32)
    ref = dense_reference(q, k_new, v_new, q_starts, seq_lens, region, slots)
    out = prefill_attention(
        q, k_new, v_new, jnp.asarray(q_starts), jnp.asarray(seq_lens),
        PriorContext(*region, 0, jnp.asarray(slots)), block=BLOCK)
    check(out, ref, live_rows(T, q_starts, seq_lens), jnp.float32)


@pytest.mark.parametrize("with_ctx", [False, True], ids=["fresh", "ctx"])
def test_dummy_lane_is_zero_and_dead_blocks_are_zero(with_ctx):
    """seq_len 0 -> the lane's whole output is 0 (not garbage, not NaN),
    and so are the query blocks past a short prompt's last live block."""
    T = 24
    q, k_new, v_new, region, slots = make_case(7, 3, T, 4, 2, jnp.float32)
    q_starts = np.asarray([0, 0, 12 if with_ctx else 0], np.int32)
    seq_lens = np.asarray([0, 5, q_starts[2] + T], np.int32)
    ctx = (PriorContext(*region, 0, jnp.asarray(slots)) if with_ctx
           else None)
    out = np.asarray(prefill_attention(
        q, k_new, v_new, jnp.asarray(q_starts), jnp.asarray(seq_lens), ctx,
        block=BLOCK))
    assert (out[0] == 0).all()
    assert (out[1, BLOCK:] == 0).all() and (out[1, :5] != 0).any()
    ref = dense_reference(q, k_new, v_new, q_starts, seq_lens,
                          region if with_ctx else None, slots)
    check(out, ref, live_rows(T, q_starts, seq_lens), jnp.float32)


def tree_mask(parents):
    """Ancestor-or-self visibility of a packed tree (parent -1 = root)."""
    n = len(parents)
    m = np.zeros((n, n), bool)
    for i in range(n):
        j = i
        while j >= 0:
            m[i, j] = True
            j = parents[j]
    return m


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_tree_chunk_mask(dtype):
    """A packed token tree: a node sees its ancestor chain, which lies
    ABOVE the diagonal for no node but is not a prefix either; a padding
    node (all-False row) emits zeros; a dummy lane costs and emits 0."""
    T = 12
    parents = [-1, 0, 0, 1, 1, 2, 3, 5, 5, 8]
    cm = np.zeros((3, T, T), bool)
    cm[0, :10, :10] = tree_mask(parents)
    cm[1, :10, :10] = tree_mask(list(range(-1, 9)))  # a chain = causal
    q, k_new, v_new, region, slots = make_case(3, 3, T, 8, 2, dtype)
    q_starts = np.asarray([19, 6, 0], np.int32)
    seq_lens = np.asarray([19 + T, 6 + T, 0], np.int32)
    ref = dense_reference(q, k_new, v_new, q_starts, seq_lens, region, slots,
                          cm)
    out = prefill_attention(
        q, k_new, v_new, jnp.asarray(q_starts), jnp.asarray(seq_lens),
        PriorContext(*region, 0, jnp.asarray(slots)),
        jnp.asarray(cm), block=BLOCK, ctx_span=32)
    rows = np.zeros((3, T), bool)
    rows[:2, :10] = True
    check(out, ref, rows, dtype)
    out = np.asarray(out, np.float32)
    # padding nodes still see the committed prefix (as before this
    # attention): finite, never read
    assert np.isfinite(out).all() and (out[2] == 0).all()


def test_int8_region_is_dequantized_per_block():
    """An int8 region with a per-(lane, group) scale grid reads as the
    dequantized float region would, group narrower than the block."""
    T, g = 16, 4
    q, k_new, v_new, region, slots = make_case(11, 2, T, 4, 2, jnp.float32)
    rng = np.random.default_rng(5)
    lanes = region[0].shape[2]
    scale = jnp.asarray(rng.uniform(0.01, 0.03, (2, 1, lanes, S // g)),
                        jnp.float32)
    qreg = [jnp.asarray(rng.integers(-127, 128, region[0].shape), jnp.int8)
            for _ in range(2)]
    deq = [x.astype(jnp.float32)
           * jnp.repeat(sc, g, axis=-1)[:, None, :, :, None]
           for x, sc in zip(qreg, scale)]
    q_starts = np.asarray([27, 32], np.int32)
    seq_lens = np.asarray([27 + 9, 32 + T], np.int32)
    ref = dense_reference(q, k_new, v_new, q_starts, seq_lens, deq, slots)
    out = prefill_attention(
        q, k_new, v_new, jnp.asarray(q_starts), jnp.asarray(seq_lens),
        PriorContext(qreg[0], qreg[1], 0, jnp.asarray(slots),
                     scale[0], scale[1]),
        block=BLOCK)
    check(out, ref, live_rows(T, q_starts, seq_lens), jnp.float32)


# ---------------------------------------------------------------------------
# the fused kernel (ops/flash_prefill.py), interpreted, against the loops:
# the expanded latent chunks' K and V per head, V narrower than K, keys as
# columns; and the dense decoder's groups of ``rep`` query heads a K/V
# head, keys as the rows the engine's region holds (PR 63)

FUSED_T, FUSED_SPAN = 32, 48
# (q_starts, live rows of each chunk, with a region)
FUSED_CASES = [
    pytest.param([0, 0, 0], [32, 32, 32], False, id="fresh"),
    pytest.param([0, 0, 0], [32, 19, 3], False,
                 id="fresh-last-block-partly-dead"),
    pytest.param([13, 16, 40], [32, 9, 5], True,
                 id="continuing-ragged-starts-and-lengths"),
    pytest.param([24, 0, 7], [32, 0, 32], True, id="dummy-lane"),
    pytest.param([0, 48, 0], [20, 32, 0], True,
                 id="fresh-lane-in-a-continuing-program"),
    pytest.param([0, 0, 0], [0, 0, 0], True, id="nothing-live"),
]


def fused_case(seed, dtype=jnp.float32, K=3, nh=4, hd=24, hd_v=16, kvh=0):
    """``kvh`` 0: K and V per head; else ``kvh`` K/V heads, chunk and
    region, under the ``nh`` query heads."""
    rng = np.random.default_rng(seed)
    kvh = kvh or nh

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    return (draw(K, FUSED_T, nh, hd), draw(K, FUSED_T, kvh, hd),
            draw(K, FUSED_T, kvh, hd_v),
            (draw(1, kvh, 5, FUSED_SPAN, hd),
             draw(1, kvh, 5, FUSED_SPAN, hd_v)),
            jnp.asarray(rng.permutation(5)[:K], jnp.int32))


# (query heads a grid step, query heads, K/V heads): the latent form, a
# head and all four a step; groups of 2 and of 4 query heads, a group and
# every group a step, the region's keys read as rows in place
FUSED_HEADS = [
    pytest.param(1, 4, 4, id="a-head-a-step"),
    pytest.param(4, 4, 4, id="all-heads"),
    pytest.param(2, 4, 2, id="rep2-a-group-a-step"),
    pytest.param(8, 8, 2, id="rep4-all-groups"),
]


@pytest.mark.parametrize("heads,nh,kvh", FUSED_HEADS)
@pytest.mark.parametrize("q_starts,n_live,with_ctx", FUSED_CASES)
def test_fused_kernel_equals_the_loops(q_starts, n_live, with_ctx, heads,
                                       nh, kvh):
    """Every row, live or not, to float32-rounding distance: the same
    masks over the same blocks in the same order; dead blocks and dummy
    lanes 0 in both."""
    q, k, v, region, slots = fused_case(sum(n_live) + heads, nh=nh, kvh=kvh)
    qs = jnp.asarray(q_starts, jnp.int32)
    sl = qs + jnp.asarray(n_live, jnp.int32)
    ctx = PriorContext(*region, 0, slots) if with_ctx else None
    assert prefill_fuses(FUSED_T, nh, kvh, FUSED_SPAN * with_ctx, BLOCK)
    want = np.asarray(prefill_attention(q, k, v, qs, sl, ctx, block=BLOCK))
    got = np.asarray(fused_prefill_attention(
        q, k, v, qs, sl, ctx, block=BLOCK, interpret=True, heads=heads,
        key_rows=nh > kvh))
    assert got.shape == want.shape == (3, FUSED_T, nh, 16)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    dead = ~np.repeat(live_rows(FUSED_T, q_starts, np.asarray(sl))[
        :, ::BLOCK], BLOCK, axis=1)
    assert not got[dead].any()
    ref = dense_reference(q, k, v, q_starts, np.asarray(sl),
                          region if with_ctx else None, np.asarray(slots))
    check(got, ref, live_rows(FUSED_T, q_starts, np.asarray(sl)),
          jnp.float32)


@pytest.mark.parametrize("rep", [1, 4], ids=["rep1", "rep4"])
@pytest.mark.parametrize("q_starts,n_live,with_ctx", FUSED_CASES)
def test_pair_count_is_what_the_fused_kernel_walks(q_starts, n_live,
                                                   with_ctx, rep):
    """``prefill_attention_pairs`` still mirrors the device: the list the
    kernel's grid walks holds one step a scored (query block, key block)
    pair, each item's steps ascending and its last one marked; ONE list
    whatever the group (a step is a K/V head's tile against its ``rep``
    query heads' blocks: the grid walks the list once a block of K/V
    heads)."""
    span = FUSED_SPAN * with_ctx
    assert prefill_fuses(FUSED_T, 2 * rep, 2, span, BLOCK)
    sl = np.asarray(q_starts) + np.asarray(n_live)
    below = np.minimum(np.minimum(q_starts, sl), span)
    (lane_of, qb_of, j_of, last_of, total), block_live, pblk = prefill_steps(
        jnp.asarray(n_live), jnp.asarray(below), FUSED_T, span, BLOCK)
    total = int(total)
    assert total * BLOCK * BLOCK == prefill_attention_pairs(
        FUSED_T, q_starts, sl, span, block=BLOCK)[1]
    items = list(zip(np.asarray(lane_of)[:total], np.asarray(qb_of)[:total]))
    want = [(lane, qb) for lane, n in enumerate(n_live)
            for qb in range(-(-n // BLOCK))
            for _ in range(-(-int(below[lane]) // BLOCK) + qb + 1)]
    assert items == want
    assert int(np.asarray(last_of)[:total].sum()) == len(set(want)) == int(
        block_live.sum())
    j_of = np.asarray(j_of)[:total]
    assert all(j_of[i] == (0 if i == 0 or items[i] != items[i - 1]
                           else j_of[i - 1] + 1) for i in range(total))


@pytest.mark.parametrize("nh,kvh,hd", [(2, 2, 192), (4, 1, 128)],
                         ids=["latent-192", "rep4-rows-of-128"])
def test_fused_kernel_at_whole_lanes(nh, kvh, hd):
    """At the served widths (blocks of whole 128-lane tiles, V 128 wide)
    the running max rides replicated over a register's lanes and the
    running sum as lane-wise partial sums: the form the chip runs, here
    interpreted at one block of 128; a group of four heads over ONE K/V
    head of 128 with a partial last block and a prior region read in
    place."""
    rng = np.random.default_rng(3)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, k, v = (draw(2, 256, nh, hd), draw(2, 256, kvh, hd),
               draw(2, 256, kvh, 128))
    ctx = PriorContext(draw(1, kvh, 3, 256, hd), draw(1, kvh, 3, 256, 128), 0,
                       jnp.asarray([2, 0], jnp.int32))
    qs, sl = jnp.asarray([130, 256]), jnp.asarray([130 + 256, 256 + 9])
    want = np.asarray(prefill_attention(q, k, v, qs, sl, ctx, block=128))
    got = np.asarray(fused_prefill_attention(q, k, v, qs, sl, ctx, block=128,
                                             interpret=True, heads=nh,
                                             key_rows=nh > kvh))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    assert not got[1, 128:].any() and got[1, :9].any()


@pytest.mark.parametrize("kvh", [4, 2], ids=["rep1", "rep2-key-rows"])
def test_fused_kernel_row_that_sees_no_key_emits_zero(kvh):
    """The kernel's own gate (no caller's mask reaches it under plain
    causality: every listed row sees key 0): a lane on the list whose
    live length the kernel is told is 0 scores only masked keys, holds
    p = exp(0) a key, and must emit 0, not their mean."""
    q, k, v, _, _ = fused_case(5, K=1, kvh=kvh)
    steps, _, _ = prefill_steps(jnp.asarray([FUSED_T]), jnp.asarray([0]),
                                FUSED_T, 0, BLOCK)
    rep = 4 // kvh
    # the kernel's operands as ``fused_prefill_attention`` lays them out
    qt = q.reshape(1, FUSED_T // BLOCK, BLOCK, kvh, rep, 24).transpose(
        0, 3, 1, 4, 2, 5).reshape(1, kvh, FUSED_T * rep, 24)
    out = flash_prefill_attention(
        qt, k.transpose((0, 2, 1, 3) if rep > 1 else (0, 2, 3, 1)),
        v.transpose(0, 2, 1, 3), steps, jnp.asarray([0]), block=BLOCK,
        heads=2, key_rows=rep > 1, interpret=True)
    assert out.shape == (1, FUSED_T, 4 * 16) and not np.asarray(out).any()


@pytest.mark.parametrize("width,heads,kv_heads,span,fuses", [
    (4096, 32, 32, 16384, True),
    (128, 32, 32, 0, True),
    (1024, 32, 8, 0, True),      # a group of four heads a K/V head
    (4096, 32, 8, 4096, True),   # (Mistral-7B; a shard of Nemo-12B)
    (256, 8, 2, 4096, True),
    (128, 20, 1, 0, True),       # every head on ONE K/V head
    (1024, 32, 12, 0, False),    # no whole groups
    (300, 32, 32, 0, False),     # the last block would slide back
    (300, 32, 8, 0, False),
    (256, 32, 32, 1000, False),  # and so would the region's
    (256, 32, 8, 1000, False),
])
def test_fused_kernel_shape_rule(width, heads, kv_heads, span, fuses):
    assert prefill_fuses(width, heads, kv_heads, span) is fuses


@pytest.mark.parametrize("holds", ["tree-mask", "int8-region", "no-attn",
                                   "reference", "head-of-64"])
def test_what_a_dense_call_holds_keeps_the_loops(holds):
    """The dense decoder's call site (``dense_prefill_attention``) by what
    the call holds: a tree mask (the speculative verifier) and an int8
    region keep the XLA loops though the engine's programs are traced for
    the kernel, and so does a caller that hands no ``attn`` over or the
    jnp reference's, and a head that is no whole 128-lane tiles (the
    smoke's llama3_1b: XLA holds its region rows-minor): the loops' own
    result, and no fused body in the text. A window and a selection are
    no arguments of the call: their callers (models/ssm_moe.py) call
    ``prefill_attention``."""
    q, k, v, region, slots = fused_case(
        11, hd=64 if holds == "head-of-64" else 128, kvh=2)
    qs, sl = jnp.asarray([13, 16, 0]), jnp.asarray([45, 25, 0])
    ctx = PriorContext(*region, 0, slots)
    attn, masks = DecodeAttention(PALLAS_INTERPRET), None
    if holds == "tree-mask":
        masks = jnp.asarray(np.tril(np.ones((FUSED_T, FUSED_T), bool))[
            None].repeat(3, 0))
    elif holds == "int8-region":
        scale = jnp.full((1, 5, FUSED_SPAN // 16), 0.05, jnp.float32)
        ctx = PriorContext(*(jnp.clip(jnp.round(x / 0.05), -127, 127).astype(
            jnp.int8) for x in region), 0, slots, scale, scale)
    elif holds == "no-attn":
        attn = None
    elif holds == "reference":
        attn = DecodeAttention("reference")

    @jax.jit
    def both(q, k, v, qs, sl, ctx, masks):
        return (dense_prefill_attention(attn, q, k, v, qs, sl, ctx, masks),
                prefill_attention(q, k, v, qs, sl, ctx, masks))

    text = both.lower(q, k, v, qs, sl, ctx, masks).as_text()
    assert "fused_prefill_attention" not in text or holds == "int8-region"
    got, want = both(q, k, v, qs, sl, ctx, masks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_plain_dense_call_runs_the_kernel():
    """... and the call that holds none of those runs the kernel (here
    interpreted), equal to the loops to float32 rounding; traced for the
    compiled kernel it lowers, on the CPU, to the loops inside the one
    ``fused_prefill_attention`` body."""
    q, k, v, region, slots = fused_case(11, hd=128, kvh=2)
    qs, sl = jnp.asarray([13, 16, 0]), jnp.asarray([45, 25, 0])
    ctx = PriorContext(*region, 0, slots)
    want = np.asarray(prefill_attention(q, k, v, qs, sl, ctx))
    for impl in (PALLAS_INTERPRET, PALLAS):
        got = np.asarray(dense_prefill_attention(
            DecodeAttention(impl), q, k, v, qs, sl, ctx))
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    jaxpr = str(jax.make_jaxpr(lambda *a: dense_prefill_attention(
        DecodeAttention(PALLAS_INTERPRET), *a))(q, k, v, qs, sl, ctx))
    assert "pallas_call" in jaxpr


def test_outside_the_shape_rule_the_loops_run():
    """A width that is no whole number of blocks: the one call site gets
    the loops' own result (bit for bit), kernel asked for or not."""
    q, k, v, region, slots = fused_case(9)
    q, k, v = q[:, :20], k[:, :20], v[:, :20]
    qs, sl = jnp.asarray([0, 9, 0]), jnp.asarray([20, 26, 0])
    ctx = PriorContext(*region, 0, slots)
    np.testing.assert_array_equal(
        np.asarray(fused_prefill_attention(q, k, v, qs, sl, ctx, block=BLOCK,
                                           interpret=True)),
        np.asarray(prefill_attention(q, k, v, qs, sl, ctx, block=BLOCK)))


# ---------------------------------------------------------------------------
# the host-side pair count (engine counters)

def brute_pairs(width, q_starts, seq_lens, ctx_span, block, causal=True):
    """Walk every block pair the device loops would visit."""
    blk = min(block, width)
    live = scored = 0
    for qs, sl in zip(q_starts, seq_lens):
        n = min(max(sl - qs, 0), width)
        for i in range(n):
            live += qs + i + 1
        for qb in range(-(-width // blk)):
            if qb * blk >= n:
                continue
            if ctx_span:
                cb = min(block, ctx_span)
                j = 0
                while j * cb < min(qs, sl, ctx_span):
                    scored += blk * cb
                    j += 1
            for kb in range(-(-width // blk)):
                if kb * blk >= n or (causal and kb > qb):
                    continue
                scored += blk * blk
    return live, scored


@pytest.mark.parametrize("width,q_starts,seq_lens,ctx_span", [
    (1024, [0], [300], 0),
    (1024, [0], [1024], 0),
    (256, [0, 0, 0, 0], [256, 31, 0, 0], 0),
    (512, [1024, 0], [1024 + 400, 77], 4096),
    (128, [4000], [4096], 4096),
    (24, [13], [30], 48),
])
def test_pair_count_matches_block_walk(width, q_starts, seq_lens, ctx_span):
    for causal in (True, False):
        assert prefill_attention_pairs(
            width, q_starts, seq_lens, ctx_span, causal=causal
        ) == brute_pairs(width, q_starts, seq_lens, ctx_span, 256, causal)


def test_pair_count_by_hand():
    # 300 live rows in a 1024 bucket, block 256: two query blocks, the
    # first scores one key block, the second two
    assert prefill_attention_pairs(1024, [0], [300]) == (
        300 * 301 // 2, 3 * 256 * 256)
    # a continuation: every live row also sees its 512 prior rows, and
    # each live query block scores the two region blocks below q_start
    assert prefill_attention_pairs(256, [512, 0], [512 + 100, 0], 4096) == (
        100 * 512 + 100 * 101 // 2, 256 * (512 + 256))


# ---------------------------------------------------------------------------
# through the model: fresh vs continued prefill

CFG = ModelConfig.tiny(num_layers=2)
LANES, REGION = 4, 64


@pytest.fixture(scope="module")
def tiny():
    params = llama.init_params(CFG, 0)
    return params


def new_ctx():
    return llama.init_ctx(CFG, LANES, REGION, jnp.float32)


def test_fresh_and_continued_prefill_agree(tiny):
    """One prompt, three ways: the fresh program in one chunk; the
    default (region-reading) program in one chunk; two chunks, the second
    continuing at q_start 16. Same logits, same region rows."""
    rng = np.random.default_rng(0)
    n, T = 27, 32
    prompt = rng.integers(1, CFG.vocab_size, n).astype(np.int32)
    toks = np.zeros(T, np.int32)
    toks[:n] = prompt
    slot = jnp.int32(2)

    def run(ctx, toks, start, end, **kw):
        return llama.prefill(CFG, tiny, ctx, jnp.asarray(toks), slot,
                             jnp.int32(start), jnp.int32(end), **kw)

    ctx_a, logits_a = run(new_ctx(), toks, 0, n, fresh=True)
    ctx_b, logits_b = run(new_ctx(), toks, 0, n)
    first = np.zeros(16, np.int32)
    first[:] = prompt[:16]
    ctx_c, _ = run(new_ctx(), first, 0, 16, fresh=True)
    rest = np.zeros(16, np.int32)
    rest[: n - 16] = prompt[16:]
    ctx_c, logits_c = run(ctx_c, rest, 16, n)
    np.testing.assert_allclose(logits_a, logits_b, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(logits_a, logits_c, atol=2e-4, rtol=2e-4)
    for name in ("k", "v"):
        a, b, c = (np.asarray(x[name])[:, :, 2, :n]
                   for x in (ctx_a, ctx_b, ctx_c))
        np.testing.assert_allclose(a, b, atol=1e-6)
        np.testing.assert_allclose(a, c, atol=2e-4, rtol=2e-4)


def test_batched_prefill_matches_solo_with_dummy_lanes(tiny):
    """A group of two real prompts and two dummy lanes through
    batch_prefill gives each prompt the solo program's logits and region
    rows — fresh (ctx_span 0) and continuing (ctx_span = the region)."""
    rng = np.random.default_rng(1)
    T = 16
    lens = [16, 9]
    prompts = [rng.integers(1, CFG.vocab_size, 16 + n).astype(np.int32)
               for n in lens]
    for q_start, span in ((0, 0), (16, REGION)):
        ctx_solo, ctx_batch = new_ctx(), new_ctx()
        toks = np.zeros((4, T), np.int32)
        solo_logits = []
        for i, (p, n) in enumerate(zip(prompts, lens)):
            if q_start:
                head = jnp.asarray(p[:16])
                ctx_solo, _ = llama.prefill(
                    CFG, tiny, ctx_solo, head, jnp.int32(i), jnp.int32(0),
                    jnp.int32(16), fresh=True)
                ctx_batch, _ = llama.prefill(
                    CFG, tiny, ctx_batch, head, jnp.int32(i), jnp.int32(0),
                    jnp.int32(16), fresh=True)
            chunk = p[q_start: q_start + n]
            toks[i, :n] = chunk
            ctx_solo, lg = llama.prefill(
                CFG, tiny, ctx_solo, jnp.asarray(toks[i]), jnp.int32(i),
                jnp.int32(q_start), jnp.int32(q_start + n),
                fresh=q_start == 0)
            solo_logits.append(lg)
        slots = jnp.asarray([0, 1, LANES, LANES], jnp.int32)
        q_starts = jnp.asarray([q_start, q_start, 0, 0], jnp.int32)
        seq_lens = jnp.asarray(
            [q_start + lens[0], q_start + lens[1], 0, 0], jnp.int32)
        ctx_batch, logits = llama.batch_prefill(
            CFG, tiny, ctx_batch, jnp.asarray(toks), slots, q_starts,
            seq_lens, span)
        for i, n in enumerate(lens):
            np.testing.assert_allclose(logits[i], solo_logits[i],
                                       atol=2e-4, rtol=2e-4)
            for name in ("k", "v"):
                np.testing.assert_allclose(
                    np.asarray(ctx_batch[name])[:, :, i, : q_start + n],
                    np.asarray(ctx_solo[name])[:, :, i, : q_start + n],
                    atol=2e-4, rtol=2e-4)


def test_dense_programs_agree_with_the_kernel_in_them():
    """The dense decoder's solo, continuing and batched programs handed a
    kernel's ``attn`` (here interpreted; a head of 128) against the same
    programs on the loops: the same logits to float32 rounding and the
    same rows in the region, a dummy lane included."""
    cfg = ModelConfig.tiny(num_layers=2, head_dim=128, dtype="float32")
    params = llama.init_params(cfg, 0)
    kernel = DecodeAttention(PALLAS_INTERPRET)
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 250, 40),
                       jnp.int32)
    got = {}
    for attn in (None, kernel):
        ctx = llama.init_ctx(cfg, 4, 64, jnp.float32)
        ctx, first = llama.prefill(
            cfg, params, ctx, toks[:16], jnp.int32(1), jnp.int32(0),
            jnp.int32(16), None, None, jnp.int32(0), fresh=True, attn=attn)
        ctx, then = llama.prefill(
            cfg, params, ctx, toks[16:32], jnp.int32(1), jnp.int32(16),
            jnp.int32(27), None, None, jnp.int32(0), attn=attn)
        ctx, both = llama.batch_prefill(
            cfg, params, ctx,
            jnp.stack([toks[24:40], toks[:16], toks[:16]]),
            jnp.asarray([2, 3, 4]), jnp.asarray([0, 8, 0]),
            jnp.asarray([16, 19, 0]), 64, jnp.zeros(3, jnp.int32), attn=attn)
        got[attn] = (first, then, both[:2], ctx["k"][:, :, 1:4, :32],
                     ctx["v"][:, :, 1:4, :32])
    for a, b in zip(got[None], got[kernel]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# program size: the guard for what set-up pays

def abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


@pytest.fixture(scope="module")
def shapes():
    cfg = CFG
    params = jax.eval_shape(lambda: llama.init_params(cfg, 0))
    ctx = jax.eval_shape(lambda: llama.init_ctx(cfg, 8, 4096, jnp.bfloat16))
    return cfg, abstract(params), abstract(ctx)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def lowered_solo(shapes, T, fresh):
    cfg, params, ctx = shapes
    return llama.prefill.lower(
        cfg, params, ctx, i32(T), i32(), i32(), i32(), None, None, i32(),
        fresh=fresh).as_text()


def lowered_batch(shapes, K, T, span):
    cfg, params, ctx = shapes
    return llama.batch_prefill.lower(
        cfg, params, ctx, i32(K, T), i32(K), i32(K), i32(K), span, i32(K),
    ).as_text()


def n_ops(text):
    return sum(1 for line in text.splitlines() if " = " in line)


@pytest.mark.parametrize("fresh", [True, False], ids=["fresh", "ctx"])
def test_solo_program_size_is_independent_of_the_bucket(shapes, fresh):
    assert n_ops(lowered_solo(shapes, 256, fresh)) == n_ops(
        lowered_solo(shapes, 1024, fresh))


@pytest.mark.parametrize("span", [0, 4096], ids=["fresh", "ctx"])
def test_batched_program_size_is_independent_of_lanes_and_bucket(
        shapes, span):
    base = n_ops(lowered_batch(shapes, 2, 256, span))
    assert n_ops(lowered_batch(shapes, 8, 256, span)) == base
    assert n_ops(lowered_batch(shapes, 8, 1024, span)) == base


def test_fresh_programs_hold_no_region_sized_tensor_in_attention(shapes):
    """Outside the region operands themselves (argument, result, and the
    tail's in-place span writes, all of the full [L, kvh, lanes, S, hd]
    type), nothing in a fresh program has an S_max-sized axis: no slab,
    no [T, S+T] score or mask."""
    cfg, _, ctx = shapes
    region = "x".join(str(d) for d in ctx["k"].shape) + "xbf16"
    for text in (lowered_solo(shapes, 256, True),
                 lowered_batch(shapes, 8, 256, 0)):
        assert region in text
        rest = text.replace(region, "")
        sized = re.findall(r"tensor<(?:\d+x)*(?:4096|4352|4224)(?:x\d+)*x",
                           rest)
        assert not sized, sized[:5]
    # the guard can see one: the region-reading program slices blocks of
    # the region inside its loops (operands of the full type only), and
    # the old dense path's [T, S+T] shapes would match the pattern
    assert re.search(r"tensor<(?:\d+x)*4096(?:x\d+)*x",
                     "tensor<32x256x4096xf32>")


def test_loops_are_rolled_and_layers_share_one_attention(shapes):
    """One outer and one inner while loop in the attention when fresh
    (one more inner loop over the region when not), and the batched
    program's tail adds its one loop over the lanes — whatever T and K,
    and whatever the depth: the unrolled layers call ONE lowered
    attention function."""
    for text, loops in ((lowered_solo(shapes, 1024, True), 2),
                        (lowered_solo(shapes, 1024, False), 3),
                        (lowered_batch(shapes, 8, 1024, 0), 2 + 1),
                        (lowered_batch(shapes, 8, 1024, 4096), 3 + 1)):
        assert text.count("stablehlo.while") == loops
        assert len(re.findall(r"func\.func private @prefill_attention",
                              text)) == 1
        assert len(re.findall(r"call @prefill_attention", text)) == \
            CFG.num_layers


@pytest.mark.parametrize("program", ["solo-fresh", "solo-ctx",
                                     "batch-fresh", "batch-ctx"])
def test_dense_layers_share_one_fused_attention(program):
    """Handed the engine's ``attn`` (a kernel's), the dense decoder's
    prefill programs call ONE lowered ``fused_prefill_attention`` body
    from every layer, the layer a value (on the CPU, the loops inside
    it: the kernel is a TPU lowering); handed none, the loops directly,
    and no fused body is in the text. (A head of whole 128-lane tiles,
    as the served dense models have: a narrower head keeps the loops.)"""
    cfg = ModelConfig.tiny(num_layers=2, head_dim=128)
    params = abstract(jax.eval_shape(lambda: llama.init_params(cfg, 0)))
    ctx = abstract(jax.eval_shape(
        lambda: llama.init_ctx(cfg, 8, 4096, jnp.bfloat16)))
    for attn, fused in ((DecodeAttention(PALLAS), cfg.num_layers), (None, 0)):
        if program.startswith("solo"):
            text = llama.prefill.lower(
                cfg, params, ctx, i32(1024), i32(), i32(), i32(), None, None,
                i32(), fresh=program == "solo-fresh", attn=attn).as_text()
        else:
            text = llama.batch_prefill.lower(
                cfg, params, ctx, i32(2, 1024), i32(2), i32(2), i32(2),
                0 if program == "batch-fresh" else 4096, i32(2),
                attn=attn).as_text()
        assert len(re.findall(r"func\.func private @fused_prefill_attention\b",
                              text)) == (1 if fused else 0)
        assert len(re.findall(r"call @fused_prefill_attention\b",
                              text)) == fused
        assert len(re.findall(r"func\.func private @prefill_attention\b",
                              text)) == 1
        assert len(re.findall(r"call @prefill_attention\b", text)) == (
            1 if fused else cfg.num_layers)
        assert "tpu_custom_call" not in text


@pytest.mark.parametrize("span", [0, 1024], ids=["fresh", "ctx"])
def test_latent_layers_share_one_fused_attention(span):
    """The expanded latent chunks' call site is jitted like the loops, the
    layer a value: a program's layers call ONE lowered body (here, on the
    CPU, the loops inside it: the kernel is a TPU lowering)."""
    cfg = ModelConfig.tiny_mla_moe(dtype="float32")
    params = abstract(jax.eval_shape(
        lambda: llama.serving_params(cfg, llama.init_params(cfg, 0))))
    ctx = abstract(jax.eval_shape(
        lambda: llama.init_ctx(cfg, 4, 1024, jnp.float32)))
    text = llama.batch_prefill.lower(
        cfg, params, ctx, i32(2, 256), i32(2), i32(2), i32(2), span, i32(2),
    ).as_text()
    for name in ("fused_prefill_attention", "prefill_attention"):
        assert len(re.findall(rf"func\.func private @{name}\b", text)) == 1
    assert len(re.findall(r"call @fused_prefill_attention\b", text)) == \
        cfg.num_layers
    assert len(re.findall(r"call @prefill_attention\b", text)) == 1
    assert "tpu_custom_call" not in text
