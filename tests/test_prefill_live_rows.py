"""A dense prefill chunk's row-wise halves over the live row blocks
(PR 44, ``llama._live_rows``): the looped halves against the straight-line
ones through ``prefill`` and ``batch_prefill`` at toy widths, where the
loop is taken and where it is not, the size of the lowered programs, and
the host's mirror of the loop bound. All CPU: values and counts, never a
device time.

The block height is the module constant ``llama.LIVE_ROW_BLOCK`` (512 on
the chip); the value cases trace fresh jits under a toy height of 8 and
under one no bucket reaches (straight-line), the program guards lower the
real one.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from dynamo_tpu.tenancy.adapters import (
    init_adapter_bank,
    random_adapter,
    set_adapter,
)

R, T, S, B = 8, 64, 128, 3         # toy block, bucket, region rows, lanes
STRAIGHT = 1 << 20                 # a block no bucket is three of
LENGTHS = (1, R - 1, R, R + 1, T)


def _config(quant=None, **kw):
    return ModelConfig.tiny(dtype="float32", quant=quant, **kw)


def _params(c, lora=False):
    params = llama.init_params(c, 0)
    if lora:
        bank = init_adapter_bank(c, 3, 4)
        for aid in (1, 2):
            bank = set_adapter(bank, aid,
                               random_adapter(c, 4, seed=aid, scale=0.5))
        params = dict(params, adapters=bank)
    return params


def _traced_under(monkeypatch, block, impl, static_argnums, **impl_kw):
    """A fresh jit of a prefill function, traced with the row blocks
    ``block`` high (the jit cache knows nothing of the constant)."""
    def run(*args):
        monkeypatch.setattr(llama, "LIVE_ROW_BLOCK", block)
        return jax.jit(lambda *a: impl(*a, **impl_kw),
                       static_argnums=static_argnums)(*args)
    return run


def _tokens(rng, *shape):
    return rng.randint(1, 255, shape).astype(np.int32)


def _prefix(c, params, ctx, slot, n, rng):
    """``n`` rows of lane ``slot`` computed by a fresh chunk."""
    toks = np.zeros(T, np.int32)
    toks[:n] = _tokens(rng, n)
    ctx, _ = llama.prefill(c, params, ctx, jnp.asarray(toks), jnp.int32(slot),
                           jnp.int32(0), jnp.int32(n), fresh=True)
    return ctx


def _agree(got, want, rows):
    """(ctx, logits) of the looped program against the straight-line one:
    logits at float32's tolerance, the greedy token, and every region
    row below each lane's live length. ``rows``: (slot, length) pairs."""
    (ctx_a, logits_a), (ctx_b, logits_b) = got, want

    def close(a, b):
        # float32 sums in another order: a few ulps of the largest value
        tol = 2e-5 * max(1.0, float(np.abs(b).max()))
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=tol)

    close(logits_a, logits_b)
    assert (np.argmax(logits_a, -1) == np.argmax(logits_b, -1)).all()
    for slot, n in rows:
        for kind in ("k", "v"):
            close(ctx_a[kind][:, :, slot, :n], ctx_b[kind][:, :, slot, :n])


# ---- values: the looped halves are the straight-line ones --------------

@pytest.mark.parametrize("quant", [None, "int8"], ids=["dense", "int8"])
@pytest.mark.parametrize("q_start", [0, 16], ids=["fresh", "continuing"])
@pytest.mark.parametrize("n", LENGTHS)
def test_solo_prefill_looped_equals_straight(monkeypatch, n, q_start, quant):
    c = _config(quant)
    params = _params(c)
    rng = np.random.RandomState(n + q_start)
    ctx = llama.init_ctx(c, B, S, jnp.float32)
    if q_start:
        ctx = _prefix(c, params, ctx, 1, q_start, rng)
    toks = np.zeros(T, np.int32)
    toks[:n] = _tokens(rng, n)
    args = (c, params, ctx, jnp.asarray(toks), jnp.int32(1),
            jnp.int32(q_start), jnp.int32(q_start + n))
    out = [_traced_under(monkeypatch, block, llama.prefill_impl, (0,),
                         fresh=q_start == 0)(*args)
           for block in (R, STRAIGHT)]
    _agree(*out, rows=[(1, q_start + n)])


LANES = {
    "unequal": ((0, 0), (R + 3, T)),
    "dummy_lane": ((0, 0), (3 * R, 0)),
    "continuing": ((16, 24), (16 + 5, 24 + T)),
    "one_row_and_full": ((0, 0), (1, T)),
}


@pytest.mark.parametrize("quant", [None, "int8"], ids=["dense", "int8"])
@pytest.mark.parametrize("case", sorted(LANES))
def test_batch_prefill_looped_equals_straight(monkeypatch, case, quant):
    """K = 2: unequal lengths, a dummy lane (seq_len 0, the scratch lane),
    continuing chunks over rows an earlier chunk left."""
    c = _config(quant)
    params = _params(c)
    q_starts, seq_lens = (np.asarray(x, np.int32) for x in LANES[case])
    rng = np.random.RandomState(7)
    ctx = llama.init_ctx(c, B, S, jnp.float32)
    slots = np.asarray([0, 2], np.int32)
    for slot, q0 in zip(slots, q_starts):
        if q0:
            ctx = _prefix(c, params, ctx, slot, int(q0), rng)
    if case == "dummy_lane":
        slots[1] = B                                  # the scratch lane
    toks = _tokens(rng, 2, T)
    args = (c, params, ctx, jnp.asarray(toks), jnp.asarray(slots),
            jnp.asarray(q_starts), jnp.asarray(seq_lens),
            S if q_starts.max() else 0, jnp.zeros(2, jnp.int32))
    out = [_traced_under(monkeypatch, block, llama.batch_prefill_impl,
                         (0, 7))(*args)
           for block in (R, STRAIGHT)]
    live = [(int(s), int(n)) for s, n in zip(slots, seq_lens) if n]
    (_, logits_a), (_, logits_b) = out
    lanes = seq_lens > 0                  # a dummy lane's logits are no one's
    _agree((out[0][0], logits_a[lanes]), (out[1][0], logits_b[lanes]), live)


@pytest.mark.parametrize("batched", [False, True], ids=["solo", "batched"])
def test_lora_factors_follow_the_blocks_lane(monkeypatch, batched):
    """A bank with two adapters: the block's lane picks its factors, and
    the adapters do change the result (the check is not vacuous)."""
    c = _config()
    params = _params(c, lora=True)
    rng = np.random.RandomState(3)
    ctx = llama.init_ctx(c, B, S, jnp.float32)
    if batched:
        toks = _tokens(rng, 2, T)
        lens = np.asarray([R + 3, 3 * R + 1], np.int32)

        def args(ids):
            return (c, params, ctx, jnp.asarray(toks),
                    jnp.asarray([0, 2], jnp.int32), jnp.zeros(2, jnp.int32),
                    jnp.asarray(lens), 0, jnp.asarray(ids, jnp.int32))
        impl, static, rows = (llama.batch_prefill_impl, (0, 7),
                              [(0, R + 3), (2, 3 * R + 1)])
        ids, base = (1, 2), (0, 0)
    else:
        toks = np.zeros(T, np.int32)
        toks[:21] = _tokens(rng, 21)

        def args(aid):
            return (c, params, ctx, jnp.asarray(toks), jnp.int32(1),
                    jnp.int32(0), jnp.int32(21), None, None, jnp.int32(aid))
        impl, static, rows = llama.prefill_impl, (0,), [(1, 21)]
        ids, base = 2, 0
    looped = _traced_under(monkeypatch, R, impl, static)
    straight = _traced_under(monkeypatch, STRAIGHT, impl, static)
    got = looped(*args(ids))
    _agree(got, straight(*args(ids)), rows)
    assert np.abs(got[1] - looped(*args(base))[1]).max() > 1e-3


def test_two_device_tp_mesh(monkeypatch):
    """Weights and region sharded over a 2-device ``tp`` mesh: the loop's
    all-reduces sit inside its body, the values are the unsharded ones."""
    c = _config()
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    params = _params(c)
    ctx = llama.init_ctx(c, B, S, jnp.float32)
    rng = np.random.RandomState(5)
    toks = _tokens(rng, 2, T)
    lens = np.asarray([2 * R + 1, T], np.int32)
    rest = (jnp.asarray(toks), jnp.asarray([0, 2], jnp.int32),
            jnp.zeros(2, jnp.int32), jnp.asarray(lens), 0,
            jnp.zeros(2, jnp.int32))
    want = _traced_under(monkeypatch, STRAIGHT, llama.batch_prefill_impl,
                         (0, 7))(c, params, ctx, *rest)
    sharded = (jax.device_put(params, llama.param_shardings(c, mesh)),
               jax.device_put(ctx, llama.ctx_shardings(c, mesh)))
    got = _traced_under(monkeypatch, R, llama.batch_prefill_impl,
                        (0, 7))(c, *sharded, *rest)
    assert len(got[0]["k"].sharding.device_set) == 2
    _agree(got, want, [(0, 2 * R + 1), (2, T)])


# ---- where the loop is taken -------------------------------------------

def _lowered(fn, *args, **kw):
    return fn.lower(*args, **kw).as_text()


def _abstract(c, lanes=2, rows=4096):
    shapes = jax.eval_shape
    return (shapes(lambda: llama.serving_params(c, llama.init_params(c, 0))),
            shapes(lambda: llama.init_ctx(c, lanes, rows, jnp.float32)))


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@pytest.mark.parametrize("T_,blocks", [(512, 0), (1024, 0), (1536, 512),
                                       (2048, 512), (4096, 512), (1600, 0)])
def test_the_shape_alone_decides(T_, blocks):
    """A bucket of one or two blocks, or of no whole number of them, keeps
    the straight-line halves; the rule sees no model's name."""
    assert llama.LIVE_ROW_BLOCK == 512
    assert llama.live_row_block(_config(), T_) == blocks


@pytest.mark.parametrize("why", ["tree", "capacity_moe", "dropless_moe",
                                 "latent_block", "hybrid_block"])
def test_straight_line_where_rows_do_not_stand_alone(why):
    """Tree chunks (live nodes are no prefix), an expert layer whose rows
    compete for capacity, and the latent block with its own halves. The
    hybrid block is the POSITIVE case since PR 49: its own halves and
    scans loop over the live row blocks by its own rule
    (``ssm_moe.live_row_block``; tests/test_hybrid_live_rows.py), which
    ``llama.live_row_block`` and the host's mirror hand over to."""
    c = {"tree": _config(),
         "capacity_moe": _config(moe=(("capacity_factor", 1.25),
                                      ("num_experts", 4), ("top_k", 2))),
         "dropless_moe": ModelConfig.tiny_moe(dtype="float32"),
         "latent_block": ModelConfig.tiny_mla_moe(dtype="float32"),
         "hybrid_block": ModelConfig.tiny_ssm_moe(dtype="float32")}[why]
    looped = why == "hybrid_block"
    assert llama.live_row_block(c, 4096, tree=why == "tree") == (
        512 if looped else 0)
    assert llama.prefill_positions_run(c, 4096, [0, 0], [2100, 0]) == (
        2 * 4096 if why not in ("tree", "hybrid_block") else 2560)
    if why == "tree":
        params, ctx = _abstract(c)
        K, N = 2, 2048
        text = _lowered(
            jax.jit(llama.batch_score_tree_impl, static_argnums=(0, 9)),
            c, params, ctx, _i32(K, N), _i32(K), _i32(K), _i32(K),
            _i32(K, N), jax.ShapeDtypeStruct((K, N, N), jnp.bool_), 4096)
        assert "_live_rows" not in text
        linear = _lowered(
            jax.jit(llama.batch_score_impl, static_argnums=(0, 7)),
            c, params, ctx, _i32(K, N), _i32(K), _i32(K), _i32(K), 4096)
        assert "_live_rows" in linear       # a linear chunk may take it
    elif "moe" in why or looped:
        params, ctx = _abstract(c)
        text = _lowered(llama.prefill, c, params, ctx, _i32(4096), _i32(),
                        _i32(), _i32(), fresh=True)
        assert "_live_rows" not in text       # the dense decoder's loop
        assert ("_live_half" in text) == looped


# ---- program guards ----------------------------------------------------

@pytest.fixture(scope="module")
def lowered_sizes():
    """Lines of the lowered dense prefill programs at the real block
    height, by (lanes, bucket, continuing)."""
    c = _config()
    params, ctx = _abstract(c)
    out = {}
    for K in (1, 2, 4):
        for T_ in (1024, 2048, 4096):
            for span in (0, 4096):
                out[K, T_, span] = _lowered(
                    llama.batch_prefill, c, params, ctx, _i32(K, T_),
                    _i32(K), _i32(K), _i32(K), span, _i32(K))
    return out


@pytest.mark.parametrize("span", [0, 4096], ids=["fresh", "continuing"])
def test_lowered_size_grows_neither_with_the_bucket_nor_the_lanes(
        lowered_sizes, span):
    lines = {key: len(text.splitlines())
             for key, text in lowered_sizes.items() if key[2] == span}
    for K in (1, 2, 4):
        assert lines[K, 2048, span] == lines[K, 4096, span]
    assert lines[2, 4096, span] == lines[4, 4096, span]
    # one lane against two: what the straight-line bucket differs by too
    # (a squeeze of the lane axis), not a lane's worth of layers
    assert (lines[2, 4096, span] - lines[1, 4096, span]
            == lines[2, 1024, span] - lines[1, 1024, span])


@pytest.mark.parametrize("layers", [2, 4])
def test_the_layers_share_one_lowered_loop_body_a_half(layers):
    c = _config(num_layers=layers)
    params, ctx = _abstract(c)
    for text in (
            _lowered(llama.batch_prefill, c, params, ctx, _i32(2, 2048),
                     _i32(2), _i32(2), _i32(2), 0, _i32(2)),
            _lowered(llama.prefill, c, params, ctx, _i32(2048), _i32(),
                     _i32(), _i32(), fresh=True)):
        assert text.count("func.func private @_live_rows") == 2
        assert text.count("call @_live_rows") == 2 * layers
        assert text.count("stablehlo.while") >= 2


MIRROR = {
    "solo_2100_of_4096": (4096, [0], [2100]),
    "two_lanes_one_dummy": (2048, [0, 0], [1537, 0]),
    "continuing": (2048, [4096, 64], [4096 + 513, 64 + 2048]),
    "full": (4096, [0, 0], [4096, 4096]),
    "one_row": (2048, [0], [1]),
}


@pytest.mark.parametrize("case", sorted(MIRROR))
def test_host_mirror_is_the_programs_trip_count(case):
    """``prefill_positions_run`` against what the program did: the row
    blocks of its final hidden states that hold anything (a block that
    never ran stays zero), times the block height."""
    T_, q_starts, seq_lens = MIRROR[case]
    c = _config(num_layers=1, max_position_embeddings=8192)
    params = llama.init_params(c, 0)
    K = len(q_starts)
    ctx = llama.init_ctx(c, K, 8192, jnp.float32)
    toks = _tokens(np.random.RandomState(1), K, T_)
    _, _, h, _ = jax.jit(llama._batch_forward, static_argnums=(0, 7))(
        c, params, ctx, jnp.asarray(toks), jnp.arange(K, dtype=jnp.int32),
        jnp.asarray(q_starts, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
        8192 if max(q_starts) else 0)
    block = llama.LIVE_ROW_BLOCK
    ran = np.abs(np.asarray(h)).reshape(K, T_ // block, -1).max(-1) > 0
    want = sum(-(-(n - q) // block) for q, n in zip(q_starts, seq_lens)
               if n > q)
    assert ran.sum() == want
    assert llama.prefill_positions_run(c, T_, q_starts, seq_lens) == (
        want * block)


# sha256 of the lowered solo prefill (fresh, one 64-token bucket, CPU) of
# the block models' toy configurations, recorded on the parent of PR 44
# (c13f2fc) by this test itself, under the conftest's matmul precision:
# their programs do not move with the dense
# path's halves. A PR that MEANS to change one records the new digest here.
# Since PR 49 the hybrid block's wide buckets take another program
# (``ssm_moe._live_prefill``); its straight-line one, which this 64-row
# bucket runs, kept the parent's text. PR 57 MEANT to move the two latent
# ones (c728cb0a7a7fceae and 23f9246b56275a3d on its parent, d39232c): the
# query product ends ahead of the reshape to heads and the products over
# W_kvb read ``wkb`` / ``wvb`` (``llama.serving_params``). PR 61 MEANT to
# move them again (8788c3406236fb77 and f840bfcf1391f976 on its parent,
# 12cb44a): their attention is ``fused_prefill_attention`` (on the CPU the
# same loops, called through one more jit) and V rides at its own width in
# the fresh programs too. The hybrid toy (no latent layer) kept its text.
BLOCK_MODEL_DIGESTS = {
    "tiny_mla_moe": "b55f9e38e5a838ec",
    "tiny_mla_moe_mhc": "ae22457b9c68c707",
    "tiny_ssm_moe": "6d845cc44ce2c3d6",
}


@pytest.mark.parametrize("name", sorted(BLOCK_MODEL_DIGESTS))
def test_block_models_keep_their_lowered_prefill(name):
    c = getattr(ModelConfig, name)(dtype="float32")
    params = jax.eval_shape(
        lambda: llama.serving_params(c, llama.init_params(c, 0)))
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, 2, 256, jnp.float32))
    text = _lowered(llama.prefill, c, params, ctx, _i32(64), _i32(), _i32(),
                    _i32(), fresh=True)
    assert "_live_rows" not in text
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == BLOCK_MODEL_DIGESTS[name])
