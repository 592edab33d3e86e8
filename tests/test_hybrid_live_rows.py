"""A prefill chunk of the hybrid block over its live row blocks (PR 49:
``ssm_moe._live_half`` over the two row-wise halves of every layer kind,
``ssm_moe._live_scan`` over the recurrent kinds' chunked scans, both on
``live_rows.over_live_blocks``): looped against straight-line at toy
widths, piece by piece (bit for bit) and through the whole program, rows
past the live blocks 0, states and windows equal, the host's mirror of the
trip count, and the shape of the lowered program. All CPU: values and
counts, never a device time.

The block heights are the module constants ``ssm_moe.LIVE_ROW_BLOCK`` (the
halves': 512 on the chip) and ``ssm_moe.SCAN_ROW_BLOCK`` (the scans': 256);
the value cases trace fresh jits under toy heights of 128 and 64 (a whole
number of every scan's chunks once the lightning chunk is 64 too) and under
one no bucket reaches (straight-line); the program guards lower the real
ones.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, ssm_moe
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.live_rows import live_row_trips

R, RS, T, S, B = 128, 64, 256, 512, 3   # toy blocks (halves', scans'),
                                   # bucket, region rows, lanes
STRAIGHT = 1 << 20                 # a block no bucket is two of
LENGTHS = (0, 1, RS + 1, R, R + 1, T)
CONFIGS = {"mamba_attention": "tiny_ssm_moe",
           "lightning_sparse": "tiny_linear_sparse",
           "delta_rule_latent": "tiny_kda_latent"}


@functools.lru_cache(maxsize=None)
def _model(name):
    c = getattr(ModelConfig, CONFIGS[name])(dtype="float32")
    return c, llama.serving_params(c, llama.init_params(c, 0))


@pytest.fixture(autouse=True)
def toy_chunks(monkeypatch):
    """Toy chunk and scan-block heights, and EVERY kind looped: the rule
    leaves a stack of lightning and sparse layers straight-line (its
    check's margin, not its values: ``ssm_moe.LIVE_ROW_KINDS``), and what
    the loops compute for them is held here all the same."""
    monkeypatch.setattr(ssm_moe, "LIN_CHUNK", 64)
    monkeypatch.setattr(ssm_moe, "SCAN_ROW_BLOCK", RS)
    monkeypatch.setattr(ssm_moe, "LIVE_ROW_KINDS", ssm_moe.LIVE_ROW_KINDS + (
        "linear_attention", "sparse_attention"))


def _kinds(name):
    """The (kind, routes) pairs of the toy configuration's layers, in
    order: from the configuration alone (collection builds no model)."""
    c = getattr(ModelConfig, CONFIGS[name])(dtype="float32")
    d = ssm_moe.dims(c)
    return [(kind, d["experts"] and i >= d["n_dense"])
            for i, kind in enumerate(d["kinds"])]


def _layers(name):
    """One layer of each (kind, routes) of the toy configuration."""
    c, params = _model(name)
    seen = {}
    for key, lp in zip(_kinds(name), params["layers"]):
        assert key[1] == ("wr" in lp)
        seen.setdefault(key, lp)
    return c, seen


KINDS = [(name, kind, routes) for name in CONFIGS
         for kind, routes in dict.fromkeys(_kinds(name))]


def _rows(c, kind, lp, K, seed=0):
    """The per-row operands of the layer's halves and scan, [K, T, ...],
    made by a straight-line pass over random hidden states."""
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(K, T, c.hidden_size), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T))
    ins = tuple(a.reshape(K, T, *a.shape[1:]) for a in ssm_moe._mix_in(
        c, kind, lp, h.reshape(K * T, -1), pos.reshape(K * T)))
    return h, pos, ins


def _trips(lens, block=R):
    lens = np.asarray(lens, np.int64)
    return jnp.asarray(live_row_trips(np.zeros_like(lens), lens, T, block),
                       jnp.int32)


def _equal_on_live_rows(got, want, lens, ulps=0, block=R):
    """Bit for bit on each lane's live rows (or within ``ulps`` of
    float32's last bit at the values' scale); exactly 0 past its last
    live block."""
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        for lane, n in enumerate(lens):
            if ulps and n:
                np.testing.assert_allclose(
                    g[lane, :n], w[lane, :n], rtol=0,
                    atol=ulps * 2.0 ** -23 * np.abs(w[lane, :n]).max())
            else:
                np.testing.assert_array_equal(g[lane, :n], w[lane, :n])
            assert not g[lane, -(-n // block) * block:].any()


# ---- pieces: a half, a scan, bit for bit -------------------------------

@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("name,kind,routes", KINDS,
                         ids=[f"{k}{'_routed' * r}" for _, k, r in KINDS])
def test_a_looped_half_is_the_straight_line_half(name, kind, routes, n):
    """``_mix_in`` and ``_mix_out`` of every layer kind, K = 2 with a
    full second lane (or a dummy one beside a full first)."""
    c, layers = _layers(name)
    lp = layers[kind, routes]
    lens = (n, T) if n else (T, 0)
    h, pos, ins = _rows(c, kind, lp, 2)
    # what the sequence operation would hand the second half: shaped like
    # the first half's outputs (values are free: the half is row-wise)
    if kind == "mamba":
        z, xbc, _ = ins
        seq = (ssm_moe._split_xbc(c, xbc)[0].astype(jnp.float32), xbc, z)
    elif kind == "kda":
        d = ssm_moe.dims(c)
        seq = (ins[1][..., :d["kda_dim"]], ins[3])
    elif kind == "latent_attention":
        seq = (ins[0][..., :ins[2].shape[-1]],)
    else:
        seq = (ins[0],) + ins[3:]
    flat = lambda a: a.reshape(2 * T, *a.shape[2:])  # noqa: E731
    for half, rows in ((ssm_moe._mix_in, (h, pos)),
                       (ssm_moe._mix_out, (h,) + seq)):
        want = [a.reshape(2, T, *a.shape[1:]) for a in jax.jit(
            lambda lp, *rows: half(c, kind, lp, *map(flat, rows)))(lp, *rows)]
        got = ssm_moe._live_half(half, kind, c, lp, _trips(lens), rows, R)
        # the rotary's sin / cos over a run of another length differ in the
        # last bit on XLA:CPU; every other kind's halves are bit for bit
        _equal_on_live_rows(got, want, lens,
                            ulps=2 * (kind == "linear_attention"))


SCANS = [k for k in KINDS if k[1] in ("mamba", "linear_attention", "kda")]


@pytest.mark.parametrize("start", ["fresh", "continuing"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("name,kind,routes", SCANS,
                         ids=[f"{k}{'_routed' * r}" for _, k, r in SCANS])
def test_a_looped_scan_is_the_chunked_scan(name, kind, routes, n, start):
    """The three chunked scans through ``_live_scan``: outputs bit for
    bit on the live rows, 0 past the last live block, and the state after
    the last live block is the state the masks give — from zeros and from
    a state an earlier chunk left; a lane with no live row keeps its
    own."""
    c, layers = _layers(name)
    lp = layers[kind, routes]
    lens = (n, T) if n else (T, 0)
    _, _, ins = _rows(c, kind, lp, 2, seed=n)
    rows = {"mamba": ins[1:], "linear_attention": ins[:3],
            "kda": ins[:3]}[kind]
    real = jnp.arange(T)[None] < jnp.asarray(lens)[:, None]
    d = ssm_moe.dims(c)
    shape = {"mamba": lambda: (d["nh"], d["P"], d["N"]),
             "linear_attention": lambda: (d["lin_heads"],) + (d["lin_dim"],) * 2,
             "kda": lambda: (d["kda_heads"],) + (d["kda_dim"],) * 2}[kind]()
    S0 = jnp.zeros((2,) + shape, jnp.float32)
    if start == "continuing":
        S0 = jnp.asarray(np.random.RandomState(1).randn(2, *shape) * 0.1,
                         jnp.float32)
    (want,), S_want = jax.jit(jax.vmap(
        lambda S, *blk: ssm_moe._scan_block(c, kind, lp, S, *blk)
    ))(S0, *rows, real)
    got, S_got = ssm_moe._live_scan(kind, c, lp, _trips(lens, RS),
                                    rows + (real,), S0, RS)
    _equal_on_live_rows([got], [want], lens, block=RS)
    np.testing.assert_array_equal(S_got, S_want)
    if not n:
        np.testing.assert_array_equal(S_got[1], S0[1])


# ---- the whole program -------------------------------------------------

@functools.lru_cache(maxsize=None)
def _program(name, block, K, span):
    """``batch_prefill_impl`` of the toy configuration as ONE jit, traced
    (at its first call) with the row blocks ``block`` high: the jit cache
    knows nothing of the constant. Lengths are values, so one trace serves
    them all."""
    c, _ = _model(name)
    f = jax.jit(lambda *a: ssm_moe.batch_prefill_impl(c, *a, span))

    def run(*args):
        kept, ssm_moe.LIVE_ROW_BLOCK = ssm_moe.LIVE_ROW_BLOCK, block
        try:
            return f(*args)
        finally:
            ssm_moe.LIVE_ROW_BLOCK = kept
    return run


def _close(a, b):
    # float32 sums in another order (XLA:CPU picks a dot's by its row
    # count): a few ulps of the largest value
    a, b = np.asarray(a), np.asarray(b)
    if not b.size:
        assert a.shape == b.shape
        return
    tol = 2e-5 * max(1.0, float(np.abs(b).max()))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=tol)


@pytest.mark.parametrize("K", [1, 2], ids=["solo", "with_dummy_lane"])
@pytest.mark.parametrize("start", ["fresh", "continuing"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_looped_prefill_equals_straight(name, n, start, K):
    """Logits, the greedy token, the region's live rows of every kind and
    every recurrent leaf of the lane, looped against straight-line; K = 2
    carries a dummy lane (seq_len 0, the scratch lane). A fresh chunk
    starts over a region of ONES, so that the rows of blocks that never
    ran are seen to be written as 0."""
    c, params = _model(name)
    q0 = R + 5 if start == "continuing" else 0
    rng = np.random.RandomState(n + q0)
    ctx = llama.init_ctx(c, B, S, jnp.float32)
    if q0:
        toks = np.zeros((1, T), np.int32)
        toks[0, :q0] = rng.randint(1, 255, q0)
        ctx = _program(name, STRAIGHT, 1, 0)(
            params, ctx, jnp.asarray(toks), jnp.asarray([1], jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.asarray([q0], jnp.int32))[0]
    else:
        ctx = {k: (jnp.ones_like(v) if k in ("k", "v", ssm_moe.KV) else v)
               for k, v in ctx.items()}
    toks = rng.randint(1, 255, (K, T)).astype(np.int32)
    slots = np.asarray([1, B][:K], np.int32)
    q_starts = np.asarray([q0, 0][:K], np.int32)
    seq_lens = np.asarray([q0 + n, 0][:K], np.int32)
    args = (params, ctx, jnp.asarray(toks), jnp.asarray(slots),
            jnp.asarray(q_starts), jnp.asarray(seq_lens))
    (ctx_a, logits_a, _), (ctx_b, logits_b, _) = (
        _program(name, block, K, S if q0 else 0)(*args)
        for block in (R, STRAIGHT))
    if n:
        _close(logits_a[0], logits_b[0])
        assert np.argmax(logits_a[0]) == np.argmax(logits_b[0])
    ran = -(-n // R) * R
    for kind, a in ctx_a.items():
        b = ctx_b[kind]
        if isinstance(a, list):          # a recurrent leaf a layer
            for x, y in zip(a, b):
                _close(x[1], y[1])
        elif kind == ssm_moe.KC:
            visible = (q0 + n) // ssm_moe.dims(c)["sparse"].stride - 1
            _close(a[:, :, 1, :max(visible, 0)], b[:, :, 1, :max(visible, 0)])
        else:
            _close(a[:, :, 1, :q0 + n], b[:, :, 1, :q0 + n])
            if not q0:   # blocks that never ran wrote zeros over the ones
                assert not np.asarray(a[:, :, 1, ran:T]).any()
                assert np.asarray(a[:, :, 1, T:] == 1).all()


# ---- the rule, the mirror, the lowered program -------------------------

@pytest.mark.parametrize("T_,blocks", [(512, 0), (1024, 512), (2048, 512),
                                       (4096, 512), (1600, 0), (256, 0)])
def test_the_shape_alone_decides(T_, blocks, monkeypatch):
    monkeypatch.undo()                 # the real heights and chunks
    assert (ssm_moe.LIVE_ROW_BLOCK, ssm_moe.SCAN_ROW_BLOCK) == (512, 256)
    for name in CONFIGS:
        c, _ = _model(name)
        # a stack of lightning + sparse layers stays straight-line
        assert ssm_moe.live_row_block(c, T_) == (
            blocks if name != "lightning_sparse" else 0)
        assert llama.live_row_block(c, T_) == ssm_moe.live_row_block(c, T_)


def test_a_block_is_whole_chunks_of_every_scan(monkeypatch):
    """The published chunk sizes (256 / 64 / 256) divide the block; a
    block that would cut a scan's chunk leaves the chunk straight-line."""
    monkeypatch.setattr(ssm_moe, "LIN_CHUNK", 256)
    monkeypatch.setattr(ssm_moe, "SCAN_ROW_BLOCK", 256)
    c, _ = _model("lightning_sparse")
    assert ssm_moe.live_row_block(c, 4096) == 512
    monkeypatch.setattr(ssm_moe, "LIN_CHUNK", 384)
    assert ssm_moe.live_row_block(c, 4096) == 0
    assert llama.prefill_positions_run(c, 4096, [0], [100]) == 4096
    monkeypatch.setattr(ssm_moe, "LIN_CHUNK", 256)
    monkeypatch.setattr(ssm_moe, "SCAN_ROW_BLOCK", 384)   # no part of 512
    assert ssm_moe.live_row_block(c, 4096) == 0


MIRROR = {
    "solo_2100_of_4096": (4096, [0], [2100]),
    "two_lanes_one_dummy": (2048, [0, 0], [1537, 0]),
    "continuing": (2048, [4096, 64], [4096 + 513, 64 + 2048]),
    "full": (4096, [0, 0], [4096, 4096]),
    "one_row_of_two_blocks": (1024, [0], [1]),
}


@pytest.mark.parametrize("case", sorted(MIRROR))
def test_host_mirror_counts_the_live_blocks(case):
    """``llama.prefill_positions_run`` at the real block height: live row
    blocks x 512, a dummy lane nothing."""
    T_, q_starts, seq_lens = MIRROR[case]
    c, _ = _model("mamba_attention")
    want = sum(-(-(n - q) // 512) for q, n in zip(q_starts, seq_lens)
               if n > q) * 512
    assert llama.prefill_positions_run(c, T_, q_starts, seq_lens) == want


@pytest.mark.parametrize("n", LENGTHS)
def test_host_mirror_is_the_programs_trip_count(n):
    """What the program did, read off the region: the row blocks of the
    attention layers' K rows that hold anything (a block that never ran is
    written as 0), against the mirror under the same toy block."""
    c, params = _model("mamba_attention")
    ctx = llama.init_ctx(c, B, S, jnp.float32)
    toks = np.random.RandomState(n).randint(1, 255, (2, T)).astype(np.int32)
    q_starts, seq_lens = [0, 0], [n, T]
    out, _, _ = _program("mamba_attention", R, 2, 0)(
        params, ctx, jnp.asarray(toks), jnp.asarray([0, 2], jnp.int32),
        jnp.asarray(q_starts, jnp.int32), jnp.asarray(seq_lens, jnp.int32))
    k = np.abs(np.asarray(out["k"]))[0, 0][[0, 2], :T]       # [2, T, hd]
    ran = int((k.reshape(2, T // R, -1).max(-1) > 0).sum())
    assert ran == -(-n // R) + T // R
    kept, ssm_moe.LIVE_ROW_BLOCK = ssm_moe.LIVE_ROW_BLOCK, R
    try:
        assert llama.prefill_positions_run(c, T, q_starts, seq_lens) == ran * R
    finally:
        ssm_moe.LIVE_ROW_BLOCK = kept


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_same_kind_layers_share_one_lowered_loop_body(name, monkeypatch):
    """The layers are unrolled in the caller and their weights are
    arguments of the jitted loops: one private function a (half, kind,
    routes) and a scanned (kind, routes), called once a layer, and a ``while`` each;
    the lowered program has the same size at two looped buckets (both wide
    enough for the expert path's own looped form)."""
    monkeypatch.setattr(ssm_moe, "LIN_CHUNK", 256)   # the real heights
    monkeypatch.setattr(ssm_moe, "SCAN_ROW_BLOCK", 256)   # and chunks
    c, _ = _model(name)
    params = jax.eval_shape(
        lambda: llama.serving_params(c, llama.init_params(c, 0)))
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, 2, 8192, jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    texts = [llama.prefill.lower(c, params, ctx, i32(T_), i32(), i32(),
                                 i32(), fresh=True).as_text()
             for T_ in (4096, 8192)]
    kinds = _kinds(name)
    scanned = [k for k in kinds
               if k[0] in ("mamba", "linear_attention", "kda")]
    for text in texts:
        assert text.count("func.func private @_live_half") == 2 * len(
            set(kinds))
        assert text.count("call @_live_half") == 2 * len(kinds)
        assert text.count("func.func private @_live_scan") == len(
            set(scanned))
        assert text.count("call @_live_scan") == len(scanned)
    assert len(texts[0].splitlines()) == len(texts[1].splitlines())
    short = llama.prefill.lower(c, params, ctx, i32(256), i32(), i32(),
                                i32(), fresh=True).as_text()
    assert "_live_half" not in short and "_live_scan" not in short
