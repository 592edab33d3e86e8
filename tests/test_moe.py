"""Expert-parallel MoE tests (SURVEY §2.5 EP/wide-EP row; reference does
this via SGLang+DeepEP — here shard_map + all_to_all over the ep axis,
tested on the virtual 8-device CPU mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models import moe
from dynamo_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    moe_layer,
    moe_params_shardings,
    moe_reference,
)


def ep_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("ep",))


def place(h, params, mesh):
    sh = moe_params_shardings(mesh)
    return (
        jax.device_put(h, NamedSharding(mesh, P("ep", None))),
        {k: jax.device_put(v, sh[k]) for k, v in params.items()},
    )


@pytest.mark.parametrize("ep", [2, 4, 8])
def test_moe_matches_dense_reference(ep):
    """With ample capacity (no drops) the distributed dispatch must equal
    the dense single-device computation exactly."""
    cfg = MoEConfig(hidden_size=16, intermediate_size=32, num_experts=8,
                    top_k=2, capacity_factor=8.0)  # no overflow
    params = init_moe_params(cfg, 0)
    rng = np.random.default_rng(1)
    T = 32
    h = jnp.asarray(rng.standard_normal((T, 16)), jnp.float32)
    ref = moe_reference(h, params, cfg)

    mesh = ep_mesh(ep)
    hs, ps = place(h, params, mesh)
    out = moe_layer(hs, ps, cfg, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_moe_capacity_overflow_drops_not_corrupts():
    """Tiny capacity: overflowing tokens lose their expert contribution
    (GShard drop semantics) but never corrupt other tokens or NaN."""
    cfg = MoEConfig(hidden_size=8, intermediate_size=16, num_experts=4,
                    top_k=1, capacity_factor=0.25)  # force drops
    params = init_moe_params(cfg, 0)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    mesh = ep_mesh(4)
    hs, ps = place(h, params, mesh)
    out = np.asarray(moe_layer(hs, ps, cfg, mesh))
    assert np.isfinite(out).all()
    # kept tokens match the reference; dropped ones are zero
    ref = np.asarray(moe_reference(h, params, cfg))
    per_tok = np.abs(out).sum(-1)
    kept = per_tok > 0
    assert kept.any()
    np.testing.assert_allclose(out[kept], ref[kept], rtol=2e-5, atol=2e-5)


def test_moe_validates_divisibility():
    cfg = MoEConfig(hidden_size=8, intermediate_size=16, num_experts=6)
    params = init_moe_params(cfg, 0)
    mesh = ep_mesh(4)
    h = jnp.zeros((16, 8), jnp.float32)
    with pytest.raises(ValueError, match="experts 6 not divisible"):
        moe_layer(h, params, cfg, mesh)


# ---------------------------------------------------------------------------
# The served, grouped layer's two row movements over the live row blocks
# (PR 46). ``moe.move_block`` is the rule (a held share, whole blocks of 256
# rows, more than 4096 sorted rows): the value cases trace fresh jits under
# a rule that gives a toy block of 8, and under one that gives none
# (straight-line).

R, T, K, H, I = 8, 32, 2, 16, 8     # toy block, tokens, picks, widths
E_ALL, E_HELD, FIRST = 8, 4, 2      # the share holds experts 2..5 of 8
# live sorted rows: none, one, exactly a block, a block and one, every row
TOTALS = {"none": 0, "one": 1, "block": R, "block+1": R + 1, "all": T * K}


def _under(monkeypatch, block, first):
    """A fresh jit of ``grouped_experts`` traced under a rule that gives
    every call the block height ``block`` (the jit cache knows nothing of
    the rule)."""
    def run(*args):
        monkeypatch.setattr(moe, "move_block", lambda *a: block)
        return jax.jit(lambda *a: moe.grouped_experts(*a, first=first))(*args)
    return run


def _layer_inputs(seed, experts):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    w = jnp.asarray(rng.random((T, K)), jnp.float32)
    ws = [jnp.asarray(rng.standard_normal(s), jnp.float32)
          for s in ((experts, H, I), (experts, H, I), (experts, I, H))]
    return rng, x, w, ws


@pytest.mark.parametrize("total", sorted(TOTALS))
@pytest.mark.parametrize("dead", ["held-elsewhere", "padding"])
def test_looped_row_movements_equal_the_straight_line_form(monkeypatch, dead,
                                                           total):
    """``total`` live sorted rows, the others dead because their expert
    is held elsewhere (``first=``, every token valid but the last) or
    because their token is padding (``valid`` alone, a form the rule gives
    no call today): the looped form's output is the straight-line form's
    bit for bit on the valid rows and 0 on the others, and the groups'
    sizes are the same."""
    n = TOTALS[total]
    if dead == "held-elsewhere":
        rng, x, w, ws = _layer_inputs(n, E_HELD)
        # every pick on an expert of the other share, then n of them here
        sel = rng.choice([0, 1, 6, 7], (T - 1) * K)
        here = rng.permutation((T - 1) * K)[:n]
        sel[here] = rng.integers(FIRST, FIRST + E_HELD, here.size)
        if n == T * K:
            sel = rng.integers(FIRST, FIRST + E_HELD, T * K)
        sel = np.resize(sel, T * K).reshape(T, K)
        valid = jnp.arange(T) < (T if n == T * K else T - 1)
        first = FIRST
    else:
        if n % K:
            n += 1                  # whole tokens: a block and one token
        rng, x, w, ws = _layer_inputs(n, E_ALL)
        sel = np.argsort(rng.random((T, E_ALL)), axis=1)[:, :K]
        valid, first = jnp.arange(T) < n // K, None
    args = (x, jnp.asarray(sel, jnp.int32), w, *ws, valid)
    want, sizes = _under(monkeypatch, 0, first)(*args)
    got, got_sizes = _under(monkeypatch, R, first)(*args)
    assert int(sizes.sum()) == n
    np.testing.assert_array_equal(got_sizes, sizes)
    np.testing.assert_array_equal(got, want)
    assert not np.asarray(got)[~np.asarray(valid)].any()
    if n:
        assert np.asarray(got).any()


@pytest.mark.parametrize("tokens,picks,first,block", [
    (32, 10, 0, 0),       # a decode round of 32 lanes with a share: 320 rows
    (64, 8, None, 0),     # one of 64 lanes, every expert held
    (256, 10, 0, 0),      # a share, a whole block, 2560 rows: under the bound
    (512, 10, 36, 256),   # a share and 5120 rows
    (2048, 10, 0, 256),   # the hybrid cell's buckets
    (4096, 10, 0, 256),
    (4096, 10, None, 0),  # the same rows with every expert held
    (4096, 4, None, 0),   # the long-document latent cell's wide bucket
    (2048, 8, None, 0),   # two lanes of the chat cell's widest
])
def test_the_shape_rule_and_its_host_mirror_agree_with_the_lowered_program(
        tokens, picks, first, block):
    """``move_block`` IS the rule: the traced layer holds loops where it
    gives a block height (the gather's and the combine's) and none where
    it gives 0, at the real constants and the cells' shapes (traced from
    shapes alone: nothing is compiled or run)."""
    assert (moe.MOVE_ROWS, moe.MOVE_STRAIGHT_ROWS) == (256, 4096)
    assert moe.move_block(tokens, picks, first is not None) == block
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    text = str(jax.make_jaxpr(
        lambda *a: moe.grouped_experts(*a, first=first))(
        f32(tokens, 64), jax.ShapeDtypeStruct((tokens, picks), jnp.int32),
        f32(tokens, picks), f32(16, 64, 32), f32(16, 64, 32),
        f32(16, 32, 64), jax.ShapeDtypeStruct((tokens,), jnp.bool_)))
    assert text.count("while[") == (2 if block else 0)


def test_rows_moved_counts_whole_blocks():
    got = moe.rows_moved(np.asarray([0, 1, 255, 256, 257, 40960]), 256)
    assert got.tolist() == [0, 256, 256, 256, 512, 40960]
