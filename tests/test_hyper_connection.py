"""The four-stream residual (ops/hyper_connection.py) and YaRN
(ops/rope.py) as the latent-attention block uses them, on the CPU at tiny
widths: the tokens-minor slab form against the textbook [N, n, n] form,
what the Sinkhorn iterations reach, the tie to the one-stream block, and
the rotary numbers against values worked out by hand.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, mla_moe
from dynamo_tpu.models.config import _TINY_MHC, _TINY_MLA_MOE, ModelConfig
from dynamo_tpu.ops import hyper_connection as hc
from dynamo_tpu.ops.attention import REFERENCE
from dynamo_tpu.ops.rope import yarn_inv_freq, yarn_mscale

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, n, C = 37, 4, 24
KW = dict(n=n, iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)


def drawn(seed=0, scale_b=1.0):
    r = np.random.RandomState(seed)
    x = r.randn(N, n, C).astype(np.float32)
    phi = (r.randn(n * C, 2 * n + n * n) / np.sqrt(n * C)).astype(np.float32)
    a = np.asarray([0.7, 1.3, 1.0], np.float32)
    b = (scale_b * r.randn(2 * n + n * n)).astype(np.float32)
    return x, phi, a, b


def textbook(x, phi, a, b, iters=20, eps=1e-6, lo=-30.0, hi=30.0,
             norm_eps=1e-6):
    """float64 numpy, one token at a time, the paper's [n, n] matrices."""
    x = x.astype(np.float64)
    pre, post, res = [], [], []
    for X in x:
        v = X.reshape(-1)
        t = (v / np.sqrt(np.mean(v * v) + norm_eps)) @ phi.astype(np.float64)
        sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
        pre.append(sig(a[0] * t[:n] + b[:n]))
        post.append(2.0 * sig(a[1] * t[n:2 * n] + b[n:2 * n]))
        M = np.exp(np.clip(a[2] * t[2 * n:] + b[2 * n:], lo, hi)).reshape(
            n, n)
        for _ in range(iters):
            M = M / (M.sum(axis=1, keepdims=True) + eps)
            M = M / (M.sum(axis=0, keepdims=True) + eps)
        res.append(M)
    return np.stack(pre), np.stack(post), np.stack(res)


def test_pre_and_post_equal_the_textbook_matrices():
    x, phi, a, b = drawn()
    f = np.random.RandomState(1).randn(N, C).astype(np.float32)
    mix = hc.mix_coefficients(*map(jnp.asarray, (x, phi, a, b)), **KW)
    pre, post, res = textbook(x, phi, a, b)
    # the coefficients, tokens minor here, token major there
    np.testing.assert_allclose(np.asarray(mix.pre).T, pre, atol=2e-6)
    np.testing.assert_allclose(np.asarray(mix.post).T, post, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(mix.res).transpose(2, 0, 1), res, atol=2e-6)
    u = np.einsum("ti,tic->tc", pre, x)
    out = (np.einsum("tij,tjc->tic", res, x)
           + post[:, :, None] * f[:, None, :])
    np.testing.assert_allclose(
        np.asarray(hc.hc_pre(jnp.asarray(x), mix)), u, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(hc.hc_post(jnp.asarray(x), jnp.asarray(f), mix)), out,
        atol=1e-5)


def test_the_clamp_bounds_the_logits_before_exp():
    x, phi, a, b = drawn(2)
    b[2 * n] = 500.0          # exp(500) overflows float32
    mix = hc.mix_coefficients(*map(jnp.asarray, (x, phi, a, b)), **KW)
    assert np.isfinite(np.asarray(mix.res)).all()
    np.testing.assert_allclose(
        np.asarray(mix.res).transpose(2, 0, 1), textbook(x, phi, a, b)[2],
        atol=2e-6)


def test_h_res_is_doubly_stochastic_after_20_iterations_not_after_1():
    # logits of order 1: Sinkhorn's rate is set by how far from uniform
    # the matrix starts (at order 2, one token in 40 stands at 5e-4)
    x, phi, a, b = map(jnp.asarray, drawn(3, scale_b=0.5))

    def sums(m):
        return np.asarray(m.res).sum(1), np.asarray(m.res).sum(0)

    rows, cols = sums(hc.mix_coefficients(x, phi, a, b, **KW))
    assert np.abs(rows - 1).max() < 1e-5 and np.abs(cols - 1).max() < 1e-5
    one = hc.mix_coefficients(x, phi, a, b, **dict(KW, iters=1))
    rows1, cols1 = sums(one)
    assert np.abs(cols1 - 1).max() < 1e-5      # the last step normalises them
    assert np.abs(rows1 - 1).max() > 1e-2      # the rows are not
    # and the counter the engine carries home reads exactly that
    assert float(hc.row_sum_residual(one)) == pytest.approx(
        np.abs(rows1 - 1).max(), rel=1e-5)
    assert float(hc.row_sum_residual(
        hc.mix_coefficients(x, phi, a, b, **KW))) < 1e-5


def fixed_coefficients(params):
    """H_pre = 1/n, H_post = 1, H_res = I for every token: phi 0, and
    offsets that the three maps take there."""
    layers = dict(params["layers"])
    b = np.zeros(layers["hc_b"].shape, np.float32)
    b[..., :n] = np.log(1.0 / (n - 1))                 # sigmoid -> 1/n
    b[..., 2 * n:] = np.where(np.eye(n).reshape(-1) > 0, 30.0, -30.0)
    layers["hc_b"] = jnp.asarray(b)
    layers["hc_phi"] = jnp.zeros_like(layers["hc_phi"])
    return dict(params, layers=layers)


def test_fixed_coefficients_reproduce_the_one_stream_block():
    """With H_pre = 1/n, H_post = 1, H_res = I every stream is the
    one-stream residual and the head's RMS norm takes out the factor n:
    the new residual path is tied to the one cell 3 runs. Prefill, then a
    decode step through the cache."""
    rope = dict(_TINY_MHC)["rope_scaling"]
    one = ModelConfig.tiny_mla_moe(rope_scaling=rope)
    four = ModelConfig.tiny_mla_moe_mhc()
    p4 = fixed_coefficients(
        llama.serving_params(four, llama.init_params(four, 5)))
    p1 = dict(p4, layers={k: v for k, v in p4["layers"].items()
                          if not k.startswith("hc_")})
    toks = jnp.asarray(
        np.random.RandomState(7).randint(1, 256, (1, 96)), jnp.int32)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    step = jax.jit(functools.partial(mla_moe.decode_step_impl,
                                     attn=REFERENCE), static_argnums=(0,))
    got = {}
    for name, cfg, params in (("one", one, p1), ("four", four, p4)):
        ctx = llama.init_ctx(cfg, 1, 128, jnp.float32)
        ctx, logits = llama.batch_prefill(
            cfg, params, ctx, toks, i32(0), i32(0), i32(90), 0)
        ring = llama.init_ring(cfg, 1, 1, dtype=jnp.float32)
        _, lg, stats = step(cfg, params, ctx, ring, i32(11), i32(91),
                            i32(90), jnp.int32(0))
        got[name] = np.asarray(logits[0]), np.asarray(lg[0]), stats
    for a, b in zip(got["one"][:2], got["four"][:2]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    # the counters: three for the one-stream block, the residual's bits
    # as a fourth (H_res = I has converged: ~0)
    assert got["one"][2].shape == (3,) and got["four"][2].shape == (4,)
    np.testing.assert_array_equal(got["one"][2], got["four"][2][:3])
    assert np.asarray(got["four"][2][3:]).view(np.float32)[0] < 1e-5


def test_yarn_frequencies_and_scale_against_hand_computed_values():
    """d = 64, theta 1e4, factor 64 over 4096, beta 32 / 1: the ramp runs
    from dimension floor(10.47) = 10 to ceil(22.51) = 23; dimension 16
    sits 6/13 up it; m = 0.1 ln 64 + 1."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4-mhc-d7.json")) as f:
        published = json.load(f)
    s = published["rope_scaling"]
    inv = yarn_inv_freq(64, 10000.0, s)
    assert inv.shape == (32,) and inv.dtype == np.float32
    np.testing.assert_allclose(inv[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(inv[10], 10000.0 ** (-20 / 64), rtol=1e-6)
    np.testing.assert_allclose(inv[16], 0.01 * (6 / 13 / 64 + 7 / 13),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[16], 0.00545673, rtol=1e-5)
    np.testing.assert_allclose(inv[23], 10000.0 ** (-46 / 64) / 64,
                               rtol=1e-6)
    np.testing.assert_allclose(inv[31], 2.08363e-6, rtol=1e-5)
    assert yarn_mscale(64, 1) == pytest.approx(1.4158883)
    assert yarn_mscale(1, 1) == 1.0 and yarn_mscale(64, 0) == 1.0
    c = ModelConfig.from_hf_dict(published)
    inv_c, on_cos_sin, on_scale = mla_moe._rotary(c)
    np.testing.assert_array_equal(inv_c, inv)
    assert on_cos_sin == 1.0                   # mscale == mscale_all_dim
    assert on_scale * 192 ** -0.5 == pytest.approx(0.144680, rel=1e-5)
    # no rope_scaling: the plain rule, untouched
    plain = mla_moe._rotary(ModelConfig.tiny_mla_moe())
    assert plain[1:] == (1.0, 1.0)
    np.testing.assert_allclose(plain[0], 10000.0 ** (-np.arange(4) / 4),
                               rtol=1e-6)


def test_the_softmax_factor_is_applied_in_float32_and_rounded_once():
    """bfloat16 holds YaRN's 2.0047 as 2.0 (its step there is 2^-6): a
    bfloat16 query times the rounded constant scores 0.23 % low. Under
    YaRN both attention forms take the product in float32; without a
    rotary factor the one-stream block keeps the product it has always
    lowered (its programs' hashes are held equal to the parent's)."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4-mhc-d7.json")) as f:
        c = ModelConfig.from_hf_dict(json.load(f))
    k = mla_moe._rotary(c)[2]
    assert float(jnp.asarray(k, jnp.bfloat16)) == 2.0 and k > 2.004
    q = jnp.asarray(np.random.RandomState(3).randn(64, 8), jnp.bfloat16)
    got = mla_moe._scaled(c, q, k)
    assert got.dtype == jnp.bfloat16
    want = (q.astype(jnp.float32) * k).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    low = np.asarray(q * jnp.asarray(k, jnp.bfloat16), np.float32)
    assert (low != np.asarray(got, np.float32)).any()
    one = ModelConfig.tiny_mla_moe()
    np.testing.assert_array_equal(
        np.asarray(mla_moe._scaled(one, q, 0.0722), np.float32),
        np.asarray(q * jnp.asarray(0.0722, jnp.bfloat16), np.float32))


def test_the_published_file_reads_as_the_four_stream_block():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4-mhc-d7.json")) as f:
        published = json.load(f)
    c = ModelConfig.from_hf_dict(published)
    d = mla_moe.dims(c)
    assert (d["n"], d["nh"], d["q_rank"], d["kv_rank"], d["stored"], d["E"],
            d["K"], d["I_e"], d["n_dense"]) == (4, 32, 768, 512, 640, 64, 4,
                                                1024, 2)
    assert c.hc_dict == {
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
    assert (c.num_layers, c.hidden_size, c.vocab_size) == (7, 3584, 131072)
    shapes = jax.eval_shape(lambda: llama.init_params(c, 0))["layers"]
    assert shapes["hc_phi"].shape == (7, 2, 4 * 3584, 24)
    assert shapes["hc_a"].shape == (7, 2, 3)
    assert shapes["hc_b"].shape == (7, 2, 24)
    # a seed gives the block without streams the weights it always gave
    a = llama.init_params(ModelConfig.tiny_mla_moe(), 9)
    b = llama.init_params(ModelConfig.tiny_mla_moe_mhc(), 9)
    for x, y in zip(jax.tree.leaves(a["experts"]),
                    jax.tree.leaves(b["experts"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


YARN = dict(_TINY_MHC)["rope_scaling"]


@pytest.mark.parametrize("change,match", [
    ({"rope_scaling": dict(YARN, type="linear")}, "does not implement"),
    ({"rope_scaling": dict(YARN, type="llama3")}, "does not implement"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "does not implement"),
    ({"rope_scaling": dict(YARN, extrapolation_factor=1)},
     "does not implement"),
    ({"hc_mult": None}, "missing"),
    ({"hc_sinkhorn_iters": None}, "missing"),
    ({"mhc_h_res_clamp_max": None}, "missing"),
    ({"hc_mult": 1}, "does not implement"),
    ({"hc_sinkhorn_iters": 0}, "does not implement"),
], ids=["rope-linear", "rope-llama3", "yarn-keys-missing", "yarn-key-unknown",
        "no-hc_mult", "no-hc_sinkhorn_iters", "no-clamp_max", "one-stream-hc",
        "no-iterations"])
def test_config_values_the_block_does_not_implement_are_refused(change,
                                                                match):
    d = dict(_TINY_MLA_MOE, **_TINY_MHC)
    for k, v in change.items():
        if v is None:
            del d[k]
        else:
            d[k] = v
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict(d)
