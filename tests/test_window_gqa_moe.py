"""The window + full rotary GQA stack (the rotary GQA form of
models/ssm_moe.py: ``attention`` and ``window_attention`` layers with their
OWN numbers of query heads, a gate a head, a rotary rule a kind, the
one-group sigmoid router with a share; ops/rope.py: ``kind_rotary``)
against its plain reference (benchmarks/references/window_gqa_moe.py), on
seeded random weights at tiny widths on the CPU: six layers (full, window x
3, full, window), 18 / 12 query heads (groups of 9 and 6) over two K/V
heads of 16, a window of 8 in a lane buffer of 8 rows, YaRN over half the
head on the full layers, one dense layer and then 16 experts top 4 of which
this share holds 4.

Comparisons are float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ in the ORDER
of float32 sums (blocked against whole softmax, grouped against dense
expert products), so log-probs agree to ~1e-5 and the tolerance is 2e-4;
the faults the controls inject move them by 4e-2 to 4.
"""
import asyncio
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models import llama, ssm_moe
from dynamo_tpu.models.config import _TINY_LAGUNA, ModelConfig
from dynamo_tpu.ops.attention import (
    PALLAS_INTERPRET,
    REFERENCE,
    DecodeAttention,
    PriorContext,
    ctx_decode_attention,
    prefill_attention,
)
from dynamo_tpu.ops.rope import apply_rope_leading, kind_rotary, rope_cos_sin
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
BUCKETS = (32, 64)
TOP = 5
LANES = 6
HF = dict(_TINY_LAGUNA, engine={"prefill_buckets": list(BUCKETS)})
i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731


def load(kind, name):
    path = os.path.join(REPO, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-ep8-d12.json")) as f:
        return json.load(f)


def rnd(seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * 0.3,
                       jnp.float32)


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).tolist()


# ---------------------------------------------------------------------------
# the served path against the reference: ONE engine, every case through it

SERVED = {
    # one padded bucket; 40 decode steps wrap the lane's 8-row buffer five
    # times, ten flushes of 4
    "one-chunk": ([23], 40),
    # shorter than the window throughout its prefill: a window layer is
    # still full attention, and the buffer is not full when decode starts
    "below-the-window": ([5], 12),
    # 64 + 36: the continuing chunk starts on a buffer that has wrapped
    # eight times and reads the full layers' prior rows; padded bucket
    "two-chunks": ([100], 16),
    # 64 + 64 + 3: the last chunk is shorter than the window
    "three-chunks-a-short-tail": ([131], 12),
    # prompts arriving together: a batched prefill, then lanes under and
    # over the window, at different buffer offsets, in one round
    "a-batch": ([30, 6, 30, 6], 12),
}


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny_laguna(dtype="float32")
    return cfg, llama.init_params(cfg, 3), load("references",
                                                "window_gqa_moe")


@pytest.fixture(scope="module")
def served(setup):
    """{case: [(prompt, tokens, top log-probs) a prompt]} from one engine
    (prefill fresh, continuing, padded, batched; decode through ring,
    region, the lanes' window buffers and the fused rounds)."""
    cfg, params, _ = setup
    eng = TpuEngine(cfg, EngineConfig(
        num_pages=16, page_size=8, max_pages_per_seq=32,
        max_decode_slots=LANES, prefill_buckets=BUCKETS, flush_every=4,
        cache_dtype="float32", max_logprobs=TOP),
        params=params, mesh_config=MeshConfig(tp=1))

    async def serve(prompt, n):
        req = PreprocessedRequest(
            token_ids=list(prompt), model="t",
            stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            output_options=OutputOptions(logprobs=TOP))
        toks, tops = [], []
        async for out in eng.generate(req):
            toks += out.token_ids
            tops += out.top_logprobs or []
        assert len(toks) == n and len(tops) == n
        return prompt, toks, tops

    async def every_case():
        out = {}
        for case, (lens, n) in SERVED.items():
            out[case] = await asyncio.gather(*(
                serve(prompt_of(m, 10 + i), n) for i, m in enumerate(lens)))
        await eng.stop()
        return out

    return asyncio.run(every_case())


def distance(ref, params, prompt, toks, tops, control=None):
    """(max, mean) |log-prob difference| over the engine's top tokens,
    every step, against the reference's full forward of prompt + tokens."""
    want = ref.logprobs(HF, params, list(prompt) + toks,
                        [len(prompt) - 1 + i for i in range(len(toks))],
                        control=control)
    diffs = []
    for i, row in enumerate(tops):
        ids = np.asarray([p[0] for p in row])
        diffs.append(np.abs(np.asarray([p[1] for p in row]) - want[i, ids]))
    diffs = np.concatenate(diffs)
    return float(diffs.max()), float(diffs.mean())


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_path_equals_the_reference(setup, served, case):
    _, params, ref = setup
    for prompt, toks, tops in served[case]:
        assert distance(ref, params, prompt, toks, tops)[0] < TOL


def test_every_required_control_is_named_and_stated():
    ref = load("references", "window_gqa_moe")
    assert set(ref.CONTROLS_REQUIRED) >= {
        "gate_off", "rope_window_as_full", "rope_full_as_window",
        "factor_on_scale", "window_off", "window_dropped", "scale_1",
        "pick_elsewhere", "experts_fp8"}
    assert set(ref.CONTROLS_NAMED) >= {"window_minus", "window_plus",
                                       "router_bf16"}
    for name in ref.CONTROLS_REQUIRED + ref.CONTROLS_NAMED:
        assert f'``"{name}"``' in ref.__doc__, name


CONTROLS = ("gate_off", "rope_window_as_full", "rope_full_as_window",
            "factor_on_scale", "window_off", "window_dropped", "scale_1",
            "pick_elsewhere", "experts_fp8", "experts_int8", "rows_fp8",
            "window_minus", "window_plus", "router_bf16")


@pytest.mark.parametrize("control", CONTROLS)
def test_a_faulty_program_stands_ten_tolerances_off(setup, served, control):
    """What each control computes (the gate, a kind's rotary rule, where
    YaRN's factor goes, the window and its edge, a buffer lost at the
    chunk boundary, the routed scale, a share's own experts, 8-bit experts
    or rows, a bfloat16 router) is not what the program served: the
    two-chunk prompt's log-probs stand >= 10 x the tolerance off it."""
    _, params, ref = setup
    (prompt, toks, tops), = served["two-chunks"]
    with jax.disable_jit():   # the 14 controls are 14 programs a piece to
        # trace and compile; eager, on 116 positions, each is a fifth of a second
        far = distance(ref, params, prompt, toks, tops, control)[0]
    assert far > 10 * TOL


# ---------------------------------------------------------------------------
# unequal head counts through both attention ops, against the plain form

def plain_attention(q, keys, values):
    """q [heads, w] over keys / values [n, kvh, w], the softmax whole."""
    kvh = keys.shape[1]
    qg = q.reshape(kvh, -1, q.shape[-1])
    s = jnp.einsum("grh,ngh->grn", qg, keys) / np.sqrt(q.shape[-1])
    return jnp.einsum("grn,ngh->grh", jax.nn.softmax(s, -1),
                      values).reshape(q.shape)


@pytest.mark.parametrize("group,window", [(9, 16), (9, 11), (6, 0)])
@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_decode_attention_at_groups_of_nine_and_six(impl, group, window):
    """A q block of [kvh, group, w] against a lane's rows and the ring: a
    window layer's group of 9 over its modular buffer of 16 rows, a full
    layer's group of 6 over rows of the region's length; lanes under and
    over the buffer, one not live. Key by key against the plain form."""
    L, B, S, R, kvh, w = 2, 4, 16 if window else 64, 4, 2, 128
    ck, cv = rnd(0, L, kvh, B + 1, S, w), rnd(1, L, kvh, B + 1, S, w)
    rk, rv = rnd(2, L, kvh, B, R, w), rnd(3, L, kvh, B, R, w)
    q = rnd(4, B, kvh * group, w)
    base, live = i32(5, 37, 48, 21), jnp.asarray([True, True, True, False])
    attn = REFERENCE if impl == "reference" else DecodeAttention(
        PALLAS_INTERPRET)
    out = ctx_decode_attention(attn, q, ck, cv, rk, rv, jnp.int32(1),
                               base + 3, base, live=live, window=window)
    for b in range(3):
        bb, keys, values = int(base[b]), [], []
        for p in range(max(0, bb + 3 - window) if window else 0, bb + 3):
            src, at = ((ck, cv), p % S) if p < bb else ((rk, rv), p - bb)
            keys.append(src[0][1, :, b, at])
            values.append(src[1][1, :, b, at])
        want = plain_attention(q[b], jnp.stack(keys), jnp.stack(values))
        np.testing.assert_allclose(out[b], want, atol=1e-5)
    assert not np.asarray(out[3]).any()


@pytest.mark.parametrize("group,window,prior", [(9, 8, 0), (9, 24, 32),
                                                (6, 0, 32)])
def test_prefill_attention_at_groups_of_nine_and_six(group, window, prior):
    """Chunks of 64 rows in blocks of 16 at 18 and 12 query heads over two
    K/V heads: behind a window (fresh, and continuing from a workspace of
    the lanes' last 32 prior rows) and over the whole context."""
    K, T, kvh, w = 2, 64, 2, 32
    q = rnd(0, K, T, kvh * group, w)
    k, v = rnd(1, K, T, kvh, w), rnd(2, K, T, kvh, w)
    starts = i32(40, 5) if prior else i32(0, 0)
    lens = starts + i32(64, 37)
    # the prior rows: under a window a workspace of the lanes' last 32
    # (row i = position q_start - 32 + i), else the region's own 64 (row i
    # = position i)
    span = prior if window else 2 * prior
    pk, pv = rnd(3, 1, kvh, K, span, w), rnd(4, 1, kvh, K, span, w)
    ctx = PriorContext(pk, pv, jnp.int32(0), i32(0, 1)) if prior else None
    out = prefill_attention(q, k, v, starts, lens, ctx, block=16,
                            ctx_span=span, window=window)
    for lane in range(K):
        q0, n = int(starts[lane]), int(lens[lane] - starts[lane])
        for row in (0, n // 2, n - 1):
            keys, values = [], []
            first = max(0, q0 + row - window + 1) if window else 0
            for p in range(first, q0 + row + 1):
                if p >= q0:
                    keys.append(k[lane, p - q0])
                    values.append(v[lane, p - q0])
                else:   # the workspace: the window's last rows, or row p
                    at = p - q0 + prior if window else p
                    keys.append(pk[0, :, lane, at])
                    values.append(pv[0, :, lane, at])
            want = plain_attention(q[lane, row], jnp.stack(keys),
                                   jnp.stack(values))
            np.testing.assert_allclose(out[lane, row], want, atol=1e-5)


# ---------------------------------------------------------------------------
# rotary by kind, against the formula written out

def rotary_by_hand(x, pos, rule, hd):
    """x [T, hd] (one head) at positions pos, by the published rule."""
    rot = int(hd * rule.get("partial_rotary_factor", 1))
    theta = rule["rope_theta"]
    out = np.array(x, np.float64)
    for t, p in enumerate(pos):
        for i in range(rot // 2):
            f = theta ** (-2.0 * i / rot)
            factor = 1.0
            if rule["rope_type"] == "yarn":
                orig = rule["original_max_position_embeddings"]
                turn = lambda n: (rot * math.log(orig / (n * 2 * math.pi))  # noqa: E731
                                  / (2 * math.log(theta)))
                low = max(math.floor(turn(rule["beta_fast"])), 0)
                high = min(math.ceil(turn(rule["beta_slow"])), rot - 1)
                ramp = min(max((i - low) / ((high - low) or 0.001), 0), 1)
                f = f / rule["factor"] * ramp + f * (1 - ramp)
                factor = rule["attention_factor"]
            c, s = math.cos(p * f) * factor, math.sin(p * f) * factor
            a, b = x[t, i], x[t, i + rot // 2]
            out[t, i], out[t, i + rot // 2] = a * c - b * s, b * c + a * s
    return out


@pytest.mark.parametrize("hf", ["toy", "published"])
@pytest.mark.parametrize("kind", ["attention", "window_attention"])
def test_rotary_by_kind_is_the_formula(kind, hf):
    """Partial (the leading dimensions rotate, rotate-half among
    themselves, the rest pass), theta a kind, YaRN's ramp over the ROTATED
    dimensions and its factor on cos and sin: element by element."""
    d = published() if hf == "published" else _TINY_LAGUNA
    hd = d["head_dim"]
    cfg = ModelConfig.from_hf_dict(d)
    rule = ssm_moe.dims(cfg)["rotary"][kind]
    stated = d["rope_parameters"][
        "full_attention" if kind == "attention" else "sliding_attention"]
    assert rule["rot"] == hd * stated.get("partial_rotary_factor", 1)
    pos = np.asarray([0, 1, 7, 500, 9000, 17000])
    x = np.random.RandomState(1).randn(len(pos), 3, hd).astype(np.float32)
    inv_freq, factor = kind_rotary(hd, rule)
    assert len(inv_freq) == rule["rot"] // 2
    got = apply_rope_leading(jnp.asarray(x), *rope_cos_sin(
        jnp.asarray(pos), jnp.asarray(inv_freq)), factor)
    for h in range(3):
        np.testing.assert_allclose(
            got[:, h], rotary_by_hand(x[:, h], pos, stated, hd), atol=2e-3)
    # the dimensions past the rotated ones pass through bit for bit
    np.testing.assert_array_equal(np.asarray(got)[..., rule["rot"]:],
                                  x[..., rule["rot"]:])


# ---------------------------------------------------------------------------
# the share: what every chip of the eight computes adds up to the layer

def test_the_shares_add_up_to_the_uncut_layer(setup):
    """One expert layer of 16 published experts cut four ways: the program's
    routed part of each share (its own four experts, ``first`` = index x
    4), plus the shared expert counted ONCE, is what the reference gives
    for the layer uncut (all 16 held by one chip)."""
    _, _, ref = setup
    E, held, H, I = 16, 4, 64, 32
    r = np.random.RandomState(5)
    w = lambda *s: jnp.asarray(r.randn(*s) / np.sqrt(s[-2]), jnp.float32)  # noqa: E731
    whole = {"wr": w(H, E), "bias": jnp.asarray(r.randn(E) * 0.01,
                                                jnp.float32),
             "we_g": w(E, H, I), "we_u": w(E, H, I), "we_d": w(E, I, H),
             "ws_g": w(H, I), "ws_u": w(H, I), "ws_d": w(I, H)}
    x = jnp.asarray(r.randn(24, H), jnp.float32)
    total = jnp.zeros_like(x)
    for index in range(E // held):
        c = ModelConfig.tiny_laguna(dtype="float32", expert_share={
            "published_experts": E, "of": E // held, "index": index})
        lp = dict(whole, **{n: whole[n][index * held:(index + 1) * held]
                            for n in ("we_g", "we_u", "we_d")})
        y, stats = ssm_moe._ffn(c, lp, x, None, ssm_moe.stats_zero(c))
        total = total + (y - ssm_moe._shared(lp, x))
        # every token's 4 picks counted, this share's among them
        assert int(stats[3]) == 24 * 4 and 0 < int(stats[1]) < 24 * 4
    total = total + ssm_moe._shared(whole, x)
    uncut = ref.hyper(dict(_TINY_LAGUNA, num_experts=E, expert_share=None))
    want = ref.routed(uncut, whole, x) + ref.swiglu(
        x, whole["ws_g"], whole["ws_u"], whole["ws_d"])
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# the reader

def test_the_reader_maps_the_published_keys():
    d = published()
    c = ModelConfig.from_hf_dict(d)
    k = c.hybrid_dict
    assert (c.hidden_size, c.num_kv_heads, c.head_dim, c.intermediate_size,
            c.num_layers, c.vocab_size, c.tie_word_embeddings) == (
        3072, 8, 128, 12288, 12, 12544, False)
    assert k["layer_types"] == ("attention",) + ("window_attention",) * 3 \
        + ("attention",) + ("window_attention",) * 3 \
        + ("attention",) + ("window_attention",) * 3
    assert k["heads_by_layer"] == (48, 72, 72, 72) * 3
    assert (k["window"], k["window_rows"], k["gate"], k["n_dense"]) == (
        512, 512, "head", 1)
    assert (k["published_experts"], k["num_local_experts"], k["share_of"],
            k["share_index"], k["num_experts_per_tok"], k["intermediate_size"],
            k["shared_intermediate_size"]) == (256, 32, 8, 0, 10, 1024, 1024)
    assert (k["router"], k["n_group"], k["topk_group"],
            k["routed_scaling_factor"]) == ("sigmoid_groups", 1, 1, 2.5)
    rope = {kind: dict(rule) for kind, rule in k["rope"]}
    assert rope["window_attention"] == {"type": "default", "theta": 1e4,
                                        "rot": 128}
    assert rope["attention"] == {
        "type": "yarn", "theta": 5e5, "rot": 64, "factor": 128.0,
        "original_max_position_embeddings": 8192.0, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.4852030263919618}
    d = ssm_moe.dims(c)
    assert (d["n_attn"], d["n_win"], d["first"], d["heads"][:2]) == (
        3, 9, 0, (48, 72))
    # every width is the published one
    assert {w: d[w] for w in ("E", "held", "K", "I_e", "I_s")} == {
        "E": 256, "held": 32, "K": 10, "I_e": 1024, "I_s": 1024}


REFUSALS = {
    "gating": ({"gating": True}, "gating True"),
    "gating_types": ({"gating_types": ["per_head"] * 5 + ["per_channel"]},
                     "gating_types other than per_head"),
    "softcapping": ({"moe_router_logit_softcapping": 30},
                    "moe_router_logit_softcapping"),
    "weight_on_input": ({"moe_apply_router_weight_on_input": True},
                        "moe_apply_router_weight_on_input"),
    "sparse_step": ({"decoder_sparse_step": 2}, "decoder_sparse_step 2"),
    "heads_list": ({"num_attention_heads_per_layer": [12, 18]},
                   "num_attention_heads_per_layer"),
    "layer_types_list": ({"layer_types": ["full_attention"] * 5},
                         "layer_types"),
    "layer_kind": ({"layer_types": ["full_attention"] * 5 + ["mamba"]},
                   "layer_types other than"),
    "heads_multiple": ({"num_attention_heads_per_layer":
                        [12, 18, 18, 18, 12, 17]}, "no multiple"),
    "norm_topk": ({"norm_topk_prob": False}, "norm_topk_prob false"),
    "tied": ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    "bias": ({"attention_bias": True}, "attention_bias"),
    "dense_run": ({"mlp_only_layers": [1]}, "no leading run"),
    "window": ({"sliding_window": None}, "sliding_window"),
    "rope_type": ({"rope_parameters": dict(
        _TINY_LAGUNA["rope_parameters"], sliding_attention={
            "rope_type": "llama3", "rope_theta": 1e4})}, "rope_parameters"),
    "rope_odd": ({"rope_parameters": dict(
        _TINY_LAGUNA["rope_parameters"], sliding_attention={
            "rope_type": "default", "rope_theta": 1e4,
            "partial_rotary_factor": 0.3})}, "rope_parameters"),
    "share": ({"expert_share": {"published_experts": 16, "of": 3,
                                "index": 0}}, "expert_share"),
    "share_index": ({"expert_share": {"published_experts": 16, "of": 4,
                                      "index": 4}}, "expert_share index"),
    "missing": ({"gating": None, "drop": "moe_routed_scaling_factor"},
                "missing"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_the_reader_refuses_by_name(case):
    change, match = REFUSALS[case]
    d = dict(_TINY_LAGUNA, **change)
    d.pop(d.pop("drop", None), None)
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict(d)


# ---------------------------------------------------------------------------
# the published widths, by shapes alone

def test_published_leaves_and_row_bytes():
    """At the published widths: every weight bfloat16 but the router's
    bias, ``wq`` / ``wo`` / ``wg`` at the LAYER'S number of query heads,
    one K/V geometry under both, rows of two lengths in the region."""
    c = ModelConfig.from_hf_dict(published())
    shapes = jax.eval_shape(lambda: llama.init_params(c, 0))
    for l, lp in enumerate(shapes["layers"]):
        heads = 48 if l % 4 == 0 else 72
        assert lp["wq"].shape == (3072, heads * 128)
        assert lp["wo"].shape == (heads * 128, 3072)
        assert lp["wg"].shape == (3072, heads)
        assert lp["wk"].shape == lp["wv"].shape == (3072, 1024)
        if l == 0:
            assert lp["w_g"].shape == (3072, 12288) and "wr" not in lp
        else:
            assert lp["wr"].shape == (3072, 256)
            assert lp["we_g"].shape == (32, 3072, 1024)
            assert lp["we_d"].shape == (32, 1024, 3072)
            assert lp["ws_g"].shape == (3072, 1024)
            assert lp["bias"].dtype == jnp.float32
        assert all(v.dtype == jnp.bfloat16 for n, v in lp.items()
                   if n != "bias")
    assert shapes["head"].shape == (3072, 12544)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 4325529344
    assert ssm_moe.kv_row_bytes(c, 2) == 3 * 4096
    assert ssm_moe.window_bytes(c, 2) == 9 * 512 * 4096
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, 16, 17408))
    assert {n: v.shape for n, v in ctx.items()} == {
        "k": (3, 8, 17, 17408, 128), "v": (3, 8, 17, 17408, 128),
        "wk": (9, 8, 17, 512, 128), "wv": (9, 8, 17, 512, 128)}
    assert ssm_moe.state_bytes(c, 2) == 0
    assert ssm_moe.stepped_kinds(c, ctx) == ()


# ---------------------------------------------------------------------------
# the host's mirrors and the cell's readers

def test_decode_mirror_counts_by_kind():
    """Full rows a layer, window rows and their bound over all window
    layers, and the query-head rows of each kind: live lanes x steps x the
    heads of every layer of the kind."""
    c = ModelConfig.tiny_laguna()
    mirror = ssm_moe.decode_mirror(c, 256, 4, DecodeAttention("pallas"))
    got = dict(mirror(np.asarray([3, 20, 100, 7]),
                      np.asarray([True, True, True, False]), 4))
    assert got["dynamo_decode_attn_q_rows_full"] == 3 * 4 * (12 + 12)
    assert got["dynamo_decode_attn_q_rows_window"] == 3 * 4 * 18 * 4
    # four window layers: min(n + step, 8) a live lane a step
    assert got["dynamo_attn_window_rows_bound"] == 4 * (
        sum(min(3 + s, 8) for s in range(4)) + 8 * 4 * 2)
    assert "dynamo_attn_shared_rows_read" not in got
    assert got["dynamo_decode_attn_rows_read"] > 0


def test_the_cells_readers_read_what_the_program_counts():
    byname = load(".", "byname")
    here = os.path.join(REPO, "benchmarks", "layer_metrics")
    hist = lambda **kw: {"histograms": {  # noqa: E731
        k: {"sum": v, "count": 1 if v else 0} for k, v in kw.items()}}
    names = dict(full="dynamo_decode_attn_rows_read",
                 win="dynamo_attn_window_rows_read",
                 qf="dynamo_decode_attn_q_rows_full",
                 qw="dynamo_decode_attn_q_rows_window")
    before = hist(**{v: 0 for v in names.values()})
    after = hist(**{names["full"]: 1000, names["win"]: 500,
                    names["qf"]: 144, names["qw"]: 648})
    for h in after["histograms"].values():
        h["count"] = 2
    sources = {"config": published(), "before": before, "after": after,
               "byname": byname}
    read = lambda name: byname.module_with(here, name, "read").read(sources)  # noqa: E731
    assert read("attn.full_rows_read_share") == pytest.approx(
        3000 / 3500 * 100)
    assert read("attn.window_q_rows_share") == pytest.approx(648 / 792 * 100)
    # a program without the counters (the parent): nothing to read
    empty = dict(sources, before={"histograms": {}},
                 after={"histograms": {}})
    for name in ("attn.full_rows_read_share", "attn.window_q_rows_share",
                 "kernel.full_gqa_decode_roofline",
                 "kernel.window_gqa_decode_roofline",
                 "moe.experts_touched_share.codeturn-open"):
        assert byname.module_with(here, name, "read").read(empty) is None
