"""The delta-rule linear attention (KDA) + latent attention stack with
grouped sigmoid routing (layer kinds ``kda`` / ``latent_attention`` of
models/ssm_moe.py; ops/kda.py) against its plain reference
(benchmarks/references/kda_mla_moe.py), on seeded random weights at tiny
widths on the CPU: one dense layer, then (kda, kda, latent) twice and a
kda, 16 experts in 4 groups of which 2 are kept, top 4, share 0 of 4.

Comparisons are float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ in the
ORDER of float32 sums (the chunked UT form against the recurrence as
written, blocked against whole softmax, absorbed against expanded
attention, the grouped product against a dense one), so log-probs agree
to ~1e-5 and the tolerance is 2e-4; the faults the controls inject move
them by 1e-3 to 1. The bfloat16 run's tolerance has its reason at the
test.
"""
import asyncio
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models import llama, ssm_moe
from dynamo_tpu.models.config import _TINY_KDA_LATENT, ModelConfig
from dynamo_tpu.ops import kda
from dynamo_tpu.ops.attention import (
    PALLAS_INTERPRET,
    REFERENCE,
    DecodeAttention,
)
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
PS = 8
BUCKETS = (32, 64)
TOP = 5
LANES = 6   # a round's six counters ride home in a row this wide
HF = dict(_TINY_KDA_LATENT, engine={"prefill_buckets": list(BUCKETS)})


def load_reference():
    path = os.path.join(REPO, "benchmarks", "references", "kda_mla_moe.py")
    spec = importlib.util.spec_from_file_location("ref_kda", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ling3-flash-ep8-d12.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny_kda_latent(dtype="float32")
    return cfg, llama.init_params(cfg, 3), load_reference()


def engine(cfg, params, **kw):
    ecfg = EngineConfig(**{**dict(
        num_pages=16, page_size=PS, max_pages_per_seq=32,
        max_decode_slots=LANES, prefill_buckets=BUCKETS, flush_every=4,
        cache_dtype="float32", max_logprobs=TOP), **kw})
    return TpuEngine(cfg, ecfg, params=params, mesh_config=MeshConfig(tp=1))


async def serve(eng, prompt, n):
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
    return toks, tops


def distance(ref, params, prompt, toks, tops, control=None, hf=HF):
    """(max, mean) |log-prob difference| over the engine's top tokens,
    every step, against the reference's full forward of prompt +
    tokens."""
    want = ref.logprobs(hf, params, list(prompt) + toks,
                        [len(prompt) - 1 + i for i in range(len(toks))],
                        control=control)
    diffs = []
    for i, row in enumerate(tops):
        ids = np.asarray([p[0] for p in row])
        assert ids.max() < want.shape[1]
        diffs.append(np.abs(np.asarray([p[1] for p in row]) - want[i, ids]))
    diffs = np.concatenate(diffs)
    return float(diffs.max()), float(diffs.mean())


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).tolist()


# ---------------------------------------------------------------------------
# ops/kda.py alone

def kda_inputs(T, H=2, D=16, seed=0, bound=-5.0, at_bound=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda i, *s: jax.random.normal(ks[i], s)  # noqa: E731
    q, k, v = n(0, T, H, D), n(1, T, H, D), n(2, T, H, D)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(D)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = bound * (jnp.ones((T, H, D)) if at_bound
                 else jax.nn.sigmoid(3 * n(3, T, H, D)))
    return q, k, v, g, jax.nn.sigmoid(n(4, T, H)), n(5, H, D, D)


def recurrence(q, k, v, g, b, real, S):
    """ops/kda.step one position at a time over the real rows."""
    out = []
    for t in range(q.shape[0]):
        if not real[t]:
            out.append(jnp.zeros_like(v[t]))
            continue
        o, S = kda.step(*(a[t][None] for a in (q, k, v, g, b)), S[None])
        S = S[0]
        out.append(o[0])
    return jnp.stack(out), S


@pytest.mark.parametrize("T, chunk, n_real", [
    (128, 64, 128), (100, 64, 90), (64, 64, 64), (48, 16, 40), (64, 32, 1),
    (8, 64, 8), (96, 32, 0)],
    ids=["two-chunks", "ragged-last-chunk", "one-chunk", "sub-block-chunks",
         "one-real-row", "below-a-sub-block", "all-padding"])
def test_chunked_scan_equals_the_recurrence(T, chunk, n_real):
    """The UT-transform form over chunks against the recurrence as
    written, from a random state; padding leaves the state where the last
    real row left it."""
    q, k, v, g, b, S = kda_inputs(T)
    real = np.arange(T) < n_real
    o, S_end = kda.chunk_scan(q, k, v, g, b, jnp.asarray(real), S, chunk)
    want, S_want = recurrence(q, k, v, g, b, real, S)
    np.testing.assert_allclose(o[:n_real], want[:n_real], atol=2e-5)
    np.testing.assert_allclose(S_end, S_want, atol=2e-5)


@pytest.mark.parametrize("same_key", [False, True],
                         ids=["random-keys", "one-key-repeated"])
def test_gates_at_the_bound_for_a_whole_chunk_stay_finite(same_key):
    """g = -5 at all 64 positions: e^{-G} alone would be e^320. And one
    key repeated 64 times with b = 1: the Neumann product of (I + A)^-1
    would cancel catastrophically, forward substitution does not."""
    q, k, v, g, b, S = kda_inputs(64, at_bound=not same_key)
    if same_key:
        k = jnp.broadcast_to(k[:1], k.shape)
        b = jnp.ones_like(b)
        g = g / 50
    real = np.ones(64, bool)
    o, S_end = kda.chunk_scan(q, k, v, g, b, jnp.asarray(real), S)
    want, S_want = recurrence(q, k, v, g, b, real, S)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, want, atol=5e-5)
    np.testing.assert_allclose(S_end, S_want, atol=5e-5)


@pytest.mark.parametrize("live", [(True, False, True, False), (True,) * 4,
                                  (False,) * 4],
                         ids=["holes", "all-live", "none-live"])
@pytest.mark.parametrize("H, D", [(4, 16), (8, 128)],
                         ids=["toy", "a-tile-of-published-heads"])
def test_the_step_kernel_equals_the_step(H, D, live):
    """The Pallas kernel (interpreted) over a work list against
    ``kda.step`` on the lanes the list holds; every other lane and the
    scratch lane keep their state bit for bit, and their ``o`` is 0."""
    q, k, v, g, b, _ = kda_inputs(4, H, D, seed=3)
    S = jax.random.normal(jax.random.PRNGKey(9), (5, H, D, D))
    o, S_new = kda.step(q, k, v, g, b, S[:4])
    lanes, n_live = kda.work_list(jnp.asarray(live))
    assert int(n_live[0]) == sum(live)
    o2, S2 = kda.step_pallas(q, k, v, g, b, S, lanes, n_live, interpret=True)
    for lane, on in enumerate(live):
        if on:
            np.testing.assert_allclose(o2[lane], o[lane], atol=1e-5)
            np.testing.assert_allclose(S2[lane], S_new[lane], atol=1e-5)
        else:
            np.testing.assert_array_equal(S2[lane], S[lane])
            assert not np.asarray(o2[lane]).any()
    np.testing.assert_array_equal(S2[4], S[4])


# ---------------------------------------------------------------------------
# the served path against the reference

SERVED = {
    # one padded bucket, 24 decode steps (six rounds)
    "one-chunk": ([23], 24),
    # 64 + 36: state, windows and latent rows cross a chunk boundary; the
    # continuing chunk's bucket is padded
    "two-chunks": ([100], 16),
    # 64 + 64 + 3: the last chunk is shorter than the convolution's reach
    "three-chunks-a-short-tail": ([131], 12),
    # prompts arriving together: lanes at different positions in a round
    "four-lanes": ([70, 50, 90, 33], 12),
}


@pytest.mark.parametrize("case", sorted(SERVED))
async def test_served_path_equals_the_reference(setup, case):
    """Prefill (fresh, continuing, padded) and decode through ring,
    region and the fused rounds, against the reference, on the log-probs
    the engine itself reports."""
    cfg, params, ref = setup
    lens, n = SERVED[case]
    eng = engine(cfg, params)
    prompts = [prompt_of(m, 10 + i) for i, m in enumerate(lens)]
    got = await asyncio.gather(*(serve(eng, p, n) for p in prompts))
    for p, (toks, tops) in zip(prompts, got):
        assert distance(ref, params, p, toks, tops)[0] < TOL
    if case == "three-chunks-a-short-tail":
        assert eng.dispatch_counts["prefill"] == 3
    assert eng.allocator.hit_blocks == 0 and not eng._seal_queue
    await eng.stop()


async def test_chunks_interleave_with_other_lanes_decode_and_lanes_are_reused(
        setup):
    """A prompt prefilled in three chunks WHILE another lane decodes
    between the chunks, then a lane reused by a later, shorter request;
    and what the counters say afterwards."""
    cfg, params, ref = setup
    eng = engine(cfg, params)
    first, long, later = prompt_of(60, 1), prompt_of(150, 2), prompt_of(21, 3)
    running = asyncio.ensure_future(serve(eng, first, 60))
    await asyncio.sleep(0.5)
    chunked = await serve(eng, long, 13)
    toks, tops = await running
    assert distance(ref, params, first, toks, tops)[0] < TOL
    assert distance(ref, params, long, *chunked)[0] < TOL
    short = await serve(eng, later, 5)
    assert distance(ref, params, later, *short)[0] < TOL
    snap = eng.telemetry.snapshot()
    # five KDA layers: a [4, 16, 16] float32 state and three windows of
    # 3 x 64 float32 values each; two latent layers of a 128-wide row
    assert snap["dynamo_ssm_state_bytes"]["sum"] == ssm_moe.state_bytes(
        cfg, 4) == 5 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert snap["dynamo_kv_row_bytes"]["sum"] == ssm_moe.kv_row_bytes(
        cfg, 4) == 2 * 128 * 4
    # the program's own count: the live lanes' states a step, five layers
    rounds = snap["dynamo_kda_state_rows_stepped"]
    lane_steps = snap["dynamo_engine_round_live_lane_steps"]
    assert rounds["count"] == lane_steps["count"] > 0
    assert rounds["sum"] == lane_steps["sum"] * 5
    # a lane stood empty: fewer than steps x lanes x layers
    assert rounds["sum"] < rounds["count"] * 4 * LANES * 5
    picks = snap["dynamo_moe_picks_routed"]["sum"]
    held = snap["dynamo_moe_tokens_routed"]["sum"]
    kept = snap["dynamo_moe_groups_kept_here"]["sum"]
    # four picks a routed token; a pick lands here only if its group was
    # kept; one group of four is held, two of four are kept
    assert 0 < held <= picks / 2 and 0 < kept < picks / 4
    assert held <= 4 * kept
    assert snap["dynamo_decode_attn_rows_read"]["sum"] > 0
    await eng.stop()


_SERVED: dict = {}


@pytest.mark.parametrize("control", [
    "delta_off", "gate_per_head", "state_zeroed", "conv_zeroed",
    "no_group_mask", "share_index_1", "state_bf16", "fp8"])
async def test_the_check_sees_each_fault(setup, control):
    """What the engine served, against the reference computing a FAULTY
    model: every control moves the log-probs past the tolerance the sound
    comparison keeps (a prompt of 100 crosses the chunk boundary at 64).
    The engine serves the prompt once for all the controls (``_SERVED``:
    an engine built anew compiles its programs anew, ~10 s a case)."""
    cfg, params, ref = setup
    prompt = prompt_of(100, 7)
    if not _SERVED:
        eng = engine(cfg, params)
        _SERVED["out"] = await serve(eng, prompt, 40)
        await eng.stop()
    toks, tops = _SERVED["out"]
    assert distance(ref, params, prompt, toks, tops)[0] < TOL
    assert distance(ref, params, prompt, toks, tops, control)[0] > 5 * TOL


async def test_bfloat16_weights_and_cache_stay_near_the_reference():
    """The stated precision at toy widths: bfloat16 weights, activations
    and latent rows, float32 state. Without experts the same stack reads
    0.028 mean / 0.11 max (seven layers of ~6 roundings of 2^-9 each at a
    hidden size of 64); with 16 experts, top 4, a near-tied pick that
    flips under the rounding replaces a quarter of a token's routed sum,
    and three weight seeds read 0.12-0.15 mean / 0.56-0.92 max. Held to
    0.3 / 1.5, which a dropped delta term (1.7-1.8 mean) or a state
    dropped at the chunk boundary (1.0-1.4) fails."""
    cfg = ModelConfig.tiny_kda_latent()
    params = llama.init_params(cfg, 3)
    ref = load_reference()
    eng = engine(cfg, params, cache_dtype="bfloat16")
    assert all(s.dtype == jnp.float32 for s in eng.ctx[ssm_moe.KDA])
    prompt = prompt_of(100, 7)
    toks, tops = await serve(eng, prompt, 24)
    await eng.stop()
    worst, mean = distance(ref, params, prompt, toks, tops)
    assert worst < 1.5 and mean < 0.3
    for control in ("delta_off", "state_zeroed"):
        assert distance(ref, params, prompt, toks, tops, control)[1] > 0.6


def test_a_lane_that_is_not_live_keeps_its_state_and_windows(setup):
    cfg, params, _ = setup
    B = 3
    rng = np.random.RandomState(0)
    ctx = jax.tree.map(lambda a: jnp.asarray(rng.randn(*a.shape), a.dtype),
                       llama.init_ctx(cfg, B, 128, jnp.float32))
    assert ssm_moe.stepped_kinds(cfg, ctx) == ("kda_conv_state", "kda_state")
    assert llama.row_kinds(ctx) == ("kv",)
    state = {n: ctx[n] for n in ssm_moe.stepped_kinds(cfg, ctx)}
    live = jnp.asarray([True, False, True])
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    for attn in (REFERENCE, DecodeAttention(PALLAS_INTERPRET)):
        _, new, logits, _ = ssm_moe.decode_step_impl(
            cfg, llama.serving_params(cfg, params), ctx,
            llama.init_ring(cfg, B, 2, jnp.float32),
            state, i32(5, 6, 7), i32(4, 4, 5), i32(3, 3, 4), jnp.int32(0),
            live, attn=attn)
        assert bool(jnp.isfinite(logits).all())
        for name in state:
            for old, now in zip(state[name], new[name]):
                # the dead lane and the scratch lane, bit for bit
                np.testing.assert_array_equal(now[1], old[1])
                np.testing.assert_array_equal(now[3], old[3])
                assert not np.array_equal(now[0], old[0])


def test_the_kernel_step_and_the_xla_step_give_one_decode(setup):
    cfg, params, _ = setup
    B = 2
    rng = np.random.RandomState(1)
    ctx = jax.tree.map(
        lambda a: jnp.asarray(0.1 * rng.randn(*a.shape), a.dtype),
        llama.init_ctx(cfg, B, 64, jnp.float32))
    state = {n: ctx[n] for n in ssm_moe.stepped_kinds(cfg, ctx)}
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    out = [ssm_moe.decode_step_impl(
        cfg, llama.serving_params(cfg, params), ctx,
        llama.init_ring(cfg, B, 2, jnp.float32), state,
        i32(5, 6), i32(9, 30), i32(8, 29), jnp.int32(0),
        jnp.asarray([True, True]), attn=attn)
        for attn in (REFERENCE, DecodeAttention(PALLAS_INTERPRET))]
    np.testing.assert_allclose(out[0][2], out[1][2], atol=1e-5)
    for a, b in zip(out[0][1][ssm_moe.KDA], out[1][1][ssm_moe.KDA]):
        np.testing.assert_allclose(a, b, atol=1e-5)


# ---------------------------------------------------------------------------
# routing and the share

def router_setup(setup, bias=None, x=None):
    cfg, params, ref = setup
    lp = dict(next(lp for lp in params["layers"] if "wr" in lp))
    if bias is not None:
        lp["bias"] = jnp.asarray(bias, jnp.float32)
    if x is None:
        x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.hidden_size))
    return cfg, lp, ref, x


def dense_weights(cfg, sel, w):
    return np.asarray(jnp.zeros((sel.shape[0], 16)).at[
        jnp.arange(sel.shape[0])[:, None], sel].set(w))


@pytest.mark.parametrize("case", ["random", "ties", "a-masked-group-wins"])
def test_group_limited_routing_equals_the_reference(setup, case):
    """Picks and weights of ``ssm_moe.route`` against the reference's
    ``combine_weights`` (which sorts, and takes the lowest index among
    equals)."""
    cfg, lp, ref, x = router_setup(setup)
    if case == "ties":
        # every score the same: groups 0 and 1 are kept and experts 0-3
        # picked, on both sides
        lp["wr"] = jnp.zeros_like(lp["wr"])
        lp["bias"] = jnp.zeros_like(lp["bias"])
    if case == "a-masked-group-wins":
        # expert 15 has the best score + bias of all, but its group's
        # second best is poor: the group is dropped and 15 is not picked
        lp["bias"] = jnp.zeros(16).at[15].set(2.0).at[12:15].set(-5.0)
    sel, w, here = ssm_moe.route(cfg, lp, x)
    got = dense_weights(cfg, sel, w)
    want = np.asarray(ref.combine_weights(ref.hyper(HF), lp, x))
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got.sum(-1), 2.5, rtol=1e-5)
    if case == "ties":
        assert sorted(np.asarray(sel[0])) == [0, 1, 2, 3]
        assert bool(here.all())
    if case == "a-masked-group-wins":
        assert not (np.asarray(sel) == 15).any()
        free = np.asarray(ref.combine_weights(
            ref.hyper(HF), lp, x, control="no_group_mask"))
        assert (free[:, 15] > 0).all()
    # a pick on a held expert (group 0) means group 0 was kept, and the
    # picks of a token lie in two groups
    assert ((np.asarray(sel) // 4 == 0).any(1) <= np.asarray(here)).all()
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(sel))


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        setup):
    """Four chips hold one group of four experts each: what each computes
    with ``ssm_moe._ffn`` (its routed part; the shared expert comes with
    every share, so it is counted once) adds up to the reference's layer
    over all 16 experts."""
    cfg, params, ref = setup
    rng = jax.random.PRNGKey(11)
    x = jax.random.normal(rng, (48, cfg.hidden_size))
    lp = dict(next(lp for lp in params["layers"] if "wr" in lp))
    full = {n: jax.random.normal(jax.random.fold_in(rng, i),
                                 (16,) + lp[n].shape[1:]) / 8
            for i, n in enumerate(("we_g", "we_u", "we_d"))}
    shared = np.asarray(ssm_moe._mlp(x, lp["ws_g"], lp["ws_u"], lp["ws_d"]))
    total = np.zeros_like(shared)
    for index in range(4):
        c = ModelConfig.tiny_kda_latent(
            dtype="float32",
            expert_share={"published_experts": 16, "of": 4, "index": index})
        part = dict(lp, **{n: w[4 * index:4 * index + 4]
                           for n, w in full.items()})
        y, stats = ssm_moe._ffn(c, part, x, None, ssm_moe.stats_zero(c))
        total += np.asarray(y) - shared
        assert int(stats[3]) == 48 * 4
    uncut = dict(HF, num_local_experts=16,
                 expert_share={"published_experts": 16, "of": 1, "index": 0})
    hp = ref.hyper(uncut)
    want = ref.routed(hp, dict(lp, **full), x) + ref.swiglu(
        x, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    # and one share is the reference's share
    np.testing.assert_allclose(
        np.asarray(ref.routed(ref.hyper(HF), dict(
            lp, **{n: w[:4] for n, w in full.items()}), x)),
        np.asarray(ssm_moe._ffn(
            cfg, dict(lp, **{n: w[:4] for n, w in full.items()}), x, None,
            ssm_moe.stats_zero(cfg))[0]) - shared, atol=2e-5)


# ---------------------------------------------------------------------------
# the configuration

def test_the_published_configuration_reads_as_the_issue_states():
    hf = published()
    c = ModelConfig.from_hf_dict(hf)
    d = ssm_moe.dims(c)
    assert (c.hidden_size, c.num_heads, c.intermediate_size,
            c.vocab_size) == (2560, 32, 6144, 19648)
    assert d["kinds"] == ("kda",) * 5 + ("latent_attention",) + (
        "kda",) * 5 + ("latent_attention",)
    assert (d["E"], d["held"], d["K"], d["groups"], d["kept"], d["first"],
            d["I_e"], d["I_s"], d["n_dense"]) == (512, 64, 8, 8, 4, 0, 768,
                                                  768, 2)
    assert (d["kda_heads"], d["kda_dim"], d["kda_W"]) == (32, 128, 4)
    assert c.mla_dict == {"q_lora_rank": None, "kv_lora_rank": 512,
                          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                          "v_head_dim": 128}
    # a lane: ten float32 states of [32, 128, 128] and three windows of
    # 3 x 4096 bfloat16 values each; two latent layers of 640 stored values
    assert ssm_moe.state_bytes(c, 2) == 10 * (2097152 + 73728) == 21708800
    assert ssm_moe.kv_row_bytes(c, 2) == 2560
    assert sorted(hf["reduced"]) == sorted([
        "num_hidden_layers", "vocab_size", "num_local_experts",
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"])
    assert sorted(hf["assumed"])[:8] == [f"A{i}" for i in range(1, 9)]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_state_leaves_are_float32_at_the_published_widths(dtype):
    c = ModelConfig.from_hf_dict(published())
    ctx = jax.eval_shape(
        lambda: llama.init_ctx(c, 48, 20480, jnp.dtype(dtype)))
    assert [tuple(s.shape) for s in ctx[ssm_moe.KDA]] == [
        (49, 32, 128, 128)] * 10
    assert all(s.dtype == jnp.float32 for s in ctx[ssm_moe.KDA])
    assert [tuple(s.shape) for s in ctx[ssm_moe.KDA_CONV]] == [
        (49, 3, 12288)] * 10
    assert tuple(ctx["kv"].shape) == (2, 1, 49, 20480, 640)
    assert ctx["kv"].dtype == jnp.dtype(dtype)


REFUSED = {
    "use_nGPT": True, "value_norm": True, "up_proj_norm": True,
    "scale_router_input": True, "use_kda_lora": True, "mtp_use_kda": True,
    "score_function": "softmax", "num_kv_heads_for_linear_attn": 8,
    "q_lora_rank": 16, "kda_safe_gate": False, "use_mla_nope": True,
    "expert_swiglu_limit_list": [0, 0, 0, 0, 0, 0, 4],
    "share_expert_swiglu_limit_list": [0, 0, 0, 0, 0, 5, 0],
    "expert_share": {"published_experts": 16, "of": 3, "index": 0},
    "gated_attention_proj_granularity_type": "channel_wise",
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_the_reader_refuses_what_the_program_does_not_build(key):
    name = {"expert_swiglu_limit_list": "clamp",
            "share_expert_swiglu_limit_list": "clamp",
            "num_kv_heads_for_linear_attn": "num_kv_heads_for_linear_attn",
            }.get(key, key)
    with pytest.raises(ValueError, match=name):
        ModelConfig.tiny_kda_latent(**{key: REFUSED[key]})


def test_a_missing_key_is_named_and_the_latent_reader_is_not_tried():
    d = dict(_TINY_KDA_LATENT)
    del d["kda_lower_bound"]
    with pytest.raises(ValueError, match="delta-rule.*kda_lower_bound"):
        ModelConfig.from_hf_dict(d)
    # a file of this type has kv_lora_rank: it must not fall into the
    # latent block's reader, which would ask for its routed keys
    c = ModelConfig.from_hf_dict(dict(_TINY_KDA_LATENT))
    assert c.hybrid is not None and c.routed is None
    assert llama.block_of(c) is ssm_moe
    with pytest.raises(ValueError, match="_from_hf_kda_latent"):
        ModelConfig.from_hf_dict({"model_type": "ling9"})


async def test_the_sliced_vocabulary_is_the_whole_vocabulary_here(setup):
    """The configuration's vocab_size IS the slice: the head, the sampler
    and the reported top tokens never name an id past it."""
    cfg, params, _ = setup
    assert params["head"].shape == (cfg.hidden_size, cfg.vocab_size)
    eng = engine(cfg, params)
    req = PreprocessedRequest(
        token_ids=prompt_of(20, 5), model="t",
        stop_conditions=StopConditions(max_tokens=12, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=1.0, top_k=50, seed=4),
        output_options=OutputOptions(logprobs=TOP))
    ids = []
    async for out in eng.generate(req):
        ids += out.token_ids + [p[0] for row in out.top_logprobs or []
                                for p in row]
    await eng.stop()
    assert ids and max(ids) < cfg.vocab_size
