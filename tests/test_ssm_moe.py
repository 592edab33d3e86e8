"""The state-space + attention hybrid with routed experts
(models/ssm_moe.py, ops/mamba2.py) against its plain reference
(benchmarks/references/ssm_moe.py), on seeded random weights at tiny
widths on the CPU: two periods of (mamba, mamba, attention), 8 experts
top 2 of which share 0 of 2 holds 4, a scan chunk of 8.

Every comparison is float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ in the
ORDER of float32 sums only (a chunked scan against the recurrence as
written, blocked against whole softmax, grouped against dense experts),
so log-probs agree to ~1e-6 and the tolerance is 1e-4. A state dropped at
a chunk boundary, padding let into the state or a multiplier left out
moves them by 1e-3 to 2 (the controls, below).
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
from dynamo_tpu.models import llama, moe, ssm_moe
from dynamo_tpu.models.config import _TINY_SSM_MOE, ModelConfig
from dynamo_tpu.ops import kda, mamba2
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
TOL = 1e-4
PS = 8
BUCKETS = (16, 32)
TOP = 5
LANES = 6   # a round's five counters ride home in a row this wide


def load_reference():
    path = os.path.join(REPO, "benchmarks", "references", "ssm_moe.py")
    spec = importlib.util.spec_from_file_location("ref_ssm_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


HF = dict(_TINY_SSM_MOE, engine={"prefill_buckets": list(BUCKETS)})


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny_ssm_moe(dtype="float32")
    return cfg, llama.init_params(cfg, 3), load_reference()


def engine(cfg, params, **kw):
    ecfg = EngineConfig(**{**dict(
        num_pages=16, page_size=PS, max_pages_per_seq=24,
        max_decode_slots=LANES, prefill_buckets=BUCKETS, flush_every=8,
        cache_dtype="float32", max_logprobs=TOP), **kw})
    return TpuEngine(cfg, ecfg, params=params, mesh_config=MeshConfig(tp=1))


async def serve(eng, prompt, n):
    """One request through the engine's normal path: its tokens and the
    top log-probs of every step."""
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
    """max |log-prob difference| over the engine's top tokens, every
    step, against the reference's full forward of prompt + tokens."""
    want = ref.logprobs(hf, params, list(prompt) + toks,
                        [len(prompt) - 1 + i for i in range(len(toks))],
                        control=control)
    worst = 0.0
    for i, row in enumerate(tops):
        ids = np.asarray([p[0] for p in row])
        got = np.asarray([p[1] for p in row])
        worst = max(worst, float(np.abs(got - want[i, ids]).max()))
    return worst


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).tolist()


# what each case sends: (prompt lengths sent together, decode steps)
SERVED = {
    # shorter than its 16 bucket, no multiple of the scan's chunk of 8
    "fresh-short-of-its-bucket": ([11], 48),
    # exactly a bucket, then decode across six round boundaries
    "fresh-whole-bucket": ([32], 48),
    # 32 + 13: a fresh and a continuing chunk, the last ragged
    "two-chunks": ([45], 20),
    # 32 + 32 + 7
    "three-chunks": ([71], 20),
    # prompts of one bucket arriving together: K = 2. Four of them, as in
    # tests/test_engine.py: under a loaded box the first can be admitted
    # alone (the driver's six workers: two prompts then prefilled one
    # after the other), and the rest still arrive as a group
    "batched-prefill": ([27, 19, 23, 30], 12),
}


@pytest.mark.parametrize("case", sorted(SERVED))
async def test_served_path_equals_the_reference(setup, case):
    """Prefill (fresh, continuing, padded, batched) and decode through
    the fused rounds, against the reference, on the log-probs the engine
    itself reports."""
    cfg, params, ref = setup
    lens, n = SERVED[case]
    eng = engine(cfg, params)
    prompts = [prompt_of(m, 10 + i) for i, m in enumerate(lens)]
    got = await asyncio.gather(*(serve(eng, p, n) for p in prompts))
    for p, (toks, tops) in zip(prompts, got):
        assert distance(ref, params, p, toks, tops) < TOL
    if case == "batched-prefill":
        assert eng.batch_prefills >= 1
    if case == "three-chunks":
        assert eng.dispatch_counts["prefill"] == 3
    assert eng.allocator.hit_blocks == 0 and not eng._seal_queue
    await eng.stop()


async def test_looped_expert_rows_serve_the_reference_and_are_counted(
        monkeypatch, setup):
    """The served path with the expert layers' row movements looped over
    toy blocks of 8 (a config of its own, so that no cached program of
    another test is met): a fresh 32-token chunk and a ragged continuing
    one still equal the reference, and each prefill program's count of
    the rows it moved lands in telemetry beside the rows it sorted,
    without a fetch the host waits for."""
    _, _, ref = setup
    monkeypatch.setattr(moe, "MOVE_ROWS", 8)
    monkeypatch.setattr(moe, "MOVE_STRAIGHT_ROWS", 0)
    hf = dict(HF, rms_norm_eps=1.5e-5)
    cfg = ModelConfig.tiny_ssm_moe(dtype="float32", rms_norm_eps=1.5e-5)
    params = llama.init_params(cfg, 3)
    eng = engine(cfg, params)
    prompt = prompt_of(45, 10)
    toks, tops = await serve(eng, prompt, 12)
    assert distance(ref, params, prompt, toks, tops, hf=hf) < TOL
    assert eng.dispatch_counts["prefill"] == 2 and not eng._moe_rows
    snap = eng.telemetry.snapshot()
    rows_sorted = snap["dynamo_moe_prefill_rows_sorted"]
    rows_moved = snap["dynamo_moe_prefill_rows_moved"]
    assert rows_sorted["count"] == rows_moved["count"] == 2
    # 32 + 16 positions x 2 picks x 6 expert layers
    assert rows_sorted["sum"] == ssm_moe.prefill_rows_sorted(
        cfg, 32) + ssm_moe.prefill_rows_sorted(cfg, 16) == 48 * 2 * 6
    # about half of 45 tokens' picks are held here, in blocks of 8
    assert 0 < rows_moved["sum"] < rows_sorted["sum"]
    assert rows_moved["sum"] % 8 == 0
    await eng.stop()
    # under the real rule these programs run straight-line, uncounted
    monkeypatch.undo()
    assert ssm_moe.prefill_rows_sorted(cfg, 32) == 0


async def test_live_row_blocks_serve_the_reference_and_are_counted(
        monkeypatch, setup):
    """The served path with a chunk's halves and scans looped over toy row
    blocks of 8 (a config of its own, so that no cached program of another
    test is met): a 37-token prompt runs a full 32-token chunk (four live
    blocks) and a continuing 5-token one in the 16 bucket (one live block
    of two), still equals the reference, and the padded-positions counter
    reads the 40 positions the programs ran, not the 48 of the buckets."""
    _, _, ref = setup
    monkeypatch.setattr(ssm_moe, "LIVE_ROW_BLOCK", 8)
    monkeypatch.setattr(ssm_moe, "SCAN_ROW_BLOCK", 8)
    hf = dict(HF, rms_norm_eps=1.7e-5)
    cfg = ModelConfig.tiny_ssm_moe(dtype="float32", rms_norm_eps=1.7e-5)
    assert [ssm_moe.live_row_block(cfg, T) for T in BUCKETS] == [8, 8]
    params = llama.init_params(cfg, 4)
    eng = engine(cfg, params)
    prompt = prompt_of(37, 11)
    toks, tops = await serve(eng, prompt, 12)
    assert distance(ref, params, prompt, toks, tops, hf=hf) < TOL
    assert eng.dispatch_counts["prefill"] == 2
    snap = eng.telemetry.snapshot()
    assert snap["dynamo_engine_prefill_tokens"]["sum"] == 37
    assert snap["dynamo_engine_prefill_padded_tokens"]["sum"] == 32 + 8
    await eng.stop()
    # under the real rule these buckets run straight-line
    monkeypatch.undo()
    assert llama.prefill_positions_run(cfg, 16, [32], [37]) == 16


async def test_chunks_interleave_with_other_lanes_decode_and_lanes_are_reused(
        setup):
    """A prompt prefilled in three chunks WHILE another lane decodes
    between the chunks (its state must not be touched by those rounds,
    nor theirs by its chunks), then a lane reused by a later request
    after its first tenant finished mid-round (5 steps of a round of 8:
    the lane kept stepping on garbage until the patch)."""
    cfg, params, ref = setup
    eng = engine(cfg, params)
    first, long, later = prompt_of(9, 1), prompt_of(71, 2), prompt_of(21, 3)
    running = asyncio.ensure_future(serve(eng, first, 60))
    await asyncio.sleep(0.5)              # lane 0 is decoding by now
    rounds_before = eng.dispatch_counts["round"] + eng.dispatch_counts[
        "round_seal"]
    chunked = await serve(eng, long, 13)  # 3 chunks, rounds between them
    assert eng.dispatch_counts["round"] + eng.dispatch_counts[
        "round_seal"] > rounds_before
    toks, tops = await running
    assert distance(ref, params, first, toks, tops) < TOL
    assert distance(ref, params, long, *chunked) < TOL
    short = await serve(eng, later, 5)    # finishes mid-round
    again = await serve(eng, prompt_of(30, 4), 11)   # the same lane
    assert distance(ref, params, later, *short) < TOL
    assert distance(ref, params, prompt_of(30, 4), *again) < TOL
    snap = eng.telemetry.snapshot()
    assert snap["dynamo_ssm_state_bytes"]["sum"] == ssm_moe.state_bytes(
        cfg, 4)
    assert snap["dynamo_kv_row_bytes"]["sum"] == ssm_moe.kv_row_bytes(cfg, 4)
    # the routing counters count the HELD experts' picks, beside all picks
    assert 0 < snap["dynamo_moe_tokens_routed"]["sum"] < snap[
        "dynamo_moe_picks_routed"]["sum"]
    # the program's own count: the live lanes' states a step, four
    # Mamba-2 layers, a row a consumed round
    stepped = snap["dynamo_ssm_state_rows_stepped"]
    lane_steps = snap["dynamo_engine_round_live_lane_steps"]
    assert stepped["count"] == lane_steps["count"] > 0
    assert stepped["sum"] == lane_steps["sum"] * 4
    assert stepped["sum"] < stepped["count"] * 8 * LANES * 4   # steps, lanes
    assert snap["dynamo_kda_state_rows_stepped"]["count"] == 0
    await eng.stop()


def step_inputs(cfg, B, seed):
    rng = np.random.RandomState(seed)
    ctx = llama.init_ctx(cfg, B, 32, jnp.float32)
    state = {n: [jnp.asarray(rng.randn(*a.shape), a.dtype) for a in ctx[n]]
             for n in llama.state_kinds(ctx)}
    return ctx, state, llama.init_ring(cfg, B, 2, jnp.float32)


@pytest.mark.parametrize("attn", [REFERENCE, DecodeAttention(PALLAS_INTERPRET)],
                         ids=["xla-step", "step-kernel"])
def test_a_lane_that_is_not_live_keeps_its_state_bit_for_bit(setup, attn):
    cfg, params, _ = setup
    B = 3
    ctx, state, ring = step_inputs(cfg, B, 0)
    live = jnp.asarray([True, False, True])
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    _, new, logits, stats = ssm_moe.decode_step_impl(
        cfg, params, ctx, ring, state,
        i32(5, 6, 7), i32(3, 3, 3), i32(2, 2, 2), jnp.int32(0), live,
        attn=attn)
    for name in state:
        for old, upd in zip(state[name], new[name]):
            np.testing.assert_array_equal(upd[1], old[1])   # not live
            np.testing.assert_array_equal(upd[B], old[B])   # scratch
            assert not np.array_equal(upd[0], old[0])
    d = ssm_moe.dims(cfg)
    assert int(stats[3]) == 2 * d["K"] * cfg.num_layers
    assert 0 < int(stats[1]) <= int(stats[3])
    # two live lanes x four Mamba-2 layers, counted by the program
    assert int(stats[-1]) == 2 * d["n_ssm"] == 8
    assert np.isfinite(np.asarray(logits)).all()


def test_the_kernel_step_and_the_xla_step_give_one_decode(setup):
    """Decode under the Mamba-2 step kernel (interpreted) over the work
    list against the XLA step over every lane."""
    cfg, params, _ = setup
    ctx, state, ring = step_inputs(cfg, 3, 1)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    out = [ssm_moe.decode_step_impl(
        cfg, params, ctx, ring, state, i32(5, 6, 7), i32(9, 30, 4),
        i32(8, 29, 3), jnp.int32(0), jnp.asarray([True, True, False]),
        attn=attn)
        for attn in (REFERENCE, DecodeAttention(PALLAS_INTERPRET))]
    np.testing.assert_allclose(out[0][2][:2], out[1][2][:2], atol=1e-5)
    for a, b in zip(out[0][1][ssm_moe.SSM], out[1][1][ssm_moe.SSM]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_array_equal(out[0][3], out[1][3])


STEP_LIVE = {"holes": [True, False, True, True], "all-live": [True] * 4,
             "none-live": [False] * 4, "the-last-lane": [False] * 3 + [True]}


@pytest.mark.parametrize("live", sorted(STEP_LIVE))
@pytest.mark.parametrize("H,P,N", [(4, 8, 16), (6, 8, 16), (32, 64, 128)],
                         ids=["toy", "toy-heads-of-two", "a-published-tile"])
def test_the_step_kernel_equals_the_step(H, P, N, live):
    """The Pallas kernel (interpreted) over a work list against
    ``mamba2.scan_step`` on the lanes the list holds; every other lane and
    the scratch lane keep their state bit for bit, and their ``y`` is 0.
    ``[32, 64, 128]`` is one grid step's state block at the published
    widths (sixteen blocks of two heads); the toys hold one block of four
    heads, and three grid steps of one block of two."""
    live = STEP_LIVE[live]
    rng = np.random.RandomState(7)
    f32 = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    x, Bm, Cm, S = f32(4, H, P), f32(4, N), f32(4, N), f32(5, H, P, N)
    dt = jnp.abs(f32(4, H)) * 0.3
    A = -jnp.abs(f32(H)) - 0.5
    y, S_new = mamba2.scan_step(x, dt, A, Bm, Cm, S[:4])
    lanes, n_live = kda.work_list(jnp.asarray(live))
    assert int(n_live[0]) == sum(live)
    y2, S2 = mamba2.scan_step_pallas(x, dt, A, Bm, Cm, S, lanes, n_live,
                                     interpret=True)
    for lane, on in enumerate(live):
        if on:
            np.testing.assert_allclose(y2[lane], y[lane], rtol=1e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(S2[lane], S_new[lane], rtol=1e-6,
                                       atol=1e-6)
        else:
            np.testing.assert_array_equal(S2[lane], S[lane])
            assert not np.asarray(y2[lane]).any()
    np.testing.assert_array_equal(S2[4], S[4])


def test_chunked_scan_equals_the_recurrence_and_masks_its_padding():
    """ops/mamba2.py alone: the chunked form over a padded bucket from a
    non-zero state equals one-step updates over the real positions."""
    rng = np.random.RandomState(2)
    T, n_real, H, P, N = 24, 19, 4, 8, 16
    x = jnp.asarray(rng.randn(T, H, P), jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(T, H)) * 0.3, jnp.float32)
    dt = jnp.where(jnp.arange(T)[:, None] < n_real, dt, 0.0)
    A = -jnp.asarray(np.abs(rng.randn(H)) + 0.5, jnp.float32)
    Bm, Cm = (jnp.asarray(rng.randn(T, N), jnp.float32) for _ in range(2))
    S0 = jnp.asarray(rng.randn(H, P, N), jnp.float32)
    y, S = mamba2.chunk_scan(x, dt, A, Bm, Cm, S0, chunk=8)
    want_S, want_y = S0[None], []
    for t in range(n_real):
        yt, want_S = mamba2.scan_step(x[t][None], dt[t][None], A,
                                      Bm[t][None], Cm[t][None], want_S)
        want_y.append(yt[0])
    np.testing.assert_allclose(S, want_S[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y[:n_real], jnp.stack(want_y), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("tokens,block", [(13, 0), (16, 4)],
                         ids=["straight-line", "looped"])
def test_the_shares_and_the_shared_mlp_once_add_up_to_the_uncut_layer(
        monkeypatch, setup, tokens, block):
    """One test ties the share to the model: the program's expert layer
    as share 0 of 2 and as share 1 of 2, the shared MLP counted once, is
    the reference's UNCUT layer (all 8 experts held) — with the layer's
    row movements straight-line, and looped over blocks of 4 rows."""
    cfg, _, ref = setup
    if block:
        monkeypatch.setattr(moe, "MOVE_ROWS", block)
        monkeypatch.setattr(moe, "MOVE_STRAIGHT_ROWS", 0)
    assert moe.move_block(tokens, 2, True) == block
    uncut_hf = dict(_TINY_SSM_MOE, num_local_experts=8, expert_share=None)
    whole = llama.init_params(
        ModelConfig.from_hf_dict(dict(uncut_hf, dtype="float32")), 5)
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), whole["layers"][0])
    x = jnp.asarray(np.random.RandomState(7).randn(tokens, cfg.hidden_size),
                    jnp.float32)
    hp = ref.hyper(uncut_hf)
    want = ref.routed(hp, lp, x) + ref.shared(lp, x)
    total = -ref.shared(lp, x)            # it rides in both shares
    picks = 0
    for index in (0, 1):
        share = ModelConfig.tiny_ssm_moe(
            dtype="float32",
            expert_share={"published_experts": 8, "of": 2, "index": index})
        mine = dict(lp, **{k: lp[k][4 * index:4 * index + 4]
                           for k in ("we_g", "we_u", "we_d")})
        y, stats = ssm_moe._ffn(share, mine, x, None,
                                ssm_moe.stats_zero(share))
        total = total + y
        picks += int(stats[1])
        assert int(stats[3]) == tokens * 2
        # and the reference given the same share computes the same part
        part = ref.routed(ref.hyper(dict(_TINY_SSM_MOE, expert_share={
            "published_experts": 8, "of": 2, "index": index})), mine, x)
        np.testing.assert_allclose(y - ref.shared(lp, x), part, rtol=1e-4,
                                   atol=1e-4)
    assert picks == tokens * 2               # every pick landed on one share
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("control", load_reference().CONTROLS_REQUIRED)
async def test_the_references_controls_stand_far_from_the_served_path(
        setup, control):
    """Each fault the chip's check must catch, computed by the reference
    (``control=``), stands over a thousand times further from the served
    path than the sound reference does (5e-7 here; the weakest, the state
    dropped at a boundary of this toy's short memories, 1.1e-3): a prompt
    of two chunks (the boundary at 32), its last chunk padded."""
    cfg, params, ref = setup
    eng = engine(cfg, params)
    prompt = prompt_of(45, 20)
    toks, tops = await serve(eng, prompt, 12)
    await eng.stop()
    assert distance(ref, params, prompt, toks, tops) < TOL
    assert distance(ref, params, prompt, toks, tops) < TOL / 10
    assert distance(ref, params, prompt, toks, tops, control) > 5 * TOL


def test_from_hf_dict_reads_the_published_keys_and_refuses_the_unbuilt():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite4h-ep2-d10.json")) as f:
        published = json.load(f)
    c = ModelConfig.from_hf_dict(published)
    d = ssm_moe.dims(c)
    assert (d["nh"], d["P"], d["N"], d["W"], d["inner"], d["conv"],
            d["chunk"]) == (128, 64, 128, 4, 8192, 8448, 256)
    assert (d["E"], d["held"], d["K"], d["I_e"], d["I_s"], d["first"]) == (
        72, 36, 10, 768, 1536, 0)
    assert (d["n_ssm"], d["n_attn"], d["kinds"][5]) == (9, 1, "attention")
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.vocab_size,
            c.hidden_size, c.num_layers) == (32, 8, 128, 100352, 4096, 10)
    assert c.tie_word_embeddings and c.mla is None and c.routed is None
    assert ssm_moe.state_bytes(c, 2) == 9 * (128 * 64 * 128 * 4
                                             + 3 * 8448 * 2)
    assert ssm_moe.kv_row_bytes(c, 2) == 4096
    for key, value in (
            ("position_embedding_type", "rope"), ("mamba_n_groups", 7),
            ("attention_bias", True), ("mamba_proj_bias", True),
            ("hidden_act", "gelu"), ("normalization_function", "layernorm"),
            ("rope_scaling", {"type": "yarn", "factor": 4}),
            ("tie_word_embeddings", False), ("mamba_conv_bias", False),
            ("layer_types", ["mamba"] * 9 + ["window"]),
            ("layer_types", ["mamba"] * 9),
            # a share is a whole-number split of the published experts
            ("num_local_experts", 35),
            ("expert_share", {"published_experts": 72, "of": 3, "index": 0}),
            ("expert_share", {"published_experts": 72, "of": 2, "index": 2}),
            ("expert_share", {"published_experts": 72, "of": 2})):
        with pytest.raises(ValueError, match="state-space hybrid block"):
            ModelConfig.from_hf_dict(dict(published, **{key: value}))
    with pytest.raises(ValueError, match="missing"):
        ModelConfig.from_hf_dict(
            {k: v for k, v in published.items() if k != "mamba_d_state"})
    with pytest.raises(ValueError, match="refusing to read it as a Llama"):
        ModelConfig.from_hf_dict(dict(published, model_type="mistral"))


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "float32"])
def test_the_ssm_state_is_float32_whatever_the_cache_dtype(cache_dtype):
    """The configuration states a float32 SSM state and the reference
    check does not see a bfloat16 one (``assumed``: "what the check does
    not hold"): the leaves are held to it HERE, at the published widths,
    with the bytes the counter ``dynamo_ssm_state_bytes`` reports."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite4h-ep2-d10.json")) as f:
        c = ModelConfig.from_hf_dict(json.load(f))
    dtype = jnp.dtype(cache_dtype)
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, 32, 8192, dtype))
    assert [(a.shape, a.dtype) for a in ctx[ssm_moe.SSM]] == [
        ((33, 128, 64, 128), jnp.float32)] * 9
    assert [(a.shape, a.dtype) for a in ctx[ssm_moe.CONV]] == [
        ((33, 3, 8448), dtype)] * 9
    a_lane = sum(a.size * a.dtype.itemsize // 33
                 for kind in (ssm_moe.SSM, ssm_moe.CONV) for a in ctx[kind])
    assert a_lane == ssm_moe.state_bytes(c, dtype.itemsize)
    assert ssm_moe.state_bytes(c, 2) == 38204928


@pytest.mark.parametrize("plane,kw", [
    ("int8 KV", {"kv_quant": "int8"}),
    ("offload", {"host_offload_pages": 8}),
    ("spec/", {"speculative": "ngram"}),
    ("LoRA", {"lora_adapters": 2}),
    ("sequence-parallel", {"sp_prefill_threshold": 64}),
])
def test_a_plane_that_cannot_carry_a_recurrent_state_refuses_at_start(
        setup, plane, kw):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="recurrent"):
        engine(cfg, params, **kw)


def test_an_engine_of_fewer_lanes_than_counters_starts(setup):
    """No plane: the five counters ride home behind the round's tokens in
    rows ``max_decode_slots`` wide, as many as they fill (until PR 64 four
    lanes were refused; tests/test_mla_moe.py serves through two rows)."""
    cfg, params, _ = setup
    assert len(llama.stats_layout(cfg)) == 5
    assert engine(cfg, params, max_decode_slots=4).ecfg.max_decode_slots == 4


def test_the_other_planes_and_meshes_refuse_a_recurrent_state(setup):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="recurrent"):
        TpuEngine(cfg, EngineConfig(
            num_pages=16, page_size=PS, max_pages_per_seq=4,
            max_decode_slots=4, prefill_buckets=BUCKETS,
            cache_dtype="float32"), params=params,
            mesh_config=MeshConfig(tp=1), on_dispatch=lambda *a: None)
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="recurrent"):
        eng._refuse_latent_transfer()
    assert not eng.allocator.enable_prefix_caching   # bypassed, by name
    z = jnp.zeros(1, jnp.int32)
    ctx1 = llama.init_ctx(cfg, 1, 32, jnp.float32)
    for call in (
        lambda: llama.decode_step_impl(
            cfg, params, ctx1, llama.init_ring(cfg, 1, 1, jnp.float32),
            z, z, z, jnp.int32(0), attn=REFERENCE),
        lambda: llama.batch_score_impl(cfg, params, ctx1, z[None], z, z, z,
                                       32),
        lambda: llama.write_ctx_span(ctx1, jnp.int32(0), {}),
        lambda: llama.init_ctx(cfg, 1, 32, kv_quant="int8"),
    ):
        with pytest.raises(ValueError, match="recurrent"):
            call()
    from dynamo_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    for axes in ({"tp": 2}, {"ep": 2}):
        with pytest.raises(ValueError, match="not sharded over"):
            llama.param_shardings(
                cfg, make_mesh(MeshConfig(**axes), jax.devices()[:2]))


def test_the_movers_pass_over_the_recurrent_leaves(setup):
    """``row_kinds`` does not see them; seal and flush move rows only and
    a page load hands them back untouched."""
    cfg, _, _ = setup
    ctx = jax.tree.map(lambda a: a + 3, llama.init_ctx(cfg, 2, 32,
                                                       jnp.float32))
    assert llama.row_kinds(ctx) == ("k", "v")
    assert llama.state_kinds(ctx) == ("conv_state", "ssm_state")
    cache = llama.init_cache(cfg, 4, PS, jnp.float32)
    z = jnp.zeros(1, jnp.int32)
    sealed = llama.seal_blocks(cache, ctx, z, z, z + 1, page_size=PS)
    assert set(sealed) == {"k", "v"}
    np.testing.assert_array_equal(sealed["k"][:, :, 1], 3.0)
    loaded = llama.load_ctx_pages(
        jax.tree.map(jnp.copy, ctx), sealed, jnp.int32(1), z + 1)
    for name in llama.state_kinds(ctx):
        for a, b in zip(loaded[name], ctx[name]):
            np.testing.assert_array_equal(a, b)
