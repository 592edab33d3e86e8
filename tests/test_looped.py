"""The LOOPED dense decoder (``model_type: "ouro"``: models/llama.py's dense
decoder run ``total_ut_steps`` times over the same weights, a K/V plane a
(step, layer), sandwich norms, the final norm and the exit gate after every
pass) against its plain reference (benchmarks/references/looped.py), on
seeded random weights at ``ModelConfig.tiny_looped`` widths on the CPU: 3
weight layers x 4 passes = 12 cache planes, 4 query heads on 2 K/V heads
of 16.

Comparisons are float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ in the ORDER
of float32 sums (blocked attention, the ring beside the region), so
log-probs agree to ~1e-5 and the tolerance is 2e-4; the faults the controls
inject move them by far more.
"""
import asyncio
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.kv_transfer import KvCacheLayout
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import REFERENCE
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.telemetry import metrics as tmetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
BUCKETS = (16, 32)
TOP = 5
LANES = 3       # fewer than the counter row's four columns: two rows ride
PAGE = 8
# the toy model as a published file would state it
HF = {
    "model_type": "ouro", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "total_ut_steps": 4,
    "early_exit_threshold": 1, "layer_types": ["full_attention"] * 3,
    "max_window_layers": 3, "use_sliding_window": False,
    "sliding_window": None, "tie_word_embeddings": False,
}
STEPS, LAYERS = 4, 3
CELL = "ouro-2p6b-ut4"


def load(kind, name):
    path = os.path.join(REPO, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           CELL + ".json")) as f:
        return json.load(f)


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).tolist()


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(ModelConfig.from_hf_dict(HF), dtype="float32")
    return cfg, llama.init_params(cfg, 3), load("references", "looped")


def test_the_reader_sets_the_loop_and_counts_the_weights_once(setup):
    cfg, params, _ = setup
    assert cfg == ModelConfig.tiny_looped(dtype="float32")
    assert (cfg.loop_steps, cfg.sandwich_norms, cfg.exit_gate) == (4, 1, 1)
    assert cfg.looped and cfg.cache_planes == STEPS * LAYERS
    assert cfg.num_params() == sum(
        x.size for x in jax.tree.leaves(params))
    # the published file: 2.668 B parameters held once, 192 planes
    big = ModelConfig.from_hf_dict(published())
    assert (big.num_layers, big.loop_steps, big.cache_planes) == (48, 4, 192)
    assert big.num_params() == 48 * 51_388_416 + 201_326_592 + 4097
    plain = ModelConfig.tiny()
    assert not plain.looped and plain.cache_planes == plain.num_layers


# ---------------------------------------------------------------------------
# the served path against the reference: ONE engine, every case through it

SERVED = {
    # one padded bucket; 12 decode steps are three flushes of 4 into the
    # region's twelve planes
    "one-chunk": ([21], 12),
    # 32 + 13: the continuing chunk's attention at (t, l) reads plane
    # t * L + l's prior rows
    "two-chunks": ([45], 8),
    # prompts arriving together: a batched prefill, then lanes of
    # different lengths in one round
    "a-batch": ([30, 6, 30], 8),
}


@pytest.fixture(scope="module")
def served(setup):
    """{case: [(prompt, tokens, top log-probs) a prompt]} from one engine
    (prefill fresh, continuing, padded, batched; decode through ring,
    region and the fused rounds; then a prompt that shares four sealed
    pages with an earlier one)."""
    cfg, params, _ = setup
    eng = TpuEngine(cfg, EngineConfig(
        num_pages=32, page_size=PAGE, max_pages_per_seq=16,
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

    def sums():
        return {name: h["sum"] for name, h in
                eng.telemetry.snapshot().items()}

    async def every_case():
        out = {}
        for case, (lens, n) in SERVED.items():
            out[case] = await asyncio.gather(*(
                serve(prompt_of(m, 10 + i), n) for i, m in enumerate(lens)))
        # the prefix pool's round trip: the two-chunk prompt's first four
        # pages are sealed; a prompt that shares them loads 12 planes a page
        before = sums()[tmetrics.PREFILL_MATCHED[0]]
        shared = prompt_of(45, 10)[:4 * PAGE] + prompt_of(9, 77)
        out["shared-prefix"] = [await serve(shared, 8)]
        out["matched"] = sums()[tmetrics.PREFILL_MATCHED[0]] - before
        out["metrics"] = sums()
        out["counts"] = {name: h["count"] for name, h in
                         eng.telemetry.snapshot().items()}
        out["page_bytes_a_token"] = sum(
            x.nbytes for x in jax.tree.leaves(eng.cache)) / (32 * PAGE)
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


@pytest.mark.parametrize("case", sorted(SERVED) + ["shared-prefix"])
def test_served_path_equals_the_reference(setup, served, case):
    _, params, ref = setup
    for prompt, toks, tops in served[case]:
        assert distance(ref, params, prompt, toks, tops)[0] < TOL
    if case == "shared-prefix":
        assert served["matched"] == 4 * PAGE


def test_the_served_rounds_count_the_passes_and_the_exit_cdfs(served):
    """Four columns ride home behind the tokens of a three-lane round (two
    rows): every consumed round says its tokens ran all four passes, and
    the live lanes' mean exit CDF after passes 0, 1, 2 rises towards 1."""
    m, n = served["metrics"], served["counts"]
    rounds = n[tmetrics.LOOP_STEPS_RUN[0]]
    assert rounds > 0 and m[tmetrics.LOOP_STEPS_RUN[0]] == STEPS * rounds
    cdf = [m[name] / n[name] for name, _ in tmetrics.LOOP_EXIT_CDF[:3]]
    assert all(n[name] == rounds for name, _ in tmetrics.LOOP_EXIT_CDF[:3])
    assert 0 < cdf[0] < cdf[1] < cdf[2] < 1


CONTROLS = ("one_pass_less", "norm_last_only", "no_sandwich",
            "plane_of_step_0", "fp8", "kv_fp8", "step_norm_fp8")


def test_every_control_is_named_and_stated():
    ref = load("references", "looped")
    assert set(ref.CONTROLS_REQUIRED + ref.CONTROLS_NAMED) == set(CONTROLS)
    assert "fp8" in ref.CONTROLS_REQUIRED
    for name in CONTROLS:
        assert f'``"{name}"``' in ref.__doc__, name


@pytest.mark.parametrize("control", CONTROLS)
def test_a_faulty_program_stands_ten_tolerances_off(setup, served, control):
    """What each control computes (three passes, the step's norm after the
    last pass only, no sandwich norms, every pass over pass 0's keys and
    values, 8-bit operands, 8-bit K/V, the step's norm on 8-bit rows) is
    not what the program served."""
    _, params, ref = setup
    (prompt, toks, tops), = served["two-chunks"]
    assert distance(ref, params, prompt, toks, tops, control)[0] > 10 * TOL


# ---------------------------------------------------------------------------
# the programs themselves: planes, chunks, the pool's round trip, the gate

T_PROMPT = 29


@pytest.fixture(scope="module")
def one_chunk(setup):
    """A 29-token prompt prefilled as ONE chunk (bucket 32) into lane 1 of
    a 3-lane region, beside the reference's whole forward."""
    cfg, params, ref = setup
    toks = prompt_of(T_PROMPT, 5)
    ctx = llama.init_ctx(cfg, 3, 64, jnp.float32)
    padded = jnp.asarray(toks + [0] * (32 - T_PROMPT), jnp.int32)
    ctx, logits = llama.prefill(
        cfg, params, ctx, padded, jnp.int32(1), jnp.int32(0),
        jnp.int32(T_PROMPT), fresh=True)
    want = ref.forward(HF, params, toks, keep_keys=True)
    return toks, ctx, np.asarray(logits), want


@pytest.mark.parametrize("step,layer", [(t, l) for t in range(STEPS)
                                        for l in range(LAYERS)])
def test_plane_t_L_plus_l_holds_step_t_layer_l_keys(one_chunk, step, layer):
    """(b) The K rows in plane ``t * L + l`` after a prefill are the
    reference's step-t layer-l rotated keys, for every (t, l)."""
    _, ctx, _, want = one_chunk
    plane = step * LAYERS + layer
    got = np.asarray(ctx["k"][plane, :, 1, :T_PROMPT])      # [kvh, T, hd]
    np.testing.assert_allclose(
        got, np.asarray(want["keys"][plane]).transpose(1, 0, 2), atol=2e-5)
    assert not np.asarray(ctx["k"][plane, :, 0]).any()     # lane 0 untouched


def test_prefill_logits_equal_the_references_head(setup, one_chunk):
    cfg, params, ref = setup
    toks, _, logits, _ = one_chunk
    want = ref.logprobs(HF, params, toks, [T_PROMPT - 1])[0]
    got = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("form", ["continuing", "batched"])
def test_a_continuing_chunk_and_a_batched_prefill_equal_one_chunk(
        setup, one_chunk, form):
    """(c) 16 + 13 tokens as two chunks (the second reads every plane's
    prior rows), and the same prompt as one lane of a batched prefill
    beside a shorter one: the same twelve planes of rows and the same
    logits as the one-chunk prefill."""
    cfg, params, _ = setup
    toks, ctx1, logits1, _ = one_chunk
    ctx = llama.init_ctx(cfg, 3, 64, jnp.float32)
    if form == "continuing":
        ctx, _ = llama.prefill(
            cfg, params, ctx, jnp.asarray(toks[:16], jnp.int32),
            jnp.int32(1), jnp.int32(0), jnp.int32(16), fresh=True)
        tail = jnp.asarray(toks[16:] + [0] * 3, jnp.int32)
        ctx, logits = llama.prefill(
            cfg, params, ctx, tail, jnp.int32(1), jnp.int32(16),
            jnp.int32(T_PROMPT))
    else:
        both = jnp.asarray([toks + [0] * 3, prompt_of(7, 6) + [0] * 25],
                           jnp.int32)
        ctx, logits = llama.batch_prefill(
            cfg, params, ctx, both, jnp.asarray([1, 0], jnp.int32),
            jnp.zeros(2, jnp.int32), jnp.asarray([T_PROMPT, 7], jnp.int32),
            0)
        logits = logits[0]
    np.testing.assert_allclose(np.asarray(logits), logits1, atol=TOL)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(ctx[name][:, :, 1, :T_PROMPT]),
            np.asarray(ctx1[name][:, :, 1, :T_PROMPT]), atol=2e-5)


def test_sealed_pages_loaded_into_another_lane_resume_the_prompt(
        setup, one_chunk):
    """(d) seal -> pool -> ``load_ctx_pages``: three pages of the prompt
    (12 planes a page) sealed from lane 1 and loaded into lane 2, the last
    five tokens prefilled there as a continuing chunk: the logits of a
    fresh prefill."""
    cfg, params, _ = setup
    toks, ctx, logits1, _ = one_chunk
    ctx = jax.tree.map(jnp.copy, ctx)      # the movers donate theirs
    pool = llama.init_cache(cfg, 8, PAGE, jnp.float32)
    assert pool["k"].shape[0] == STEPS * LAYERS
    pages = jnp.asarray([3, 5, 2, 0], jnp.int32)        # the last: padding
    pool = llama.seal_blocks(
        pool, ctx, jnp.asarray([1, 1, 1, 3], jnp.int32),
        jnp.asarray([0, 8, 16, 0], jnp.int32), pages, page_size=PAGE)
    ctx = llama.load_ctx_pages(ctx, pool, jnp.int32(2), pages)
    tail = jnp.asarray(toks[24:] + [0] * 11, jnp.int32)
    ctx, logits = llama.prefill(
        cfg, params, ctx, tail, jnp.int32(2), jnp.int32(24),
        jnp.int32(T_PROMPT))
    np.testing.assert_allclose(np.asarray(logits), logits1, atol=TOL)


def test_a_decode_steps_exit_cdfs_equal_the_references(setup, one_chunk):
    """(a) The round's step through ring and region: the new position's
    logits and its three exit CDFs against the reference's forward of the
    prompt + one token; the counter row carries the passes and the live
    lane's CDFs as float32 bits, a dead lane's left out of the mean."""
    cfg, params, ref = setup
    toks, ctx, logits1, _ = one_chunk
    nxt = int(np.argmax(logits1))
    want = ref.forward(HF, params, toks + [nxt])
    ring = llama.init_ring(cfg, 2, 4, jnp.float32)
    # lane 0 dead (its device length keeps counting), lane 1 the prompt's
    ring, _, logits, stats = llama.round_step(
        cfg, params, ctx, ring, {}, jnp.asarray([7, nxt], jnp.int32),
        jnp.asarray([3, T_PROMPT + 1], jnp.int32),
        jnp.asarray([2, T_PROMPT], jnp.int32), jnp.int32(0),
        jnp.asarray([False, True]), None, llama.stats_zero(cfg),
        attn=REFERENCE)
    head = ref.logprobs(HF, params, toks + [nxt], [T_PROMPT])[0]
    np.testing.assert_allclose(
        np.asarray(jax.nn.log_softmax(logits[1])), head, atol=TOL)
    assert int(stats[0]) == STEPS
    cdfs = np.asarray(stats[1:]).view(np.float32)
    np.testing.assert_allclose(
        cdfs, np.asarray(want["cdfs"])[:, T_PROMPT], atol=2e-5)
    assert 0 < cdfs[0] < cdfs[1] < cdfs[2] < 1


# ---------------------------------------------------------------------------
# what is refused, by name


def test_a_threshold_under_one_is_refused_by_name():
    with pytest.raises(ValueError, match="early_exit_threshold=0.5 < 1.*"
                                         "not served yet"):
        ModelConfig.from_hf_dict(dict(HF, early_exit_threshold=0.5))


@pytest.mark.parametrize("key,value,match", [
    ("sliding_window", 128, "sliding window"),
    ("use_sliding_window", True, "sliding window"),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"], "sliding_attention"),
    ("num_experts", 8, "num_experts"),
], ids=["window", "window-flag", "layer-kind", "foreign-key"])
def test_an_ouro_file_that_says_more_than_no_window_is_refused(
        key, value, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict(dict(HF, **{key: value}))


@pytest.mark.parametrize("plane,options", [
    ("kv_quant=int8", {"kv_quant": "int8"}),
    ("offload tiers", {"host_offload_pages": 4}),
    ("speculative decoding", {"speculative": "ngram"}),
    ("LoRA adapters", {"lora_adapters": 2}),
    ("sequence-parallel prefill", {"sp_prefill_threshold": 64}),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_a_plane_that_cannot_carry_the_loop_refuses_at_engine_start(
        setup, plane, options):
    """(f) The planes that size or index a cache by the weight layers
    refuse a looped stack by name, before anything is built."""
    cfg, params, _ = setup
    with pytest.raises(ValueError, match=f"{plane}.*cannot carry a looped "
                                         "layer stack"):
        TpuEngine(cfg, EngineConfig(num_pages=8, page_size=PAGE,
                                    max_pages_per_seq=4, **options),
                  params=params, mesh_config=MeshConfig(tp=1))


def _spec_args(cfg):
    ctx = llama.init_ctx(cfg, 2, 32, jnp.float32)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    return ctx, i32(2, 4), i32(2), i32(2), i32(2)


@pytest.mark.parametrize("plane", ["encode", "sp_prefill", "score",
                                   "score_tree", "draft"])
def test_a_function_that_runs_the_layers_once_refuses_the_loop(setup, plane):
    """(f) and their entry points: no function of a plane that runs each
    layer once is silently wrong on a looped stack."""
    cfg, params, _ = setup
    ctx, toks, *three = _spec_args(cfg)
    calls = {
        "encode": lambda: llama.encode_impl(cfg, params, toks[0],
                                            jnp.int32(4)),
        "sp_prefill": lambda: llama.sp_prefill(cfg, params, toks[0],
                                               jnp.int32(4), None),
        "score": lambda: llama.batch_score_impl(cfg, params, ctx, toks,
                                                *three, 32),
        "score_tree": lambda: llama.batch_score_tree_impl(
            cfg, params, ctx, toks, *three, toks, jnp.ones((2, 4, 4), bool),
            32),
        "draft": lambda: llama.batch_draft_impl(cfg, params, ctx, toks,
                                                *three, 32, 2),
    }
    with pytest.raises(ValueError, match="cannot carry a looped layer "
                                         "stack yet"):
        calls[plane]()


# ---------------------------------------------------------------------------
# (g) one number for the bytes a token holds, wherever it is asked


def test_every_site_says_the_same_bytes_a_token(setup, served):
    cfg, _, _ = setup
    want = 2 * STEPS * LAYERS * cfg.num_kv_heads * cfg.head_dim * 4
    m = served["metrics"]
    assert m[tmetrics.KV_ROW_BYTES[0]] == want            # the region
    assert m[tmetrics.KV_CACHE_PLANES[0]] == STEPS * LAYERS
    assert served["page_bytes_a_token"] == want           # the pool's page
    layout = KvCacheLayout(
        num_layers=cfg.cache_planes, num_kv_heads=cfg.num_kv_heads,
        page_size=PAGE, head_dim=cfg.head_dim, dtype="float32")
    assert np.prod(layout.page_shape(1)) * 4 / PAGE == want   # the wire
    assert llama.init_ring(cfg, 2, 4, jnp.float32)["k"].shape[0] == 12
    # and at the published widths, in bf16, the benchmark's own count
    big = published()
    bytes_ = load("bytes", "looped")
    assert bytes_.shapes(big)["kv_token"] == 1_572_864 == (
        2 * ModelConfig.from_hf_dict(big).cache_planes * 16 * 128 * 2)


def test_the_byte_count_reads_the_stack_once_a_pass():
    """24.3 ms of weights at 819 GB/s whatever the batch, and the live
    lanes' rows over 192 planes in whole 512-row chunks."""
    bytes_ = load("bytes", "looped")
    parts = bytes_.decode_parts({"config": published()}, [300.0, 700.0])
    assert parts["stack"] == 4 * 48 * 102_760_448
    assert parts["head"] == 2048 * 49152 * 2
    assert parts["rows"] == (512 + 1024) * 1_572_864
    none = bytes_.decode_bytes_per_step({"config": published()}, [])
    assert 24.2e-3 < none / 819e9 < 24.4e-3
    assert not hasattr(bytes_, "full_decode_bytes")
