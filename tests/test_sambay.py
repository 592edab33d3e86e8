"""The decoder-hybrid-decoder stack (layer kinds ``window_attention``,
``cross_attention`` and ``gmu`` of models/ssm_moe.py beside ``mamba1``
without inner norms and the differential ``attention`` layer; the window as
a bound in ops/flash_decode.py and ops/attention.py; the wrapping flush of
models/llama.py) against its plain reference
(benchmarks/references/sambay.py), on seeded random weights at tiny widths
on the CPU: eight layers (Mamba-1, window, Mamba-1, window, Mamba-1, full,
gated memory unit, cross), four query heads on two K/V heads of 16 (ONE
pair-wide row of 32), a window of 8 in a lane buffer of 8 rows.

Comparisons are float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ in the ORDER
of float32 sums (pair-wide rows against heads of 16, blocked against whole
softmax, the state held [N, inner] against [inner, N]), so log-probs agree
to ~1e-5 and the tolerance is 2e-4; the faults the controls inject move
them by 1e-3 to 1.
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
from dynamo_tpu.models.config import _TINY_PHI4FLASH, ModelConfig
from dynamo_tpu.ops.attention import (
    PALLAS_INTERPRET,
    REFERENCE,
    DecodeAttention,
    PriorContext,
    ctx_decode_attention,
    prefill_attention,
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
LANES = 6
HF = dict(_TINY_PHI4FLASH, engine={"prefill_buckets": list(BUCKETS)})
i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731


def load(kind, name):
    path = os.path.join(REPO, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "phi4-mini-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny_phi4flash()
    return cfg, llama.init_params(cfg, 3), load("references", "sambay")


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
        diffs.append(np.abs(np.asarray([p[1] for p in row]) - want[i, ids]))
    diffs = np.concatenate(diffs)
    return float(diffs.max()), float(diffs.mean())


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).tolist()


def rnd(seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * 0.3,
                       jnp.float32)


# ---------------------------------------------------------------------------
# the window as a bound in the two attention ops, each against its plain form

def plain_attention(q, keys, values):
    """q [heads, w] over keys / values [n, kvh, w], the softmax whole."""
    kvh = keys.shape[1]
    qg = q.reshape(kvh, -1, q.shape[-1])
    s = jnp.einsum("grh,ngh->grn", qg, keys) / np.sqrt(q.shape[-1])
    return jnp.einsum("grn,ngh->grh", jax.nn.softmax(s, -1),
                      values).reshape(q.shape)


@pytest.mark.parametrize("window", [16, 11, 5])
@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_window_decode_reads_the_lanes_modular_buffer(impl, window):
    """Decode over a lane's buffer of 16 rows (position p in slot p mod 16)
    and the ring: lanes below the buffer's length, past one wrap and on a
    multiple of it, one lane not live; a window as long as the buffer and
    shorter ones (the bound is a mask inside the chunk). Against the
    softmax over the visible positions gathered one by one."""
    L, B, W, R, nh, kvh, w = 2, 4, 16, 4, 8, 2, 128
    ck, cv = rnd(0, L, kvh, B + 1, W, w), rnd(1, L, kvh, B + 1, W, w)
    rk, rv = rnd(2, L, kvh, B, R, w), rnd(3, L, kvh, B, R, w)
    q = rnd(4, B, nh, w)
    base, live = i32(5, 37, 64, 21), jnp.asarray([True, True, True, False])
    attn = REFERENCE if impl == "reference" else DecodeAttention(
        PALLAS_INTERPRET)
    out = ctx_decode_attention(attn, q, ck, cv, rk, rv, jnp.int32(1),
                               base + 3, base, live=live, window=window)
    for b in range(3):
        bb, keys, values = int(base[b]), [], []
        for p in range(max(0, bb + 3 - window), bb + 3):
            src, at = ((ck, cv), p % W) if p < bb else ((rk, rv), p - bb)
            keys.append(src[0][1, :, b if p < bb else b, at])
            values.append(src[1][1, :, b, at])
        want = plain_attention(q[b], jnp.stack(keys), jnp.stack(values))
        np.testing.assert_allclose(out[b], want, atol=1e-5)
    assert not np.asarray(out[3]).any()


def test_a_window_needs_a_power_of_two_of_buffer_rows():
    args = (rnd(0, 2, 4, 128), rnd(1, 1, 1, 3, 24, 128),
            rnd(1, 1, 1, 3, 24, 128), rnd(2, 1, 1, 2, 4, 128),
            rnd(2, 1, 1, 2, 4, 128), jnp.int32(0), i32(9, 9), i32(8, 8))
    with pytest.raises(ValueError, match="power of two"):
        ctx_decode_attention(DecodeAttention(PALLAS_INTERPRET), *args,
                             window=8)


@pytest.mark.parametrize("window", [8, 24, 100])
@pytest.mark.parametrize("prior", [0, 32], ids=["fresh", "continuing"])
def test_prefill_attention_under_a_window(prior, window):
    """Chunks of 64 rows in blocks of 16: a window shorter than a block,
    longer than one and longer than the chunk; fresh, and continuing from
    a workspace of the lanes' last 32 prior rows (one lane has only 5
    prior positions, one is fresh in a continuing program). Against the
    softmax over each row's visible positions."""
    K, T, nh, kvh, w = 3, 64, 4, 1, 32
    q, k, v = rnd(0, K, T, nh, w), rnd(1, K, T, kvh, w), rnd(2, K, T, kvh, w)
    starts = i32(40, 5, 0) if prior else i32(0, 0, 0)
    lens = starts + i32(64, 37, 20)
    pk, pv = rnd(3, 1, kvh, K, 32, w), rnd(4, 1, kvh, K, 32, w)
    ctx = PriorContext(pk, pv, jnp.int32(0), i32(0, 1, 2)) if prior else None
    out = prefill_attention(q, k, v, starts, lens, ctx, block=16,
                            ctx_span=prior, window=window)
    for lane in range(K):
        q0, n = int(starts[lane]), int(lens[lane] - starts[lane])
        for row in (0, 3, n // 2, n - 1):
            keys, values = [], []
            for p in range(max(0, q0 + row - window + 1), q0 + row + 1):
                if p >= q0:
                    keys.append(k[lane, p - q0])
                    values.append(v[lane, p - q0])
                elif p >= q0 - prior:   # workspace row i: q0 - prior + i
                    keys.append(pk[0, :, lane, p - q0 + prior])
                    values.append(pv[0, :, lane, p - q0 + prior])
            want = plain_attention(q[lane, row], jnp.stack(keys),
                                   jnp.stack(values))
            np.testing.assert_allclose(out[lane, row], want, atol=1e-5)


@pytest.mark.parametrize("base", [(0, 3, 13), (14, 16, 30), (5, 29, 31)])
def test_the_flush_wraps_a_kind_shorter_than_the_region(base):
    """Ring -> region for rows of two lengths: the long kind as a span at
    the ring's base, the short one modulo its 16 rows (a span that wraps is
    written in two parts), entries past ``valid`` and a freed lane's (the
    scratch lane takes them) leaving the rest as it was."""
    L, kvh, B, S, W, R, w = 2, 1, 3, 64, 16, 4, 8
    ctx = {"k": rnd(0, 1, kvh, B + 1, S, w), "wk": rnd(1, L, kvh, B + 1, W, w)}
    ring = {"k": rnd(2, 1, kvh, B, R, w), "wk": rnd(3, L, kvh, B, R, w)}
    dest, valid = i32(0, B, 2), i32(4, 4, 2)
    out = llama.flush_ctx_impl(dict(ctx), ring, dest, i32(*base), valid)
    want = {n: np.array(x) for n, x in ctx.items()}
    for b in range(B):
        for r in range(int(valid[b])):
            p = base[b] + r
            want["k"][:, :, int(dest[b]), p] = ring["k"][:, :, b, r]
            want["wk"][:, :, int(dest[b]), p % W] = ring["wk"][:, :, b, r]
    for n in want:
        np.testing.assert_array_equal(out[n], want[n])


# ---------------------------------------------------------------------------
# the served path against the reference

SERVED = {
    # one padded bucket; 40 decode steps wrap the lane's 8-row buffer five
    # times, ten flushes of 4
    "one-chunk": ([23], 40),
    # shorter than the window: the buffer is not full when decode starts
    "below-the-window": ([5], 12),
    # 64 + 36: Mamba state, convolution window, window buffer, layer 5's
    # rows and m cross a chunk boundary; the continuing bucket is padded
    "two-chunks": ([100], 16),
    # 64 + 64 + 3: the last chunk is shorter than the window (its window
    # layers read the buffer for most of their keys)
    "three-chunks-a-short-tail": ([131], 12),
    # prompts arriving together: a batched prefill, then lanes at
    # different positions (and buffer offsets) in a round
    "a-batch": ([30, 25, 60, 33], 12),
}


@pytest.mark.parametrize("case", sorted(SERVED))
async def test_served_path_equals_the_reference(setup, case):
    """Prefill (fresh, continuing, padded, batched; only each chunk's last
    row above layer 5) and decode through ring, region, the lanes' window
    buffers and the fused rounds, against the reference's every row
    through every layer, on the log-probs the engine itself reports."""
    cfg, params, ref = setup
    lens, n = SERVED[case]
    eng = engine(cfg, params)
    prompts = [prompt_of(m, 10 + i) for i, m in enumerate(lens)]
    got = await asyncio.gather(*(serve(eng, p, n) for p in prompts))
    for p, (toks, tops) in zip(prompts, got):
        assert distance(ref, params, p, toks, tops)[0] < TOL
    if case == "three-chunks-a-short-tail":
        assert eng.dispatch_counts["prefill"] == 3
    # (whether "a-batch" dispatched a batched prefill depends on when its
    # requests reach the engine: the K = 3 programs are held directly below)
    assert eng.allocator.hit_blocks == 0 and not eng._seal_queue
    await eng.stop()


async def test_lanes_are_reused_and_the_counters_add_up(setup):
    """A prompt prefilled in three chunks WHILE another lane decodes, then
    the lanes reused by shorter requests (a freed lane's window buffer and
    state hold the old request's rows: a fresh chunk must read none); and
    what the counters say afterwards."""
    cfg, params, ref = setup
    eng = engine(cfg, params)   # a free lane is taken lowest first
    first, long = prompt_of(60, 1), prompt_of(150, 2)
    running = asyncio.ensure_future(serve(eng, first, 60))
    await asyncio.sleep(0.5)
    chunked = await serve(eng, long, 13)
    toks, tops = await running
    assert distance(ref, params, first, toks, tops)[0] < TOL
    assert distance(ref, params, long, *chunked)[0] < TOL
    for seed, m in ((3, 21), (4, 6), (5, 70)):
        later = prompt_of(m, seed)
        assert distance(ref, params, later,
                        *await serve(eng, later, 9))[0] < TOL
    snap = eng.telemetry.snapshot()
    # the region: ONE layer's K and V rows, pair-wide (1 pair of 32
    # float32 values), a token; and the two window layers' buffers of 8
    # rows a lane, spread over the region's 256 positions
    rows, window = 2 * 32 * 4, 2 * 8 * 2 * 32 * 4
    assert ssm_moe.kv_row_bytes(cfg, 4) == rows
    assert ssm_moe.window_bytes(cfg, 4) == window
    assert snap["dynamo_kv_row_bytes"]["sum"] == rows + window / 256
    assert snap["dynamo_ssm_state_bytes"]["sum"] == ssm_moe.state_bytes(
        cfg, 4) == 3 * (16 * 128 * 4 + 3 * 128 * 4)
    # the program's own count: the live lanes' states a step, 3 layers
    stepped = snap["dynamo_ssm_state_rows_stepped"]
    lane_steps = snap["dynamo_engine_round_live_lane_steps"]
    assert stepped["sum"] == lane_steps["sum"] * 3
    # the host's mirrors of a round: the full layer's rows read by it and
    # the ONE cross layer; the window layers' rows against the window's
    # bound (the jnp reference of the CPU meshes reads every lane's whole
    # buffer: lanes x 8 rows x 2 layers a step)
    read, shared = (snap[n]["sum"] for n in (
        "dynamo_decode_attn_rows_read", "dynamo_attn_shared_rows_read"))
    assert shared == 2 * read > 0
    assert snap["dynamo_attn_window_rows_read"]["sum"] >= snap[
        "dynamo_attn_window_rows_bound"]["sum"] > 0
    # the skip: every real prompt row x 8 layers, of which all but one row
    # a chunk never ran layers 5, 6 and 7
    ran, skipped = (snap[n] for n in (
        "dynamo_prefill_layer_rows", "dynamo_prefill_layer_rows_not_climbed"))
    prompt_rows = 60 + 150 + 21 + 6 + 70
    chunks = 1 + 3 + 1 + 1 + 2
    assert ran["sum"] == 8 * prompt_rows
    assert skipped["sum"] == 3 * (prompt_rows - chunks)
    await eng.stop()


_SERVED: dict = {}
CONTROLS = ["window_minus", "window_plus", "lam_wrong_layer",
            "cross_own_rows", "m_after_gate", "m_stale", "window_dropped",
            "state_zeroed", "state_bf16", "fp8"]


@pytest.mark.parametrize("control", CONTROLS)
async def test_the_check_sees_each_fault(setup, control):
    """What the engine served, against the reference computing a FAULTY
    model: every control moves the log-probs past the tolerance the sound
    comparison keeps (a prompt of 100 crosses the chunk boundary at 64).
    The engine serves the prompt once for all the controls."""
    cfg, params, ref = setup
    prompt = prompt_of(100, 7)
    if not _SERVED:
        eng = engine(cfg, params)
        _SERVED["out"] = await serve(eng, prompt, 24)
        await eng.stop()
    toks, tops = _SERVED["out"]
    assert distance(ref, params, prompt, toks, tops)[0] < TOL
    assert distance(ref, params, prompt, toks, tops, control)[0] > 10 * TOL
    assert set(CONTROLS) == set(ref.CONTROLS_REQUIRED + ref.CONTROLS_NAMED)


# ---------------------------------------------------------------------------
# the prefill that stops a chunk's rows below the cross-decoder

def chunk_inputs(cfg, span):
    ctx = llama.init_ctx(cfg, 3, 256, jnp.float32)
    # a lane's leaves are never zero when a chunk arrives
    ctx = jax.tree.map(lambda x: x + 0.25, ctx)
    tokens = jnp.asarray([prompt_of(128, s) for s in (4, 5, 6)], jnp.int32)
    starts = i32(64, 0, 8) if span else i32(0, 0, 0)
    return ctx, tokens, starts, starts + i32(70, 9, 0)


@pytest.mark.parametrize("span", [0, 256], ids=["fresh", "continuing"])
def test_only_the_last_row_climbs_and_the_logits_do_not_move(
        setup, monkeypatch, span):
    """The control of the skip: the same chunks with EVERY row through
    layers 5-7 (the full layer's attention over all rows, m of every
    position, the cross layer's prefill attention over layer 5's rows)
    give the same logits and leave the same region."""
    cfg, params, _ = setup
    ctx, tokens, starts, lens = chunk_inputs(cfg, span)
    chunk = lambda: ssm_moe.batch_prefill_impl(  # noqa: E731
        cfg, params, ctx, tokens, i32(0, 1, 2), starts, lens, span)
    assert ssm_moe.dims(cfg)["climbs"] and ssm_moe.SKIP_ROWS
    got_ctx, got, _ = chunk()
    monkeypatch.setattr(ssm_moe, "SKIP_ROWS", False)
    want_ctx, want, _ = chunk()
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    for a, b in zip(jax.tree.leaves(got_ctx), jax.tree.leaves(want_ctx)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("span", [0, 256], ids=["fresh", "continuing"])
def test_the_looped_prefill_equals_the_straight_line_one(setup, monkeypatch,
                                                         span):
    """A chunk whose halves and scans follow the lanes' live row blocks
    (toy heights 32 / 16 in a bucket of 128: a lane of 70 rows crosses a
    block of both, one of 9 ends inside the first, a dummy lane runs none)
    against the same chunk straight-line."""
    cfg, params, _ = setup
    ctx, tokens, starts, lens = chunk_inputs(cfg, span)
    chunk = lambda: ssm_moe.batch_prefill_impl(  # noqa: E731
        cfg, params, ctx, tokens, i32(0, 1, 2), starts, lens, span)
    assert not ssm_moe.live_row_block(cfg, 128)
    want_ctx, want, _ = chunk()
    monkeypatch.setattr(ssm_moe, "LIVE_ROW_BLOCK", 32)
    monkeypatch.setattr(ssm_moe, "SCAN_ROW_BLOCK", 16)
    assert ssm_moe.live_row_block(cfg, 128) == 32
    got_ctx, got, _ = chunk()
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    for name in (ssm_moe.M1, ssm_moe.M1_CONV):
        for a, b in zip(got_ctx[name], want_ctx[name]):
            np.testing.assert_allclose(a[:2], b[:2], atol=2e-5)
    # the window buffers of the two lanes that hold a request, whole
    for name in (ssm_moe.WK, ssm_moe.WV):
        np.testing.assert_allclose(got_ctx[name][:, :, :2],
                                   want_ctx[name][:, :, :2], atol=2e-5)
    for name in ("k", "v"):
        for lane, (q0, n) in enumerate(zip(np.asarray(starts),
                                           np.asarray(lens - starts))):
            np.testing.assert_allclose(
                got_ctx[name][:, :, lane, q0:q0 + n],
                want_ctx[name][:, :, lane, q0:q0 + n], atol=2e-5)


def test_the_kernels_and_the_xla_forms_give_one_model(setup):
    """A decode step with the Pallas kernels interpreted (the differential
    read of both row kinds through the work-list kernel, the Mamba-1 step
    kernel) against the jnp forms: lanes on both sides of a buffer wrap,
    one not live."""
    cfg, params, _ = setup
    B, R = 4, 4
    ctx = jax.tree.map(lambda x: rnd(7, *x.shape),
                       llama.init_ctx(cfg, B, 64, jnp.float32))
    ring = jax.tree.map(lambda x: rnd(8, *x.shape),
                        llama.init_ring(cfg, B, R, jnp.float32))
    stepped = {n: ctx[n] for n in llama.stepped_kinds(cfg, ctx)}
    base = i32(3, 22, 40, 9)
    live = jnp.asarray([True, True, True, False])
    step = lambda attn: ssm_moe.decode_step_impl(  # noqa: E731
        cfg, params, ctx, ring, stepped, i32(5, 6, 7, 8), base + 2, base,
        1, live, attn=attn)
    want, got = step(REFERENCE), step(DecodeAttention(PALLAS_INTERPRET))
    np.testing.assert_allclose(got[2][:3], want[2][:3], atol=2e-5)
    # the live lanes' ring rows and recurrent leaves (a lane that is not
    # live computes garbage either way, and not the same)
    for a, b in zip(jax.tree.leaves(got[0]), jax.tree.leaves(want[0])):
        np.testing.assert_allclose(a[:, :, :3], b[:, :, :3], atol=2e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a[:3], b[:3], atol=2e-5)


async def test_bfloat16_weights_and_cache_stay_near_the_reference():
    """The stated precision at toy widths: bfloat16 weights, activations
    and rows (the scan's state stays float32) against the float32
    reference of the same weights. Rounding only: 0.01-0.03 over eight
    layers at these widths; a fault reads 0.1 to 1."""
    cfg = ModelConfig.tiny_phi4flash()
    params = llama.init_params(cfg, 5)
    ref = load("references", "sambay")
    eng = engine(cfg, params, cache_dtype="bfloat16")
    prompt = prompt_of(100, 9)
    toks, tops = await serve(eng, prompt, 16)
    worst, mean = distance(ref, params, prompt, toks, tops)
    assert worst < 0.15 and mean < 0.03
    await eng.stop()


# ---------------------------------------------------------------------------
# the configuration, its reader and what the region holds

def test_the_published_configuration_reads_as_the_issue_states():
    hf = published()
    cfg = ModelConfig.from_hf_dict(hf)
    d = ssm_moe.dims(cfg)
    kinds = d["kinds"]
    assert [l for l, k in enumerate(kinds) if k == "mamba1"] == list(
        range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == "window_attention"] == (
        list(range(1, 16, 2)))
    assert kinds[17] == "attention" and kinds.count("attention") == 1
    assert [l for l, k in enumerate(kinds) if k == "gmu"] == list(
        range(18, 32, 2))
    assert [l for l, k in enumerate(kinds) if k == "cross_attention"] == (
        list(range(19, 32, 2)))
    assert (d["rows_from"], d["scan_from"], d["window"], d["window_rows"],
            d["climbs"]) == (17, 16, 512, 512, True)
    assert (d["m1_inner"], d["m1_N"], d["m1_rank"], d["m1_W"]) == (
        5120, 16, 160, 4)
    assert ssm_moe.row_heads(cfg) == (10, 128)
    assert hf["reduced"] == {} and cfg.tie_word_embeddings
    # rows of two lengths in one region, none for the cross layers
    eng = hf["engine"]
    lanes = eng["max_decode_slots"]
    S = eng["max_pages_per_seq"] * eng["page_size"]
    ctx = jax.eval_shape(lambda: llama.init_ctx(cfg, lanes, S, jnp.bfloat16))
    assert ctx["k"].shape == ctx["v"].shape == (1, 10, lanes + 1, S, 128)
    assert ctx["wk"].shape == ctx["wv"].shape == (8, 10, lanes + 1, 512, 128)
    assert {x.dtype for x in ctx[ssm_moe.M1]} == {jnp.dtype("float32")}
    assert ssm_moe.kv_row_bytes(cfg, 2) == 5120
    assert ssm_moe.window_bytes(cfg, 2) == 8 * 512 * 5120
    assert ssm_moe.state_bytes(cfg, 2) == 9 * 358400
    # the byte count is the model's own arithmetic
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, 0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert 3.85e9 < n < 3.86e9
    s = load("bytes", "sambay").shapes(hf)
    assert (s["n_m1"], s["n_win"], s["n_cross"], s["n_gmu"]) == (9, 8, 7, 7)
    assert s["row"] == 5120 and s["state_lane"] == 358400
    # no inner norms in this family's Mamba-1; cell 9's has all three
    assert "dt_norm" not in shapes["layers"][0]
    jamba = jax.eval_shape(lambda: llama.init_params(
        ModelConfig.tiny_jamba(), 0))
    assert "dt_norm" in jamba["layers"][0]


REFUSED = {
    "mb_per_layer": {"mb_per_layer": 1},
    "num_hidden_layers": {"num_hidden_layers": 6},
    "sliding_window": {"sliding_window": [8, None, 8, None, None, None,
                                          None, None]},
    "num_key_value_heads": {"num_key_value_heads": 1},
    "tie_word_embeddings": {"tie_word_embeddings": False},
    "mlp_bias": {"mlp_bias": True},
    "hidden_act": {"hidden_act": "gelu"},
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_the_reader_refuses_what_the_program_does_not_build(key):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_dict(dict(_TINY_PHI4FLASH, **REFUSED[key]))


def test_a_window_list_that_maps_to_the_roles_is_read_and_jamba_still_refuses():
    per_layer = [8 if l % 2 and l < 4 else None for l in range(8)]
    cfg = ModelConfig.from_hf_dict(dict(_TINY_PHI4FLASH,
                                        sliding_window=per_layer))
    assert cfg == ModelConfig.tiny_phi4flash()
    with pytest.raises(ValueError, match="missing"):
        ModelConfig.from_hf_dict({k: v for k, v in _TINY_PHI4FLASH.items()
                                  if k != "mb_per_layer"})
    from dynamo_tpu.models.config import _TINY_JAMBA
    with pytest.raises(ValueError, match="sliding_window"):
        ModelConfig.from_hf_dict(dict(_TINY_JAMBA, sliding_window=512))


@pytest.mark.parametrize("plane,kw", [
    ("int8 KV", {"kv_quant": "int8"}),
    ("speculation", {"speculative": "ngram"}),
])
def test_the_row_only_planes_refuse_this_state_by_name(setup, plane, kw):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="recurrent"):
        engine(cfg, params, **kw)


def test_a_ring_longer_than_the_window_buffer_is_refused(setup):
    cfg, _, _ = setup
    with pytest.raises(ValueError, match="flush_every"):
        llama.init_ring(cfg, 2, 16)


# ---------------------------------------------------------------------------
# the benchmark's readers and byte counts (no JAX in them)

def hist(**sums):
    return {"histograms": {n: {"sum": s, "count": c}
                           for n, (s, c) in sums.items()}}


def sources(after, trace=None):
    before = hist(**{n: (0, 0) for n in after["histograms"]})
    return {"config": published(), "before": before, "after": after,
            "trace": trace, "byname": load("", "byname"),
            "peaks": load("", "peaks"),
            "engine_up": {"device_kind": "TPU v5 lite", "platform": "tpu"}}


def test_the_window_rows_read_are_set_against_the_windows_bound():
    read = load("layer_metrics", "attn.window_rows_read_over_window").read
    src = sources(hist(dynamo_attn_window_rows_read=(6144, 3),
                       dynamo_attn_window_rows_bound=(4096, 3)))
    assert read(src) == 1.5
    assert read(sources(hist(dynamo_attn_window_rows_read=(0, 0)))) is None


def test_the_skips_share_is_rows_not_climbed_over_rows():
    read = load("layer_metrics", "step.prefill_rows_not_climbed_share").read
    src = sources(hist(dynamo_prefill_layer_rows=(32000, 4),
                       dynamo_prefill_layer_rows_not_climbed=(14985, 4)))
    assert abs(read(src) - 46.828125) < 1e-9
    assert read(sources(hist(dynamo_prefill_layer_rows=(0, 0)))) is None


def test_the_byte_count_is_low_and_the_shares_add_up():
    mod = load("bytes", "sambay")
    src = sources(hist())
    parts = mod.decode_parts(src, [3200.0] * 40)
    assert parts["shared_rows"] == 40 * 3200 * 5120 * 8
    assert parts["window_rows"] == 40 * 512 * 5120 * 8
    assert parts["state"] == 2 * 40 * 9 * 358400
    assert 7.70e9 < parts["weights"] < 7.71e9
    # a lane below the window reads its own rows, one past the region none
    # beyond it
    short = mod.decode_parts(src, [100.0, 1e6])
    assert short["window_rows"] == (100 + 512) * 5120 * 8
    assert short["shared_rows"] == (100 + 18432) * 5120 * 8


@pytest.mark.parametrize("kernels,share", [
    ({"diff_decode_attention": 0.5, "m1_step": 9.0}, 5.0),
    ({"diff_decode_attention (x)": 0.25, "other": 1.0}, 10.0),
    ({"m1_step": 1.0}, None),
])
def test_the_differential_decode_kernels_roofline(kernels, share):
    """Bytes a step at the span's own live lanes (from the generator's
    log: the lanes' own rows x the readers and what the window admits,
    never the whole chunks the kernel fetched) x the span's steps, over the
    kernel's seconds in the traced span."""
    read = load("layer_metrics", "kernel.diff_decode_roofline").read
    _, bw = load("", "peaks").peaks_for("TPU v5 lite")
    # ten lanes decoding all through the span, each at n rows: a step of
    # the kernel's sixteen calls reads 10 x (8 n + 8 x 512) rows of 5120 B
    n = 0.025 * bw / 16 / 5120 / 10 / 8 - 512
    log = [{"ok": True, "chunks": [0.0, 10.0], "tokens": 0,
            "prompt_tokens": n} for _ in range(10)]
    trace = {"kernels": kernels,
             "modules": {"jit_engine_round_seal": {"count": 4}}}
    src = dict(sources(hist(), trace), log=log, trace_span=(4.0, 7.0))
    src["engine_up"]["flush_every"] = 4
    got = read(src)
    assert got is None if share is None else abs(got - share) < 1e-6
    assert read(dict(src, trace=None)) is None
    assert read(dict(src, trace_span=None)) is None
