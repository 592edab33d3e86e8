"""The linear-attention + block-sparse-attention stack (the other mixers
of models/ssm_moe.py; ops/lightning.py, ops/sparse_attention.py) against
its plain reference (benchmarks/references/sala.py), on seeded random
weights at tiny widths on the CPU: six layers of both kinds with two
sparse ones adjacent, blocks of 8, top 6, compressed keys of 4 every 2, a
window of 16 and the switch to the selection at position 64.

Every comparison is float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ in the
ORDER of float32 sums only (a chunked scan against the recurrence as
written, blocked against whole softmax, gathered blocks against a mask),
so log-probs agree to ~1e-6 and the tolerance is 1e-4. A state dropped at
a chunk boundary, a dropped selection or a stale compressed key moves
them by 1e-3 to 1 (the controls, below).
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
from dynamo_tpu.models.config import _TINY_LINEAR_SPARSE, ModelConfig
from dynamo_tpu.ops import lightning
from dynamo_tpu.ops import sparse_attention as sa
from dynamo_tpu.ops.attention import REFERENCE
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
BUCKETS = (32, 64)
TOP = 5
GEO = sa.Geometry(kernel=4, stride=2, block=8, topk=6, init_blocks=1,
                  window=16, dense_len=64)


def load_reference():
    path = os.path.join(REPO, "benchmarks", "references", "sala.py")
    spec = importlib.util.spec_from_file_location("ref_sala", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


HF = dict(_TINY_LINEAR_SPARSE, engine={"prefill_buckets": list(BUCKETS)})


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "minicpm-sala-d16.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny_linear_sparse(dtype="float32")
    return cfg, llama.init_params(cfg, 3), load_reference()


def engine(cfg, params, **kw):
    ecfg = EngineConfig(**{**dict(
        num_pages=16, page_size=PS, max_pages_per_seq=32,
        max_decode_slots=4, prefill_buckets=BUCKETS, flush_every=4,
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


def distance(ref, params, prompt, toks, tops, control=None):
    """max |log-prob difference| over the engine's top tokens, every
    step, against the reference's full forward of prompt + tokens."""
    want = ref.logprobs(HF, params, list(prompt) + toks,
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
    # every query below dense_len: the dense read only, a padded bucket
    "all-dense": ([23], 24),
    # one chunk that ends under the switch; decode crosses it at step 7
    # and completes compressed keys on the way
    "decode-crosses-the-switch": ([58], 24),
    # 64 + 36: the state crosses a chunk boundary, the second chunk's
    # queries select, its last bucket is padded
    "two-chunks-the-second-selecting": ([100], 16),
    # 64 + 64 + 22, then decode over ~20 blocks
    "three-chunks": ([150], 16),
    # prompts of one bucket arriving together: K = 2, fresh and continuing
    "batched-prefill": ([70, 50, 90, 110], 12),
}


@pytest.mark.parametrize("case", sorted(SERVED))
async def test_served_path_equals_the_reference(setup, case):
    """Prefill (fresh, continuing, padded, batched) and decode through
    ring, region and the fused rounds, against the reference, on the
    log-probs the engine itself reports."""
    cfg, params, ref = setup
    lens, n = SERVED[case]
    eng = engine(cfg, params)
    prompts = [prompt_of(m, 10 + i) for i, m in enumerate(lens)]
    got = await asyncio.gather(*(serve(eng, p, n) for p in prompts))
    for p, (toks, tops) in zip(prompts, got):
        assert distance(ref, params, p, toks, tops) < TOL
    if case == "three-chunks":
        assert eng.dispatch_counts["prefill"] == 3
    snap = eng.telemetry.snapshot()
    read = snap["dynamo_sparse_attn_rows_read"]["sum"]
    live = snap["dynamo_sparse_attn_rows_live"]["sum"]
    assert read > 0 and live > 0
    if case == "all-dense":
        assert snap["dynamo_sparse_prefill_pairs_scored"]["sum"] >= snap[
            "dynamo_sparse_prefill_pairs_selected"]["sum"] == 3 * 23 * 24 / 2
    assert eng.allocator.hit_blocks == 0 and not eng._seal_queue
    await eng.stop()


async def test_chunks_interleave_with_other_lanes_decode_and_lanes_are_reused(
        setup):
    """A prompt prefilled in three chunks WHILE another lane decodes
    between the chunks (its state and compressed keys must not be touched
    by those rounds, nor theirs by its chunks), then a lane reused by a
    later, shorter request (stale rows and keys of the first tenant lie
    past its end and must stay invisible)."""
    cfg, params, ref = setup
    eng = engine(cfg, params)
    first, long, later = prompt_of(60, 1), prompt_of(150, 2), prompt_of(21, 3)
    running = asyncio.ensure_future(serve(eng, first, 60))
    await asyncio.sleep(0.5)
    chunked = await serve(eng, long, 13)
    toks, tops = await running
    assert distance(ref, params, first, toks, tops) < TOL
    assert distance(ref, params, long, *chunked) < TOL
    short = await serve(eng, later, 5)
    again = await serve(eng, prompt_of(70, 4), 11)
    assert distance(ref, params, later, *short) < TOL
    assert distance(ref, params, prompt_of(70, 4), *again) < TOL
    snap = eng.telemetry.snapshot()
    assert snap["dynamo_ssm_state_bytes"]["sum"] == ssm_moe.state_bytes(
        cfg, 4) == 3 * 4 * 16 * 16 * 4
    assert snap["dynamo_kv_row_bytes"]["sum"] == ssm_moe.kv_row_bytes(
        cfg, 4) == 3 * 2 * 32 * 4 + 3 * 32 * 4 / 2
    # a dense MLP routes nothing: the routing series stay empty
    assert snap["dynamo_moe_experts_touched"]["count"] == 0
    await eng.stop()


def test_a_lane_that_is_not_live_keeps_its_state_and_keys_bit_for_bit(setup):
    cfg, params, _ = setup
    B = 3
    rng = np.random.RandomState(0)
    ctx = jax.tree.map(lambda a: jnp.asarray(rng.randn(*a.shape), a.dtype),
                       llama.init_ctx(cfg, B, 128, jnp.float32))
    assert ssm_moe.stepped_kinds(cfg, ctx) == ("kc", "lin_state")
    state = {n: ctx[n] for n in ssm_moe.stepped_kinds(cfg, ctx)}
    live = jnp.asarray([True, False, True])
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    # position 3 ends a compressed key (kernel 4, stride 2): lanes 0 and
    # 1 would both write row 0; lane 2 stands at position 4, which ends
    # none
    _, new, logits, _ = ssm_moe.decode_step_impl(
        cfg, params, ctx, llama.init_ring(cfg, B, 2, jnp.float32), state,
        i32(5, 6, 7), i32(4, 4, 5), i32(3, 3, 4), jnp.int32(0), live,
        attn=REFERENCE)
    for old, upd in zip(state["lin_state"], new["lin_state"]):
        np.testing.assert_array_equal(upd[1], old[1])   # not live
        np.testing.assert_array_equal(upd[B], old[B])   # scratch
        assert not np.array_equal(upd[0], old[0])
        assert upd.dtype == jnp.float32
    old, upd = np.asarray(state["kc"]), np.asarray(new["kc"])
    assert not np.array_equal(upd[:, :, 0, 0], old[:, :, 0, 0])
    np.testing.assert_array_equal(upd[:, :, 0, 1:], old[:, :, 0, 1:])
    for lane in (1, 2, B):
        np.testing.assert_array_equal(upd[:, :, lane], old[:, :, lane])
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("n_real,chunk", [(19, 8), (24, 8), (5, 16)])
def test_chunked_lightning_equals_the_recurrence_as_written(n_real, chunk):
    """ops/lightning.py alone: the chunked form over a padded bucket from
    a non-zero state equals the recurrence, one position a step, over the
    real positions, and the state that comes out is the state after the
    last REAL one."""
    rng = np.random.RandomState(2)
    T, H, D = 24, 4, 8
    q, k, v = (jnp.asarray(rng.randn(T, H, D), jnp.float32) for _ in "qkv")
    decay = jnp.asarray(lightning.log_decays(H))
    S0 = jnp.asarray(rng.randn(H, D, D), jnp.float32)
    real = jnp.arange(T) < n_real
    o, S = lightning.chunk_scan(q, k, v, decay, real, S0, chunk)
    want_S, want_o = S0[None], []
    for t in range(n_real):
        ot, want_S = lightning.step(q[t][None], k[t][None], v[t][None],
                                    decay[None], want_S)
        want_o.append(ot[0])
    np.testing.assert_allclose(S, want_S[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o[:n_real], jnp.stack(want_o), rtol=1e-4,
                               atol=1e-4)
    lam = np.exp(np.asarray(decay, np.float64))
    np.testing.assert_allclose(
        lam, np.exp(-(2.0 ** (-8.0 * (np.arange(H) + 1) / H))), rtol=1e-6)


def explicit_selection(q_t, kc, t, g=GEO):
    """The selection as the issue writes it, in numpy: q_t [rep, hd] one
    K/V group's query heads at position t, kc [J, hd] -> block ids."""
    nb = t // g.block + 1
    J = (t - g.kernel + 1) // g.stride + 1
    lc = q_t @ kc[:J].T / np.sqrt(q_t.shape[-1])
    p = np.exp(lc - lc.max(-1, keepdims=True))
    s = (p / p.sum(-1, keepdims=True)).sum(0)
    score = np.zeros(nb)
    for b in range(nb):
        js = [j for j in range(J) if g.stride * j < g.block * (b + 1)
              and g.stride * j + g.kernel > g.block * b]
        score[b] = max(s[j] for j in js) if js else 0.0
    forced = [b for b in range(nb) if b < g.init_blocks
              or g.block * b + g.block - 1 >= t - g.window + 1]
    rest = [b for b in np.argsort(-score, kind="stable") if b not in forced]
    return sorted(forced + rest[:g.topk - len(forced)])


def keys_and_queries(T, seed=0, kvh=2, rep=2, hd=16):
    rng = np.random.RandomState(seed)
    q = rng.randn(T, kvh, rep, hd).astype(np.float32) * 2
    k = rng.randn(T, kvh, hd).astype(np.float32) * 2
    v = rng.randn(T, kvh, hd).astype(np.float32)
    kc = np.stack([k[g * GEO.stride:g * GEO.stride + GEO.kernel].mean(0)
                   for g in range((T - GEO.kernel) // GEO.stride + 1)])
    return q, k, v, kc


@pytest.mark.parametrize("q_start", [0, 64])
def test_prefill_selection_equals_an_explicit_argsort(q_start):
    """``compress_chunk`` + ``overlay`` + ``prefill_block_mask`` for a
    chunk of 64 at ``q_start`` (the earlier keys from the lane's region)
    against the selection computed in numpy, every row at or past
    dense_len; rows below it read everything."""
    T = 64
    q, k, _, kc = keys_and_queries(q_start + T)
    Sc = 128
    lane = np.zeros((2, Sc, 16), np.float32)
    before = q_start // GEO.stride - 1
    if before > 0:
        lane[:, :before] = kc[:before].transpose(1, 0, 2)
    tail = (k[q_start - GEO.stride:q_start] if q_start
            else np.zeros((GEO.stride, 2, 16), np.float32))
    new = sa.compress_chunk(GEO, jnp.asarray(k[q_start:]), jnp.asarray(tail))
    lane = sa.overlay(jnp.asarray(lane), new, q_start // GEO.stride - 1)
    np.testing.assert_allclose(np.asarray(lane)[:, :len(kc)],
                               kc.transpose(1, 0, 2), atol=1e-6)
    mask = np.asarray(sa.prefill_block_mask(
        GEO, jnp.asarray(q[q_start:]), lane, jnp.int32(q_start),
        jnp.int32(T)))
    for i in range(T):
        t = q_start + i
        for g in range(2):
            got = np.flatnonzero(mask[g, i, :t // GEO.block + 1])
            if t < GEO.dense_len:
                assert len(got) == t // GEO.block + 1
            else:
                assert list(got) == explicit_selection(q[t, g], kc[:, g], t)
                assert len(got) == GEO.topk


@pytest.mark.parametrize("t,ring_rows", [(64, 1), (97, 2), (200, 4)])
def test_sparse_decode_reads_its_selection_and_equals_dense_under_the_mask(
        t, ring_rows):
    """``decode_attention``: the chosen ids are the explicit selection's
    (at most ``topk`` blocks a K/V group), and the gathered read equals a
    dense softmax over the whole context under the selection's mask. The
    newest ``ring_rows`` positions live in the ring; the region's rows
    there are garbage."""
    S, R, B = 256, 4, 2
    q, k, v, kc = keys_and_queries(t + 1, seed=t)
    base = t + 1 - ring_rows
    rng = np.random.RandomState(1)
    ctx_k, ctx_v = (rng.randn(1, 2, B + 1, S, 16).astype(np.float32)
                    for _ in "kv")
    ctx_k[0, :, 1, :base] = k[:base].transpose(1, 0, 2)
    ctx_v[0, :, 1, :base] = v[:base].transpose(1, 0, 2)
    ring_k, ring_v = (rng.randn(1, 2, B, R, 16).astype(np.float32)
                      for _ in "kv")
    ring_k[0, :, 1, :ring_rows] = k[base:].transpose(1, 0, 2)
    ring_v[0, :, 1, :ring_rows] = v[base:].transpose(1, 0, 2)
    ctx_kc = rng.randn(1, 2, B + 1, S // 2, 16).astype(np.float32)
    ctx_kc[0, :, 1, :len(kc)] = kc.transpose(1, 0, 2)
    qs = np.zeros((B, 4, 16), np.float32)
    qs[1] = q[t].reshape(4, 16)
    o, ids = sa.decode_attention(
        GEO, *(jnp.asarray(a) for a in (qs, ctx_k, ctx_v, ctx_kc, ring_k,
                                        ring_v)),
        0, 0, jnp.asarray([1, t + 1], jnp.int32),
        jnp.asarray([0, base], jnp.int32), jnp.asarray([False, True]))
    assert ids.shape == (B, 2, GEO.topk)
    assert not np.asarray(o[0]).any()      # a lane that is not on: skipped
    for g in range(2):
        chosen = explicit_selection(q[t, g], kc[:, g], t)
        assert sorted(np.asarray(ids[1, g]).tolist()) == chosen
        ok = np.isin(np.arange(t + 1) // GEO.block, chosen)
        s = q[t, g] @ k[:, g].T / 4.0
        p = np.exp(np.where(ok, s, -np.inf) - s[:, ok].max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ v[:, g]
        np.testing.assert_allclose(
            np.asarray(o[1]).reshape(2, 2, 16)[g], want, rtol=2e-4, atol=2e-4)
    # the host's mirror: a lane past the switch reads its compressed
    # keys, topk blocks and the ring, whatever its context; one below it
    # its own rows; a lane that is not live nothing
    read, live = sa.decode_rows(GEO, [t + 1, 30, 99], [True, True, False],
                                S, R)
    assert read == S // GEO.stride + GEO.topk * GEO.block + R + 30
    assert live == t + 1 + 30


@pytest.mark.parametrize("t,base", [(39, 39), (39, 37), (37, 34), (38, 38)])
def test_compressed_keys_complete_in_decode_steps_from_ring_and_region(
        t, base):
    """``compress_step``: the key that ends at the newest position is the
    mean of the last ``kernel`` keys, wherever the ring's base cuts them,
    written into its row of a live lane; every other row, and a lane that
    is not live, comes back bit for bit."""
    _, k, _, kc = keys_and_queries(40)
    ctx_k = np.zeros((1, 2, 3, 64, 16), np.float32)
    ring = np.zeros((1, 2, 2, 4, 16), np.float32)
    for lane in (0, 1):
        ctx_k[0, :, lane, :base] = k[:base].transpose(1, 0, 2)
        ring[0, :, lane, :t + 1 - base] = k[base:t + 1].transpose(1, 0, 2)
    old = np.random.RandomState(3).randn(1, 2, 3, 32, 16).astype(np.float32)
    new = np.asarray(sa.compress_step(
        GEO, jnp.asarray(ctx_k), jnp.asarray(ring), jnp.asarray(old), 0, 0,
        jnp.asarray([t, t], jnp.int32), jnp.asarray([base, base], jnp.int32),
        jnp.asarray([True, False])))
    np.testing.assert_array_equal(new[:, :, 1:], old[:, :, 1:])
    if (t + 1 - GEO.kernel) % GEO.stride:
        np.testing.assert_array_equal(new, old)        # t ends no key
        return
    j = (t + 1 - GEO.kernel) // GEO.stride
    np.testing.assert_allclose(new[0, :, 0, j], kc[j], atol=1e-6)
    keep = np.arange(32) != j
    np.testing.assert_array_equal(new[:, :, 0, keep], old[:, :, 0, keep])


@pytest.mark.parametrize(
    "control", load_reference().CONTROLS_REQUIRED + ("kc_stale",))
async def test_the_references_controls_stand_far_from_the_served_path(
        setup, control):
    """Each fault the chip's check must catch, computed by the reference
    (``control=``), stands far further from the served path than the
    sound reference does: a prompt of three chunks (the boundary at 64),
    its last chunk padded, decode selecting among ~20 blocks. Stale
    compressed keys are held HERE (the toy's window is 16 positions, so
    keys completed by decode steps leave it within the 12 steps); the
    chip's check, 48 steps against a window of 2048, cannot see them."""
    cfg, params, ref = setup
    eng = engine(cfg, params)
    prompt = prompt_of(150, 20)
    toks, tops = await serve(eng, prompt, 12)
    await eng.stop()
    assert distance(ref, params, prompt, toks, tops) < TOL / 10
    assert distance(ref, params, prompt, toks, tops, control) > 5 * TOL


def test_from_hf_dict_reads_the_published_keys():
    hf = published()
    c = ModelConfig.from_hf_dict(hf)
    d = ssm_moe.dims(c)
    assert (d["n_lin"], d["n_sparse"], d["n_attn"], d["n_ssm"]) == (12, 4, 4,
                                                                    0)
    assert [i for i, k in enumerate(d["kinds"])
            if k == "sparse_attention"] == [0, 7, 8, 13]
    assert not d["experts"]
    assert d["sparse"] == sa.Geometry(32, 16, 64, 64, 1, 2048, 8192)
    assert (d["lin_heads"], d["lin_dim"]) == (32, 128)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.vocab_size,
            c.hidden_size, c.intermediate_size, c.num_layers) == (
        32, 2, 128, 73448, 4096, 16384, 16)
    h = c.hybrid_dict
    assert h["embedding_multiplier"] == 12 and h["logits_scaling"] == 16
    # the PUBLISHED depth, whatever the cut
    assert h["residual_multiplier"] == pytest.approx(1.4 / np.sqrt(32))
    assert not c.tie_word_embeddings and c.mla is None and c.routed is None
    assert ssm_moe.state_bytes(c, 2) == 25165824
    assert ssm_moe.kv_row_bytes(c, 2) == 4096 + 128
    shapes = jax.eval_shape(lambda: llama.init_params(c, 0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) * 2 == pytest.approx(
        10.08e9, rel=0.01)


UNBUILT = [
    ("mixer_types", ["lightning-attn"] * 15 + ["window"]),
    ("mixer_types", ["lightning-attn"] * 15),
    ("qk_norm", False), ("use_output_gate", False),
    ("use_output_norm", False), ("attn_use_output_gate", False),
    ("attn_use_rope", True), ("lightning_use_rope", False),
    ("lightning_scale", "1"), ("lightning_nkv", 8),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True),
    ("sparse_config", {"kernel_size": 32, "kernel_stride": 16}),
    ("sparse_config", dict(published()["sparse_config"], kernel_size=48)),
    ("sparse_config", dict(published()["sparse_config"], block_size=40)),
    ("sparse_config", dict(published()["sparse_config"], topk=16)),
    ("sparse_config", dict(published()["sparse_config"], dense_len=2048)),
    ("num_key_value_heads", 5),
]


@pytest.mark.parametrize("key,value", UNBUILT,
                         ids=[f"{k}-{i}" for i, (k, _) in enumerate(UNBUILT)])
def test_from_hf_dict_refuses_each_unbuilt_value_by_name(key, value):
    with pytest.raises(ValueError, match="linear \\+ sparse attention block"):
        ModelConfig.from_hf_dict(dict(published(), **{key: value}))


def test_from_hf_dict_refuses_a_missing_key_and_another_block():
    hf = published()
    with pytest.raises(ValueError, match="missing"):
        ModelConfig.from_hf_dict(
            {k: v for k, v in hf.items() if k != "sparse_config"})
    with pytest.raises(ValueError, match="is no block this program builds"):
        ModelConfig.from_hf_dict(dict(hf, model_type="minicpm_sala2"))


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "float32"])
def test_the_matrix_state_is_float32_whatever_the_cache_dtype(cache_dtype):
    """At the published widths, with the bytes the counter
    ``dynamo_ssm_state_bytes`` reports; the compressed keys take the
    cache's dtype, one row every 16 positions."""
    c = ModelConfig.from_hf_dict(published())
    dtype = jnp.dtype(cache_dtype)
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, 16, 32768, dtype))
    assert [(a.shape, a.dtype) for a in ctx[ssm_moe.LIN]] == [
        ((17, 32, 128, 128), jnp.float32)] * 12
    assert (ctx[ssm_moe.KC].shape, ctx[ssm_moe.KC].dtype) == (
        (4, 2, 17, 2048, 128), dtype)
    assert ctx["k"].shape == (4, 2, 17, 32768, 128)
    ring = jax.eval_shape(lambda: llama.init_ring(c, 16, 4, dtype))
    assert sorted(ring) == ["k", "v"]
    pool = jax.eval_shape(lambda: llama.init_cache(c, 8, 64, dtype))
    assert pool[ssm_moe.KC].shape == (4, 2, 8, 4, 128)


@pytest.mark.parametrize("plane,kw", [
    ("int8 KV", {"kv_quant": "int8"}),
    ("offload", {"host_offload_pages": 8}),
    ("spec/", {"speculative": "ngram"}),
    ("LoRA", {"lora_adapters": 2}),
    ("sequence-parallel", {"sp_prefill_threshold": 64}),
    # a chunk starts on a page and has to start on a block
    ("pages of half a block", {"page_size": 4, "max_pages_per_seq": 64}),
])
def test_a_plane_that_cannot_carry_the_new_leaves_refuses_at_start(
        setup, plane, kw):
    cfg, params, _ = setup
    with pytest.raises(
            ValueError,
            match="compressed-key rows|recurrent|no multiple"):
        engine(cfg, params, **kw)


def test_an_engine_of_fewer_lanes_than_counters_starts(setup):
    """No plane: the counter row takes as many rows behind the round's
    tokens as its columns fill (until PR 64 three lanes were refused)."""
    cfg, params, _ = setup
    assert len(llama.stats_layout(cfg)) > 3
    assert engine(cfg, params, max_decode_slots=3).ecfg.max_decode_slots == 3


def test_the_other_planes_and_meshes_refuse_the_new_leaves(setup):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="compressed-key rows"):
        TpuEngine(cfg, EngineConfig(
            num_pages=16, page_size=PS, max_pages_per_seq=4,
            max_decode_slots=4, prefill_buckets=BUCKETS,
            cache_dtype="float32"), params=params,
            mesh_config=MeshConfig(tp=1), on_dispatch=lambda *a: None)
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="compressed-key rows"):
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
        lambda: llama.gather_pages(ctx1, z),
        lambda: llama.init_ctx(cfg, 1, 32, kv_quant="int8"),
    ):
        with pytest.raises(ValueError, match="recurrent"):
            call()
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    from dynamo_tpu.parallel.mesh import make_mesh
    for axes in ({"tp": 2}, {"ep": 2}):
        with pytest.raises(ValueError, match="not sharded over"):
            llama.param_shardings(
                cfg, make_mesh(MeshConfig(**axes), jax.devices()[:2]))


def test_the_movers_carry_compressed_keys_at_their_own_density(setup):
    """``row_kinds`` names ``kc`` beside ``k`` and ``v``: a sealed block
    takes its ``page_size / stride`` compressed rows with it, a page load
    puts them back, the flush (whose ring holds K and V only) and every
    mover pass over the matrix state."""
    cfg, _, _ = setup
    rng = np.random.RandomState(4)
    ctx = jax.tree.map(lambda a: jnp.asarray(rng.randn(*a.shape), a.dtype),
                       llama.init_ctx(cfg, 2, 32, jnp.float32))
    assert llama.row_kinds(ctx) == ("k", "kc", "v")
    assert llama.state_kinds(ctx) == ("lin_state",)
    cache = llama.init_cache(cfg, 4, PS, jnp.float32)
    assert cache["kc"].shape[3] == PS // GEO.stride
    one = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
    sealed = llama.seal_blocks(cache, ctx, one(1), one(16), one(2),
                               page_size=PS)
    assert set(sealed) == {"k", "kc", "v"}
    np.testing.assert_array_equal(sealed["k"][:, :, 2], ctx["k"][:, :, 1,
                                                                16:24])
    np.testing.assert_array_equal(sealed["kc"][:, :, 2], ctx["kc"][:, :, 1,
                                                                  8:12])
    loaded = llama.load_ctx_pages(
        jax.tree.map(jnp.copy, ctx), sealed, jnp.int32(0), one(2))
    np.testing.assert_array_equal(loaded["kc"][:, :, 0, :4],
                                  ctx["kc"][:, :, 1, 8:12])
    np.testing.assert_array_equal(loaded["k"][:, :, 0, :8],
                                  ctx["k"][:, :, 1, 16:24])
    for a, b in zip(loaded["lin_state"], ctx["lin_state"]):
        np.testing.assert_array_equal(a, b)
    ring = llama.init_ring(cfg, 2, 2, jnp.float32)
    flushed = llama.flush_ctx(jax.tree.map(jnp.copy, ctx), ring,
                              jnp.asarray([0, 1], jnp.int32),
                              jnp.asarray([4, 4], jnp.int32),
                              jnp.asarray([2, 2], jnp.int32))
    assert set(flushed) == {"k", "v"}
