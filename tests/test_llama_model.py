"""Golden tests: our contiguous-ctx llama forward vs HuggingFace.

The reference gets model correctness for free from vLLM; we validate ours
against the HF torch implementation on a tiny random-init config (float32 so
comparisons are tight). Covers: full prefill, decode steps, prefix-hit
continuation prefill, pool<->ctx copies (load_ctx_pages/seal_blocks), and
GSPMD-sharded execution on the CPU test mesh.
"""
import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import REFERENCE
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

PAGE = 8
S_MAX = 64


@pytest.fixture(scope="module")
def pair():
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = ModelConfig.tiny(dtype="float32")
    hf_cfg = LlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta,
        max_position_embeddings=cfg.max_position_embeddings,
        tie_word_embeddings=False,
        attention_bias=False,
        mlp_bias=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg).eval()
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    params = llama.params_from_state_dict(cfg, sd, dtype="float32")
    return cfg, model, params


def hf_logits(model, tokens: list[int]) -> np.ndarray:
    with torch.no_grad():
        out = model(torch.tensor([tokens])).logits
    return out[0].float().numpy()  # [T, V]


def pad_to(tokens: list[int], mult: int) -> np.ndarray:
    t = list(tokens)
    while len(t) % mult:
        t.append(0)
    return np.asarray(t, np.int32)


def test_prefill_matches_hf(pair):
    cfg, model, params = pair
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, cfg.vocab_size, size=21).tolist()

    ctx = llama.init_ctx(cfg, 1, S_MAX, dtype=jnp.float32)
    ctx, logits = llama.prefill(
        cfg, params, ctx,
        jnp.asarray(pad_to(prompt, PAGE)),
        jnp.int32(0), jnp.int32(0), jnp.int32(len(prompt)),
    )
    ref = hf_logits(model, prompt)[-1]
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=2e-3, atol=2e-3)


def test_decode_matches_hf(pair):
    cfg, model, params = pair
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, cfg.vocab_size, size=13).tolist()

    # B=2 slots; slot 1 inactive (scratch-destined garbage lane)
    B = 2
    ctx = llama.init_ctx(cfg, B, S_MAX, dtype=jnp.float32)
    ctx, logits = llama.prefill(
        cfg, params, ctx,
        jnp.asarray(pad_to(prompt, PAGE)),
        jnp.int32(0), jnp.int32(0), jnp.int32(len(prompt)),
    )
    seq = list(prompt)
    tok = int(np.argmax(np.asarray(logits)))
    R = 2  # rounds of 2 ring steps then a flush: exercises both tiers
    ring = llama.init_ring(cfg, B, R, dtype=jnp.float32)
    dest = jnp.asarray([0, B], jnp.int32)  # slot 1 -> scratch lane
    for _ in range(3):
        ring_base = jnp.asarray([len(seq), 0], jnp.int32)
        for s in range(R):
            seq.append(tok)
            tokens = jnp.asarray([tok, 0], jnp.int32)
            ctx_lens = jnp.asarray([len(seq), 1], jnp.int32)
            ring, logits = llama.decode_step(
                cfg, params, ctx, ring, tokens, ctx_lens,
                ring_base, jnp.int32(s), attn=REFERENCE,
            )
            ref = hf_logits(model, seq)[-1]
            got = np.asarray(logits)[0]
            np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
            tok = int(np.argmax(got))
        ctx = llama.flush_ctx(
            ctx, ring, dest, ring_base, jnp.asarray([R, 0], jnp.int32),
        )


def test_prefix_continuation_matches_hf(pair):
    """Prefix-cache hit path: prefill 16 cached tokens, then continue with 5
    new ones; logits must equal a fresh full-21-token forward."""
    cfg, model, params = pair
    rng = np.random.RandomState(3)
    full = rng.randint(1, cfg.vocab_size, size=21).tolist()

    ctx = llama.init_ctx(cfg, 1, S_MAX, dtype=jnp.float32)
    # stage 1: the "cached prefix" (16 tokens = 2 pages, page-aligned)
    ctx, _ = llama.prefill(
        cfg, params, ctx,
        jnp.asarray(pad_to(full[:16], PAGE)),
        jnp.int32(0), jnp.int32(0), jnp.int32(16),
    )
    # stage 2: continuation of the remaining 5 tokens
    ctx, logits = llama.prefill(
        cfg, params, ctx,
        jnp.asarray(pad_to(full[16:], PAGE)),
        jnp.int32(0), jnp.int32(16), jnp.int32(21),
    )
    ref = hf_logits(model, full)[-1]
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=2e-3, atol=2e-3)


def test_seal_and_reload_roundtrip(pair):
    """seal_blocks (ctx->pool) then load_ctx_pages (pool->ctx) on another
    lane must reproduce the continuation logits exactly — the admission/
    commit data path of the prefix cache."""
    cfg, model, params = pair
    rng = np.random.RandomState(5)
    full = rng.randint(1, cfg.vocab_size, size=21).tolist()

    ctx = llama.init_ctx(cfg, 2, S_MAX, dtype=jnp.float32)
    cache = llama.init_cache(cfg, num_pages=8, page_size=PAGE,
                             dtype=jnp.float32)
    # prefill the 16-token page-aligned prefix on lane 0
    ctx, _ = llama.prefill(
        cfg, params, ctx,
        jnp.asarray(pad_to(full[:16], PAGE)),
        jnp.int32(0), jnp.int32(0), jnp.int32(16),
    )
    # seal its two blocks into pool pages 3 and 4
    cache = llama.seal_blocks(
        cache, ctx,
        jnp.asarray([0, 0], jnp.int32),
        jnp.asarray([0, PAGE], jnp.int32),
        jnp.asarray([3, 4], jnp.int32),
        page_size=PAGE,
    )
    # load them into lane 1 and continue there
    ctx = llama.load_ctx_pages(
        ctx, cache, jnp.int32(1), jnp.asarray([3, 4], jnp.int32)
    )
    ctx, logits = llama.prefill(
        cfg, params, ctx,
        jnp.asarray(pad_to(full[16:], PAGE)),
        jnp.int32(1), jnp.int32(16), jnp.int32(21),
    )
    ref = hf_logits(model, full)[-1]
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=2e-3, atol=2e-3)


def test_sharded_prefill_matches_unsharded(pair):
    """TP=2 GSPMD execution must be numerically equivalent (CPU mesh)."""
    cfg, _, params = pair
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    shardings = llama.param_shardings(cfg, mesh)
    params_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, s), params, shardings
    )
    ctx = llama.init_ctx(cfg, 1, S_MAX, dtype=jnp.float32)
    ctx_sh = jax.tree.map(
        lambda x, s: jax.device_put(x, s),
        llama.init_ctx(cfg, 1, S_MAX, dtype=jnp.float32),
        llama.ctx_shardings(cfg, mesh),
    )
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, cfg.vocab_size, size=10).tolist()
    args = (
        jnp.asarray(pad_to(prompt, PAGE)), jnp.int32(0),
        jnp.int32(0), jnp.int32(len(prompt)),
    )
    _, ref = llama.prefill(cfg, params, ctx, *args)
    with mesh:
        _, got = llama.prefill(cfg, params_sh, ctx_sh, *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_load_ctx_pages_pow2_clamp_at_bench_r05_shape():
    """Regression pin for a crash in a run's tail: a 46-page matched run
    pow2-padded to 64 pages (update span 64*64 = 4096 tokens) loaded into
    a ctx region of S = 3328 (52 pages) must clamp statically to the
    region — the unclamped dynamic_update_slice was a trace-time
    TypeError ("update shape must be smaller than operand shape ...
    (…, 4096, …) for operand (…, 3328, …)") that killed the whole engine
    round. Geometry is EXACTLY the r05 shape; L/kvh/hd are shrunk (the
    crash class lives on the page/region axes alone)."""
    L, kvh, hd = 1, 1, 4
    ps, S = 64, 3328          # 52-page region (r05 ctx region)
    n_real, pad_w = 46, 64    # 46 matched pages -> pow2_cover 64
    rng = np.random.RandomState(0)
    cache = {
        name: jnp.asarray(rng.standard_normal(
            (L, kvh, pad_w + 1, ps, hd)).astype(np.float32))
        for name in ("k", "v")
    }
    want = {name: np.asarray(cache[name][:, :, 1:n_real + 1]).reshape(
        L, kvh, n_real * ps, hd) for name in ("k", "v")}
    ctx = {name: jnp.zeros((L, kvh, 2, S, hd), jnp.float32)
           for name in ("k", "v")}
    padded = np.zeros(pad_w, np.int32)  # padding -> scratch page 0
    padded[:n_real] = np.arange(1, n_real + 1)
    out = llama.load_ctx_pages(
        ctx, cache, jnp.int32(0), jnp.asarray(padded)
    )
    for name in ("k", "v"):
        assert out[name].shape == (L, kvh, 2, S, hd)
        # every real matched page landed at its region position; only
        # the padding overflow (pages 53..64) was dropped
        np.testing.assert_array_equal(
            np.asarray(out[name][:, :, 0, : n_real * ps]), want[name]
        )
