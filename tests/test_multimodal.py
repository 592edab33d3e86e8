"""Multimodal E/P/D graph tests (reference examples/multimodal:
encode_worker.py:148, 3-stage disaggregation): vision tower -> embedding
transfer over the runtime -> prefill consumes image embeddings -> decode
produces the caption."""
import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import REFERENCE
from dynamo_tpu.models.vision import (
    VisionConfig,
    encode_image,
    init_vision_params,
)
from dynamo_tpu.multimodal import (
    EncodeWorker,
    MultimodalEngine,
    encode_image_payload,
)
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions

PS = 16
IMG_TOK = 7   # placeholder token id used in prompts


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny(dtype="float32")
    vcfg = VisionConfig.tiny(out_hidden_size=cfg.hidden_size)
    vparams = init_vision_params(vcfg, 0)
    params = llama.init_params(cfg, 0)
    ecfg = EngineConfig(
        num_pages=32, page_size=PS, max_pages_per_seq=8,
        max_decode_slots=2, prefill_buckets=(32, 64),
        cache_dtype="float32",
    )
    return cfg, vcfg, params, vparams, ecfg


def image(seed):
    rng = np.random.RandomState(seed)
    return rng.rand(16, 16, 3).astype(np.float32)


def mm_prompt(vcfg):
    """Prompt: 4 text tokens, then num_patches image placeholders, then
    3 more text tokens. Returns (tokens, image_pos)."""
    n = vcfg.num_patches
    toks = [1, 2, 3, 4] + [IMG_TOK] * n + [5, 6, 8]
    return toks, 4


def mm_request(vcfg, img, n_new=6):
    toks, pos = mm_prompt(vcfg)
    return PreprocessedRequest(
        token_ids=toks,
        stop_conditions=StopConditions(max_tokens=n_new, ignore_eos=True),
        multimodal={"images": [dict(encode_image_payload(img), pos=pos)]},
    )


async def collect(engine, req):
    toks = []
    async for out in engine.generate(req):
        toks.extend(out.token_ids)
    return toks


def test_vision_encoder_shapes(setup):
    cfg, vcfg, _, vparams, _ = setup
    out = encode_image(vcfg, vparams, jnp.asarray(image(0)))
    assert out.shape == (vcfg.num_patches, cfg.hidden_size)
    assert np.isfinite(np.asarray(out)).all()
    # different images -> different embeddings
    out2 = encode_image(vcfg, vparams, jnp.asarray(image(1)))
    assert not np.allclose(np.asarray(out), np.asarray(out2))


async def test_multimodal_e2e_inprocess(setup):
    """image -> encode -> prefill(inject) -> decode, against a manual
    reference computed with llama.prefill + explicit embeds."""
    cfg, vcfg, params, vparams, ecfg = setup
    rt = None
    inner = TpuEngine(cfg, ecfg, params=params, mesh_config=MeshConfig(tp=1))
    enc = EncodeWorker(rt, vcfg, vparams)
    eng = MultimodalEngine(inner, local_encoder=enc)

    img = image(0)
    out = await collect(eng, mm_request(vcfg, img))
    assert len(out) == 6
    assert eng.images_resolved == 1

    # manual reference: same embeds through the raw model
    emb = np.asarray(encode_image(vcfg, vparams, jnp.asarray(img)),
                     np.float32)
    toks, pos = mm_prompt(vcfg)
    T = 32
    padded = np.zeros(T, np.int32)
    padded[: len(toks)] = toks
    ov = np.zeros((T, cfg.hidden_size), np.float32)
    msk = np.zeros(T, bool)
    ov[pos: pos + len(emb)] = emb
    msk[pos: pos + len(emb)] = True
    ctx = llama.init_ctx(cfg, 1, ecfg.max_context, jnp.float32)
    ctx, logits = llama.prefill(
        cfg, params, ctx, jnp.asarray(padded), jnp.int32(0),
        jnp.int32(0), jnp.int32(len(toks)),
        jnp.asarray(ov), jnp.asarray(msk),
    )
    ref = [int(np.argmax(np.asarray(logits)))]
    seq_len = len(toks)
    ring = llama.init_ring(cfg, 1, 1, dtype=jnp.float32)
    for _ in range(5):
        seq_len += 1
        rb = jnp.asarray([seq_len - 1], jnp.int32)
        ring, lg = llama.decode_step(
            cfg, params, ctx, ring, jnp.asarray([ref[-1]], jnp.int32),
            jnp.asarray([seq_len], jnp.int32), rb, jnp.int32(0),
            attn=REFERENCE,
        )
        ctx = llama.flush_ctx(ctx, ring, jnp.asarray([0], jnp.int32), rb,
                              jnp.asarray([1], jnp.int32))
        ref.append(int(np.argmax(np.asarray(lg)[0])))
    assert out == ref, "engine must match the explicit-embeds reference"

    # different image -> different prefill logits (embeddings really
    # reach the model; tiny random models may still argmax identically,
    # so compare the distribution, not sampled tokens)
    emb_b = np.asarray(encode_image(vcfg, vparams, jnp.asarray(image(1))),
                       np.float32)
    ov_b = ov.copy()
    ov_b[pos: pos + len(emb_b)] = emb_b
    ctx2 = llama.init_ctx(cfg, 1, ecfg.max_context, jnp.float32)
    _, logits_b = llama.prefill(
        cfg, params, ctx2, jnp.asarray(padded), jnp.int32(0),
        jnp.int32(0), jnp.int32(len(toks)),
        jnp.asarray(ov_b), jnp.asarray(msk),
    )
    assert not np.allclose(np.asarray(logits), np.asarray(logits_b))

    # same image again: prefix-cache may hit, output must stay identical
    out_c = await collect(eng, mm_request(vcfg, img))
    assert out_c == out
    await eng.stop()


async def test_multimodal_digest_prevents_cross_image_cache_hits(setup):
    """Two requests with IDENTICAL placeholder tokens but different images
    must not share prefix-cache blocks (the digest salt)."""
    cfg, vcfg, params, vparams, ecfg = setup
    inner = TpuEngine(cfg, ecfg, params=params, mesh_config=MeshConfig(tp=1))
    enc = EncodeWorker(None, vcfg, vparams)
    eng = MultimodalEngine(inner, local_encoder=enc)

    out_a = await collect(eng, mm_request(vcfg, image(0)))
    hits_before = inner.allocator.hit_blocks
    out_b = await collect(eng, mm_request(vcfg, image(1)))
    assert inner.allocator.hit_blocks == hits_before, \
        "different image must MISS the prefix cache"
    assert len(out_b) == len(out_a) == 6
    await eng.stop()


async def test_multimodal_over_distributed_runtime(setup):
    """Full graph: encode worker registered on the runtime; the decode
    side resolves embeddings over the encode ENDPOINT (the reference's
    worker-to-worker embedding handoff)."""
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.store import serve_store

    cfg, vcfg, params, vparams, ecfg = setup
    server, store = await serve_store(port=0, sweep_interval_s=0.05)
    port = server.sockets[0].getsockname()[1]
    rt_enc = await DistributedRuntime.connect(port=port)
    rt_dec = await DistributedRuntime.connect(port=port)
    enc = await EncodeWorker(rt_enc, vcfg, vparams, namespace="mm").start()
    inner = TpuEngine(cfg, ecfg, params=params, mesh_config=MeshConfig(tp=1))
    eng = MultimodalEngine(inner, rt=rt_dec, namespace="mm")
    try:
        out = await collect(eng, mm_request(vcfg, image(0)))
        assert len(out) == 6
        assert enc.images_encoded == 1
    finally:
        await eng.stop()
        await enc.stop()
        await rt_dec.close()
        await rt_enc.close()
        server.close()


async def test_multimodal_http_image_lowering(setup):
    """HTTP surface: a chat message with an image content part is lowered
    to placeholder tokens + encode-worker payload by the preprocessor,
    resolved by the MultimodalEngine, and served end to end."""
    from aiohttp.test_utils import TestClient, TestServer

    from dynamo_tpu.backend import Backend
    from dynamo_tpu.frontend import HttpService, ModelChain, ModelManager
    from dynamo_tpu.preprocessor import OpenAIPreprocessor, PromptFormatter
    from dynamo_tpu.tokenizer import make_test_tokenizer

    cfg, vcfg, params, vparams, ecfg = setup
    tok = make_test_tokenizer([f"w{i}" for i in range(60)])
    fmt = PromptFormatter(
        template="{% for m in messages %}{{ m.content }}{% endfor %}"
    )
    inner = TpuEngine(cfg, ecfg, params=params, mesh_config=MeshConfig(tp=1))
    eng = MultimodalEngine(inner, local_encoder=EncodeWorker(None, vcfg, vparams))
    chain = ModelChain(
        name="mm",
        preprocessor=OpenAIPreprocessor(
            tokenizer=tok, formatter=fmt, model_name="mm",
            image_token_id=IMG_TOK, image_token_count=vcfg.num_patches,
        ),
        engine=eng,
        backend=Backend(tok),
    )
    manager = ModelManager()
    manager.register(chain)
    svc = HttpService(manager)
    client = TestClient(TestServer(svc.app))
    await client.start_server()
    try:
        img = image(3)
        payload = encode_image_payload(img)
        r = await client.post("/v1/chat/completions", json={
            "model": "mm",
            "messages": [{"role": "user", "content": [
                {"type": "text", "text": "w1 w2 "},
                {"type": "image_data", "data": payload["data"],
                 "shape": payload["shape"]},
                {"type": "text", "text": " w3"},
            ]}],
            "max_tokens": 5,
        })
        assert r.status == 200
        body = await r.json()
        assert body["usage"]["completion_tokens"] == 5
        # prompt tokens include the placeholder run
        assert body["usage"]["prompt_tokens"] >= vcfg.num_patches + 3
        assert eng.images_resolved == 1
        # non-data image URLs are rejected (zero-egress host)
        r2 = await client.post("/v1/chat/completions", json={
            "model": "mm",
            "messages": [{"role": "user", "content": [
                {"type": "image_url",
                 "image_url": {"url": "https://example.com/x.png"}},
            ]}],
            "max_tokens": 2,
        })
        assert r2.status == 400
    finally:
        await client.close()
        await eng.stop()


def test_vision_clip_checkpoint_roundtrip(tmp_path):
    """A CLIP-shape vision tower + LLaVA projector written as safetensors
    loads into the param tree with the right transposes (conv->patch
    matmul, torch [out,in] -> [in,out]) and runs a forward pass
    (reference: the encode worker serves a real LLaVA/Qwen-VL tower)."""
    import numpy as np
    from safetensors.numpy import save_file

    from dynamo_tpu.models.vision import (
        VisionConfig,
        encode_image,
        load_vision_params,
    )

    cfg = VisionConfig.tiny(use_class_token=True)
    rng = np.random.RandomState(0)
    H, I, P = cfg.hidden_size, cfg.intermediate_size, cfg.patch_size
    OUT = cfg.out_hidden_size
    sd = {
        "vision_tower.vision_model.embeddings.patch_embedding.weight":
            rng.randn(H, 3, P, P).astype(np.float32) * 0.05,
        "vision_tower.vision_model.embeddings.class_embedding":
            rng.randn(H).astype(np.float32) * 0.02,
        "vision_tower.vision_model.embeddings.position_embedding.weight":
            rng.randn(cfg.num_positions, H).astype(np.float32) * 0.02,
        "vision_tower.vision_model.pre_layrnorm.weight":
            np.ones(H, np.float32),
        "vision_tower.vision_model.pre_layrnorm.bias":
            np.zeros(H, np.float32),
        "vision_tower.vision_model.post_layernorm.weight":
            np.ones(H, np.float32),
        "vision_tower.vision_model.post_layernorm.bias":
            np.zeros(H, np.float32),
        "multi_modal_projector.linear_1.weight":
            rng.randn(OUT, H).astype(np.float32) * 0.05,
        "multi_modal_projector.linear_1.bias":
            np.zeros(OUT, np.float32),
        "multi_modal_projector.linear_2.weight":
            rng.randn(OUT, OUT).astype(np.float32) * 0.05,
        "multi_modal_projector.linear_2.bias":
            np.zeros(OUT, np.float32),
    }
    for l in range(cfg.num_layers):
        p = f"vision_tower.vision_model.encoder.layers.{l}."
        for nm, shp in (("layer_norm1", H), ("layer_norm2", H)):
            sd[p + nm + ".weight"] = np.ones(shp, np.float32)
            sd[p + nm + ".bias"] = np.zeros(shp, np.float32)
        for nm in ("q_proj", "k_proj", "v_proj", "out_proj"):
            sd[p + f"self_attn.{nm}.weight"] = (
                rng.randn(H, H).astype(np.float32) * 0.05)
            sd[p + f"self_attn.{nm}.bias"] = np.zeros(H, np.float32)
        sd[p + "mlp.fc1.weight"] = rng.randn(I, H).astype(np.float32) * 0.05
        sd[p + "mlp.fc1.bias"] = np.zeros(I, np.float32)
        sd[p + "mlp.fc2.weight"] = rng.randn(H, I).astype(np.float32) * 0.05
        sd[p + "mlp.fc2.bias"] = np.zeros(H, np.float32)
    save_file(sd, str(tmp_path / "model.safetensors"))

    params = load_vision_params(cfg, str(tmp_path))
    # transposes verified leaf-wise
    np.testing.assert_allclose(
        np.asarray(params["layers"]["wq"][0]),
        sd["vision_tower.vision_model.encoder.layers.0.self_attn.q_proj.weight"].T,
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(params["proj"]),
        sd["multi_modal_projector.linear_1.weight"].T, rtol=1e-6,
    )
    conv = sd["vision_tower.vision_model.embeddings.patch_embedding.weight"]
    np.testing.assert_allclose(
        np.asarray(params["patch_embed"]),
        conv.transpose(2, 3, 1, 0).reshape(cfg.patch_dim, H), rtol=1e-6,
    )
    img = np.random.RandomState(1).rand(
        cfg.image_size, cfg.image_size, 3).astype(np.float32)
    out = np.asarray(encode_image(cfg, params, img))
    assert out.shape == (cfg.num_patches, OUT)
    assert np.isfinite(out).all()


async def test_rpc_embeddings_travel_as_array_frames(setup):
    """Over the distributed runtime, embeddings must ride the frame2
    array channel (tickets), not JSON float lists."""
    import numpy as np

    from dynamo_tpu.kv_transfer import take_remote_array
    from dynamo_tpu.multimodal import EncodeWorker, encode_image_payload
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.store import serve_store

    cfg, vcfg, _params, vparams, _ecfg = setup
    server, _ = await serve_store(port=0, sweep_interval_s=0.1)
    port = server.sockets[0].getsockname()[1]
    rt = await DistributedRuntime.connect(port=port)
    enc = await EncodeWorker(rt, vcfg, vparams).start()
    try:
        img = encode_image_payload(
            np.random.RandomState(0).rand(16, 16, 3).astype(np.float32))
        client = await rt.namespace("dynamo").component(
            "encoder").endpoint("encode").client()
        resp = None
        async for item in client.generate({"images": [img, img]}):
            resp = item
        ents = resp["embeddings"]
        assert all("ticket" in e and "data" not in e for e in ents)
        arr = await take_remote_array(
            ents[0]["host"], ents[0]["port"], ents[0]["ticket"])
        assert arr.shape == tuple(ents[0]["shape"])
        assert arr.dtype == np.float32
        # tickets are one-shot
        import pytest as _pytest

        from dynamo_tpu.kv_transfer import BlockTransferError
        with _pytest.raises(BlockTransferError):
            await take_remote_array(
                ents[0]["host"], ents[0]["port"], ents[0]["ticket"])
    finally:
        await enc.stop()
        await rt.close()
        server.close()
