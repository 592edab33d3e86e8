"""The plain reference the benchmark holds the served model to.

A Mistral/Llama-type decoder block as published (pre-norm RMSNorm,
rotary embeddings in the HF "rotate-half" layout, grouped-query causal
attention, SwiGLU), written in straightforward ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, the whole sequence at once,
no cache, no batching, no kernels, no padding. It imports nothing from
the program. It takes the engine's own weight arrays (so both sides
compute the same model) and dequantizes an int8 leaf as the scheme
defines it, ``W = q * s`` per output channel, one layer at a time so
that only one layer is ever held in float32.

Departures from the published model: none in the mathematics. Sliding
window is null in both configurations, and rope scaling is refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dense(w, axis: int) -> jnp.ndarray:
    """A float32 matrix from a bf16 leaf or an int8 ``{"q", "s"}`` leaf
    whose scale ``s`` lacks dimension ``axis`` of ``q``."""
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * jnp.expand_dims(
            w["s"].astype(jnp.float32), axis)
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, heads, hd]; HF layout: the two halves of hd pair up."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(hp: dict, lp: dict, h):
    """One decoder layer over the whole sequence h [T, H]."""
    nh, nkv, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
    T = h.shape[0]
    pos = jnp.arange(T)
    x = rms_norm(h, lp["ln1"].astype(jnp.float32), hp["eps"])
    q = (x @ dense(lp["wq"], 0)).reshape(T, nh, hd)
    k = (x @ dense(lp["wk"], 0)).reshape(T, nkv, hd)
    v = (x @ dense(lp["wv"], 0)).reshape(T, nkv, hd)
    q, k = rope(q, pos, hp["theta"]), rope(k, pos, hp["theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)      # query head i reads kv head
    v = jnp.repeat(v, nh // nkv, axis=1)      # i // (nh / nkv)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    h = h + a.reshape(T, nh * hd) @ dense(lp["wo"], 0)
    x = rms_norm(h, lp["ln2"].astype(jnp.float32), hp["eps"])
    gate = jax.nn.silu(x @ dense(lp["wg"], 0))
    return h + (gate * (x @ dense(lp["wu"], 0))) @ dense(lp["wd"], 0)


def logprobs(hf: dict, params: dict, tokens: list[int], positions: list[int]
             ) -> np.ndarray:
    """log-softmax over the vocabulary, [len(positions), V], of the next
    token after each of ``positions`` of ``tokens``."""
    if hf.get("rope_scaling") or hf.get("sliding_window"):
        raise ValueError("the reference has no rope scaling / window")
    nh = hf["num_attention_heads"]
    hp = {
        "heads": nh,
        "kv_heads": hf.get("num_key_value_heads", nh),
        "head_dim": hf.get("head_dim") or hf["hidden_size"] // nh,
        "theta": float(hf.get("rope_theta", 10000.0)),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
    }
    def embed(emb, toks):
        if isinstance(emb, dict):
            return (emb["q"][toks].astype(jnp.float32)
                    * emb["s"][toks].astype(jnp.float32)[:, None])
        return emb[toks].astype(jnp.float32)

    def head(norm_f, w, h, pos):
        h = rms_norm(h[pos], norm_f.astype(jnp.float32), hp["eps"])
        if hf.get("tie_word_embeddings"):
            logits = h @ dense(w, 1).T
        else:
            logits = h @ dense(w, 0)
        return jax.nn.log_softmax(logits, -1)

    # jitted only so that each piece is one program (and one entry of the
    # compile cache) instead of dozens of eager ops
    with jax.default_matmul_precision("highest"):
        h = jax.jit(embed)(params["embed"], jnp.asarray(tokens, jnp.int32))
        layer = jax.jit(lambda l, layers, h: _layer(
            hp, jax.tree.map(lambda a: a[l], layers), h))
        for l in range(hf["num_hidden_layers"]):
            h = layer(jnp.int32(l), params["layers"], h)
        w = params["embed" if hf.get("tie_word_embeddings") else "lm_head"]
        return np.asarray(jax.jit(head)(
            params["norm_f"], w, h, jnp.asarray(positions, jnp.int32)))
