"""Rotary position embeddings (HF llama "rotate-half" convention, incl.
llama3 frequency scaling), and YaRN as DeepSeek-V3's config.json
parameterises it (the latent-attention block, models/mla_moe.py); and a
rule a layer KIND for a stack whose kinds rotate differently
(``kind_rotary``, ``apply_rope_leading``: models/ssm_moe.py)."""
from __future__ import annotations

import math
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np


def rope_inv_freq(
    head_dim: int,
    theta: float,
    scaling: Optional[dict[str, Any]] = None,
) -> np.ndarray:
    """Inverse frequencies [head_dim/2], with optional llama3 NTK scaling."""
    inv_freq = 1.0 / (
        theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    )
    if scaling and scaling.get("rope_type", scaling.get("type")) == "llama3":
        factor = scaling["factor"]
        low = scaling["low_freq_factor"]
        high = scaling["high_freq_factor"]
        orig = scaling["original_max_position_embeddings"]
        wavelen = 2 * np.pi / inv_freq
        low_wavelen = orig / low
        high_wavelen = orig / high
        scaled = np.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
        smooth = (orig / wavelen - low) / (high - low)
        mid = (1 - smooth) * inv_freq / factor + smooth * inv_freq
        is_mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
        inv_freq = np.where(is_mid, mid, scaled)
    return inv_freq.astype(np.float32)


def yarn_inv_freq(head_dim: int, theta: float,
                  scaling: dict[str, Any]) -> np.ndarray:
    """YaRN's inverse frequencies [head_dim/2]: dimensions that turn more
    than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are slowed
    by ``factor``, and a linear ramp over the dimension index joins them
    (DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding``)."""
    factor = float(scaling["factor"])
    orig = scaling["original_max_position_embeddings"]

    def turns_at(n_rot: float) -> float:
        """The (fractional) dimension index that turns n_rot times."""
        return (head_dim * math.log(orig / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_at(scaling["beta_slow"])), head_dim - 1)
    i = np.arange(head_dim // 2, dtype=np.float64)
    extra = 1.0 / theta ** (2 * i / head_dim)
    # equal bounds: the published code widens the ramp by 0.001
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 mscale ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_cos_sin(positions: jnp.ndarray, inv_freq: jnp.ndarray):
    """cos/sin tables for given positions. positions [...], -> [..., head_dim]."""
    freqs = positions[..., None].astype(jnp.float32) * inv_freq  # [..., hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [..., n_heads, head_dim]; cos/sin: [..., head_dim] (broadcast over heads)."""
    c = cos[..., None, :]
    s = sin[..., None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * c + rotated.astype(jnp.float32) * s).astype(x.dtype)


def kind_rotary(head_dim: int, rule: dict[str, Any]):
    """One layer KIND's rotary rule (a stack whose kinds rotate
    differently: models/ssm_moe.py) -> (inverse frequencies [rot / 2], the
    factor on cos and sin). ``rule``: ``theta``, ``rot`` (the leading
    dimensions of the head that rotate, rotate-half among themselves; the
    rest pass through) and ``type``: ``default``, or ``yarn`` with
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow`` and ``attention_factor`` (YaRN over the ROTATED
    dimensions; its factor rides on cos and sin, so it scales the rotated
    part of q and of k and not the part that passes through: with part of
    the head rotated that is no factor on the softmax scale)."""
    rot, theta = int(rule["rot"]), float(rule["theta"])
    if rot % 2 or not 0 < rot <= head_dim:
        raise ValueError(f"{rot} rotated dimensions of a head of {head_dim}")
    if rule["type"] == "yarn":
        return yarn_inv_freq(rot, theta, rule), float(rule["attention_factor"])
    return rope_inv_freq(rot, theta), 1.0


def apply_rope_leading(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                       factor: float = 1.0) -> jnp.ndarray:
    """``apply_rope`` over the first ``cos.shape[-1]`` dimensions of the
    head, cos and sin x ``factor``; the dimensions past them pass through.
    x: [..., n_heads, head_dim]; cos / sin: [..., rot]."""
    rot = cos.shape[-1]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
