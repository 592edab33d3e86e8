"""Resident LoRA adapter banks: many fine-tune variants, one engine.

The bank is a per-site stack of low-rank A/B factor pairs —
``{site: {"a": [N, L, d_in, r], "b": [N, L, r, d_out]}}`` — resident in
HBM alongside the base weights. It rides INSIDE the engine's params
pytree (``params["adapters"]``), so every existing jitted program
(fused round, prefill, batched prefill) carries it with zero signature
churn; the model functions look it up with ``params.get("adapters")``,
a trace-time presence check, so engines without a bank trace the
identical pre-tenancy programs.

Adapter 0 is the all-zeros identity — the base model, exactly: the
rank-r delta ``(x @ A) @ B`` is exactly 0.0 for zero factors, so
adapter_id=0 requests are greedy token-identical to an engine with no
bank at all. Per-slot adapter ids live in the device state
(``dev["adapter"]``), gathered inside the fused round program as a
batched row gather + rank-r einsum fused into the existing
qkv/o/mlp matmuls — mixed adapter ids in one decode batch cost zero
extra dispatches.

``AdapterRegistry`` maps servable variant model names to
``(base_model, adapter_id)`` so the frontend/model_resolver can route
variant requests onto the base engine with the right bank row.
"""
from __future__ import annotations

import threading
from typing import Any, Optional

import jax
import numpy as np

# weight sites carrying an adapter pair. The MoE expert stacks are NOT
# adapted (dense-dispatch einsums have no per-token weight identity);
# MoE models adapt attention only.
ATTN_SITES = ("wq", "wk", "wv", "wo")
MLP_SITES = ("wg", "wu", "wd")


def adapter_site_dims(config: Any) -> dict[str, tuple[int, int]]:
    """site -> (d_in, d_out) for the model's adaptable matmuls."""
    c = config
    dims = {
        "wq": (c.hidden_size, c.q_dim),
        "wk": (c.hidden_size, c.kv_dim),
        "wv": (c.hidden_size, c.kv_dim),
        "wo": (c.q_dim, c.hidden_size),
    }
    if c.moe is None:
        dims.update({
            "wg": (c.hidden_size, c.intermediate_size),
            "wu": (c.hidden_size, c.intermediate_size),
            "wd": (c.intermediate_size, c.hidden_size),
        })
    return dims


def init_adapter_bank(config: Any, n_adapters: int, rank: int):
    """Zero-initialized resident bank for ``n_adapters`` slots (id 0 =
    identity base model) at LoRA rank ``rank``. f32 factors — they cast
    to the activation dtype at the delta einsum, and the bank is tiny
    next to the base weights (2 * d * r per site-layer)."""
    import jax.numpy as jnp

    c = config
    n = max(1, int(n_adapters))
    r = max(1, int(rank))
    bank = {}
    for site, (d_in, d_out) in adapter_site_dims(c).items():
        bank[site] = {
            "a": jnp.zeros((n, c.num_layers, d_in, r), jnp.float32),
            "b": jnp.zeros((n, c.num_layers, r, d_out), jnp.float32),
        }
    return bank


def set_adapter(bank, adapter_id: int, weights: dict):
    """Functionally install one adapter's factors into the bank.

    ``weights`` maps site -> {"a": [L, d_in, r], "b": [L, r, d_out]}
    (numpy or jax arrays); sites absent from ``weights`` keep their
    current rows. Returns the updated bank (callers re-device_put /
    re-merge into params). Adapter 0 is the identity by contract —
    refusing to overwrite it keeps the base model addressable."""
    import jax.numpy as jnp

    aid = int(adapter_id)
    if aid == 0:
        raise ValueError("adapter 0 is the identity base model")
    out = {}
    for site, ab in bank.items():
        w = weights.get(site)
        if w is None:
            out[site] = ab
            continue
        a = jnp.asarray(np.asarray(w["a"], np.float32))
        b = jnp.asarray(np.asarray(w["b"], np.float32))
        if a.shape != ab["a"].shape[1:] or b.shape != ab["b"].shape[1:]:
            raise ValueError(
                f"adapter factors for site {site!r} have shape "
                f"{a.shape}/{b.shape}, bank rows are "
                f"{ab['a'].shape[1:]}/{ab['b'].shape[1:]}"
            )
        out[site] = {
            "a": ab["a"].at[aid].set(a),
            "b": ab["b"].at[aid].set(b),
        }
    return out


def random_adapter(config: Any, rank: int, seed: int = 0,
                   scale: float = 0.05) -> dict:
    """Small random factors for every site — test fixture for a
    visibly non-identity adapter."""
    rng = np.random.default_rng(seed)
    c = config
    out = {}
    for site, (d_in, d_out) in adapter_site_dims(c).items():
        out[site] = {
            "a": rng.standard_normal(
                (c.num_layers, d_in, rank)).astype(np.float32) * scale,
            "b": rng.standard_normal(
                (c.num_layers, rank, d_out)).astype(np.float32) * scale,
        }
    return out


def replicate_bank(bank, mesh):
    """Device-put the bank fully replicated (it is tiny; replication
    keeps the delta einsums local to every shard of the base matmul)."""
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.tree.map(lambda x: jax.device_put(x, sh), bank)


class AdapterRegistry:
    """Servable variant names -> (base model, adapter id).

    The frontend registers each fine-tune variant as its own model name
    (``my-org/base:support-bot``); resolution hands back the base chain
    plus the bank row to stamp onto the request. Thread-safe — the
    watcher registers from asyncio while handlers resolve."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._variants: dict[str, tuple[str, int]] = {}

    def register(self, name: str, base: str, adapter_id: int) -> None:
        if int(adapter_id) <= 0:
            raise ValueError(
                "variant adapter ids start at 1 (0 is the base model)"
            )
        with self._lock:
            self._variants[name] = (base, int(adapter_id))

    def unregister(self, name: str) -> None:
        with self._lock:
            self._variants.pop(name, None)

    def resolve(self, name: str) -> Optional[tuple[str, int]]:
        with self._lock:
            return self._variants.get(name)

    def variants(self) -> dict[str, tuple[str, int]]:
        with self._lock:
            return dict(self._variants)
