"""Mixture-of-Experts layer with expert parallelism over the `ep` mesh
axis.

Parity target: the reference's wide-EP story is SGLang+DeepEP on 104 GPUs
(SURVEY §2.5 EP row, examples/sglang/dsr1-wideep.md) — DP attention with
expert-parallel MoE and all_to_all dispatch. TPU-native redesign
(GShard/Switch-style): tokens are sharded over `ep`; each device routes
its tokens top-k, packs them into a capacity-bounded dispatch tensor
[E, C, H], exchanges slices with `jax.lax.all_to_all` over ICI, runs its
LOCAL experts as one batched einsum (E_local lanes on the MXU), and
all_to_alls results back for the weighted combine. Per-device memory is
O(E_local) expert weights + O(E·C) activations; overflow beyond capacity
is dropped (standard GShard semantics).

Shapes (per device, inside shard_map; n = ep size):
  h:    [Tl, H]            tokens on this shard
  wr:   [H, E]             router (replicated)
  wg/wu:[E_local, H, I]    local experts' gate/up
  wd:   [E_local, I, H]    local experts' down
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MoEConfig:
    hidden_size: int
    intermediate_size: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25

    def capacity(self, tokens_per_shard: int) -> int:
        """Per-expert, per-source-shard token slots. capacity_factor <= 0
        means DROPLESS: every (token, pick) gets a slot (C = T*K). That is
        the serving default — capacity drops make a token's activations
        depend on what it was co-batched with, which breaks prefix-cache
        reproducibility (a resend recomputing a chunk alone would get
        different KV than the original). Capacity-bounded mode is for
        throughput-oriented deployments that accept the approximation."""
        if self.capacity_factor <= 0:
            return max(tokens_per_shard * self.top_k, 1)
        c = math.ceil(
            tokens_per_shard * self.top_k * self.capacity_factor
            / self.num_experts
        )
        return max(c, 1)


def init_moe_params(cfg: MoEConfig, rng: jax.Array | int = 0,
                    dtype=jnp.float32) -> dict:
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    E, H, I = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size

    def rnd(k, *s):
        return (jax.random.normal(k, s, jnp.float32)
                / np.sqrt(s[-2])).astype(dtype)

    return {
        "wr": rnd(k1, H, E),
        "wg": rnd(k2, E, H, I),
        "wu": rnd(k3, E, H, I),
        "wd": rnd(k4, E, I, H),
    }


def moe_params_shardings(mesh: Mesh) -> dict:
    """Experts shard over ep; the router is replicated."""
    return {
        "wr": NamedSharding(mesh, P(None, None)),
        "wg": NamedSharding(mesh, P("ep", None, None)),
        "wu": NamedSharding(mesh, P("ep", None, None)),
        "wd": NamedSharding(mesh, P("ep", None, None)),
    }


def _expert_ffn(x, wg, wu, wd):
    # x [E_local, S, H]; one batched einsum per projection: E_local lanes
    g = jnp.einsum("esh,ehi->esi", x, wg)
    u = jnp.einsum("esh,ehi->esi", x, wu)
    return jnp.einsum("esi,eih->esh", jax.nn.silu(g) * u, wd)


def moe_layer(
    h: jnp.ndarray,        # [T, H], sharded over ep on T
    params: dict,
    cfg: MoEConfig,
    mesh: Mesh,
    axis: str = "ep",
) -> jnp.ndarray:
    """Top-k routed MoE FFN with all_to_all expert dispatch. Returns
    [T, H] with the same sharding as `h`."""
    n = mesh.shape[axis]
    T = h.shape[0]
    if T % n:
        raise ValueError(f"tokens {T} not divisible by ep={n}")
    if cfg.num_experts % n:
        raise ValueError(
            f"experts {cfg.num_experts} not divisible by ep={n}"
        )
    Tl = T // n
    run = _build_moe(mesh, axis, cfg, n, Tl)
    return run(h, params["wr"], params["wg"], params["wu"], params["wd"])


@functools.lru_cache(maxsize=64)
def _build_moe(mesh: Mesh, axis: str, cfg: MoEConfig, n: int, Tl: int):
    """Cached shard_map program per (mesh, axis, config, geometry) — a
    fresh closure per call would re-trace every layer every step."""
    E = cfg.num_experts
    E_local = E // n
    K = cfg.top_k
    C = cfg.capacity(Tl)
    H = cfg.hidden_size

    tok_spec = P(axis, None)
    exp_spec = P(axis, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(tok_spec, P(None, None), exp_spec, exp_spec, exp_spec),
        out_specs=tok_spec,
    )
    def run(hl, wr, wg, wu, wd):
        # ---- route ----
        logits = (hl @ wr).astype(jnp.float32)          # [Tl, E]
        gates = jax.nn.softmax(logits, axis=-1)
        gate_w, sel = jax.lax.top_k(gates, K)           # [Tl, K]
        gate_w = gate_w / jnp.maximum(
            gate_w.sum(-1, keepdims=True), 1e-9
        )

        # ---- pack into the capacity-bounded dispatch tensor ----
        sel_f = sel.reshape(-1)                          # [Tl*K]
        onehot = jax.nn.one_hot(sel_f, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot        # arrival order
        pos_f = jnp.sum(pos * onehot, axis=-1)           # [Tl*K]
        keep = pos_f < C
        pos_c = jnp.minimum(pos_f, C - 1)
        h_rep = jnp.repeat(hl, K, axis=0)                # [Tl*K, H]
        contrib = jnp.where(keep[:, None], h_rep, 0).astype(hl.dtype)
        disp = jnp.zeros((E, C, H), hl.dtype).at[sel_f, pos_c].add(contrib)

        # ---- all_to_all: every shard sends each expert-slice home ----
        # [E, C, H] -> [n, E_local, C, H]; slice j goes to device j
        recv = jax.lax.all_to_all(
            disp.reshape(n, E_local, C, H), axis, 0, 0
        )                                                # [n, E_local, C, H]
        xin = recv.transpose(1, 0, 2, 3).reshape(E_local, n * C, H)

        # ---- local experts, one batched einsum ----
        y = _expert_ffn(xin, wg, wu, wd)                 # [E_local, n*C, H]

        # ---- return results to their source shards ----
        back = jax.lax.all_to_all(
            y.reshape(E_local, n, C, H).transpose(1, 0, 2, 3), axis, 0, 0
        )                                                # [n, E_local, C, H]
        out_ecH = back.reshape(E, C, H)

        # ---- weighted combine ----
        picked = out_ecH[sel_f, pos_c]                   # [Tl*K, H]
        picked = jnp.where(keep[:, None], picked, 0)
        picked = picked.reshape(Tl, K, H)
        return jnp.einsum(
            "tk,tkh->th", gate_w.astype(picked.dtype), picked
        ).astype(hl.dtype)

    return run


# ---------------------------------------------------------------------------
# The SERVED expert layer: dropless, grouped. (token, pick) rows are
# sorted by expert and each projection is ONE grouped product (the
# megablox Pallas kernel on the TPU, jax.lax.ragged_dot elsewhere) that
# visits only the experts some row was routed to, so a decode step reads
# the weights of the experts touched and not all E of them, no [T*K, E, C]
# slot tensor exists, and no token is ever dropped. Routing (which score
# function, bias, scale) is the caller's: this takes the picks and their
# combine weights.

GMM_ROWS = 128   # rows per tile of the TPU kernel
# the most one weight tile may hold: the kernel double-buffers it inside
# the 16 MiB of VMEM a Mosaic call gets by default, beside the row and
# output tiles. 2048 x 768 (3 MiB) fits whole; 3584 x 1024 (7 MiB) does
# not ("Ran out of memory in memory space vmem", compile-only, PR 37)
GMM_TILE_BYTES = 4 * 2 ** 20
# The two row movements of a call that holds a SHARE of the experts (the
# gather into sorted order, the un-sort) loop over blocks of MOVE_ROWS
# rows, of which only those with a live row run, where the call sorts more
# than MOVE_STRAIGHT_ROWS rows. Both chosen on the chip
# (tools/moe_rows_bench.py, PERF.md section 6, PR 46): 256 rows beat 128
# and 512 at 20480 and 40960 sorted rows; with a share the loops tie
# straight-line at 2560 rows and win from 5120 up.
MOVE_ROWS = 256
MOVE_STRAIGHT_ROWS = 4096


def gmm_tile_n(k: int, n: int, itemsize: int) -> int:
    """Output columns of a weight tile [k, tn]: the whole matrix where it
    fits GMM_TILE_BYTES, else n halved (whole 128-column lanes) until it
    does; where halving ends on an ODD number of 128-lane columns and the
    tile is still too large (2688 = 21 columns; 1920 = 15), the columns are
    dealt evenly over the fewest tiles that fit (21 -> 3 x 7, 15 -> 3 x
    5; the last tile of a width that is no whole number of tiles or of
    columns is short, which the kernel takes). The contraction is never
    split: a tile then needs no second visit to finish its sums."""
    tn = n
    while k * tn * itemsize > GMM_TILE_BYTES and tn % 256 == 0:
        tn //= 2
    if k * tn * itemsize <= GMM_TILE_BYTES:
        return tn
    fit = max(GMM_TILE_BYTES // (k * itemsize * 128), 1)   # columns a tile
    tiles = -(-tn // (fit * 128))
    return -(-tn // (tiles * 128)) * 128


def stored_width(n: int) -> int:
    """The width an expert's matrices are STORED at for the grouped
    product: ``n`` itself where it is a whole number of 128-lane columns
    (or at most one), else the next whole number, the columns of W_u and
    the rows of W_d beyond ``n`` ZERO (exact under any activation that
    maps 0 to 0 with no gate beside it: relu(0)^2 = 0 meets a zero row).
    A tile whose last 64 columns hang over the matrix's edge streams at a
    third of the pace of a whole one on the v5e (1856 against 1920 under
    hidden 2688: 205 against 589 GB/s of PUBLISHED bytes a decode-shaped
    call; PERF.md section 6, PR 60)."""
    return n if n <= 128 or n % 128 == 0 else -(-n // 128) * 128


def _gmm_tpu(x, w, group_sizes):
    """The grouped product on the TPU: the megablox Pallas kernel, each
    tile one expert's WHOLE [in, out] matrix where VMEM holds it (full-K,
    full-N tiles; else full-K and half or a quarter of N, ``gmm_tile_n``):
    a visit streams the matrix once at ~700 GB/s on a v5e, where
    ``ragged_dot``'s own lowering reads it at ~210 GB/s (PERF.md section
    6, PR 31). Empty experts are never visited."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = x.shape
    tm = next((t for t in (GMM_ROWS, 64, 32, 16, 8) if m % t == 0), None)
    if tm is None:
        return jax.lax.ragged_dot(x, w, group_sizes)
    return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
               tiling=(tm, k, gmm_tile_n(k, w.shape[2], w.dtype.itemsize)))


def relu2(u):
    """``relu(u)^2``, squared in float32 and rounded once."""
    return jnp.square(jax.nn.relu(u).astype(jnp.float32)).astype(u.dtype)


def _grouped(x, w, group_sizes, expert_of_row):
    """Rows of x (sorted by expert) times their expert's matrix; dense
    or int8 ``{"q", "s"}`` experts (scale per [E, out])."""
    if isinstance(w, dict):
        y = jax.lax.ragged_dot(x, w["q"].astype(x.dtype), group_sizes)
        return y * w["s"].astype(x.dtype)[expert_of_row]
    return jax.lax.platform_dependent(
        x, w, group_sizes, tpu=_gmm_tpu, default=jax.lax.ragged_dot)


def grouped_experts(
    x: jnp.ndarray,        # [T, H]
    sel: jnp.ndarray,      # [T, K] i32 — the experts each token picked
    weights: jnp.ndarray,  # [T, K] f32 — their combine weights
    wg, wu, wd,            # [E, H, I], [E, H, I], [E, I, H]; wg None
                           # under ``act`` "relu2" (no gate matrix)
    valid=None,            # [T] bool — False: routed NOWHERE (padding,
                           # garbage decode lanes); its output row is 0
    first: int | None = None,  # a SHARE of the experts is held: wg/wu/wd
                           # are experts first .. first + E of the ones
                           # ``sel`` names; None: all of them
    act: str = "swiglu",   # an expert: "swiglu" W_d (silu(x W_g) * x W_u),
                           # three grouped products; "relu2" W_d relu(x
                           # W_u)^2, two (``wg`` is None)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """sum_k weights[t, k] * expert_{sel[t, k]}(x[t]) for every token, and
    the tokens each expert received ([E] i32: what the counters read).

    With ``first`` the layer holds one chip's share of an expert-parallel
    deployment: ``sel`` and ``weights`` are the router's over ALL its
    experts, a pick that lands on an expert held elsewhere is routed
    nowhere and adds nothing, and the result is this share's part of the
    layer's sum (what the other chips would add arrives by an exchange
    that one chip does not run). The counts are of the held experts."""
    T, K = sel.shape
    E = (wu["q"] if isinstance(wu, dict) else wu).shape[0]
    flat = sel.reshape(T * K).astype(jnp.int32)
    if first is not None:
        # the same idiom as ``valid`` below: id E belongs to no group
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < E), flat, E)
    if valid is not None:
        # expert id E sorts last and belongs to no group: rows past the
        # groups' total are never computed
        flat = jnp.where(jnp.repeat(valid, K), flat, E)
    order = jnp.argsort(flat, stable=True)
    expert_of_row = jnp.minimum(flat[order], E - 1)
    group_sizes = jnp.zeros(E + 1, jnp.int32).at[flat].add(1)[:E]
    held = first is not None
    R = move_block(T, K, held)
    with jax.named_scope("moe_gather"):
        if R:
            xs = _gather_live(x, order, K, group_sizes.sum(), R)
        else:
            xs = x[order // K]                           # [T*K, H]
    with jax.named_scope("moe_experts"):
        if act == "relu2":
            a = relu2(_grouped(xs, wu, group_sizes, expert_of_row))
        else:
            g = _grouped(xs, wg, group_sizes, expert_of_row)
            u = _grouped(xs, wu, group_sizes, expert_of_row)
            a = jax.nn.silu(g) * u
        y = _grouped(a, wd, group_sizes, expert_of_row)
    # the inverse permutation: the sorted row that holds pick (t, k)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * K, dtype=order.dtype))
    with jax.named_scope("moe_combine"):
        if R:
            out = _combine_live(y, back, weights, flat, valid, E, held, R)
        else:
            out = _combine(y[back], weights, flat, valid, E, held)
    return out, group_sizes


def _combine(y, weights, flat, valid, E: int, held: bool):
    """sum_k weights[t, k] * y[t, k], summed in float32, for a run of n
    tokens: y [n * K, H] their picks' rows un-sorted, flat [n * K] the
    picks' group ids. In y's dtype."""
    n, K = weights.shape
    dtype, y = y.dtype, y.reshape(n, K, -1)
    w = weights.astype(jnp.float32)
    if held:
        # picks held elsewhere, and unrouted rows, hold whatever the
        # product left there
        here = (flat < E).reshape(n, K)
        y = jnp.where(here[..., None], y, 0)
        w = jnp.where(here, w, 0.0)
    elif valid is not None:
        # unrouted rows hold whatever the product left there
        y = jnp.where(valid[:, None, None], y, 0)
        w = jnp.where(valid[:, None], w, 0.0)
    return jnp.einsum("tk,tkh->th", w, y.astype(jnp.float32)).astype(dtype)


def move_block(n_tokens: int, top_k: int, held: bool) -> int:
    """Rows a block of the looped row movements for a call that routes
    ``n_tokens`` tokens to ``top_k`` experts each, or 0 where both run
    straight-line over all n_tokens x top_k rows. Decided by what the call
    can see, and the host's mirror (the engine's counters) calls it too:
    its shape, and whether it holds a share (``held``: about half its rows
    are dead by construction, whatever the padding). A call that holds
    every expert has only its padding dead, and there the loops lose
    (PERF.md section 6, PR 46)."""
    R = MOVE_ROWS
    if not held or n_tokens % R or n_tokens * top_k <= MOVE_STRAIGHT_ROWS:
        return 0
    return R


def rows_moved(total, R: int):
    """Rows the gather loop runs for ``total`` live sorted rows: whole
    blocks. numpy or int in, the same out; traced in, traced out."""
    return (total + R - 1) // R * R


def _gather_live(x, order, K: int, total, R: int):
    """``x[order // K]`` for the sorted rows below ``total`` (the groups'
    total: held and valid picks; whatever has id E sorts behind them), in
    blocks of R rows. Rows of blocks that never ran are 0: no group holds
    them, so the grouped products never visit them, and nothing reads
    their results unmasked."""
    def block(i, xs):
        rows = jax.lax.dynamic_slice(order, (i * R,), (R,)) // K
        return jax.lax.dynamic_update_slice(xs, x[rows], (i * R, 0))

    return jax.lax.fori_loop(
        0, rows_moved(total, R) // R, block,
        jnp.zeros((order.shape[0], x.shape[1]), x.dtype))


def _combine_live(y, back, weights, flat, valid, E: int, held: bool, R: int):
    """``_combine`` over the blocks of R tokens that hold a valid one (all
    of them without ``valid``); the rows of a block that never ran are 0,
    as ``_combine`` leaves an unrouted row. A block's sums are
    ``_combine``'s own, in its order."""
    T, K = weights.shape
    if valid is None:
        n, blocks = T // R, jnp.arange(T // R, dtype=jnp.int32)
    else:
        live = valid.reshape(T // R, R).any(axis=1)
        n, blocks = live.sum(), jnp.argsort(~live, stable=True)

    def block(i, out):
        t0 = blocks[i].astype(jnp.int32) * R

        def cut(a, per=1):   # the block's tokens' part of a
            return jax.lax.dynamic_slice(
                a, (t0 * per,) + (0,) * (a.ndim - 1),
                (R * per,) + a.shape[1:])

        part = _combine(y[cut(back, K)], cut(weights), cut(flat, K),
                        None if valid is None else cut(valid), E, held)
        return jax.lax.dynamic_update_slice(out, part, (t0, 0))

    return jax.lax.fori_loop(
        0, n, block, jnp.zeros((T, y.shape[1]), y.dtype))


def moe_reference(h, params, cfg: MoEConfig) -> jnp.ndarray:
    """Single-device dense reference (no capacity drops) for testing."""
    logits = (h @ params["wr"]).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    gate_w, sel = jax.lax.top_k(gates, cfg.top_k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    outs = _expert_ffn(
        jnp.broadcast_to(h, (cfg.num_experts, *h.shape)),
        params["wg"], params["wu"], params["wd"],
    )                                                    # [E, T, H]
    picked = outs[sel.T, jnp.arange(h.shape[0])[None]]   # [K, T, H]
    return jnp.einsum(
        "tk,kth->th", gate_w.astype(picked.dtype), picked
    ).astype(h.dtype)
