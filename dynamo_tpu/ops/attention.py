"""Attention over the contiguous per-slot decode context.

Round-4 layout (see ops/flash_decode.py and models/llama.py): each decode
slot owns a contiguous KV region ``ctx_kv [L, kvh, B(+1), S, hd]``; the
paged pool exists only as prefix-cache storage, copied in/out at
admission/seal. Attention in the hot path therefore reads dense slabs —
no gathers, no page tables:

  - decode: the implementation the CALLER names with a ``DecodeAttention``
    — the compiled Pallas flash kernel (ops/flash_decode.py), mapped per
    shard over the mesh's ``tp`` axis, on TPU devices; the pure-jnp
    reference on the CPU test meshes. Nothing here looks at the process's
    default backend, and there is no fall-through from one to the other:
    a kernel that fails to compile fails the program;
  - prefill: one dense causal attention over the slot's region — prefill
    is a large matmul XLA already schedules well; no kernel needed.

This replaces the round-3 paged-attention kernel whose (slot, head, page)
grid cost 15.9 ms/step in pure invocation overhead (SURVEY.md §7 "Paged
attention on TPU" hard part; reference analogue is vLLM's paged-attention
CUDA kernel).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.ops.flash_decode import (
    flash_decode_attention,
    flash_decode_attention_reference,
)
from dynamo_tpu.parallel.mesh import AXIS_TENSOR

NEG_INF = -1e30

PALLAS = "pallas"                      # the compiled Mosaic kernel
PALLAS_INTERPRET = "pallas_interpret"  # same kernel, interpreted: tests only
REFERENCE_IMPL = "reference"           # pure jnp


@dataclasses.dataclass(frozen=True)
class DecodeAttention:
    """The decode-attention implementation a program is traced with.
    Hashable: it is a static argument of the jitted model functions."""

    impl: str
    # kernel impls: the mesh whose ``tp`` axis the kernel is mapped over
    # (Mosaic calls cannot be partitioned by GSPMD). None = unsharded
    # operands (single-device kernel tests).
    mesh: Optional[Mesh] = None
    # kernel tiling; 0 = the kernel's defaults (tools sweep these)
    chunk: int = 0
    slot_block: int = 0

    def __post_init__(self):
        if self.impl not in (PALLAS, PALLAS_INTERPRET, REFERENCE_IMPL):
            raise ValueError(f"unknown decode attention impl {self.impl!r}")


REFERENCE = DecodeAttention(REFERENCE_IMPL)


def decode_attention_for(mesh: Mesh) -> DecodeAttention:
    """The implementation an engine placed on ``mesh`` runs: the compiled
    kernel on TPU devices — the only path there — and the jnp reference
    on CPU devices (the test meshes; interpret-mode Pallas is far too
    slow to serve from). Decided from the devices the engine's arrays
    live on, once, and reported by the engine at start."""
    platform = mesh.devices.flat[0].platform
    if platform == "tpu":
        return DecodeAttention(PALLAS, mesh)
    if platform == "cpu":
        return REFERENCE
    raise ValueError(
        f"no decode attention implementation for platform {platform!r}"
    )


def ctx_decode_attention(
    attn: DecodeAttention,
    q: jnp.ndarray,          # [B, n_heads, hd] — one new token per slot
    ctx_k: jnp.ndarray,      # [L, kvh, B(+1), S, hd]
    ctx_v: jnp.ndarray,
    ring_k: jnp.ndarray,     # [L, kvh, B, R, hd] current-round writes
    ring_v: jnp.ndarray,
    layer: jnp.ndarray,      # scalar i32
    ctx_lens: jnp.ndarray,   # [B] i32 — context length INCL. current token
    ring_base: jnp.ndarray,  # [B] i32 — position held by ring slot 0
    ctx_k_scale: Optional[jnp.ndarray] = None,  # f32 [L, B(+1), S//g]
    ctx_v_scale: Optional[jnp.ndarray] = None,  # when ctx is int8
) -> jnp.ndarray:
    """Decode attention over the two-tier context (ctx region below
    ring_base + ring above). The current token's KV must already be in the
    ring. Returns [B, n_heads, hd]. When the ctx region is int8
    (scales given), each KV chunk dequantizes in VMEM right after the
    DMA — the HBM stream is the int8 bytes."""
    scales = () if ctx_k_scale is None else (ctx_k_scale, ctx_v_scale)
    if attn.impl == REFERENCE_IMPL:
        return flash_decode_attention_reference(
            q, ctx_k, ctx_v, ring_k, ring_v, layer, ctx_lens, ring_base,
            *scales,
        )

    def kernel(q, ctx_k, ctx_v, ring_k, ring_v, layer, ctx_lens, ring_base,
               *scales):
        k_scale, v_scale = scales or (None, None)
        return flash_decode_attention(
            q, ctx_k, ctx_v, ring_k, ring_v, layer, ctx_lens, ring_base,
            chunk=attn.chunk, slot_block=attn.slot_block,
            interpret=attn.impl == PALLAS_INTERPRET,
            ctx_k_scale=k_scale, ctx_v_scale=v_scale,
        )

    if attn.mesh is not None:
        # heads are independent: q/out shard on heads, ctx/ring on kv
        # heads (the layouts llama.param/ctx/ring_shardings already give
        # them), each shard runs the kernel on its own heads — no
        # collective. Scales carry no head axis: replicated.
        tp = attn.mesh.shape[AXIS_TENSOR]
        if ctx_k.shape[1] % tp:
            raise ValueError(
                f"{ctx_k.shape[1]} kv heads do not divide over tp={tp}"
            )
        heads = P(None, AXIS_TENSOR, None)
        kv = P(None, AXIS_TENSOR, None, None, None)
        kernel = jax.shard_map(
            kernel, mesh=attn.mesh,
            in_specs=(heads, kv, kv, kv, kv, P(), P(), P())
            + (P(),) * len(scales),
            out_specs=heads,
            # pallas_call has no replication rule; the other mesh axes
            # see replicated operands and produce replicated outputs
            check_vma=False,
        )
    return kernel(
        q, ctx_k, ctx_v, ring_k, ring_v, jnp.asarray(layer, jnp.int32),
        ctx_lens, ring_base, *scales,
    )


def ctx_prefill_attention(
    q: jnp.ndarray,        # [T, n_heads, hd] — new tokens (padded)
    k_ctx: jnp.ndarray,    # [kvh, S, hd] — slot's PRIOR context (< q_start)
    v_ctx: jnp.ndarray,
    k_new: jnp.ndarray,    # [T, kvh, hd] — this chunk's keys
    v_new: jnp.ndarray,
    q_start: jnp.ndarray,  # scalar i32 — #tokens already in the region
    seq_len: jnp.ndarray,  # scalar i32 — total valid context length
) -> jnp.ndarray:
    """Causal attention of T new tokens (positions q_start..q_start+T)
    against prior context [0, q_start) plus the chunk itself (causal).
    Returns [T, n_heads, hd]. The chunk's KV is passed directly rather
    than read back from the region — the region write happens ONCE at the
    end of the prefill program, so XLA never interleaves writes with the
    custom-call/einsum reads (the copy pathology this layout exists to
    avoid). Dense T×S einsums — prefill is MXU-friendly as-is."""
    T, n_heads, hd = q.shape
    kv_heads, S, _ = k_ctx.shape
    n_rep = n_heads // kv_heads

    k = jnp.concatenate(
        [k_ctx, k_new.transpose(1, 0, 2).astype(k_ctx.dtype)], axis=1
    )  # [kvh, S+T, hd]
    v = jnp.concatenate(
        [v_ctx, v_new.transpose(1, 0, 2).astype(v_ctx.dtype)], axis=1
    )
    k = jnp.repeat(k, n_rep, axis=0)  # [nh, S+T, hd]
    v = jnp.repeat(v, n_rep, axis=0)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qt = q.transpose(1, 0, 2)  # [nh, T, hd]
    scores = jnp.einsum(
        "nth,nsh->nts", qt, k, preferred_element_type=jnp.float32
    ) * scale
    q_pos = q_start + jnp.arange(T)[:, None]            # [T, 1]
    ctx_pos = jnp.arange(S)[None, :]                    # [1, S]
    ctx_ok = jnp.broadcast_to(
        (ctx_pos < q_start) & (ctx_pos < seq_len), (T, S)
    )
    new_pos = q_start + jnp.arange(T)[None, :]          # [1, T]
    new_ok = (new_pos <= q_pos) & (new_pos < seq_len)   # causal in-chunk
    mask = jnp.concatenate([ctx_ok, new_ok], axis=1)    # [T, S+T]
    scores = jnp.where(mask[None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "nts,nsh->tnh", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)

def flash_prefill_attention(
    q: jnp.ndarray,        # [T, n_heads, hd] — new tokens (padded)
    k_ctx: Optional[jnp.ndarray],  # [kvh, Sc, hd] prior context, or None
    v_ctx: Optional[jnp.ndarray],
    k_new: jnp.ndarray,    # [T, kvh, hd] — this chunk's keys
    v_new: jnp.ndarray,
    q_start: jnp.ndarray,  # scalar i32 — #tokens already in the region
    seq_len: jnp.ndarray,  # scalar i32 — total valid context length
    block: int = 256,
    chunk_mask: Optional[jnp.ndarray] = None,  # [T, T] bool in-chunk
                            # visibility (tree-causal); None = causal
) -> jnp.ndarray:
    """Blocked running-softmax ("flash") prefill attention in pure XLA.

    Same semantics as ctx_prefill_attention — T new tokens at positions
    q_start..q_start+T attend prior context [0, q_start) plus the chunk
    causally — but scores never materialize beyond [nh, T, block], so
    large chunks (T in the thousands) don't allocate the [T, S+T] f32
    score tensor the dense path does (32 heads x 3072^2 x 4B = 1.2 GB per
    layer). lax.scan over key blocks with the standard (m, l, acc)
    running-max rescale; attention FLOPs are a rounding error next to the
    parameter matmuls at serving sizes, so the causal 2x block waste is
    taken in exchange for compiler-friendly static control flow.

    Pass k_ctx=None for fresh prefill (q_start==0 everywhere): the
    context scan is omitted entirely from the compiled program instead of
    masked out. The reference's analogue of this split is vLLM's
    prefill-vs-extend kernel dispatch.

    ``chunk_mask`` replaces the causal in-chunk mask with an explicit
    [T, T] visibility matrix (chunk_mask[i, j] = query row i may attend
    chunk key j) — the tree-speculation hook: verify chunks hold a packed
    token TREE whose nodes attend their ancestor chain, not their index
    predecessors (spec/verifier.py builds it from parent pointers). The
    prior-context scan is unaffected: every tree node attends the full
    committed prefix. Rows with no visible key anywhere (padding nodes)
    fall out of the m > NEG_INF/2 gate below and emit zeros.
    """
    T, n_heads, hd = q.shape
    kvh = k_new.shape[1]
    n_rep = n_heads // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qt = q.transpose(1, 0, 2)            # [nh, T, hd]
    q_pos = q_start + jnp.arange(T)      # [T]

    m0 = jnp.full((n_heads, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_heads, T), jnp.float32)
    acc0 = jnp.zeros((n_heads, T, hd), jnp.float32)

    def blocked(k_src, v_src, mask_fn, carry):
        """Scan key blocks of k_src [kvh, S, hd]; mask_fn(key_pos[blk],
        q_pos[T]) -> [T, blk] validity."""
        S = k_src.shape[1]
        blk = min(block, S)
        nblk = -(-S // blk)
        if nblk * blk != S:  # pad the tail block; masks exclude it
            pad = ((0, 0), (0, nblk * blk - S), (0, 0))
            k_src = jnp.pad(k_src, pad)
            v_src = jnp.pad(v_src, pad)
        # scan over block starts and slice per step — the old
        # reshape+transpose built a [nblk, kvh, blk, hd] copy of the
        # whole source up front, so even exact-fit calls paid a full
        # extra materialization of the context
        starts = jnp.arange(nblk, dtype=jnp.int32) * blk

        def step(c, start):
            m, l, acc = c
            k_blk = jax.lax.dynamic_slice_in_dim(k_src, start, blk, 1)
            v_blk = jax.lax.dynamic_slice_in_dim(v_src, start, blk, 1)
            k_rep = jnp.repeat(k_blk, n_rep, axis=0)
            v_rep = jnp.repeat(v_blk, n_rep, axis=0)
            s = jnp.einsum(
                "nth,nbh->ntb", qt, k_rep,
                preferred_element_type=jnp.float32,
            ) * scale                          # [nh, T, blk]
            key_pos = start + jnp.arange(blk)
            s = jnp.where(mask_fn(key_pos)[None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "ntb,nbh->nth", p.astype(v_rep.dtype), v_rep,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        carry, _ = jax.lax.scan(step, carry, starts)
        return carry

    carry = (m0, l0, acc0)
    if k_ctx is not None:
        # prior context: valid below q_start (q_start <= seq_len always)
        carry = blocked(
            k_ctx, v_ctx,
            lambda kp: jnp.broadcast_to(
                (kp < q_start) & (kp < seq_len), (T, kp.shape[0])
            ),
            carry,
        )
    # the chunk itself: causal, bounded by seq_len — or the caller's
    # explicit (tree-causal) visibility matrix, sliced per key block
    if chunk_mask is None:
        in_chunk = lambda kp: (  # noqa: E731 — tiny closure pair
            ((q_start + kp)[None, :] <= q_pos[:, None])
            & ((q_start + kp) < seq_len)[None, :]
        )
    else:
        in_chunk = lambda kp: jnp.take(  # noqa: E731
            chunk_mask, kp, axis=1
        )
    carry = blocked(
        k_new.transpose(1, 0, 2).astype(qt.dtype),
        v_new.transpose(1, 0, 2).astype(qt.dtype),
        in_chunk,
        carry,
    )
    m, l, acc = carry
    # fully-masked rows (padding queries): their blocks contribute
    # p = exp(NEG_INF - NEG_INF) = 1 per key (NEG_INF is finite), so l
    # ends at the key count, not 0 — gate on the running max never having
    # seen a real (unmasked) score and emit zeros explicitly
    out = acc / jnp.maximum(l, 1e-30)[..., None]     # [nh, T, hd]
    out = jnp.where((m > NEG_INF / 2)[..., None], out, 0.0)
    return out.transpose(1, 0, 2).astype(q.dtype)
