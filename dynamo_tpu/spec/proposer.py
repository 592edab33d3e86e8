"""Speculative-token proposers.

Two strategies; both propose K tokens from ``history``, the request's
full committed sequence (prompt + emitted output, the pending token
last):

  - NGramProposer: model-free prompt-lookup decoding. Matches the tail
    n-gram of the history against an earlier occurrence and proposes the
    tokens that followed it. Pure host code, deterministic, zero device
    cost — wins on repetitive/structured text (code, extraction, long
    copies) where the continuation literally appears earlier.
  - DraftModelProposer: a small model sharing the target's tokenizer,
    run as ONE program for every speculating slot (llama.batch_draft):
    a catch-up chunk syncs its private ctx region with each slot's
    history, then K greedy single-token steps. The argmax chain stays on
    device — the proposed [B, K] array feeds the verifier without a host
    round trip.

Correctness note: acceptance treats every proposal as a deterministic
(point-mass) draft, so HOW tokens are proposed never biases the output
distribution — a bad proposer only lowers the acceptance rate.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig, pow2_cover
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig


class NGramProposer:
    """Prompt-lookup proposer: propose the continuation of the most
    recent earlier occurrence of the history's tail n-gram.

    Tries n = max_n .. min_n; for each n, scans for the RIGHTMOST earlier
    match (recent context predicts better than distant context) within a
    bounded lookback window — the scan runs on the engine scheduler
    thread once per verify step, and an unbounded pure-Python sweep over
    a many-thousand-token history would stall dispatch for every slot
    exactly on the low-acceptance workloads that match nothing. With no
    match, proposes zeros — those verify like any other draft and simply
    get rejected unless the target happens to agree.
    """

    def __init__(self, k: int, max_n: int = 3, min_n: int = 1,
                 max_lookback: int = 1024):
        if k < 1:
            raise ValueError("num_speculative_tokens must be >= 1")
        if min_n < 1 or max_n < min_n:
            raise ValueError("need 1 <= min_n <= max_n")
        self.k = k
        self.max_n = max_n
        self.min_n = min_n
        self.max_lookback = max_lookback

    def propose(self, history: list[int], k: int = 0) -> list[int]:
        """Propose ``k`` tokens (0 = the constructor default). Callers
        with adaptive K pass the round's effective width."""
        k = k or self.k
        hist = history[-self.max_lookback:]
        L = len(hist)
        for n in range(min(self.max_n, L - 1), self.min_n - 1, -1):
            tail = hist[-n:]
            for j in range(L - n - 1, -1, -1):
                if hist[j : j + n] == tail:
                    cont = hist[j + n : j + n + k]
                    return cont + [0] * (k - len(cont))
        return [0] * k

    def propose_tree(
        self, history: list[int], depth: int, branches: int, budget: int
    ) -> tuple[list[int], list[int]]:
        """Multi-candidate prompt lookup: collect up to ``branches``
        distinct earlier occurrences of the tail n-gram (longest n
        first, most recent first — the same preference order as
        propose) and merge their continuation chains into one token
        trie. Shared prefixes dedup into a single node, so disagreeing
        continuations fork exactly at their divergence point instead of
        burning budget on duplicated stems.

        Returns (tokens, parents) EXCLUDING the root: parent value 0
        points at the pending token, otherwise at the 1-based index of
        an earlier returned node — ready to pack behind the verifier's
        node 0. At most ``budget - 1`` nodes come back (the root takes
        one slot of the tree budget); no match degrades to the single
        zero-chain the linear path proposes."""
        hist = history[-self.max_lookback:]
        L = len(hist)
        conts: list[list[int]] = []
        for n in range(min(self.max_n, L - 1), self.min_n - 1, -1):
            tail = hist[-n:]
            for j in range(L - n - 1, -1, -1):
                if hist[j : j + n] == tail:
                    cont = hist[j + n : j + n + depth]
                    if cont and cont not in conts:
                        conts.append(cont)
                        if len(conts) >= branches:
                            break
            if len(conts) >= branches:
                break
        if not conts:
            conts = [[0] * depth]
        tokens: list[int] = []
        parents: list[int] = []
        children: dict[tuple[int, int], int] = {}  # (parent, tok) -> node
        cap = budget - 1
        for cont in conts:
            parent = 0  # the pending-token root
            for tok in cont:
                node = children.get((parent, tok))
                if node is None:
                    if len(tokens) >= cap:
                        break
                    tokens.append(tok)
                    parents.append(parent)
                    node = len(tokens)  # 1-based: 0 is the root
                    children[(parent, tok)] = node
                parent = node
        return tokens, parents


def comb_parents(k: int, m: int) -> list[int]:
    """Parent pointers for the comb tree llama.batch_draft emits in
    branch mode (m > 1): depth k, m-way fan at every level, only the
    top-1 "spine" extends. Node order matches the drafted [B, k*m]
    array — level s occupies 1 + s*m .. 1 + s*m + m - 1 with column
    s*m the spine. Returns the FULL [1 + k*m] list including the root's
    -1; pad with -2 up to the tree budget."""
    parents = [-1]
    for s in range(k):
        parents.extend([0 if s == 0 else 1 + (s - 1) * m] * m)
    return parents


class DraftModelProposer:
    """Draft-model proposer with a private contiguous ctx region.

    The draft shares the target's tokenizer (vocab ids must line up) and
    runs through ``llama.batch_draft``: a bucketed catch-up chunk writes
    each slot's history delta into its draft lane, then K-1 single-token
    steps extend it greedily. Rollback after a rejected verify is
    ``truncate(slot, n)`` — the draft region beyond ``n`` is dead weight
    that the next catch-up chunk overwrites (attention masks by seq_len,
    so it is never read meanwhile).
    """

    def __init__(
        self,
        config: ModelConfig,
        ecfg: EngineConfig,
        *,
        params: Any = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        rng_seed: int = 0,
    ):
        self.config = config
        self.ecfg = ecfg
        if params is None:
            params = llama.init_params(config, rng_seed)
        ctx = llama.init_ctx(
            config, ecfg.max_decode_slots, ecfg.max_context,
            jnp.dtype(ecfg.cache_dtype),
        )
        if mesh is not None:
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, s),
                params, llama.param_shardings(config, mesh),
            )
            ctx = jax.tree.map(
                lambda x, s: jax.device_put(x, s),
                ctx, llama.ctx_shardings(config, mesh),
            )
        self.params = params
        self.ctx = ctx
        # tokens of the slot's TRUE history whose KV the draft region
        # holds at [0, pos) — the rollback pointer
        self.pos = np.zeros(ecfg.max_decode_slots, np.int64)

    def propose_batch(
        self, rows: list[tuple[int, list[int]]], width: int, k: int,
        branches: int = 1,
    ) -> jnp.ndarray:
        """Draft k tokens for EVERY speculating slot in ONE device
        dispatch (llama.batch_draft): the per-slot catch-up chunks run as
        one [width, T] batched forward, then k-1 batched single-token
        steps advance greedily inside a fori_loop — O(1) dispatches per
        round in the number of slots and in k.

        ``rows`` is [(slot, history)] for the live rows; the remaining
        lanes up to ``width`` are dummies (scratch lane, seq_len 0),
        mirroring the verifier's batch layout so the returned [width, k]
        array splices row-aligned into the verify dispatch.

        ``branches > 1`` drafts the comb tree (see comb_parents) at the
        SAME dispatch cost — the returned array is [width, k * branches]
        in level-major node order, and only the spine's KV lands in the
        draft region, so the rollback pointer math below is unchanged.
        """
        S = self.ecfg.max_context
        scratch = self.ecfg.max_decode_slots
        chunks: list[tuple[int, list[int], int]] = []
        max_len = 1
        for slot, hist in rows:
            start = int(self.pos[slot])
            assert len(hist) > start, \
                "history must extend past the draft position"
            chunks.append((slot, hist, start))
            max_len = max(max_len, len(hist) - start)
        # one shared pow2 chunk width, clamped to the region (an
        # overflowing padded write start would be CLAMPED by
        # dynamic_update_slice, silently shifting real KV onto earlier
        # rows; the chunk itself always fits — the engine despeculates
        # before the history can outgrow the region). Rows whose
        # start + T would overflow re-feed a little extra history
        # instead (start_eff < start recomputes identical KV — harmless).
        T = min(pow2_cover(max_len, 8), S)
        toks = np.zeros((width, T), np.int32)
        slots_a = np.full(width, scratch, np.int32)
        q_starts = np.zeros(width, np.int32)
        seq_lens = np.zeros(width, np.int32)   # 0: dummy rows fully masked
        for j, (slot, hist, start) in enumerate(chunks):
            start_eff = min(start, S - T)
            chunk = hist[start_eff:]
            toks[j, : len(chunk)] = chunk
            slots_a[j] = slot
            q_starts[j] = start_eff
            seq_lens[j] = len(hist)
        self.ctx, drafted = llama.batch_draft(
            self.config, self.params, self.ctx,
            jnp.asarray(toks), jnp.asarray(slots_a),
            jnp.asarray(q_starts), jnp.asarray(seq_lens), S, k, branches,
        )
        for slot, hist, _ in chunks:
            # KV written: history plus drafted[:-1] (the last draft is
            # never fed back, so its KV was never computed)
            self.pos[slot] = len(hist) + k - 1
        return drafted

    def truncate(self, slot: int, n_valid: int) -> None:
        """Rollback after verification: only the first ``n_valid`` tokens
        of the slot's draft KV match the true sequence."""
        self.pos[slot] = min(int(self.pos[slot]), n_valid)

    def release(self, slot: int) -> None:
        """Slot freed/reused: the draft region content belongs to a dead
        request — restart from scratch."""
        self.pos[slot] = 0
