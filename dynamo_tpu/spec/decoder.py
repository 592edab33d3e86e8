"""SpecDecoder: the engine-facing facade of the speculation subsystem.

Owns the proposer (n-gram or draft model), the acceptance counters, the
acceptance-adaptive K controller, and the verify dispatch plumbing. The
engine scheduler calls:

  eligible(req)           may this request speculate? (penalties and
                          logprobs need the per-token sampler path)
  k_for(slot)/round_k()   the slot's effective K and the bucketed round
                          width covering a batch of slots
  propose(hist, k)        K candidate tokens from the n-gram proposer
                          (a host lookup, per slot by nature)
  propose_batch(...)      ONE batched draft dispatch for every
                          speculating slot (llama.batch_draft; a device
                          array, no host sync)
  verify(...)             dispatch the fused score+accept program for a
                          batch of speculating slots
  on_result(...)          commit counters, update the adaptive-K rate,
                          roll the draft KV back to the accepted length
  should_despec(slot)     has this slot's acceptance collapsed?
  release(slot)           slot freed/de-speculated — drop draft state

Counters feed two surfaces: engine.metrics() (WorkerStats spec
fields -> metrics_exporter/system_server gauges, incl. the mean
effective K as dynamo_spec_effective_k) and per-request annotations on
the finishing LLMEngineOutput (sdk.request_stats). Dispatch counters
(spec_draft_dispatch_total / spec_verify_dispatch_total) make the
O(dispatches)-per-token cost directly observable; tests/
test_spec_adaptive.py holds drafting to one dispatch a verify round.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig, pow2_cover
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.spec.proposer import DraftModelProposer, NGramProposer
from dynamo_tpu.spec.verifier import spec_verify, spec_verify_tree

# acceptance-adaptive K (AdaptiveKController): a slot's effective K walks
# within [spec_min_k, num_speculative_tokens] on an EWMA of its per-step
# acceptance fraction — grow at/above GROW_THRESHOLD, shrink at/below
# SHRINK_THRESHOLD; a slot whose rate stays at/below DESPEC_THRESHOLD
# after MIN_OBSERVATIONS verify steps de-speculates back to the fused
# decode round (speculation is costing it a full forward per ~1 emitted
# token there). The round's draft/verify width is the bucketed max of
# the participants' effective K, so an all-low-acceptance batch really
# does less device work per round.
GROW_THRESHOLD = 0.8
SHRINK_THRESHOLD = 0.4
DESPEC_THRESHOLD = 0.125
RATE_EWMA = 0.75       # weight of history in the rolling rate (and the gate's)
MIN_OBSERVATIONS = 8   # verify steps before despec may fire


class AdaptiveKController:
    """Per-slot acceptance-adaptive speculation depth.

    Each verify result updates an EWMA of the slot's per-step acceptance
    fraction (accepted / k_used). The effective K walks one step at a
    time — +1 above ``grow_at``, -1 below ``shrink_at`` — bounded by
    [k_min, k_max]; hysteresis between the thresholds keeps K stable on
    noisy workloads. A slot whose rate stays at/below ``despec_at`` after
    ``min_obs`` observations has speculation actively costing it (every
    verify is a full forward that emits ~1 token) and should be handed
    back to the fused decode round (Leviathan et al.'s adaptive
    speculation; vLLM's dynamic speculative config is the serving-stack
    analogue).
    """

    def __init__(self, k_max: int, k_min: int, *, grow_at: float,
                 shrink_at: float, despec_at: float, ewma: float,
                 min_obs: int, m_max: int = 1):
        if not 1 <= k_min <= k_max:
            raise ValueError("need 1 <= spec_min_k <= num_speculative_tokens")
        if not 0.0 <= despec_at <= shrink_at <= grow_at <= 1.0:
            raise ValueError(
                "need 0 <= despec_at <= shrink_at <= grow_at <= 1"
            )
        if m_max < 1:
            raise ValueError("spec_branches must be >= 1")
        self.k_max = k_max
        self.k_min = k_min
        self.m_max = m_max
        self.grow_at = grow_at
        self.shrink_at = shrink_at
        self.despec_at = despec_at
        self.ewma = ewma
        self.min_obs = min_obs
        # slot-indexed state arrays (grown on demand — slots are engine
        # lane indices, bounded by max_decode_slots in practice). NaN
        # rate = never observed; arrays instead of per-slot dicts so the
        # spec-round k lookups and the metrics-path effective-K mean are
        # array reads, not dict traffic on the engine hot loop.
        self._k = np.full(8, k_max, np.int32)
        self._m = np.full(8, m_max, np.int32)
        self._rate = np.full(8, np.nan, np.float64)
        self._obs = np.zeros(8, np.int32)
        self.grow_total = 0
        self.shrink_total = 0
        self.branch_grow_total = 0
        self.branch_shrink_total = 0

    def _ensure(self, slot: int) -> None:
        n = len(self._k)
        if slot < n:
            return
        grow = max(slot + 1, 2 * n)
        self._k = np.concatenate(
            [self._k, np.full(grow - n, self.k_max, np.int32)])
        self._m = np.concatenate(
            [self._m, np.full(grow - n, self.m_max, np.int32)])
        self._rate = np.concatenate(
            [self._rate, np.full(grow - n, np.nan, np.float64)])
        self._obs = np.concatenate(
            [self._obs, np.zeros(grow - n, np.int32)])

    def k_for(self, slot: int) -> int:
        # optimistic start at k_max: identical to static-K behavior until
        # evidence says otherwise
        if slot >= len(self._k):
            return self.k_max
        return int(self._k[slot])

    def k_for_slots(self, slots) -> np.ndarray:
        """Vectorized ``k_for`` over an index array (metrics path)."""
        slots = np.asarray(slots, np.int64)
        out = np.full(len(slots), self.k_max, np.int32)
        mask = slots < len(self._k)
        out[mask] = self._k[slots[mask]]
        return out

    def m_for(self, slot: int) -> int:
        """The slot's effective branch fan (tree speculation). Starts at
        m_max — a fresh stream hedges WIDE until evidence says the top-1
        chain is reliable."""
        if slot >= len(self._m):
            return self.m_max
        return int(self._m[slot])

    def m_for_slots(self, slots) -> np.ndarray:
        slots = np.asarray(slots, np.int64)
        out = np.full(len(slots), self.m_max, np.int32)
        mask = slots < len(self._m)
        out[mask] = self._m[slots[mask]]
        return out

    def rate_for(self, slot: int) -> Optional[float]:
        if slot >= len(self._rate) or np.isnan(self._rate[slot]):
            return None
        return float(self._rate[slot])

    def observe(self, slot: int, accepted: int, k_used: int) -> None:
        self._ensure(slot)
        step = accepted / max(k_used, 1)
        prev = float(self._rate[slot])
        rate = step if np.isnan(prev) else (
            self.ewma * prev + (1.0 - self.ewma) * step
        )
        self._rate[slot] = rate
        self._obs[slot] += 1
        k = int(self._k[slot])
        m = int(self._m[slot])
        if rate >= self.grow_at:
            # accepting well: the spine is reliable — go DEEPER and
            # NARROWER (hedging siblings stop earning their node budget)
            if k < self.k_max:
                self._k[slot] = k + 1
                self.grow_total += 1
            if m > 1:
                self._m[slot] = m - 1
                self.branch_shrink_total += 1
        elif rate <= self.shrink_at:
            # rejecting early: shallower, but hedge WIDER — divergence
            # at the first level is exactly what sibling branches catch
            if k > self.k_min:
                self._k[slot] = k - 1
                self.shrink_total += 1
            if m < self.m_max:
                self._m[slot] = m + 1
                self.branch_grow_total += 1

    def should_despec(self, slot: int) -> bool:
        # NaN (never observed) compares False against despec_at — the
        # same "unknown slots are healthy" default as the old dict path
        return (slot < len(self._obs)
                and int(self._obs[slot]) >= self.min_obs
                and bool(self._rate[slot] <= self.despec_at))

    def release(self, slot: int) -> None:
        if slot < len(self._k):
            self._k[slot] = self.k_max
            self._m[slot] = self.m_max
            self._rate[slot] = np.nan
            self._obs[slot] = 0


class SpecDecoder:
    def __init__(
        self,
        config: ModelConfig,
        ecfg: EngineConfig,
        *,
        mesh: Optional[jax.sharding.Mesh] = None,
        draft_config: Optional[ModelConfig] = None,
        draft_params: Any = None,
        rng_seed: int = 0,
    ):
        mode = ecfg.speculative
        if mode not in ("ngram", "draft"):
            raise ValueError(f"unknown speculative mode {mode!r}")
        if ecfg.num_speculative_tokens < 1:
            raise ValueError("num_speculative_tokens must be >= 1")
        self.mode = mode
        self.k = ecfg.num_speculative_tokens
        self.config = config
        self.ecfg = ecfg
        # tree speculation: B branches per divergence point, verified
        # under one tree-causal mask; budget bounds the packed node
        # count so ONE compiled verify shape serves every tree
        self.tree = bool(ecfg.spec_tree)
        self.branches = max(int(ecfg.spec_branches), 1)
        self.tree_budget = int(ecfg.spec_tree_budget) or (
            1 + self.k * self.branches
        )
        if self.tree and self.tree_budget < 1 + self.k:
            raise ValueError(
                "spec_tree_budget must cover the root plus one full-"
                f"depth chain (need >= {1 + self.k})"
            )
        self.adaptive: Optional[AdaptiveKController] = None
        if ecfg.spec_adaptive:
            self.adaptive = AdaptiveKController(
                self.k, min(ecfg.spec_min_k, self.k),
                grow_at=GROW_THRESHOLD,
                shrink_at=SHRINK_THRESHOLD,
                despec_at=DESPEC_THRESHOLD,
                ewma=RATE_EWMA,
                min_obs=MIN_OBSERVATIONS,
                m_max=self.branches if self.tree else 1,
            )
        self.ngram: Optional[NGramProposer] = None
        self.draft: Optional[DraftModelProposer] = None
        if mode == "ngram":
            # tail n-grams of 3 down to 1 tokens: NGramProposer's own bounds
            self.ngram = NGramProposer(self.k)
        else:
            if draft_config is None:
                raise ValueError("speculative=draft needs a draft_config")
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    "draft model must share the target tokenizer "
                    f"(vocab {draft_config.vocab_size} != "
                    f"{config.vocab_size})"
                )
            self.draft = DraftModelProposer(
                draft_config, ecfg, params=draft_params, mesh=mesh,
                rng_seed=rng_seed + 1,
            )
        # acceptance statistics (engine-lifetime)
        self.proposed_total = 0
        self.accepted_total = 0
        self.verify_steps = 0
        self.reject_events = 0   # verify steps with a mid-batch rejection
        self.despec_total = 0    # slots handed back to the fused round
        # device-program dispatch counters — the batched-drafting win is
        # draft_dispatch_total growing O(rounds), not O(slots * K)
        self.draft_dispatch_total = 0
        self.verify_dispatch_total = 0
        # tree statistics
        self.tree_nodes_total = 0        # tree nodes scored (excl. root)
        self.tree_path_len_total = 0     # accepted path tokens
        self.tree_verify_steps = 0
        # accepted nodes by branch ordinal (position among same-parent
        # siblings, index order) — the per-branch acceptance breakdown
        self.branch_accept_hist = np.zeros(
            max(self.branches, 1), np.int64
        )
        # acceptance gating: a stream whose live acceptance EWMA sits
        # below spec_gate_acceptance for spec_gate_window consecutive
        # verify steps de-speculates (chat traffic stops paying draft
        # overhead); the engine may re-arm it later
        self.gate_at = float(ecfg.spec_gate_acceptance)
        self.gate_window = max(int(ecfg.spec_gate_window), 1)
        self.gated_despec_total = 0
        self.rearm_total = 0
        self._gate_rate: dict[int, float] = {}
        self._gate_low: dict[int, int] = {}

    # ------------------------------------------------------------------

    def eligible(self, req: Any) -> bool:
        """Logprobs need the lp variant of the step and stay on the fused
        decode round. Penalized requests SPECULATE: the verifier's scan
        variant advances the counts histogram inside the accept loop
        (accept_tokens_penalized), so frequency/presence/repetition
        penalties are applied per accepted token exactly like the fused
        sampler."""
        return req.output_options.logprobs is None

    @staticmethod
    def penalized(req: Any) -> bool:
        so = req.sampling_options
        return ((so.frequency_penalty or 0.0) != 0.0
                or (so.presence_penalty or 0.0) != 0.0
                or (so.repetition_penalty or 1.0) != 1.0)

    # ------------------------------------------------------------------
    # adaptive K

    def k_for(self, slot: int) -> int:
        if self.adaptive is None:
            return self.k
        return self.adaptive.k_for(slot)

    def round_k(self, ks: list[int]) -> int:
        """The round's verify/draft width covering every participating
        slot: the max effective K, bucketed up to a power of two (each
        distinct width is its own XLA compile of the draft AND verify
        programs — bucketing bounds that at log2(K) variants) and clamped
        to the CLI K."""
        return min(pow2_cover(max(ks)), self.k)

    def should_despec(self, slot: int) -> bool:
        return self.adaptive is not None and self.adaptive.should_despec(slot)

    def m_for(self, slot: int) -> int:
        """The slot's effective branch fan (1 when tree spec is off)."""
        if not self.tree:
            return 1
        if self.adaptive is None:
            return self.branches
        return self.adaptive.m_for(slot)

    def round_m(self, ms: list[int]) -> int:
        """The round's branch fan: max effective m, bucketed to a power
        of two and clamped to the CLI fan — same compile-count argument
        as round_k, applied to the tree's second axis."""
        return min(pow2_cover(max(ms)), self.branches)

    # ------------------------------------------------------------------
    # acceptance gating (per-workload de-speculation)

    def observe_gate(self, slot: int, accepted: int, k_used: int) -> None:
        """Track the stream's live acceptance EWMA against the gate
        threshold; a window of consecutive below-gate steps marks the
        stream as losing money on speculation."""
        if self.gate_at <= 0.0:
            return
        step = accepted / max(k_used, 1)
        prev = self._gate_rate.get(slot)
        ew = RATE_EWMA
        rate = step if prev is None else ew * prev + (1.0 - ew) * step
        self._gate_rate[slot] = rate
        if rate < self.gate_at:
            self._gate_low[slot] = self._gate_low.get(slot, 0) + 1
        else:
            self._gate_low[slot] = 0

    def should_gate(self, slot: int) -> bool:
        return (self.gate_at > 0.0
                and self._gate_low.get(slot, 0) >= self.gate_window)

    def gate_rate_for(self, slot: int) -> Optional[float]:
        return self._gate_rate.get(slot)

    def on_gated_despec(self, slot: int) -> None:
        self.gated_despec_total += 1
        self.on_despec(slot)

    def on_rearm(self, slot: int) -> None:
        self.rearm_total += 1

    # ------------------------------------------------------------------
    # proposing

    def propose(self, history: list[int], k: int) -> list[int]:
        """N-gram proposal: a host lookup in the request's own history."""
        return self.ngram.propose(history, k)

    def propose_batch(
        self, rows: list[tuple[int, list[int]]], width: int, k: int
    ) -> jnp.ndarray:
        """ONE batched draft dispatch for all speculating slots."""
        self.draft_dispatch_total += 1
        return self.draft.propose_batch(rows, width, k)

    def propose_tree(
        self, history: list[int], depth: int, branches: int
    ) -> tuple[list[int], list[int]]:
        """N-gram trie proposal: (tokens, parents) excluding the root,
        at most tree_budget - 1 nodes (see NGramProposer.propose_tree)."""
        return self.ngram.propose_tree(
            history, depth, branches, self.tree_budget
        )

    def propose_batch_tree(
        self, rows: list[tuple[int, list[int]]], width: int, k: int,
        m: int,
    ) -> jnp.ndarray:
        """ONE batched comb-tree draft dispatch (llama.batch_draft with
        branches=m); parents for the emitted [width, k*m] node order are
        proposer.comb_parents(k, m)."""
        self.draft_dispatch_total += 1
        return self.draft.propose_batch(rows, width, k, branches=m)

    def verify(
        self,
        params: Any,
        ctx_kv: Any,
        tokens: jnp.ndarray,
        draft: Optional[jnp.ndarray],
        slots: np.ndarray,
        q_starts: np.ndarray,
        seq_lens: np.ndarray,
        keys: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        top_ps: np.ndarray,
        penalties=None,
    ):
        """``penalties`` is None (no slot in the round carries penalties —
        the common case, no counts upload) or a tuple of (counts [B, V],
        freq [B], pres [B], rep [B]) host arrays."""
        self.verify_dispatch_total += 1
        if penalties is not None:
            penalties = tuple(jnp.asarray(a) for a in penalties)
        return spec_verify(
            self.config, params, ctx_kv, tokens, draft,
            jnp.asarray(slots), jnp.asarray(q_starts),
            jnp.asarray(seq_lens), jnp.asarray(keys),
            jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps),
            self.ecfg.max_top_k, self.ecfg.max_context,
            penalties,
        )

    def verify_tree(
        self,
        params: Any,
        ctx_kv: Any,
        tokens: jnp.ndarray,
        draft: Optional[jnp.ndarray],
        parents: np.ndarray,
        slots: np.ndarray,
        q_starts: np.ndarray,
        seq_lens: np.ndarray,
        keys: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        top_ps: np.ndarray,
        d_max: int,
        penalties=None,
    ):
        """Tree score + accept + path-commit; returns (ctx_kv, packed
        [B, 2*d_max + 4]) — ONE fetched array per round."""
        self.verify_dispatch_total += 1
        if penalties is not None:
            penalties = tuple(jnp.asarray(a) for a in penalties)
        return spec_verify_tree(
            self.config, params, ctx_kv, tokens, draft,
            jnp.asarray(parents), jnp.asarray(slots),
            jnp.asarray(q_starts), jnp.asarray(seq_lens),
            jnp.asarray(keys), jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(top_ps), self.ecfg.max_top_k,
            self.ecfg.max_context, d_max, penalties,
        )

    # ------------------------------------------------------------------

    def on_result(
        self, slot: int, hist_len: int, accepted: int, k_used: int
    ) -> None:
        """One verify step landed: `accepted` of the round's `k_used`
        proposals (the bucketed round width) matched; the slot's true
        sequence is hist_len + accepted + 1 tokens (the bonus token is
        pending, its KV unwritten)."""
        self.proposed_total += k_used
        self.accepted_total += accepted
        self.verify_steps += 1
        if accepted < k_used:
            self.reject_events += 1
        if self.adaptive is not None:
            self.adaptive.observe(slot, accepted, k_used)
        self.observe_gate(slot, accepted, k_used)
        if self.draft is not None:
            self.draft.truncate(slot, hist_len + accepted)

    def on_result_tree(
        self,
        slot: int,
        hist_len: int,
        accepted: int,
        d_used: int,
        m_used: int,
        nodes: int,
        path_nodes: list[int],
        parents: list[int],
    ) -> None:
        """One TREE verify landed: ``accepted`` path tokens out of a
        depth-``d_used`` tree carrying ``nodes`` proposal nodes;
        ``path_nodes`` is the accepted node-index chain (depth 1..) and
        ``parents`` the slot's full parent list (root at 0). Acceptance
        rate stays tokens-per-depth (accepted / d_used) — the same
        currency the linear path and the controller thresholds use, so
        tree and linear EWMAs are comparable."""
        self.proposed_total += d_used
        self.accepted_total += accepted
        self.verify_steps += 1
        self.tree_verify_steps += 1
        self.tree_nodes_total += nodes
        self.tree_path_len_total += accepted
        if accepted < d_used:
            self.reject_events += 1
        # per-branch breakdown: each accepted node's ordinal among its
        # same-parent siblings (index order — ordinal 0 is the spine /
        # best candidate)
        for node in path_nodes[:accepted]:
            par = parents[node]
            ordinal = sum(1 for j in range(1, node) if parents[j] == par)
            if ordinal < len(self.branch_accept_hist):
                self.branch_accept_hist[ordinal] += 1
        if self.adaptive is not None:
            self.adaptive.observe(slot, accepted, d_used)
        self.observe_gate(slot, accepted, d_used)
        if self.draft is not None:
            # only the comb SPINE's KV sits in the draft region — the
            # valid draft prefix is the accepted path's run along it
            # (spine node at depth t+1 is index 1 + t*m)
            spine = 0
            for t, node in enumerate(path_nodes[:accepted]):
                if node == 1 + t * m_used:
                    spine += 1
                else:
                    break
            self.draft.truncate(slot, hist_len + spine)

    def on_despec(self, slot: int) -> None:
        self.despec_total += 1
        self.release(slot)

    def release(self, slot: int) -> None:
        if self.draft is not None:
            self.draft.release(slot)
        if self.adaptive is not None:
            self.adaptive.release(slot)
        self._gate_rate.pop(slot, None)
        self._gate_low.pop(slot, None)

    def acceptance_rate(self) -> float:
        return self.accepted_total / max(self.proposed_total, 1)

    def effective_k_mean(self, slots) -> float:
        """Mean effective K over the given (speculating) slots — the
        dynamo_spec_effective_k gauge; 0 when nothing speculates.
        Accepts a list or index array (the engine passes its
        ``np.flatnonzero`` slot mask directly)."""
        if len(slots) == 0:
            return 0.0
        if self.adaptive is None:
            return float(self.k)
        return float(self.adaptive.k_for_slots(slots).mean())

    def effective_k_dist(self, slots) -> tuple[float, float, float]:
        """(mean, p50, p95) of per-slot effective K over the given
        speculating slots. The distribution matters: one hot repetitive
        stream at K=8 disappears into a fleet mean pulled down by a
        crowd of chat streams at K=2 — exactly the signal a planner
        gate reading only the mean would miss."""
        if len(slots) == 0:
            return 0.0, 0.0, 0.0
        if self.adaptive is None:
            k = float(self.k)
            return k, k, k
        ks = self.adaptive.k_for_slots(slots).astype(np.float64)
        return (
            float(ks.mean()),
            float(np.percentile(ks, 50)),
            float(np.percentile(ks, 95)),
        )

    def tree_mean_path_len(self) -> float:
        return self.tree_path_len_total / max(self.tree_verify_steps, 1)

    def stats(self) -> dict[str, Any]:
        out = {
            "mode": self.mode,
            "k": self.k,
            "spec_proposed_total": self.proposed_total,
            "spec_accepted_total": self.accepted_total,
            "spec_verify_steps": self.verify_steps,
            "spec_reject_events": self.reject_events,
            "spec_despec_total": self.despec_total,
            "spec_acceptance_rate": self.acceptance_rate(),
            "spec_draft_dispatch_total": self.draft_dispatch_total,
            "spec_verify_dispatch_total": self.verify_dispatch_total,
            "spec_adaptive": self.adaptive is not None,
            "spec_tree": self.tree,
            "spec_branches": self.branches,
            "spec_tree_budget": self.tree_budget,
            "spec_tree_nodes_total": self.tree_nodes_total,
            "spec_tree_accepted_path_len_total": self.tree_path_len_total,
            "spec_tree_verify_steps": self.tree_verify_steps,
            "spec_tree_mean_path_len": self.tree_mean_path_len(),
            "spec_branch_accept_hist": self.branch_accept_hist.tolist(),
            "spec_gated_despec_total": self.gated_despec_total,
            "spec_rearm_total": self.rearm_total,
        }
        if self.adaptive is not None:
            out["spec_k_grow_total"] = self.adaptive.grow_total
            out["spec_k_shrink_total"] = self.adaptive.shrink_total
            out["spec_branch_grow_total"] = self.adaptive.branch_grow_total
            out["spec_branch_shrink_total"] = (
                self.adaptive.branch_shrink_total
            )
        return out
