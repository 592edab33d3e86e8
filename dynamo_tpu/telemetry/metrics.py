"""Explicit-bucket Prometheus histograms for the request latency plane.

Hand-rolled rather than prometheus_client because the engine thread
observes them, three different servers render them (frontend, per-worker
system server, aggregating exporter), and their SNAPSHOTS must travel
inside ForwardPassMetrics across the pub/sub plane — a plain
dict-of-counts representation does all three; a client registry does
none of them cleanly.

Buckets follow the Prometheus contract: ``le``-labelled CUMULATIVE
counts with a ``+Inf`` terminal bucket, plus ``_sum`` and ``_count``.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Any, NamedTuple, Optional

import numpy as np

# decode steps run ~1-100 ms, TTFT ~10 ms-10 s, E2E up to minutes: a
# 1-2-3.5-5-7.5 per-decade ladder covers every request-latency series.
# Resolution matters beyond dashboards — a percentile read off a scrape
# is interpolated within a bucket, so each step is kept under ~1.6x
# (a within-bucket shift quantizes to at most that).
DEFAULT_TIME_BUCKETS = (
    0.0005, 0.001, 0.002, 0.0035, 0.005, 0.0075,
    0.01, 0.02, 0.035, 0.05, 0.075,
    0.1, 0.2, 0.35, 0.5, 0.75,
    1.0, 2.0, 3.5, 5.0, 7.5,
    10.0, 20.0, 35.0, 60.0, 120.0,
)


class Histogram:
    """One histogram series (no labels — renderers attach the worker
    label). Thread-safe: observed from the engine thread, rendered from
    asyncio handlers."""

    def __init__(
        self,
        name: str,
        help_: str,
        buckets: tuple[float, ...] = DEFAULT_TIME_BUCKETS,
    ):
        self.name = name
        self.help = help_
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # last = +Inf
        self._sum = 0.0
        self._count = 0
        # bucket index -> (exemplar_id, value, unix_ts): the LAST observed
        # id per bucket, rendered as an OpenMetrics exemplar so a heatmap
        # cell links to a concrete request's dossier
        self._exemplars: dict[int, tuple[str, float, float]] = {}
        self._lock = threading.Lock()

    def observe(
        self, value: float, n: int = 1, exemplar_id: Optional[str] = None
    ) -> None:
        """Record ``value`` ``n`` times (n>1: a batch of identical
        observations, e.g. per-token gaps derived from one round).
        ``exemplar_id`` tags the target bucket with a trace id."""
        if n <= 0 or not math.isfinite(value):
            return
        i = len(self.buckets)
        for j, b in enumerate(self.buckets):
            if value <= b:
                i = j
                break
        with self._lock:
            self._counts[i] += n
            self._sum += value * n
            self._count += n
            if exemplar_id:
                self._exemplars[i] = (exemplar_id, value, time.time())

    def observe_many(self, values) -> None:
        """Vectorized observe for a 1-D numpy batch: one searchsorted +
        bincount and ONE lock acquisition instead of a Python bucket
        scan per value (the prof-fold path observes up to 256 rounds x
        14 segments per publish tick)."""
        values = np.asarray(values, np.float64)
        values = values[np.isfinite(values)]
        n = int(values.size)
        if not n:
            return
        # side="left": first edge with value <= edge, matching observe()
        idx = np.searchsorted(np.asarray(self.buckets), values, side="left")
        binc = np.bincount(idx, minlength=len(self.buckets) + 1)
        total = float(values.sum())
        with self._lock:
            for i in np.flatnonzero(binc):
                self._counts[i] += int(binc[i])
            self._sum += total
            self._count += n

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def snapshot(self) -> dict[str, Any]:
        """Wire form: cumulative counts aligned with ``buckets`` + +Inf.
        When exemplars were observed, an ``exemplars`` key maps bucket
        index (stringified for JSON round-trips) to [id, value, ts]."""
        with self._lock:
            cum = []
            total = 0
            for c in self._counts:
                total += c
                cum.append(total)
            snap: dict[str, Any] = {
                "buckets": list(self.buckets),
                "counts": cum,        # cumulative, last entry == count
                "sum": self._sum,
                "count": self._count,
            }
            if self._exemplars:
                snap["exemplars"] = {
                    str(i): [eid, v, ts]
                    for i, (eid, v, ts) in self._exemplars.items()
                }
            return snap

    def percentile(self, q: float) -> Optional[float]:
        return percentile_from_snapshot(self.snapshot(), q)

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.buckets) + 1)
            self._sum = 0.0
            self._count = 0
            self._exemplars.clear()

    def render(self, label: str = "", openmetrics: bool = False) -> list[str]:
        return render_histogram(
            self.name, self.help, self.snapshot(), label,
            openmetrics=openmetrics,
        )


class CounterRegistry:
    """Thread-safe fixed-family counter/gauge registry with optional
    explicit-bucket histograms, rendered as one Prometheus text block.

    Subsystem metric planes (resilience, kv-transfer) instantiate this
    with their family set so the locking, the unknown-series assert and
    the HELP/TYPE rendering live in one place instead of one copy per
    plane. Families are ``(name, type, help)`` tuples; histograms are
    ``(name, help)`` tuples using the default time buckets."""

    def __init__(
        self,
        families: tuple[tuple[str, str, str], ...],
        histograms: tuple[tuple[str, str], ...] = (),
        label: str = "registry",
    ):
        self._families = tuple(families)
        self._known = {name for name, _, _ in self._families}
        self._label = label
        self._values: dict[str, float] = {n: 0.0 for n in self._known}
        self._lock = threading.Lock()
        self._hists: dict[str, Histogram] = {
            name: Histogram(name, help_) for name, help_ in histograms
        }

    def inc(self, name: str, n: float = 1.0) -> None:
        assert name in self._known, \
            f"unknown {self._label} series {name!r}"
        with self._lock:
            self._values[name] += n

    def set(self, name: str, v: float) -> None:
        assert name in self._known, \
            f"unknown {self._label} series {name!r}"
        with self._lock:
            self._values[name] = float(v)

    def get(self, name: str) -> float:
        with self._lock:
            return self._values[name]

    def observe(
        self, name: str, value: float, n: int = 1,
        exemplar_id: Optional[str] = None,
    ) -> None:
        self._hists[name].observe(value, n, exemplar_id=exemplar_id)

    def histogram(self, name: str) -> Histogram:
        return self._hists[name]

    def reset(self) -> None:
        with self._lock:
            for name in self._values:
                self._values[name] = 0.0
        for h in self._hists.values():
            h.reset()

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            return dict(self._values)

    def render(self, openmetrics: bool = False) -> str:
        """Prometheus text for every family (trailing newline included)."""
        snap = self.snapshot()
        lines: list[str] = []
        for name, typ, help_ in self._families:
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {typ}")
            v = snap[name]
            lines.append(f"{name} {int(v) if v == int(v) else v}")
        for h in self._hists.values():
            lines.extend(h.render(openmetrics=openmetrics))
        return "\n".join(lines) + "\n"


def render_planes(openmetrics: bool = False) -> str:
    """Prometheus text of every process-local metric plane: THE list of
    module-level registries, which all three scrape surfaces (frontend
    ``/metrics``, per-worker system server, aggregating exporter) render
    through this one call, so a plane cannot ship half-scraped (lint
    rule DTL005 holds every module-level registry to being named here).
    ``openmetrics`` reaches the registries whose histograms carry
    exemplars. Imports are local: the planes import this module."""
    from dynamo_tpu.kv_fleet_metrics import KV_FLEET
    from dynamo_tpu.kv_integrity import KV_INTEGRITY
    from dynamo_tpu.kv_quant import KV_QUANT
    from dynamo_tpu.kv_transfer_metrics import KV_TRANSFER
    from dynamo_tpu.overload import OVERLOAD
    from dynamo_tpu.planner_metrics import PLANNER
    from dynamo_tpu.resilience.metrics import RESILIENCE
    from dynamo_tpu.runtime.store_metrics import STORE
    from dynamo_tpu.spec.metrics import SPEC
    from dynamo_tpu.telemetry.fleet_feed import FLEET_FEED
    from dynamo_tpu.telemetry.forensics import FORENSICS
    from dynamo_tpu.telemetry.prof import PROF
    from dynamo_tpu.tenancy import TENANT

    return (RESILIENCE.render() + KV_TRANSFER.render() + KV_QUANT.render()
            + KV_INTEGRITY.render() + OVERLOAD.render() + PROF.render()
            + STORE.render() + PLANNER.render() + KV_FLEET.render()
            + SPEC.render()
            + FLEET_FEED.render(openmetrics=openmetrics)
            + TENANT.render(openmetrics=openmetrics)
            + FORENSICS.render())


def percentile_from_snapshot(
    snap: dict[str, Any], q: float
) -> Optional[float]:
    """Estimate the q-th percentile (0..1) from cumulative bucket counts
    by linear interpolation inside the target bucket (the standard
    ``histogram_quantile`` estimator). None when empty; observations in
    the +Inf bucket clamp to the top finite edge."""
    total = snap.get("count", 0)
    buckets = snap.get("buckets") or []
    counts = snap.get("counts") or []
    if not total or not buckets or len(counts) != len(buckets) + 1:
        return None
    rank = q * total
    prev_cum = 0
    lo = 0.0
    for edge, cum in zip(buckets, counts[:-1]):
        if rank <= cum:
            in_bucket = cum - prev_cum
            frac = (rank - prev_cum) / in_bucket if in_bucket else 0.0
            return lo + (edge - lo) * frac
        prev_cum = cum
        lo = edge
    return buckets[-1]


def weighted_percentile(
    pairs: list, q: float
) -> Optional[float]:
    """q-th percentile (0..1) over (value, weight) pairs — the
    per-request ITL estimator shared by the engine's timing annotation
    and the frontend's llm_metrics event."""
    if not pairs:
        return None
    pairs = sorted(pairs)
    total = sum(n for _, n in pairs)
    if total <= 0:
        return None
    rank = q * total
    seen = 0
    for value, n in pairs:
        seen += n
        if seen >= rank:
            return value
    return pairs[-1][0]


def render_histogram(
    name: str, help_: str, snap: dict[str, Any], label: str = "",
    *, openmetrics: bool = False,
) -> list[str]:
    """Prometheus text-format lines for one snapshot. ``label`` is a
    pre-rendered extra label pair (e.g. ``worker="w0"``) or empty.

    ``openmetrics=True`` appends ``# {trace_id="..."} value ts`` exemplar
    suffixes to bucket lines that carry one (the OpenMetrics exposition
    format); the default plain Prometheus text output is byte-identical
    to what it always was — exemplars only ship to scrapers that
    negotiated ``application/openmetrics-text``."""

    def fmt(le: str) -> str:
        pairs = f'le="{le}"' if not label else f'{label},le="{le}"'
        return f"{name}_bucket{{{pairs}}}"

    exemplars = snap.get("exemplars") or {} if openmetrics else {}

    def ex(i: int) -> str:
        e = exemplars.get(str(i)) or exemplars.get(i)
        if not e:
            return ""
        eid, value, ts = e
        return f' # {{trace_id="{eid}"}} {value} {ts:.3f}'

    lines = [f"# HELP {name} {help_}", f"# TYPE {name} histogram"]
    for i, (edge, cum) in enumerate(zip(snap["buckets"], snap["counts"][:-1])):
        lines.append(f"{fmt(repr(float(edge)))} {cum}{ex(i)}")
    n_b = len(snap["buckets"])
    lines.append(f"{fmt('+Inf')} {snap['counts'][-1]}{ex(n_b)}")
    suffix = f"{{{label}}}" if label else ""
    lines.append(f"{name}_sum{suffix} {snap['sum']}")
    lines.append(f"{name}_count{suffix} {snap['count']}")
    return lines


class TelemetryRegistry:
    """Ordered set of histograms with one render/snapshot surface."""

    def __init__(self) -> None:
        self._hists: dict[str, Histogram] = {}

    def histogram(
        self,
        name: str,
        help_: str,
        buckets: tuple[float, ...] = DEFAULT_TIME_BUCKETS,
    ) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram(name, help_, buckets)
        return h

    def get(self, name: str) -> Optional[Histogram]:
        return self._hists.get(name)

    def snapshot(self) -> dict[str, dict[str, Any]]:
        """name -> {help, buckets, counts, sum, count} — the wire form
        carried in ForwardPassMetrics.histograms."""
        return {
            name: dict(h.snapshot(), help=h.help)
            for name, h in self._hists.items()
        }

    def render(self, label: str = "", openmetrics: bool = False) -> str:
        lines: list[str] = []
        for h in self._hists.values():
            lines.extend(h.render(label, openmetrics=openmetrics))
        return "\n".join(lines) + ("\n" if lines else "")

    def reset(self) -> None:
        for h in self._hists.values():
            h.reset()


# canonical request-latency series (names are the metrics contract —
# tests/test_metrics_contract.py asserts they render with HELP/TYPE and
# are documented in README)
TTFT = ("dynamo_request_ttft_seconds",
        "time from request receipt to first emitted token")
ITL = ("dynamo_request_itl_seconds",
       "inter-token latency (per-token gaps within one generation)")
E2E = ("dynamo_request_e2e_seconds",
       "end-to-end request latency (receipt to finish)")
QUEUE = ("dynamo_request_queue_seconds",
         "admission queue wait (enqueue to prefill start)")
ROUND = ("dynamo_engine_round_seconds",
         "engine round wall time (dispatch to result processed)")
# request phases closed where the work ENDS, so that engine TTFT =
# queue + first_token by construction (engine._process_first)
FIRST_TOKEN = ("dynamo_request_first_token_seconds",
               "first lane given (queue end) to first token fetched on "
               "the host: prefill device time plus the wait behind "
               "programs already in flight")
FRONTEND = ("dynamo_request_frontend_seconds",
            "HTTP handler entry to engine intake: parse, templating, "
            "tokenizing, routing and transport (requests stamped "
            "with received_unix only)")
# the time between tokens, from inside (engine._process_round,
# _note_prefill_dispatch, _poll_dry, _decode_span). A consumed fused
# round's GAP is the time from the later of its dispatch and the previous
# round's consume to its own consume, per decode step: over a stretch in
# which a round is always in flight the gaps x steps telescope to the wall
TPOT = ("dynamo_request_tpot_seconds",
        "per finished request with more than one token: (last emit - "
        "first token) / (tokens - 1), the engine's side of the client's "
        "time per output token")
STEP_GAP = ("dynamo_engine_step_gap_seconds",
            "per consumed decode round: consume time less the later of "
            "its dispatch and the previous round's consume, per step")
STEP_GAP_CLEAN = ("dynamo_engine_step_gap_clean_seconds",
                  "the same gap, of the rounds with no prefill program "
                  "dispatched since the round before them")
ROUND_PREFILL_AHEAD = (
    "dynamo_engine_round_prefill_tokens_ahead",
    "padded prompt positions of the prefill programs dispatched since the "
    "round before, observed only by rounds that had some (the count is "
    "the rounds that stood behind a prefill)")
DISPATCH_DRY = ("dynamo_engine_dispatch_found_dry",
                "per model-program dispatch (round, prefill, spec verify): "
                "1 if the newest program dispatched before it had already "
                "finished or none was, so the device stood dry; else 0")
# work and waste, one observation per dispatch / consumed round: the
# sum is the quantity, the count the dispatches (or requests, rounds)
PREFILL_TOKENS = ("dynamo_engine_prefill_tokens",
                  "real prompt tokens computed per prefill dispatch")
PREFILL_PADDED = ("dynamo_engine_prefill_padded_tokens",
                  "token positions a prefill dispatch ran: live row "
                  "blocks x block height where the program loops over "
                  "them, lanes of the compiled group (dummies included) "
                  "x bucket width otherwise")
PREFILL_MATCHED = ("dynamo_engine_prefill_matched_tokens",
                   "prompt tokens served from a prefix match (HBM or "
                   "host tier) per request starting its prefill")
PREFILL_ATTN_LIVE = ("dynamo_engine_prefill_attn_live_pairs",
                     "(query, key) pairs the attention mask admits for "
                     "the real prompt rows of a prefill dispatch")
PREFILL_ATTN_SCORED = ("dynamo_engine_prefill_attn_scored_pairs",
                       "(query, key) pairs a prefill dispatch computes a "
                       "score for: whole blocks of its live rows")
PREFILL_ATTN_BLOCKS = (
    "dynamo_engine_prefill_attn_blocks",
    "(lane, query block) pairs with a live row that the attention layers "
    "of a prefill dispatch ran: the work list's items x the layers with a "
    "prefill attention (the host's mirror, llama.prefill_mirror)")
PREFILL_ATTN_FUSED_BLOCKS = (
    "dynamo_engine_prefill_attn_fused_blocks",
    "of dynamo_engine_prefill_attn_blocks, the pairs that ran through the "
    "fused kernel (ops/flash_prefill.py): the expanded latent layers' and "
    "the dense decoder's GQA layers' on TPU devices at a geometry inside "
    "attention.prefill_fuses (no int8 region read), 0 elsewhere")
ROUND_LIVE_LANE_STEPS = ("dynamo_engine_round_live_lane_steps",
                         "lanes live at dispatch x steps per fused "
                         "decode round")
ROUND_TOKENS = ("dynamo_engine_round_tokens",
                "tokens a consumed decode round delivered to streams")
MOE_TOUCHED = ("dynamo_moe_experts_touched",
               "distinct routed experts whose weights a consumed decode "
               "round read, summed over its steps and expert layers")
MOE_ROUTED = ("dynamo_moe_tokens_routed",
              "(token, expert) picks of live lanes in a consumed decode "
              "round, summed over its steps and expert layers")
MOE_LOAD_MAX = ("dynamo_moe_expert_load_max",
                "most tokens one expert received in one step of one layer "
                "of a consumed decode round")
MOE_PICKS_ROUTED = ("dynamo_moe_picks_routed",
                    "models that hold a share of the experts: all (token, "
                    "pick) pairs the router made for live lanes in a "
                    "consumed decode round, held here or elsewhere")
MOE_GROUPS_KEPT_HERE = (
    "dynamo_moe_groups_kept_here",
    "models whose router picks within groups and that hold a share of the "
    "experts: routed tokens of live lanes in a consumed decode round, "
    "summed over its steps and expert layers, whose kept groups include "
    "one held here (the others can land no pick on this chip)")
KDA_STATE_ROWS_STEPPED = (
    "dynamo_kda_state_rows_stepped",
    "delta-rule (KDA) models: per-lane matrix states the steps of a "
    "consumed decode round moved on, counted by the program: the lanes "
    "its step kernel's work list held (the live ones), summed over the "
    "round's steps x the delta-rule layers")
SSM_STATE_ROWS_STEPPED = (
    "dynamo_ssm_state_rows_stepped",
    "state-space models (Mamba-1 selective scan or Mamba-2): per-lane "
    "states ([d_state, inner], or [heads, head, d_state]) the steps of a "
    "consumed decode round moved on, counted by the program: the lanes "
    "its step kernel's work list held (the live ones), summed over the "
    "round's steps x the state-space layers")
SSM_SCAN_POSITIONS = (
    "dynamo_ssm_scan_positions",
    "state-space models (Mamba-1 or Mamba-2): positions the prefill scans of a "
    "dispatch ran, all such layers: the lanes' live scan blocks x their "
    "height where the program loops over them, lanes x bucket width "
    "where it does not (the host's mirror, ssm_moe.prefill_mirror)")
# ``dynamo_layer_parts_run{part}``: histograms carry no labels here, so the
# label is the end of the name
LAYER_PARTS_RUN = {
    part: (f"dynamo_layer_parts_run_{part}",
           f"hybrid stacks: {what} the steps of a dispatched decode round "
           "ran (steps x layers of the part; the host's mirror, "
           "ssm_moe.decode_mirror): a stack of two-part layers runs a "
           "mixer and a feed-forward part a layer, one of one-part layers "
           "one part a layer")
    for part, what in (
        ("mixer_ssm", "recurrent (state-space, delta-rule, linear) mixers"),
        ("mixer_attn", "attention and other stateless mixers"),
        ("experts", "routed-expert parts (the layers that route)"),
        ("mlp", "dense MLP parts"))}
MOE_PREFILL_ROWS_SORTED = (
    "dynamo_moe_prefill_rows_sorted",
    "(token, pick) rows the expert layers of a finished prefill program "
    "sorted, all its expert layers, where they move rows in the looped "
    "form (models/moe.py: move_block): positions x picks x expert layers")
MOE_PREFILL_ROWS_MOVED = (
    "dynamo_moe_prefill_rows_moved",
    "rows the looped gathers of that program's expert layers ran: the "
    "row blocks (moe.MOVE_ROWS high) that hold a pick computed here "
    "(routed, on an expert held here) x their height, counted by the "
    "program itself")
SSM_STATE_BYTES = ("dynamo_ssm_state_bytes",
                   "bytes one lane holds in recurrent state (a state-space "
                   "layer's SSM state and convolution window, a "
                   "linear-attention layer's matrix state, a delta-rule "
                   "layer's matrix state and three convolution windows, a "
                   "Mamba-1 layer's state and convolution window), "
                   "all layers, "
                   "whatever its context (observed once, at engine start)")
SPARSE_ATTN_ROWS_READ = (
    "dynamo_sparse_attn_rows_read",
    "block-sparse attention models: rows a dispatched decode round's "
    "sparse layers read, all such layers and steps: the selected blocks' "
    "rows and the ring of a lane past the switch to the selection, its "
    "own rows for one below it, and the compressed keys the selection "
    "scored (every lane's whole compressed region, in their own rows)")
SPARSE_ATTN_ROWS_LIVE = (
    "dynamo_sparse_attn_rows_live",
    "block-sparse attention models: rows a dense read of that round's "
    "live lanes would take, all sparse layers and steps")
SPARSE_PREFILL_SCORED = (
    "dynamo_sparse_prefill_pairs_scored",
    "block-sparse attention models: (query, key) pairs a prefill "
    "dispatch's sparse layers computed a score for (the whole causal "
    "context in whole blocks, masked to the selection afterwards)")
SPARSE_PREFILL_SELECTED = (
    "dynamo_sparse_prefill_pairs_selected",
    "block-sparse attention models: (query, key) pairs the selection "
    "admits for that dispatch's real rows, all sparse layers: what a "
    "prefill that gathered the chosen blocks would score")
HC_SINKHORN_RESIDUAL = (
    "dynamo_hc_sinkhorn_residual",
    "hyper-connection models: max over a consumed decode round's tokens "
    "of |rowsum(H_res) - 1| in the last layer, what the Sinkhorn "
    "iterations left unconverged")
PREFILL_CONTINUED = (
    "dynamo_prefill_continued_tokens",
    "prompt tokens a prefill dispatch computed in chunks that continue a "
    "context already in the region (q_start > 0)")
DECODE_ATTN_ROWS_READ = (
    "dynamo_decode_attn_rows_read",
    "region rows a dispatched decode round's attention read a layer "
    "(latent rows, or K and V rows of the dense decoder and the hybrid "
    "block's attention layers): steps x the dispatched lanes' own rows "
    "in whole chunks under a TPU kernel's work list; on the CPU meshes "
    "steps x lanes x the longest dispatched lane (the latent XLA loop) "
    "or x the whole region (the dense jnp reference)")
DECODE_ATTN_ROWS_LIVE = (
    "dynamo_decode_attn_rows_live",
    "region rows of that round that were some live lane's own context: "
    "steps x the sum of the lanes' lengths")
ATTN_SHARED_ROWS_READ = (
    "dynamo_attn_shared_rows_read",
    "models whose cross-attention layers read ANOTHER layer's K/V rows: "
    "region rows of that one layer a dispatched decode round read, by the "
    "layer itself and by every cross layer (dynamo_decode_attn_rows_read "
    "x the readers): stored once, read once a reader")
ATTN_WINDOW_ROWS_READ = (
    "dynamo_attn_window_rows_read",
    "window-attention models: rows of the lanes' window buffers a "
    "dispatched decode round's window layers read, all such layers and "
    "steps: the dispatched lanes' buffered rows in whole chunks under a "
    "TPU kernel's work list (the whole buffer of every lane under the "
    "jnp reference of the CPU meshes)")
ATTN_WINDOW_ROWS_BOUND = (
    "dynamo_attn_window_rows_bound",
    "window-attention models: rows the window admits in that round, "
    "min(context, window) a live lane a step, all window layers")
DECODE_ATTN_Q_ROWS_FULL = (
    "dynamo_decode_attn_q_rows_full",
    "models whose layers differ in their number of query heads: "
    "query-head rows the FULL attention layers' decode scored in a "
    "dispatched round: live lanes x steps x the query heads of every such "
    "layer (each row scores its lane's whole context)")
DECODE_ATTN_Q_ROWS_WINDOW = (
    "dynamo_decode_attn_q_rows_window",
    "the same of the WINDOW attention layers (each row scores "
    "min(context, window) keys)")
PREFILL_LAYER_ROWS = (
    "dynamo_prefill_layer_rows",
    "models whose prefill stops a chunk's rows part-way up the stack: real "
    "prompt rows x layers of a prefill dispatch")
PREFILL_LAYER_ROWS_SKIPPED = (
    "dynamo_prefill_layer_rows_not_climbed",
    "of those, the rows x layers the program never ran: every row but "
    "each chunk's last real one, in the layers above the last that "
    "writes rows or state")
KV_ROW_BYTES = ("dynamo_kv_row_bytes",
                "bytes one token holds in the ctx region, all layers "
                "(observed once, at engine start)")


KV_CACHE_PLANES = ("dynamo_kv_cache_planes",
                   "planes of K/V rows a token holds in the ctx region: "
                   "one a layer, or one a (loop step, layer) of a looped "
                   "stack (observed once, at engine start)")
LOOP_STEPS_RUN = ("dynamo_loop_steps_run",
                  "passes of the layer stack a decoded token ran in a "
                  "consumed round of a looped model (every step today; "
                  "what a per-token early exit will lower)")
# a histogram a loop step before the last, as many as a counter row is
# given columns for (models/llama.py: stats_layout)
LOOP_EXIT_CDF = tuple(
    (f"dynamo_loop_exit_cdf_at_step_{t}",
     f"mean over a consumed round's live lanes of the exit gate's CDF "
     f"after loop step {t} (its last decode step): the share of exit mass "
     f"a threshold below 1 would have let leave by then")
    for t in range(3))


class Counter(NamedTuple):
    """One column of the counter row a fused decode round brings home
    (models/llama.py: ``stats_layout``): what a block's program counts,
    tied to the histogram that takes it."""
    metric: Optional[str]    # a name above; None: nothing reads the column
    f32_bits: bool = False   # the int32 column holds a float32's bits


# token-count series: powers of two up to a full 32k-position dispatch
TOKEN_BUCKETS = tuple(float(2 ** i) for i in range(16))
# pair-count series: up to eight lanes of 4096 x 4096 pairs
PAIR_BUCKETS = tuple(float(4 ** i) for i in range(4, 15))


def request_histograms(
    reg: TelemetryRegistry, *, engine: bool = False
) -> TelemetryRegistry:
    """Install the canonical request series on ``reg``. ``engine=True``
    adds the engine-only series (queue wait, round time, the request
    phases and the work-and-waste token counts)."""
    for name, help_ in (TTFT, ITL, E2E):
        reg.histogram(name, help_)
    if engine:
        for name, help_ in (QUEUE, ROUND, FIRST_TOKEN, FRONTEND, TPOT,
                            STEP_GAP, STEP_GAP_CLEAN):
            reg.histogram(name, help_)
        reg.histogram(*DISPATCH_DRY, (0.0, 1.0))
        for name, help_ in (PREFILL_TOKENS, PREFILL_PADDED, PREFILL_MATCHED,
                            ROUND_PREFILL_AHEAD,
                            ROUND_LIVE_LANE_STEPS, ROUND_TOKENS,
                            MOE_TOUCHED, MOE_ROUTED, MOE_LOAD_MAX,
                            MOE_PICKS_ROUTED, MOE_GROUPS_KEPT_HERE,
                            PREFILL_CONTINUED, PREFILL_ATTN_BLOCKS,
                            PREFILL_ATTN_FUSED_BLOCKS):
            reg.histogram(name, help_, TOKEN_BUCKETS)
        reg.histogram(*HC_SINKHORN_RESIDUAL,
                      tuple(10.0 ** i for i in range(-9, 1)))
        for name, help_ in (DECODE_ATTN_ROWS_READ, DECODE_ATTN_ROWS_LIVE,
                            MOE_PREFILL_ROWS_SORTED, MOE_PREFILL_ROWS_MOVED,
                            KDA_STATE_ROWS_STEPPED, SSM_STATE_ROWS_STEPPED,
                            SSM_SCAN_POSITIONS, ATTN_SHARED_ROWS_READ,
                            ATTN_WINDOW_ROWS_READ, ATTN_WINDOW_ROWS_BOUND,
                            DECODE_ATTN_Q_ROWS_FULL, DECODE_ATTN_Q_ROWS_WINDOW,
                            PREFILL_LAYER_ROWS, PREFILL_LAYER_ROWS_SKIPPED,
                            *LAYER_PARTS_RUN.values()):
            reg.histogram(name, help_,
                          tuple(float(4 ** i) for i in range(3, 13)))
        reg.histogram(*KV_ROW_BYTES, tuple(float(4 ** i) for i in range(3, 12)))
        reg.histogram(*KV_CACHE_PLANES,
                      tuple(float(2 ** i) for i in range(11)))
        reg.histogram(*LOOP_STEPS_RUN, tuple(float(i) for i in range(1, 9)))
        for name, help_ in LOOP_EXIT_CDF:
            reg.histogram(name, help_,
                          tuple(i / 10 for i in range(1, 11)))
        reg.histogram(*SSM_STATE_BYTES,
                      tuple(float(4 ** i) for i in range(6, 15)))
        for name, help_ in (PREFILL_ATTN_LIVE, PREFILL_ATTN_SCORED,
                            SPARSE_PREFILL_SCORED, SPARSE_PREFILL_SELECTED):
            reg.histogram(name, help_, PAIR_BUCKETS)
        for name, help_ in (SPARSE_ATTN_ROWS_READ, SPARSE_ATTN_ROWS_LIVE):
            reg.histogram(name, help_,
                          tuple(float(4 ** i) for i in range(3, 13)))
    return reg
