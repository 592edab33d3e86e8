"""Runtime configuration + logging initialization.

Parity: reference lib/runtime/src/config.rs:44,103-127 — figment layering
(defaults <- TOML file <- ``DYN_RUNTIME_*`` env) — and logging.rs:24-62 —
``DYN_LOG`` level filter, ``DYN_LOGGING_JSONL`` structured mode.

Here: dataclass defaults <- TOML file (``DYNTPU_CONFIG`` or ./dynamo_tpu
.toml) <- ``DYNTPU_*`` environment variables. Logging:

    DYNTPU_LOG=debug            root level (or "pkg=debug,other=info")
    DYNTPU_LOGGING_JSONL=1      one JSON object per line
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Optional

log = logging.getLogger(__name__)

ENV_PREFIX = "DYNTPU_"


@dataclass
class RuntimeConfig:
    """Process-wide runtime knobs (RuntimeConfig, config.rs:44).

    ``control_plane`` is None unless a file/env layer sets it — it doubles
    as the discovery-mode opt-in, so a baked-in default would silently
    flip every invocation into distributed mode."""

    control_plane: Optional[str] = None
    namespace: str = "dynamo"
    http_host: str = "0.0.0.0"
    http_port: int = 8080
    # worker defaults
    page_size: int = 64
    num_pages: int = 512
    max_decode_slots: int = 8
    cache_dtype: str = "bfloat16"
    # paged-pool KV quantization: "none" | "int8" (int8 pages with
    # per-block scales across the G1-G4 tiers and the transfer plane)
    kv_quant: str = "none"
    host_offload_pages: int = 0
    disk_offload_pages: int = 0
    disk_offload_path: Optional[str] = None
    # eager G3 startup scrub (kv_integrity): verify every manifest entry
    # against the backing file at attach instead of lazily at gather
    scrub_on_start: bool = False
    # speculative decoding (dynamo_tpu/spec/): off | ngram | draft
    speculative: str = "off"
    num_speculative_tokens: int = 4
    # acceptance-adaptive K (per-slot effective K in [spec_min_k, K])
    spec_adaptive: bool = True
    spec_min_k: int = 1
    # tree speculation: multi-branch drafts under one tree-causal verify
    # (budget 0 = auto: 1 + K * branches)
    spec_tree: bool = False
    spec_branches: int = 4
    spec_tree_budget: int = 0
    # acceptance gating (0.0 = off) + re-arm pacing
    spec_gate_acceptance: float = 0.0
    spec_gate_window: int = 4
    spec_rearm_tokens: int = 256
    # chunk-pipelined KV-transfer plane (kv_transfer.py): pages per
    # streamed chunk (0 = monolithic single-blob transfers), chunk
    # gathers/D2H copies in flight per export stream, and the deadline
    # for one queued page export/import op
    kv_transfer_chunk_pages: int = 8
    kv_transfer_inflight_chunks: int = 2
    xfer_op_timeout_s: float = 120.0
    # idle-timeout reclaiming a chunked export stream whose receiver
    # stalled (pinned gather handles/page refs freed after this long
    # without progress)
    kv_transfer_stream_idle_timeout_s: float = 15.0
    # fleet prefix economy (kv_router/fleet.py + prefetch.py): desired
    # fleet copies of a hot block (<= 1 disables the replication
    # controller), top-K hot chains examined/pushed per tick, the
    # controller tick period, the indexer's access-heat decay half-life
    # (0 = raw undecayed counters), and the dedup-admission gate
    kv_replication_target: int = 2
    kv_prefetch_hot_k: int = 8
    kv_prefetch_interval_s: float = 2.0
    kv_freq_halflife_s: float = 600.0
    kv_dedup_admission: bool = True
    # overload plane (dynamo_tpu/overload/): bounded admission budgets
    # (0 = unbounded) + the running-preemption flag
    max_waiting_requests: int = 0
    max_waiting_prefill_tokens: int = 0
    preempt_running: bool = False
    # performance-attribution plane (telemetry/prof.py): the SLO
    # burn-rate gauges dynamo_slo_{ttft,itl}_burn_rate over these targets
    slo_ttft_target_s: float = 0.5
    slo_itl_target_s: float = 0.05
    slo_objective: float = 0.99
    # tail-latency forensics (telemetry/forensics.py): SLO breaches are
    # ALWAYS captured into the /debug/outliers dossier ring; this adds a
    # coin-flip sample of healthy requests as a comparison baseline
    # (0 = breaches only)
    forensics_sample_rate: float = 0.0

    @property
    def store_host_port(self) -> tuple[str, int]:
        host, _, port = (self.control_plane or "").partition(":")
        return host or "127.0.0.1", int(port or 7111)


def _coerce(value: str, target_type) -> Any:
    if target_type is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if target_type is int:
        return int(value)
    if target_type is float:
        return float(value)
    return value


def load_config(
    path: Optional[str] = None, env: Optional[dict[str, str]] = None
) -> RuntimeConfig:
    """defaults <- TOML file <- DYNTPU_* env (later layers win). The cwd
    fallback file (./dynamo_tpu.toml) applies only under the real process
    environment — an explicit ``env`` asks for isolation."""
    from_process_env = env is None
    env = os.environ if env is None else env
    values: dict[str, Any] = {}

    path = path or env.get(ENV_PREFIX + "CONFIG")
    if path is None and from_process_env and os.path.exists("dynamo_tpu.toml"):
        path = "dynamo_tpu.toml"
    if path:
        import tomllib

        with open(path, "rb") as f:
            data = tomllib.load(f)
        section = data.get("runtime", data)  # [runtime] table or flat
        for f_ in dataclasses.fields(RuntimeConfig):
            if f_.name in section:
                values[f_.name] = section[f_.name]

    for f_ in dataclasses.fields(RuntimeConfig):
        key = ENV_PREFIX + f_.name.upper()
        if key in env:
            # field types are stringified (future annotations); the
            # default value's concrete type is the coercion target
            try:
                values[f_.name] = _coerce(env[key], type(f_.default))
            except ValueError:
                log.warning("ignoring invalid %s=%r", key, env[key])
    return RuntimeConfig(**values)


# ---------------------------------------------------------------------------
# logging (logging.rs:24-62)


class JsonlFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": self.formatTime(record, "%Y-%m-%dT%H:%M:%S"),
            "level": record.levelname,
            "logger": record.name,
            "msg": record.getMessage(),
        }
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        return json.dumps(out, separators=(",", ":"))


def init_logging(env: Optional[dict[str, str]] = None) -> None:
    """Configure root logging from DYNTPU_LOG / DYNTPU_LOGGING_JSONL.
    Idempotent; a pre-configured root (tests, embedders) is respected."""
    env = os.environ if env is None else env
    root = logging.getLogger()
    if root.handlers:
        _apply_filters(env.get(ENV_PREFIX + "LOG", ""), root)
        return

    handler = logging.StreamHandler(sys.stderr)
    if env.get(ENV_PREFIX + "LOGGING_JSONL", "").lower() in ("1", "true"):
        handler.setFormatter(JsonlFormatter())
    else:
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(name)s %(message)s"
        ))
    root.addHandler(handler)
    root.setLevel(logging.INFO)
    _apply_filters(env.get(ENV_PREFIX + "LOG", ""), root)
    # jax is chatty at INFO in some builds
    logging.getLogger("jax").setLevel(logging.WARNING)


def _apply_filters(spec: str, root: logging.Logger) -> None:
    """'debug' or 'dynamo_tpu=debug,aiohttp=warning' (DYN_LOG shape)."""
    if not spec:
        return
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            if "=" in part:
                name, _, level = part.partition("=")
                logging.getLogger(name.strip()).setLevel(
                    level.strip().upper()
                )
            else:
                root.setLevel(part.upper())
        except ValueError:
            # a typo'd level must not crash every CLI invocation
            log.warning("ignoring invalid log filter %r", part)
