"""The share of a decode step's counted bytes that are the ONE full
attention layer's K/V rows, read by that layer and by every cross-attention
layer above it (``benchmarks/bytes/<name>.py: decode_parts["shared_rows"]``
over all its parts: weights, shared rows, window rows, state), at the live
lanes of 20 instants of the traced span (step.decode_roofline's instants).
The rows are stored once and read once a reader: memory saved, bandwidth
not. A configuration whose byte count has no such part, or a run without a
traced span: nothing to read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")
PART = "shared_rows"


def read(sources):
    cfg, span = sources["config"], sources.get("trace_span")
    if "bytes" not in cfg or not span:
        return None
    mod = sources["byname"].module_with(_BYTES, cfg["bytes"],
                                        "decode_bytes_per_step")
    parts_of = getattr(mod, "decode_parts", None)
    if parts_of is None:
        return None
    live = sources["byname"].module_with(
        _HERE, "step.decode_roofline", "read").live_contexts
    part = total = 0.0
    for i in range(20):
        t = span[0] + (span[1] - span[0]) * (i + 0.5) / 20
        parts = parts_of(sources, live(sources["log"], t))
        if PART not in parts:
            return None
        part += parts[PART]
        total += sum(parts.values())
    return part / total * 100.0 if total > 0 else None
