"""The recurrent state's share of a decode step's counted bytes: the
live lanes' SSM state and convolution window, read and written once
each, over everything the configuration's byte count holds for the step
(weights, held experts touched, attention rows, state:
``benchmarks/bytes/<name>.py: decode_parts``), at the live lanes of 20
instants of the traced span (step.decode_roofline's instants). What the
step pays for recurrent layers whatever the context's length. A
configuration whose byte count has no ``decode_parts``, or a run without
a traced span: nothing to read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")


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
    state = total = 0.0
    for i in range(20):
        t = span[0] + (span[1] - span[0]) * (i + 0.5) / 20
        parts = parts_of(sources, live(sources["log"], t))
        state += parts["state"]
        total += sum(parts.values())
    return state / total * 100.0 if total > 0 else None
