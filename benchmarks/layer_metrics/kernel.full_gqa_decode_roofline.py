"""The full layers' decode attention call's share of its roofline (the
work-list kernel of ops/flash_decode.py under the name
``full_gqa_decode_attention``: one Mosaic call a full layer a decode step,
a q block of [8, 6, 128] a lane over its whole context). Bound: HBM
bandwidth.

Bytes: what a step of the kernel's calls must read at the live lanes of 20
instants of the traced span (step.decode_roofline's instants, rebuilt from
the generator's log): the live lanes' own K and V rows at their exact
lengths, 4096 B a row a layer, never the whole chunks fetched
(``benchmarks/bytes/<name>.py: full_decode_bytes``), x the steps the traced
span holds (executions of ``jit_engine_round_seal`` x ``flush_every``).
Time: the seconds of every custom call whose label starts with the
kernel's name in the traced span. (The window's mean of the host's mirrors
over-reads a short span taken early in the window, when fewer lanes are
live than later: PERF.md section 6, PR 54.) A program without the kernel,
a run without a traced span, or a byte count without the function: nothing
to read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")
MODULE = "jit_engine_round_seal"
KERNEL = "full_gqa_decode_attention"
BYTES = "full_decode_bytes"


def read_kernel(sources, kernel: str, function: str):
    """The roofline share of the decode attention call ``kernel`` whose
    bytes a step ``function`` of the configuration's byte count gives."""
    trace, cfg, span = (sources.get("trace"), sources["config"],
                        sources.get("trace_span"))
    if not trace or not span or "bytes" not in cfg or MODULE not in trace.get(
            "modules", {}):
        return None
    mod = sources["byname"].module_with(_BYTES, cfg["bytes"],
                                        "decode_bytes_per_step")
    if not hasattr(mod, function):
        return None
    seconds = sum(s for label, s in trace.get("kernels", {}).items()
                  if label.split(" ")[0] == kernel)
    if seconds <= 0:
        return None
    live = sources["byname"].module_with(
        _HERE, "step.decode_roofline", "read").live_contexts
    instants = [span[0] + (span[1] - span[0]) * (i + 0.5) / 20
                for i in range(20)]
    need = sum(getattr(mod, function)(sources, live(sources["log"], t))
               for t in instants) / len(instants)
    steps = (trace["modules"][MODULE]["count"]
             * sources["engine_up"]["flush_every"])
    _, bw = sources["peaks"].peaks_for(sources["engine_up"]["device_kind"])
    return need * steps / bw / seconds * 100.0


def read(sources):
    return read_kernel(sources, KERNEL, BYTES)
