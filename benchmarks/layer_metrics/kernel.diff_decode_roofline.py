"""The differential decode attention kernel's share of its roofline (the
work-list kernel of ops/flash_decode.py under the name
``diff_decode_attention``: one Mosaic call a window, full or cross layer a
decode step, both score maps of every query pair in one pass over pair-wide
K and V rows of 128). Bound: HBM bandwidth.

Bytes: what a step of the kernel's calls must read at the live lanes of 20
instants of the traced span (step.decode_roofline's instants, rebuilt from
the generator's log): the live lanes' own rows of the full layer x its
readers, and the rows the WINDOW admits in the window layers, never the
whole chunks fetched (``benchmarks/bytes/<name>.py: diff_decode_bytes``), x
the steps the traced span holds (executions of ``jit_engine_round_seal`` x
``flush_every``). Time: the seconds of every custom call whose label starts
with ``diff_decode_attention`` in the traced span. (The window's mean of
the host's mirrors over-reads a 3 s span taken 4 s into the window, when
fewer lanes are live than later: 104.6 % where the span's own lanes read
70 %, my chip run, PR 54.) A program without the kernel, a run without a
traced span, or a byte count without ``diff_decode_bytes``: nothing to
read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")
MODULE = "jit_engine_round_seal"
KERNEL = "diff_decode_attention"


def read(sources):
    trace, cfg, span = (sources.get("trace"), sources["config"],
                        sources.get("trace_span"))
    if not trace or not span or "bytes" not in cfg or MODULE not in trace.get(
            "modules", {}):
        return None
    mod = sources["byname"].module_with(_BYTES, cfg["bytes"],
                                        "decode_bytes_per_step")
    if not hasattr(mod, "diff_decode_bytes"):
        return None
    seconds = sum(s for label, s in trace.get("kernels", {}).items()
                  if label.split(" ")[0] == KERNEL)
    if seconds <= 0:
        return None
    live = sources["byname"].module_with(
        _HERE, "step.decode_roofline", "read").live_contexts
    instants = [span[0] + (span[1] - span[0]) * (i + 0.5) / 20
                for i in range(20)]
    need = sum(mod.diff_decode_bytes(sources, live(sources["log"], t))
               for t in instants) / len(instants)
    steps = (trace["modules"][MODULE]["count"]
             * sources["engine_up"]["flush_every"])
    _, bw = sources["peaks"].peaks_for(sources["engine_up"]["device_kind"])
    return need * steps / bw / seconds * 100.0
