"""The grouped expert product's share of its roofline in decode steps
(the megablox ``gmm`` Pallas kernel, models/moe.py: ``_gmm_tpu``). Bound:
HBM bandwidth at decode widths (2 tokens an expert: ~25 operations a
byte against the chip's 240), so the least time is the larger of bytes /
peak bytes/s and operations / peak FLOP/s, which here is the bytes'.

Time: seconds of the kernel's decode-shaped calls in the traced span
(``sources["trace"]["kernels"]``: ``gmm bf16[slots x picks, expert
width]`` for the gate and up products, ``gmm bf16[slots x picks,
hidden]`` for the down product; prefill's calls have other row counts)
over the decode steps the span holds (executions of
``jit_engine_round_seal`` x flush_every). Bytes and operations:
``benchmarks/bytes/mla_moe.py: gmm_decode`` (the experts the program's
counter says a step touched, mean over the window). A program without
the kernel or the counter: nothing to read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")
MODULE = "jit_engine_round_seal"


def read(sources):
    trace, cfg = sources.get("trace"), sources["config"]
    if not trace or "bytes" not in cfg or MODULE not in trace.get(
            "modules", {}):
        return None
    mod = sources["byname"].module_with(_BYTES, cfg["bytes"],
                                        "decode_bytes_per_step")
    need = getattr(mod, "gmm_decode", lambda s: None)(sources)
    if need is None:
        return None
    nbytes, ops, labels = need
    seconds = sum(trace.get("kernels", {}).get(k, 0.0) for k in labels)
    steps = (trace["modules"][MODULE]["count"]
             * sources["engine_up"]["flush_every"])
    if seconds <= 0 or steps <= 0:
        return None
    flops, bw = sources["peaks"].peaks_for(
        sources["engine_up"]["device_kind"])
    least = max(nbytes / bw, ops / flops)
    return least / (seconds / steps) * 100.0
