"""The decode step's share of its roofline, bound: HBM bandwidth. The
least time a chip could take for one step is the bytes it must read over
the peak bandwidth; divided by the measured device time of a step
(step.decode_ms). Live context lengths are rebuilt from the generator's
log at 20 instants of the traced span.

The byte count is the configuration's: ``"bytes": "<name>"`` in its file
names ``benchmarks/bytes/<name>.py``, whose ``decode_bytes_per_step(
sources, ctx_lens)`` gives the HBM bytes ONE chip must read for one decode
step with lanes of those live context lengths. It is handed ``sources``
so that it may read the program's counters (experts a round touched, rows
a window layer read), and it imports no JAX: readers run in ``run.py``'s
process. No key: ``peaks.decode_bytes_per_step`` (its share of the
weights + the live context of every lane, from shapes). The rule for
either: count what one chip MUST read, low and never high; a share over
100 % means bytes counted that the step did not move, and the driver
refuses the run. A named file that is missing or lacks the function is an
error, never a fall back to another block's count (``byname.py``, which
``run.py`` hands over as ``sources["byname"]``)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")


def live_contexts(log, t):
    out = []
    for r in log:
        if not r["ok"] or len(r["chunks"]) < 2:
            continue
        first, last = r["chunks"][0], r["chunks"][-1]
        if first <= t <= last:
            done = (t - first) / max(last - first, 1e-9) * r["tokens"]
            out.append((r.get("prompt_tokens") or 0) + done)
    return out


def bytes_counter(sources):
    """ctx_lens -> bytes of one decode step, as the configuration counts
    them."""
    cfg, up = sources["config"], sources["engine_up"]
    if "bytes" not in cfg:
        eng = cfg["engine"]
        max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
        return lambda ctx_lens: sources["peaks"].decode_bytes_per_step(
            cfg, up["param_bytes"], ctx_lens, up["tp"], max_ctx)
    count = sources["byname"].module_with(
        _BYTES, cfg["bytes"], "decode_bytes_per_step").decode_bytes_per_step
    return lambda ctx_lens: count(sources, ctx_lens)


def read(sources):
    step_ms = sources["byname"].module_with(
        _HERE, "step.decode_ms", "read").read(sources)
    span = sources.get("trace_span")
    if step_ms is None or not span:
        return None
    count = bytes_counter(sources)
    instants = [span[0] + (span[1] - span[0]) * (i + 0.5) / 20
                for i in range(20)]
    need = sum(count(live_contexts(sources["log"], t))
               for t in instants) / len(instants)
    _, bw = sources["peaks"].peaks_for(sources["engine_up"]["device_kind"])
    return need / bw / (step_ms / 1e3) * 100.0
