"""The decode step's share of its roofline, bound: HBM bandwidth. The
least time a chip could take for one step is (its share of the weights +
the live context of every lane, from shapes: benchmarks/peaks.py) over
the peak bandwidth; divided by the measured device time of a step
(step.decode_ms). Live context lengths are rebuilt from the generator's
log at 20 instants of the traced span."""
import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


def read(sources):
    step_ms = _sibling("step.decode_ms").read(sources)
    span = sources.get("trace_span")
    if step_ms is None or not span:
        return None
    peaks = sources["peaks"]
    cfg, up = sources["config"], sources["engine_up"]
    eng = cfg["engine"]
    max_ctx = eng["max_pages_per_seq"] * eng["page_size"]
    instants = [span[0] + (span[1] - span[0]) * (i + 0.5) / 20
                for i in range(20)]
    need = sum(peaks.decode_bytes_per_step(
        cfg, up["param_bytes"], live_contexts(sources["log"], t),
        up["tp"], max_ctx) for t in instants) / len(instants)
    _, bw = peaks.peaks_for(up["device_kind"])
    return need / bw / (step_ms / 1e3) * 100.0
