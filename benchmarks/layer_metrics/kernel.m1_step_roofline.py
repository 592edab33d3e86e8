"""The Mamba-1 decode step kernel's share of its roofline
(ops/mamba1.py: ``scan_step_pallas``, the Pallas call named ``m1_step``:
one a Mamba-1 layer a decode step, every LIVE lane's [16, inner] float32
state read and written once, in place). Bound: HBM bandwidth (a state
element is read, decayed by one ``exp``, fed one product and written: ~6
operations and one transcendental for 8 bytes, so the vector unit stands
close behind).

Bytes: the per-lane states the program's counter says the window's rounds
stepped (``dynamo_ssm_state_rows_stepped``), as a mean a round, x 2 x one
state (``benchmarks/bytes/<name>.py: m1_step_bytes``), x the rounds the
traced span holds (executions of ``jit_engine_round_seal``). Time: the
seconds of every custom call whose label starts with ``m1_step`` in the
traced span (``sources["trace"]["kernels"]``). A program without the
kernel or the counter, or a byte count without ``m1_step_bytes``: nothing
to read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")
MODULE = "jit_engine_round_seal"
KERNEL = "m1_step"


def read(sources):
    trace, cfg = sources.get("trace"), sources["config"]
    if not trace or "bytes" not in cfg or MODULE not in trace.get(
            "modules", {}):
        return None
    mod = sources["byname"].module_with(_BYTES, cfg["bytes"],
                                        "decode_bytes_per_step")
    if not hasattr(mod, "m1_step_bytes"):
        return None
    stepped = mod.m1_states_stepped(sources)
    seconds = sum(s for label, s in trace.get("kernels", {}).items()
                  if label.split(" ")[0] == KERNEL)
    if stepped is None or seconds <= 0:
        return None
    states, rounds = stepped
    nbytes = (mod.m1_step_bytes(states / rounds)(cfg)
              * trace["modules"][MODULE]["count"])
    _, bw = sources["peaks"].peaks_for(sources["engine_up"]["device_kind"])
    return nbytes / bw / seconds * 100.0
