"""Per-lane Mamba-2 states the decode steps moved on, over the states of
the lanes that held a request: delta sum dynamo_ssm_state_rows_stepped
(the states the step kernel's work lists held, all Mamba-2 layers, counted
by the program and observed when a round is consumed) / (delta sum
dynamo_engine_round_live_lane_steps, the lanes live at dispatch x the
round's steps, x the configuration's Mamba-2 layers,
``benchmarks/bytes/<name>.py: shapes(hf)["n_ssm"]``) over the window. 1.00
is a step that touches the live lanes' state and nothing else (to a
percent: the two counters are observed a round or two apart, so the
window's edges differ); a program that steps every lane would read 1 / its
lane utilisation if it counted. A program without the counter for this
stack (every one before PR 52), or a byte count without ``n_ssm``: nothing
to read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")
STEPPED = "dynamo_ssm_state_rows_stepped"
LIVE = "dynamo_engine_round_live_lane_steps"


def read(sources):
    cfg = sources["config"]
    if "bytes" not in cfg:
        return None
    d = {}
    for name in (STEPPED, LIVE):
        a = sources["before"]["histograms"].get(name)
        b = sources["after"]["histograms"].get(name)
        if a is None or b is None or b["count"] <= a["count"]:
            return None
        d[name] = b["sum"] - a["sum"]
    mod = sources["byname"].module_with(_BYTES, cfg["bytes"],
                                        "decode_bytes_per_step")
    layers = mod.shapes(cfg).get("n_ssm") if hasattr(mod, "shapes") else None
    if not layers or d[LIVE] <= 0:
        return None
    return d[STEPPED] / (d[LIVE] * layers)
