"""The Mamba-1 prefill scan kernel's share of its HBM roofline
(ops/mamba1.py: ``scan_pallas``, the Pallas call named ``m1_scan``: one a
lane's scan block a Mamba-1 layer; x and dt read and y written once each,
float32 [inner] a position, the [16, tile] state kept in VMEM). The bound
NAMED is HBM bandwidth, but the kernel is bound by the VECTOR UNIT: a
position is 16 x inner decays (an ``exp`` each), multiply-adds and a
16-way reduction for 12 x inner bytes, ~7 vector operations a byte where
the chip's balance is a fraction of one. A low share is what the
recurrence costs and says how far the kernel stands from the point where
memory would bound it.

Bytes: the positions the host's mirror says the window's prefill
dispatches scanned (``dynamo_ssm_scan_positions``, all Mamba-1 layers), as
a mean a dispatch, x 3 x inner x 4 (``benchmarks/bytes/<name>.py:
m1_scan_bytes``), x the prefill programs the traced span holds. Time: the
seconds of every custom call whose label starts with ``m1_scan`` there. A
program without the kernel or the counter, or a byte count without
``m1_scan_bytes``: nothing to read."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = os.path.join(os.path.dirname(_HERE), "bytes")
MODULES = ("jit_prefill_impl", "jit_batch_prefill_impl")
KERNEL = "m1_scan"


def read(sources):
    trace, cfg = sources.get("trace"), sources["config"]
    if not trace or "bytes" not in cfg:
        return None
    mod = sources["byname"].module_with(_BYTES, cfg["bytes"],
                                        "decode_bytes_per_step")
    if not hasattr(mod, "m1_scan_bytes"):
        return None
    scanned = mod.m1_positions_scanned(sources)
    programs = sum(trace["modules"][m]["count"] for m in MODULES
                   if m in trace.get("modules", {}))
    seconds = sum(s for label, s in trace.get("kernels", {}).items()
                  if label.split(" ")[0] == KERNEL)
    if scanned is None or programs <= 0 or seconds <= 0:
        return None
    positions, dispatches = scanned
    nbytes = mod.m1_scan_bytes(positions / dispatches)(cfg) * programs
    _, bw = sources["peaks"].peaks_for(sources["engine_up"]["device_kind"])
    return nbytes / bw / seconds * 100.0
