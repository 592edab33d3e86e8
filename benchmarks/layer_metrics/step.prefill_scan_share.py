"""The Mamba-1 prefill scan's share of the prefill programs' device time
in the traced span: the seconds of every custom call whose label starts
with ``m1_scan`` (ops/mamba1.py: the Pallas kernel, one a lane's scan
block a layer) over the seconds of the prefill modules, solo and batched
(step.prefill_ms_per_ktok's). What the recurrence costs a prompt beside
the layer's matrix products. A program without the kernel: nothing to
read."""

MODULES = ("jit_prefill_impl", "jit_batch_prefill_impl")
KERNEL = "m1_scan"


def read(sources):
    trace = sources.get("trace")
    if not trace:
        return None
    secs = sum(trace["modules"][m]["seconds"] for m in MODULES
               if m in trace.get("modules", {}))
    scan = sum(s for label, s in trace.get("kernels", {}).items()
               if label.split(" ")[0] == KERNEL)
    if secs <= 0 or scan <= 0:
        return None
    return scan / secs * 100.0
