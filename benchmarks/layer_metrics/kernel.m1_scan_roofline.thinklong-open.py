"""The shared Mamba-1 prefill scan kernel's share of its HBM roofline in
the long-thought cell (``m1_scan``, nine layers a chunk, chunks of up to
4096 rows in live 256-row blocks; bytes from ``benchmarks/bytes/sambay.py:
m1_scan_bytes``). Bound by the vector unit, as there: a low share is what
the recurrence costs. The arithmetic is kernel.m1_scan_roofline's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "kernel.m1_scan_roofline", "read").read(sources)
