"""The shared Mamba-1 decode step kernel's share of its roofline in the
long-thought cell (``m1_step``, nine layers a step, the live lanes'
[16, 5120] float32 states in place; bytes from
``benchmarks/bytes/sambay.py: m1_step_bytes``). The arithmetic is
kernel.m1_step_roofline's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "kernel.m1_step_roofline", "read").read(sources)
