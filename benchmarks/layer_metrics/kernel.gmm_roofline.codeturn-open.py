"""The grouped expert product's share of its roofline in the decode steps
of the coding-turn cell (the megablox gmm kernel at 160 rows: 16 lanes x 10
picks, of which the ~1/8 on held experts are computed; tiles of a whole
[3072, 1024] or [1024, 3072] matrix). Bound: HBM bandwidth. Bytes (the held
experts touched), operations and labels:
benchmarks/bytes/window_gqa_moe.py: gmm_decode; the arithmetic is
kernel.gmm_roofline's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "kernel.gmm_roofline", "read").read(sources)
