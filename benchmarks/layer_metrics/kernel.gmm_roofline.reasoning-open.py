"""The grouped expert product's share of its roofline in the decode steps
of the reasoning cell (the megablox gmm kernel at 384 rows: 48 lanes x 8
picks, of which the ~1/8 on held experts are computed; tiles of a whole
[2560, 768] or [768, 2560] matrix). Bound: HBM bandwidth. Bytes (the held
experts touched), operations and labels:
benchmarks/bytes/kda_mla_moe.py: gmm_decode; the arithmetic is
kernel.gmm_roofline's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "kernel.gmm_roofline", "read").read(sources)
