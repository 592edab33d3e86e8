"""The grouped expert product's share of its roofline in the decode steps
of the retrieved-passages cell (the megablox gmm kernel at 320 rows: 32
lanes x 10 picks, of which the picks on held experts are computed; tiles
of a whole contraction and half the output columns, models/moe.py:
gmm_tile_n: 4096 x 384 and 768 x 2048). Bound: HBM bandwidth. Bytes
(the held experts touched), operations and labels:
benchmarks/bytes/ssm_moe.py: gmm_decode; the arithmetic is
kernel.gmm_roofline's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "kernel.gmm_roofline", "read").read(sources)
