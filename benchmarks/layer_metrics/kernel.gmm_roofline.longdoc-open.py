"""The grouped expert product's share of its roofline in the decode steps
of the long-document cell (the megablox gmm kernel at 64 rows: 16
lanes x 4 picks; tiles of a whole contraction and half the output
columns, models/moe.py: gmm_tile_n). Bound: HBM bandwidth. Bytes,
operations and labels: benchmarks/bytes/mla_moe_mhc.py: gmm_decode;
the arithmetic is kernel.gmm_roofline's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "kernel.gmm_roofline", "read").read(sources)
