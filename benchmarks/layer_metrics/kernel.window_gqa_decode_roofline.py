"""The window layers' decode attention call's share of its roofline (the
work-list kernel of ops/flash_decode.py under the name
``window_gqa_decode_attention``: one Mosaic call a window layer a decode
step, a q block of [8, 9, 128] a lane over its lane's 512-row modular
buffer and the ring). Bound: HBM bandwidth, with the per-item latency close
behind (one 512-row chunk and a ring a lane: ~1 MB an item).

Bytes: the rows the WINDOW admits at the live lanes of 20 instants of the
traced span, ``min(n, 512)`` a lane a layer, 4096 B a row
(``benchmarks/bytes/<name>.py: window_decode_bytes``); steps, time and the
arithmetic are kernel.full_gqa_decode_roofline's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = "window_gqa_decode_attention"
BYTES = "window_decode_bytes"


def read(sources):
    return sources["byname"].module_with(
        _HERE, "kernel.full_gqa_decode_roofline", "read").read_kernel(
            sources, KERNEL, BYTES)
