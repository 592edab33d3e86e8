"""The matrix state's share of a decode step's counted bytes in the
long-context cell: the live lanes' linear-attention state (float32, 2.1
MB a layer a lane), read and written once each, over everything
``benchmarks/bytes/sala.py: decode_parts`` holds for the step (weights,
selected rows, compressed keys, state). What the step pays for the
recurrent layers whatever the context's length. The arithmetic is
step.decode_state_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.decode_state_share", "read").read(sources)
