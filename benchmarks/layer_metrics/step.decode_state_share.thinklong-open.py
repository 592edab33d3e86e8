"""The Mamba-1 state's share of a decode step's counted bytes in the
long-thought cell: the live lanes' [16, 5120] float32 state (327680 B a
layer a lane, nine layers) and convolution windows, read and written once
each, over everything ``benchmarks/bytes/sambay.py: decode_parts`` holds
for the step (7.7 GB of weights, the one full layer's rows x its eight
readers, the window rows, state). Small here (6.5 MB a live lane beside
41 KB a token of shared rows): the recurrent half of the stack is what the
context's length does NOT reach. The arithmetic is
step.decode_state_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.decode_state_share", "read").read(sources)
