"""The Mamba-1 state's share of a decode step's counted bytes in the
chat-rate cell: the live lanes' [16, 5120] float32 state (327680 B a
layer a lane, 26 layers) and convolution windows, read and written once
each, over everything ``benchmarks/bytes/jamba.py: decode_parts`` holds
for the step (6.06 GB of weights, the two attention layers' rows, state).
What the step pays for the recurrent layers whatever the context's
length: 18.6 MB a live lane beside 1 KB a token of rows. The arithmetic is
step.decode_state_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.decode_state_share", "read").read(sources)
