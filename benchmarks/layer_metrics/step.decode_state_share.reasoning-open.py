"""The delta-rule state's share of a decode step's counted bytes in the
reasoning cell: the live lanes' KDA state (float32, 2.1 MB a layer a
lane, ten layers) and convolution windows, read and written once each,
over everything ``benchmarks/bytes/kda_mla_moe.py: decode_parts`` holds
for the step (weights, held experts touched, latent rows, state). What
the step pays for the recurrent layers whatever the context's length;
with 30-48 live lanes it is the largest part. The arithmetic is
step.decode_state_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.decode_state_share", "read").read(sources)
