"""Share of the region rows the two latent layers' decode attention read
that were some live lane's own context, in the reasoning cell: contexts
of 0.5k-18k side by side, read by the kernel in whole 512-row chunks a
lane. The counters and the arithmetic are step.decode_attn_live_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.decode_attn_live_share", "read").read(sources)
