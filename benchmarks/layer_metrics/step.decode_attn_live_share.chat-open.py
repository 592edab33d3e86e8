"""Share of the region rows the dense decode attention read that were
some live lane's own context, in the chat cell: 1.9 of 8 lanes live at
contexts of 0.1-1.3k rows, read by the flash kernel's work list in whole
512-row chunks a LIVE lane (a grid over every lane's chunks read every
lane's first chunk besides, live or not; a program without the counters:
nothing to read). The counters and the arithmetic are
step.decode_attn_live_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.decode_attn_live_share", "read").read(sources)
