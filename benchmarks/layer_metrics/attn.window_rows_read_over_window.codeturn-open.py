"""Rows the nine window layers' decode read from the lanes' window
buffers, over the rows the window admits (``min(n, 512)`` a live lane a
layer a step), in the coding-turn cell: every prompt is 512 tokens or
more, so every live lane's buffer is full and is read as the ONE 512-row
chunk it is: 1.0, but for the rows of the current round that wait in the
ring. The counters and the arithmetic are
attn.window_rows_read_over_window's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "attn.window_rows_read_over_window", "read").read(sources)
