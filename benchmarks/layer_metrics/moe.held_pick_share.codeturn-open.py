"""Of the (token, pick) pairs the router made for live lanes in decode
rounds, the share that landed on an expert held HERE, in the coding-turn
cell: one chip of the eight that share a layer's 256 experts, one routing
group, so ~1/8 = 12.5 % on random weights. The counters and the arithmetic
are moe.held_pick_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "moe.held_pick_share", "read").read(sources)
