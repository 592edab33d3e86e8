"""Of the (token, pick) pairs the router made for live lanes in decode
rounds, the share that landed on an expert held HERE, in the reasoning
cell: one routing group of eight is held and four are kept a token, so
about half the tokens keep this group (delta sum
dynamo_moe_groups_kept_here / routed tokens reads that beside it in the
snapshots) and ~1/8 = 12.5 % of the picks land here on random weights.
The counters and the arithmetic are moe.held_pick_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "moe.held_pick_share", "read").read(sources)
