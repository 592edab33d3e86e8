"""Share of the routed experts (expert layers x 64 experts a layer) whose
weights one decode step read, mean over the window, in the long-document
cell: at most 16 lanes x top 4 of 64 experts, so a step can touch all of
a layer's experts only with every lane live and no two picks alike. The
counter and the arithmetic are moe.experts_touched_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "moe.experts_touched_share", "read").read(sources)
