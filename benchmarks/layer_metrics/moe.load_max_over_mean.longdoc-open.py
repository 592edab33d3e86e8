"""How uneven the routing of decode steps was in the long-document cell:
the most tokens one expert received in one step of one layer over the
mean load of a TOUCHED expert. With at most 16 lanes x top 4 of 64
experts and two or three lanes live, most touched experts see one token
and the value stands near 1; it rises with the lanes that decode
together. The counters and the arithmetic are moe.load_max_over_mean's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "moe.load_max_over_mean", "read").read(sources)
