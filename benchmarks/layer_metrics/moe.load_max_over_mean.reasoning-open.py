"""How uneven the routing of decode steps was in the reasoning cell: the
most tokens one HELD expert received in one step of one layer over the
mean load of a touched held expert. With ~40 lanes x 1 held pick over 64
held experts a touched expert sees one or two tokens. The counters (over
the held experts) and the arithmetic are moe.load_max_over_mean's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "moe.load_max_over_mean", "read").read(sources)
