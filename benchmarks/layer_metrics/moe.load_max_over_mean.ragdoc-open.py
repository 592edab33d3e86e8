"""How uneven the routing of decode steps was in the retrieved-passages
cell: the most tokens one HELD expert received in one step of one layer
over the mean load of a touched held expert. With 15-25 lanes x ~5 held
picks over 36 held experts a touched expert sees two to four tokens; the
value rises with the lanes that decode together. The counters (over the
held experts) and the arithmetic are moe.load_max_over_mean's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "moe.load_max_over_mean", "read").read(sources)
