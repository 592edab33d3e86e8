"""Most tokens one held expert received in a step of a layer over the mean
a touched expert received, in the coding-turn cell (random weights and a
selection bias of 0.01 in score units: near-uniform routing, so close to
the small-sample extreme of ~12 held picks over 32 experts). The counters
and the arithmetic are moe.load_max_over_mean's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "moe.load_max_over_mean", "read").read(sources)
