"""Per-lane Mamba-1 states the decode steps moved on, over the states of
the lanes that held a request, in the long-thought cell (nine Mamba-1
layers; lanes free and fill as answers of 256-1536 tokens end): 1.00 is a
step that touches the live lanes' state and nothing else. The arithmetic is
ssm.states_stepped_over_live's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ssm.states_stepped_over_live", "read").read(sources)
