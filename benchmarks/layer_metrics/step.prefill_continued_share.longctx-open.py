"""Share of the long-context cell's computed prompt positions that ran in
prefill chunks CONTINUING a lane (``q_start > 0``): the chunk starts from
the lane's matrix states as the chunk before left them, and its sparse
layers score the region's rows and compressed keys. Every prompt here is
over 8192 tokens, so all but its first 4096 go this way (two thirds and
more); the path the check's 4097-, 8170- and 12300-token prompts hold to
the reference. The counters and the arithmetic are
step.prefill_continued_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.prefill_continued_share", "read").read(sources)
