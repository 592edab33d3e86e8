"""Share of the coding-turn cell's computed prompt positions that ran in
prefill chunks CONTINUING a lane (``q_start > 0``): the chunk's window
layers un-rotate the lane's wrapped buffer into a workspace of its last
512 rows, its full layers read the lane's prior rows from the region.
Prompts over 4096 tokens (half the mix) send their remainder this way; the
path the check's 8300- and 4700-token prompts hold to the reference. The
counters and the arithmetic are step.prefill_continued_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.prefill_continued_share", "read").read(sources)
