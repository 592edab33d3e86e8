"""Share of the retrieved-passages cell's computed prompt positions that
ran in prefill chunks CONTINUING a lane (``q_start > 0``): the chunk
starts from the lane's recurrent state and conv window as the first
chunk left them, and its one attention layer scores the region's rows.
Prompts over 4096 tokens (a quarter of the mix) send their remainder
this way; the path the check's 4300- and 4097-token prompts hold to the
reference. The counters and the arithmetic are
step.prefill_continued_share's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "step.prefill_continued_share", "read").read(sources)
