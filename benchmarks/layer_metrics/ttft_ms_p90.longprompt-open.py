"""90th percentile of time to first token in the open-loop long-prompt mix,
from the generator's clock in the traced run. Recorded, not judged (an
open loop's TTFT tail is modal: PERF.md sections 2 and 6). The arithmetic
is the chat-open mix's reader's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p90.chat-open", "read").read(sources)
