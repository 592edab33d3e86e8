"""90th percentile of time to first token in the open-loop long-context
mix, from the generator's clock in the traced run. Recorded, not judged:
an open loop's TTFT tail lands on one of a few trajectories of its
schedule (PERF.md sections 2 and 6), and here a prompt of 28672 tokens
waits out seven chunks. The arithmetic is the chat-decode mix's
reader's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p90.chat-decode-open", "read").read(sources)
