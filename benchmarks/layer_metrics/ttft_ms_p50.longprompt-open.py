"""Median time to first token (due time -> first streamed chunk with text)
in the open-loop long-prompt mix (1536-3584 tokens in, one prefill chunk
of 2048 or 4096), from the generator's clock in the traced run. Recorded,
not judged. The arithmetic is the chat-open mix's reader's."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p50.chat-open", "read").read(sources)
