"""p90 of the time to first token (due time -> first streamed chunk with
text) in the open-loop retrieved-passages mix, from the generator's clock
in the traced run: one or two prefill chunks of up to 4096 tokens, most of
them through nine state-space mixers, stand before a first token here.
Recorded, not judged. The arithmetic is the chat-decode mix's reader's
(the generator's reduction is one)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p90.chat-decode-open", "read").read(sources)
