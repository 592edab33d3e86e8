"""Median time to first token (due time -> first streamed chunk with
text) in the open-loop long-context mix, from the generator's clock in
the traced run: two to seven prefill chunks of up to 4096 tokens, twelve
linear-attention mixers and four sparse layers each, stand before a first
token here. Recorded, not judged. The arithmetic is the chat-decode mix's
reader's (the generator's reduction is one)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p50.chat-decode-open", "read").read(sources)
