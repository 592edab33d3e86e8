"""Time to first token, p50 (due time -> first streamed chunk with text),
in the open-loop long-thought mix, from the generator's clock in the traced
run: one to four prefill chunks of up to 4096 tokens (a prompt of
128-16384) through nine Mamba-1 mixers, eight window layers and one full
layer's K/V projection, only each chunk's last row through the fifteen
layers above, between the rounds of up to 40 decoding lanes. Recorded, not
judged. The arithmetic is the chat-decode mix's reader's (the generator's
reduction is one)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p50.chat-decode-open", "read").read(sources)
