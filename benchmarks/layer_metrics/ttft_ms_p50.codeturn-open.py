"""Time to first token, p50 (due time -> first streamed chunk with text),
in the open-loop coding-turn mix, from the generator's clock in the traced
run: one to four prefill chunks of up to 4096 tokens (a prompt of
512-16384) through three full layers of 48 query heads over the whole
context and nine window layers of 72 over at most 512 keys a row, eleven
expert layers, between the rounds of up to 16 decoding lanes. Recorded,
not judged. The arithmetic is the chat-decode mix's reader's (the
generator's reduction is one)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p50.chat-decode-open", "read").read(sources)
