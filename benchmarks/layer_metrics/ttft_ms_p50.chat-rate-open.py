"""Time to first token, p50 (due time -> first streamed chunk with text),
in the open-loop chat-rate mix, from the generator's clock in the traced
run: one prefill chunk of 32-2048 tokens (a bucket of 256-2048, alone or
two lanes a program) through 26 Mamba-1 mixers and two attention layers,
between the rounds of the decoding lanes. Recorded, not judged. The
arithmetic is the chat-decode mix's reader's (the generator's reduction
is one)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p50.chat-decode-open", "read").read(sources)
