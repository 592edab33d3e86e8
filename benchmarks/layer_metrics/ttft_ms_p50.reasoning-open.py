"""Time to first token, p50 (due time -> first streamed chunk with text),
in the open-loop reasoning mix, from the generator's clock in the traced
run: one prefill chunk of up to 4096 tokens for most prompts, two to four
for the long documents, each through ten delta-rule mixers and two latent
layers, between the rounds of up to 48 decoding lanes. Recorded, not
judged. The arithmetic is the chat-decode mix's reader's (the generator's
reduction is one)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p50.chat-decode-open", "read").read(sources)
