"""Time to first token, p90 (due time -> first streamed chunk with text),
in the open-loop agent-turn mix, from the generator's clock in the traced
run: the long turns' two chunks of 4096 through 52 one-part layers behind
other prompts' chunks and the decoding lanes' rounds. Recorded, not judged
(the benchmark's contract wants the tail under the mix's own name). The
arithmetic is the chat-decode mix's reader's (the generator's reduction is
one)."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "ttft_ms_p90.chat-decode-open", "read").read(sources)
