"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for, in the open-loop long-prompt mix:
backlog carried IN from the pre-roll less backlog carried OUT past the
window's end (the arithmetic, and why a lower value is the better server
under the knee, are gen.carried_tok_s's). A prompt here is one 2048 or
4096 bucket of int8-weight prefill (250-500 ms), so the requests due in
the window's last second or two finish after it."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.carried_tok_s", "read").read(sources)
