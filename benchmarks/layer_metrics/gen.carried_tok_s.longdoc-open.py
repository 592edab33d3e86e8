"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for, in the open-loop long-document mix:
backlog carried IN from the pre-roll less backlog carried OUT past the
window's end (the arithmetic, and why a lower value is the better server
under the knee, are gen.carried_tok_s's). A first token here waits out
one to four prefill chunks, so the requests due in the window's last
second or two finish after it and the value stands below zero by about
their tokens; one far below that says the window ended with a queue."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.carried_tok_s", "read").read(sources)
