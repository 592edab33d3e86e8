"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for, in the open-loop long-context mix:
backlog carried IN from the pre-roll less backlog carried OUT past the
window's end (the arithmetic is gen.carried_tok_s's).

HIGHER is the better server HERE, as in the retrieved-passages cell and
for its reason: an answer runs to 384 tokens behind a prompt of up to
seven chunks against 6 s of pre-roll, so little is carried in, the
requests due in the window's last seconds finish after it, the value
stands BELOW zero by about their tokens, and the slower server, or one
that ends the window with a queue, reads further below. With any request
failed there is nothing to read, as there."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.carried_tok_s", "read").read(sources)
