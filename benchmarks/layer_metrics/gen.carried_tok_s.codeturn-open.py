"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for, in the open-loop coding-turn mix:
backlog carried IN from the pre-roll less backlog carried OUT past the
window's end (the arithmetic is gen.carried_tok_s's).

An answer of 64-768 tokens takes a few seconds here, against a pre-roll of
10 s and a window of 50: what is carried in is about what is carried out,
and the value's sign is not the server's speed. Recorded so that a run
whose window closed on a growing queue (far below the other runs) can be
told from one that kept up; with any request failed there is nothing to
read, as there. HIGHER is entered as better, as in the other open cells."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.carried_tok_s", "read").read(sources)
