"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for, in the open-loop long-thought mix:
backlog carried IN from the pre-roll less backlog carried OUT past the
window's end (the arithmetic is gen.carried_tok_s's).

An answer of 256-1536 tokens takes 10-60 s here: most of the window's
tokens belong to requests that began in the 20 s pre-roll or end in the
drain, so what is carried in is about what is carried out and the value's
sign is not the server's speed. Recorded so that a run whose window closed
on a growing queue (far below the other runs) can be told from one that
kept up; with any request failed there is nothing to read, as there.
HIGHER is entered as better, as in the other cells whose answers outlast
the pre-roll."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.carried_tok_s", "read").read(sources)
