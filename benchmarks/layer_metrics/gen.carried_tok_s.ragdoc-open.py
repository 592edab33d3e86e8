"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for, in the open-loop retrieved-passages
mix: backlog carried IN from the pre-roll less backlog carried OUT past
the window's end (the arithmetic is gen.carried_tok_s's).

HIGHER is the better server HERE, the other way round from cell 1. There
answers are short beside the 6 s pre-roll, what is carried in decides,
and the slower server reads more. Here an answer runs to 384 tokens
(8-10 s at this cell's pace, 17 s at 45 ms a token) against the same 6 s
of pre-roll: little is carried in, the requests due in the window's last
seconds finish after it, the value stands BELOW zero by about their
tokens, and the slower server, or one that ends the window with a queue,
reads further below (benchmarks/test_contract.py's two servers on this
cell's schedule: -14.4 slow, -4.3 fast; tier-1 holds that). With any
request failed there is nothing to read, as there."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.carried_tok_s", "read").read(sources)
