"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for, in the open-loop chat-decode mix: the
generator's ``tok_s`` less (sum of ``asked`` over the requests with
0 <= due < seconds) / seconds, i.e. backlog carried IN from the pre-roll
less backlog carried OUT past the window's end (the reasoning, and why a
lower value is the better server under the knee, is in
``gen.carried_tok_s.py``). Answers here run ~3 s (153 tokens at ~19 ms), so
some 40 requests straddle each edge of the window; the two terms should
nearly cancel, and a value that does not says the pre-roll left a queue
for the window, which the judged ``tpot_ms_p90`` would then carry.

With any request of the log failed there is nothing to read (``tok_s``
leaves a failed request's tokens out, ``asked`` keeps them in)."""


def read(sources):
    log, seconds = sources["log"], sources["seconds"]
    if not all(r["ok"] for r in log):
        return None
    own = sum(r["asked"] for r in log if 0.0 <= r["due"] < seconds)
    return sources["gen"]["tok_s"] - own / seconds
