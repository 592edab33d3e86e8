"""Tokens per second that reached the client inside the window beyond what
the window's own requests asked for: the generator's ``tok_s`` less
(sum of ``asked`` over the requests with 0 <= due < seconds) / seconds.
It is backlog carried IN from the pre-roll less backlog carried OUT past
the window's end. Recorded in the traced run, not judged.

Why ``tok_s`` is not judged in an open-loop cell under its knee
(``mistral7b-w8.chat``; PERF.md section 2): there it is the schedule plus
this term, and this term rewards a queue.
  121.5  offered: the window's 80 requests ask for 6075 tokens in 50 s
         (``traffic.open_schedule``; fixed by ``schedule_seed``)
  128.3  the parent of PR 26: some 580 of the pre-roll's 748 tokens arrive
         after t = 0, because first tokens are 1.2-2.6 s late (ledger)
  123.4  PR 26, prefill 6.6x faster: the pre-roll's tokens mostly arrive
         before the window opens, as they should (ledger)
  124.33 what PR 26 needed to pass: 128.312 less the bound, 3.978. A fast
         server with no queue reads about 123.3, one with no latency at
         all 121.5; the ceiling, (6075 + 748) / 50 = 136.5, is reached by
         being slower.
So a lower value here is the better server, and ``tok_s`` is judged only
where the offered work is not fixed: saturated cells.

The sign means that only under the knee and with no failed request.
``tok_s`` leaves a failed request's tokens out while ``asked`` would keep
them in, so a failure would read as the better value: with any request of
the log failed there is nothing to read. Above the knee more backlog
leaves the window than enters it and the value falls below zero; such a
cell judges ``tok_s`` itself and does not list this metric."""


def read(sources):
    log, seconds = sources["log"], sources["seconds"]
    if not all(r["ok"] for r in log):
        return None
    own = sum(r["asked"] for r in log if 0.0 <= r["due"] < seconds)
    return sources["gen"]["tok_s"] - own / seconds
