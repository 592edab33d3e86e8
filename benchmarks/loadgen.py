"""The load generator: sends a schedule over HTTP from threads of this one
process and logs, per request, when it was due, when it was sent and when
each streamed chunk arrived. No JAX. ``stats.py`` turns the log into
metrics.

Open loop: a dispatcher sleeps to each request's due time and hands it to
a thread of its own, whatever the server is doing. Closed loop: ``clients``
threads each send the stream's next request when their last one ended,
from the pre-roll until the window closes.
"""
from __future__ import annotations

import json
import threading
import time

import procs
import traffic


def body_for(req: traffic.Req, mix: dict, vocab: int) -> bytes:
    return json.dumps({
        "model": "bench",
        "prompt": traffic.prompt_tokens(req, mix["tokens"], vocab),
        "max_tokens": req.output_len,
        "temperature": 0.0,
        "stream": True,
        "stream_options": {"include_usage": True},
        "nvext": {"ignore_eos": True},
    }).encode()


# A request ran its course when the stream ended with finish_reason
# "length" and usage counts exactly what was asked; ``usage_short`` keeps
# the difference. ``backend.py`` drops an engine output that decodes to no
# text, tokens and all, so usage undercounts (PR 27 met it on the chip:
# 54 of 58). The launcher's tokenizer gives every id a text
# (``server.py:EveryTokenSpeaks``), so here any shortfall is a failure.


def send(port: int, body: bytes, asked: int, due: float, t0: float,
         timeout: float) -> dict:
    """One request; times in the record are relative to ``t0``."""
    sent = time.monotonic()
    rec = {"due": due, "sent": sent - t0, "chunks": [], "tokens": None,
           "asked": asked, "ok": False, "status": 0, "error": ""}
    try:
        status, arrivals, usage, finish, error = procs.http_sse(
            port, "/v1/completions", body, timeout=timeout)
    except (OSError, procs.http.client.HTTPException) as e:
        rec["error"] = repr(e)[:300]
        return rec
    rec["status"] = status
    rec["error"] = error
    rec["chunks"] = [t - t0 for t in arrivals]
    if usage:
        rec["tokens"] = usage.get("completion_tokens")
        rec["prompt_tokens"] = usage.get("prompt_tokens")
    short = asked - (rec["tokens"] or 0)
    rec["usage_short"] = short
    rec["ok"] = bool(status == 200 and not error and arrivals
                     and finish == "length"
                     and short == 0)
    return rec


def warm_up(port: int, mix: dict, vocab: int, seed: int) -> list[dict]:
    """The mix's warm-up set: every prefill bucket singly and as a
    concurrent group, decode to steady state. Part of set-up."""
    import random

    rng = random.Random(seed ^ 0x5EED)
    log: list[dict] = []
    for step in mix["warmup"]:
        reqs = [traffic.Req(0.0, step["prompt_len"], step["output_len"],
                            rng.getrandbits(48))
                for _ in range(step["concurrent"])]
        out: list = [None] * len(reqs)

        def one(i: int) -> None:
            out[i] = send(port, body_for(reqs[i], mix, vocab),
                          reqs[i].output_len, 0.0, time.monotonic(), 600.0)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        log += out
    return log


def run_open(port: int, mix: dict, vocab: int, rate_rps: float,
             seconds: float, seed: int, on_window_start) -> tuple[list, float]:
    """Returns (log, t0) where t0 is the window's start on this clock."""
    sched = traffic.open_schedule(mix, rate_rps, seconds, seed)
    bodies = [body_for(r, mix, vocab) for r in sched]   # before the clock
    grace = float(mix["drain_grace_s"])
    pre = float(mix.get("preroll_s", 0))
    t0 = time.monotonic() + pre + 0.05
    log: list[dict] = []
    lock = threading.Lock()
    threads = []
    started = None      # the window-start callback, off the send path

    def one(req, body):
        rec = send(port, body, req.output_len, req.due_s, t0,
                   timeout=seconds + grace)
        with lock:
            log.append(rec)

    for req, body in zip(sched, bodies):
        if started is None and req.due_s >= 0:
            _sleep_until(t0)
            started = _fire(on_window_start)
        _sleep_until(t0 + req.due_s)
        t = threading.Thread(target=one, args=(req, body), daemon=True)
        t.start()
        threads.append(t)
    if started is None:
        _sleep_until(t0)
        started = _fire(on_window_start)
    _sleep_until(t0 + seconds)
    started.join()
    deadline = t0 + seconds + grace
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    with lock:
        done = list(log)
    done += [_unfinished() for _ in range(len(threads) - len(done))]
    return done, t0


def run_closed(port: int, mix: dict, vocab: int, clients: int,
               seconds: float, seed: int, on_window_start
               ) -> tuple[list, float]:
    stream = traffic.closed_stream(mix, seed)
    grace = float(mix["drain_grace_s"])
    pre = float(mix.get("preroll_s", 0))
    t0 = time.monotonic() + pre + 0.05
    t_end = t0 + seconds
    log: list[dict] = []
    lock = threading.Lock()
    cursor = [0]

    def client():
        while time.monotonic() < t_end:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            req = stream[i % len(stream)]
            body = body_for(req, mix, vocab)
            due = time.monotonic() - t0
            rec = send(port, body, req.output_len, due, t0,
                       timeout=seconds + grace)
            with lock:
                log.append(rec)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    _sleep_until(t0)
    _fire(on_window_start).join()
    deadline = t_end + grace
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    with lock:
        done = list(log)
    done += [_unfinished() for t in threads if t.is_alive()]
    return done, t0


def _unfinished() -> dict:
    """A stream that never ended within the grace: failed, no latency,
    counted among the window's requests (due 0)."""
    return {"due": 0.0, "sent": 0.0, "chunks": [], "tokens": None,
            "asked": 0, "ok": False, "status": 0,
            "error": "not finished within the drain grace"}


def _fire(callback) -> threading.Thread:
    t = threading.Thread(target=callback, daemon=True)
    t.start()
    return t


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.25))
