"""Resilience plane (dynamo_tpu/resilience/): retry/breaker policies,
health tracking, mid-stream migration with exactly-once delivery,
graceful drain, chaos hooks, and the resilience metrics contract.

The keystone is the migration differential: a worker killed mid-stream
under greedy decoding must leave the client with the BYTE-IDENTICAL token
sequence of an uninterrupted run — no drops, no duplicates — while
``dynamo_migration_total`` increments.
"""
import asyncio
import random

import numpy as np
import pytest

from dynamo_tpu.kv_router.protocols import (
    KvCacheEvent,
    KvEventKind,
    StoredBlock,
)
from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter
from dynamo_tpu.kv_router.scheduler import KvRouterConfig
from dynamo_tpu.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.resilience import (
    CHAOS,
    RESILIENCE,
    BreakerState,
    CircuitBreaker,
    DrainController,
    MigrationPolicy,
    RetryPolicy,
    WorkerDrainingError,
    WorkerHealthTracker,
    build_replay_request,
)
from dynamo_tpu.telemetry import TRACES
from dynamo_tpu.tokens import compute_block_hashes

BS = 4


@pytest.fixture(autouse=True)
def _reset_globals():
    RESILIENCE.reset()
    CHAOS.reset()
    yield
    RESILIENCE.reset()
    CHAOS.reset()


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


# ---------------------------------------------------------------------------
# RetryPolicy


def test_retry_policy_backoff_grows_and_jitters():
    p = RetryPolicy(base_delay_s=0.1, max_delay_s=1.0, multiplier=2.0,
                    jitter=0.5)
    for attempt, base in ((0, 0.1), (1, 0.2), (2, 0.4), (5, 1.0)):
        for _ in range(50):
            d = p.delay(attempt)
            assert base * 0.5 <= d <= base + 1e-9, (attempt, d)
    # jitter actually varies
    assert len({round(p.delay(1), 9) for _ in range(20)}) > 1


# ---------------------------------------------------------------------------
# CircuitBreaker (fake clock — the acceptance-criterion state machine)


def test_breaker_trips_after_consecutive_failures_and_readmits():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=3, reset_timeout_s=5.0,
                       clock=clock)
    assert b.state is BreakerState.CLOSED and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state is BreakerState.CLOSED  # below threshold
    b.record_failure()
    assert b.state is BreakerState.OPEN
    assert not b.allow()                   # open: no traffic
    clock.advance(4.9)
    assert not b.allow()                   # still inside the window
    clock.advance(0.2)
    assert b.allow()                       # ONE half-open probe
    assert b.state is BreakerState.HALF_OPEN
    assert not b.allow()                   # probe outstanding: no more
    b.record_success()                     # probe succeeded
    assert b.state is BreakerState.CLOSED
    assert b.allow()


def test_breaker_half_open_failure_reopens_with_fresh_timer():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout_s=5.0,
                       clock=clock)
    b.record_failure()
    assert b.state is BreakerState.OPEN
    clock.advance(5.1)
    assert b.allow()                       # probe
    b.record_failure()                     # probe failed
    assert b.state is BreakerState.OPEN
    clock.advance(2.0)
    assert not b.allow()                   # timer restarted at the re-trip
    clock.advance(3.5)
    assert b.allow()


def test_breaker_success_resets_failure_streak():
    b = CircuitBreaker(failure_threshold=2)
    b.record_failure()
    b.record_success()
    b.record_failure()
    assert b.state is BreakerState.CLOSED  # streak broken, never tripped


def test_breaker_stray_success_does_not_reopen_tripped_breaker():
    """Regression: a stream that was in flight when the breaker tripped
    completes later — its success says nothing about new requests and
    must not bypass the reset timeout + half-open probe."""
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=2, reset_timeout_s=5.0,
                       clock=clock)
    b.record_failure()
    b.record_failure()
    assert b.state is BreakerState.OPEN
    b.record_success()                     # stray in-flight completion
    assert b.state is BreakerState.OPEN
    assert not b.allow()                   # still inside the window
    clock.advance(5.1)
    assert b.allow()                       # probe protocol intact
    b.record_success()                     # THIS one resolves the probe
    assert b.state is BreakerState.CLOSED


# ---------------------------------------------------------------------------
# WorkerHealthTracker


def test_health_tracker_blocks_tripped_worker_then_readmits():
    clock = FakeClock()
    h = WorkerHealthTracker(failure_threshold=2, reset_timeout_s=5.0,
                            clock=clock)
    ids = ["a", "b"]
    assert h.blocked(ids) == set()
    h.record_failure("a")
    h.record_failure("a")
    assert h.blocked(ids) == {"a"}
    assert RESILIENCE.get("dynamo_resilience_breaker_open") == 1
    clock.advance(5.1)
    assert h.blocked(ids) == set()         # half-open probe available
    h.on_routed("a")                       # a request dispatches: probe
    h.record_success("a")                  # probe succeeded
    assert h.blocked(ids) == set()
    assert RESILIENCE.get("dynamo_resilience_breaker_open") == 0
    assert RESILIENCE.get("dynamo_resilience_breaker_trips_total") == 1


def test_health_tracker_probe_not_starved_by_routing_elsewhere():
    """Regression: blocked() must be side-effect free. A recovered
    worker's half-open probe is consumed only when a request actually
    dispatches to it (on_routed) — routing decisions that pick OTHER
    workers must not burn the grant and starve the recovered worker."""
    clock = FakeClock()
    h = WorkerHealthTracker(failure_threshold=1, reset_timeout_s=5.0,
                            clock=clock)
    h.record_failure("a")
    clock.advance(5.1)
    # many routing decisions that all pick "b": "a" stays routable
    for _ in range(5):
        assert h.blocked(["a", "b"]) == set()
        h.on_routed("b")
    # finally a request dispatches to "a": that IS the probe
    h.on_routed("a")
    assert h.breaker("a").state is BreakerState.HALF_OPEN
    assert h.blocked(["a", "b"]) == {"a"}  # probe outstanding
    h.record_success("a")
    assert h.breaker("a").state is BreakerState.CLOSED
    assert h.blocked(["a", "b"]) == set()


def test_health_tracker_heartbeat_staleness():
    clock = FakeClock()
    h = WorkerHealthTracker(heartbeat_ttl_s=10.0, clock=clock)
    # never heartbeated: no signal, routable
    assert h.blocked(["a"]) == set()
    h.heartbeat("a")
    clock.advance(9.0)
    assert h.blocked(["a"]) == set()
    clock.advance(2.0)
    assert h.blocked(["a"]) == {"a"}       # lease-style expiry
    h.heartbeat("a")
    assert h.blocked(["a"]) == set()


# ---------------------------------------------------------------------------
# replay-request construction


def test_build_replay_request_shifts_budgets():
    req = PreprocessedRequest(
        token_ids=[1, 2, 3],
        stop_conditions=StopConditions(max_tokens=10, min_tokens=5),
    )
    r = build_replay_request(req, [7, 8])
    assert r.token_ids == [1, 2, 3, 7, 8]
    assert r.stop_conditions.max_tokens == 8
    assert r.stop_conditions.min_tokens == 3
    assert r.estimated_prefix_hit_num_blocks is None
    # the original request is untouched
    assert req.token_ids == [1, 2, 3]
    assert req.stop_conditions.max_tokens == 10


def test_build_replay_request_none_when_budget_spent():
    req = PreprocessedRequest(
        token_ids=[1], stop_conditions=StopConditions(max_tokens=2),
    )
    assert build_replay_request(req, [4, 5]) is None


# ---------------------------------------------------------------------------
# deterministic fake engines (continuation depends only on content, like
# a real LM under greedy decoding)


def _lcg_next(toks: list[int]) -> int:
    return (toks[-1] * 1103515245 + len(toks) * 12345 + 7) % 997


def lcg_sequence(prompt: list[int], n: int) -> list[int]:
    toks = list(prompt)
    out = []
    for _ in range(n):
        t = _lcg_next(toks)
        toks.append(t)
        out.append(t)
    return out


class LcgEngine:
    """Greedy 'model' whose next token is a pure function of the
    sequence so far — replaying prompt+emitted continues identically."""

    def __init__(self):
        self.served = 0

    async def generate(self, req: PreprocessedRequest):
        self.served += 1
        toks = list(req.token_ids)
        mt = req.stop_conditions.max_tokens or 8
        for i in range(mt):
            await asyncio.sleep(0)
            t = _lcg_next(toks)
            toks.append(t)
            fin = FinishReason.LENGTH if i == mt - 1 else None
            yield LLMEngineOutput(token_ids=[t], finish_reason=fin)


class AssassinEngine:
    """LcgEngine that dies mid-stream: after ``kill_after`` tokens of a
    request not yet in ``killed``, raise ConnectionError. ``killed`` is
    shared across the fleet so a migrated replay survives anywhere."""

    def __init__(self, kill_after: int, killed: set):
        self.inner = LcgEngine()
        self.kill_after = kill_after
        self.killed = killed

    async def generate(self, req: PreprocessedRequest):
        arm = req.request_id not in self.killed
        n = 0
        async for out in self.inner.generate(req):
            yield out
            n += len(out.token_ids)
            if arm and n >= self.kill_after:
                self.killed.add(req.request_id)
                raise ConnectionError("assassin: worker died mid-stream")


class DeadEngine:
    """Unreachable before the first token (connection refused shape)."""

    def __init__(self):
        self.attempts = 0

    async def generate(self, req):
        self.attempts += 1
        raise ConnectionError("connection refused")
        yield  # pragma: no cover — makes this an async generator


class Prefers:
    """A tie-break that is no draw: the named worker wherever it ties
    (at temperature 0 the selector asks its ``rng`` for ``choice`` alone)."""

    def __init__(self, worker):
        self.worker = worker

    def choice(self, best):
        return self.worker if self.worker in best else best[0]


def make_push(engines: dict, rng=None, **kw) -> KvPushRouter:
    router = KvRouter(BS, KvRouterConfig(router_temperature=0.0))
    # equal workers tie, and the selector breaks a tie by its ``rng``
    # (unseeded: the module's own, so a case that counts a worker's turns
    # fails one run in some): one seeded sequence of draws a router, or
    # the case's own script
    router.scheduler.selector.rng = rng or random.Random(0)
    return KvPushRouter(router, dict(engines), **kw)


def stored(worker, hashes, parent=0):
    return KvCacheEvent(
        kind=KvEventKind.STORED, worker_id=worker, parent_hash=parent,
        blocks=[StoredBlock(block_hash=h) for h in hashes],
    )


async def _drive(push, req):
    toks, finishes = [], []
    async for out in push.generate(req):
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            finishes.append(out.finish_reason)
    return toks, finishes


def _req(prompt, max_tokens=12):
    return PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
    )


# ---------------------------------------------------------------------------
# pre-first-token re-route (satellite: previously untested path)


async def test_reroute_before_first_token_evicts_and_recovers():
    dead = DeadEngine()
    ok = LcgEngine()
    push = make_push({"dead": dead, "ok": ok})
    prompt = list(range(1, 17))
    # warm the DEAD worker's indexer entry so routing prefers it
    hashes = compute_block_hashes(prompt, BS)
    push.router.indexer.apply_event(stored("dead", hashes))

    toks, fins = await _drive(push, _req(prompt, max_tokens=6))
    assert toks == lcg_sequence(prompt, 6)
    assert fins == [FinishReason.LENGTH]
    assert dead.attempts == 1 and ok.served == 1
    # evicted: out of the worker table AND the indexer
    assert "dead" not in push.workers
    assert push.router.indexer.find_matches(hashes).scores == {}
    assert push.reroutes == 1
    assert RESILIENCE.get("dynamo_resilience_reroute_total") == 1
    assert RESILIENCE.get("dynamo_migration_total") == 0


async def test_all_workers_unreachable_raises():
    push = make_push({"d1": DeadEngine(), "d2": DeadEngine()})
    with pytest.raises(ConnectionError):
        await _drive(push, _req(range(1, 9)))
    assert not push.workers


# ---------------------------------------------------------------------------
# mid-stream migration (the differential acceptance criterion)


async def test_migration_differential_exactly_once():
    """Kill a worker mid-stream under greedy decoding: the client
    receives the byte-identical token sequence of an uninterrupted run
    (no drops, no duplicates) and dynamo_migration_total increments."""
    prompt = list(range(10, 26))
    expected = lcg_sequence(prompt, 12)

    killed: set = set()
    push = make_push({
        "w0": AssassinEngine(4, killed),
        "w1": AssassinEngine(4, killed),
    })
    toks, fins = await _drive(push, _req(prompt, max_tokens=12))
    assert toks == expected, "migrated stream diverged"
    assert fins == [FinishReason.LENGTH]
    assert push.migrations == 1
    assert len(killed) == 1
    assert RESILIENCE.get("dynamo_migration_total") == 1
    assert RESILIENCE.get("dynamo_migration_replayed_tokens_total") == 4
    assert RESILIENCE.get("dynamo_migration_failed_total") == 0


async def test_migration_trace_always_recorded():
    """Migrated requests are traced even when sampling skipped them."""
    prompt = list(range(30, 46))
    killed: set = set()
    push = make_push({
        "w0": AssassinEngine(3, killed),
        "w1": AssassinEngine(3, killed),
    })
    req = _req(prompt, max_tokens=8)
    TRACES.start(req.request_id, sampled=False)  # below the sample rate
    toks, _ = await _drive(push, req)
    assert toks == lcg_sequence(prompt, 8)
    tr = TRACES.finish(req.request_id)
    assert tr is not None and tr.sampled
    names = tr.span_names()
    assert "migrate" in names
    TRACES.clear()


async def test_migration_budget_spent_finishes_with_length():
    """A worker dying exactly at the token budget: the replay would be a
    zero-token tail — the router closes the stream with LENGTH instead
    (matching what the uninterrupted run would have returned)."""

    class DiesAtBudget:
        async def generate(self, req):
            toks = list(req.token_ids)
            for _ in range(req.stop_conditions.max_tokens):
                t = _lcg_next(toks)
                toks.append(t)
                yield LLMEngineOutput(token_ids=[t])  # never finishes
            raise ConnectionError("died holding the last token")

    prompt = list(range(50, 66))
    push = make_push({"w0": DiesAtBudget(), "w1": LcgEngine()})
    toks, fins = await _drive(push, _req(prompt, max_tokens=5))
    assert toks == lcg_sequence(prompt, 5)
    assert fins == [FinishReason.LENGTH]
    assert RESILIENCE.get("dynamo_migration_total") == 0


async def test_no_migration_after_finish_delivered():
    """Regression: a worker that delivers the finish output and THEN
    dies (before the stream close) must not trigger migration — the
    request is complete; replaying would regenerate past the stop point
    and emit tokens after a finish chunk."""

    class DiesAfterFinish:
        async def generate(self, req):
            toks = list(req.token_ids)
            for i in range(req.stop_conditions.max_tokens):
                t = _lcg_next(toks)
                toks.append(t)
                fin = (FinishReason.LENGTH
                       if i == req.stop_conditions.max_tokens - 1 else None)
                yield LLMEngineOutput(token_ids=[t], finish_reason=fin)
            raise ConnectionError("died after the finish frame")

    prompt = list(range(70, 86))
    push = make_push({"w0": DiesAfterFinish(), "w1": LcgEngine()})
    toks, fins = await _drive(push, _req(prompt, max_tokens=5))
    assert toks == lcg_sequence(prompt, 5)
    assert fins == [FinishReason.LENGTH]      # exactly ONE finish
    assert push.migrations == 0
    assert RESILIENCE.get("dynamo_migration_total") == 0


async def test_migration_exhausted_raises_and_counts_failure():
    killed: set = set()

    class AlwaysDies:
        async def generate(self, req):
            toks = list(req.token_ids)
            t = _lcg_next(toks)
            yield LLMEngineOutput(token_ids=[t])
            raise ConnectionError("always dies")

    push = make_push({"w0": AlwaysDies(), "w1": AlwaysDies()},
                     migration=MigrationPolicy(max_migrations=3))
    with pytest.raises(ConnectionError):
        await _drive(push, _req(range(1, 9), max_tokens=6))
    assert RESILIENCE.get("dynamo_migration_failed_total") >= 1
    assert len(killed) == 0  # unused; silences lint


async def test_migration_disabled_propagates():
    killed: set = set()
    push = make_push(
        {"w0": AssassinEngine(2, killed), "w1": AssassinEngine(2, killed)},
        migration=MigrationPolicy(enabled=False),
    )
    with pytest.raises(ConnectionError):
        await _drive(push, _req(range(1, 9), max_tokens=8))


# ---------------------------------------------------------------------------
# breaker-aware routing


async def test_breaker_excludes_failing_worker_from_routing():
    clock = FakeClock()
    health = WorkerHealthTracker(failure_threshold=2, reset_timeout_s=30.0,
                                 clock=clock)
    killed: set = set()

    class DiesEveryTime:
        def __init__(self):
            self.calls = 0

        async def generate(self, req):
            self.calls += 1
            toks = list(req.token_ids)
            t = _lcg_next(toks)
            yield LLMEngineOutput(token_ids=[t])
            raise ConnectionError("mid-stream death")

    bad = DiesEveryTime()
    ok = LcgEngine()
    push = make_push({"bad": bad, "ok": ok}, health=health,
                     rng=Prefers("bad"))
    # route several requests; "bad" fails mid-stream whenever chosen (and
    # it is, wherever the two tie: two unseeded draws chose it fewer than
    # twice in eight, 9 runs in 256) and migration recovers onto "ok".
    # After 2 failures the breaker trips and "bad" stops receiving
    # traffic entirely.
    for i in range(8):
        prompt = list(range(i * 7 + 1, i * 7 + 9))
        toks, _ = await _drive(push, _req(prompt, max_tokens=4))
        assert toks == lcg_sequence(prompt, 4)
    assert health.breaker("bad").state is BreakerState.OPEN
    calls_at_trip = bad.calls
    for i in range(3):
        prompt = list(range(100 + i * 7, 108 + i * 7))
        await _drive(push, _req(prompt, max_tokens=4))
    assert bad.calls == calls_at_trip  # tripped: no traffic
    assert "bad" in push.workers       # NOT evicted — breaker, not lease
    assert len(killed) == 0


# ---------------------------------------------------------------------------
# clear_kv_blocks indexer invalidation (satellite: previously untested)


async def test_clear_kv_blocks_invalidates_indexer():
    class Clearable(LcgEngine):
        def __init__(self, n):
            super().__init__()
            self.n = n
            self.cleared = 0

        async def clear_kv_blocks(self):
            self.cleared += 1
            return self.n

    e0, e1 = Clearable(3), Clearable(5)
    push = make_push({"w0": e0, "w1": e1})
    hashes = compute_block_hashes(list(range(1, 17)), BS)
    push.router.indexer.apply_event(stored("w0", hashes))
    push.router.indexer.apply_event(stored("w1", hashes[:2]))
    assert push.router.indexer.find_matches(hashes).scores == {
        "w0": 4, "w1": 2,
    }
    total = await push.clear_kv_blocks()
    assert total == 8
    assert e0.cleared == 1 and e1.cleared == 1
    # the radix view is stale by construction: dropped for every worker
    assert push.router.indexer.find_matches(hashes).scores == {}
    # workers stay routable (clearing caches is not a failure)
    assert set(push.workers) == {"w0", "w1"}


# ---------------------------------------------------------------------------
# graceful drain


async def test_drain_controller_finishes_inflight_then_refuses():
    from dynamo_tpu.mocker import MockerArgs, MockerEngine

    eng = MockerEngine(MockerArgs(speedup_ratio=1.0, page_size=BS,
                                  num_pages=64,
                                  decode_time_per_step_s=0.005))
    stream = eng.generate(_req(list(range(1, 9)), max_tokens=12))
    first = await stream.__anext__()          # admitted + first token
    assert first.token_ids
    controller = DrainController(eng, timeout_s=10.0)
    ev = controller.request_drain(reason="test")
    assert controller.state == "draining"
    # new admissions refused with the RETRIABLE error class
    with pytest.raises(WorkerDrainingError):
        async for _ in eng.generate(_req(list(range(1, 9)))):
            pass
    # the in-flight request runs to completion
    got = [t for t in first.token_ids]
    async for out in stream:
        got.extend(out.token_ids)
    assert len(got) == 12
    await asyncio.wait_for(ev.wait(), timeout=10.0)
    assert controller.state == "drained"
    assert RESILIENCE.get("dynamo_resilience_drains_total") == 1
    assert RESILIENCE.get("dynamo_resilience_draining") == 0
    await eng.stop()


async def test_drain_controller_hooks_fire_in_order():
    events = []

    class InstantEngine:
        def begin_drain(self):
            events.append("begin")

        def drained(self):
            return True

    async def dereg():
        events.append("dereg")

    controller = DrainController(
        InstantEngine(), on_deregister=dereg,
        on_drained=lambda: events.append("done"),
    )
    ev = controller.request_drain()
    await asyncio.wait_for(ev.wait(), timeout=5.0)
    # admissions stop synchronously, then deregister, then completion
    assert events == ["begin", "dereg", "done"]
    # idempotent
    assert controller.request_drain() is ev


# ---------------------------------------------------------------------------
# planner scale-down drains instead of killing (acceptance criterion)


async def test_local_connector_scale_down_drains_gracefully(tmp_path):
    """LocalConnector retirement sends SIGTERM and grants the drain
    grace: a worker that finishes its work and exits is never
    SIGKILLed."""
    import sys

    from dynamo_tpu.planner import LocalConnector

    marker = tmp_path / "drained"
    script = (
        "import signal, sys, time\n"
        "def h(*a):\n"
        f"    open({str(marker)!r}, 'w').write('ok')\n"
        "    sys.exit(0)\n"
        "signal.signal(signal.SIGTERM, h)\n"
        "time.sleep(60)\n"
    )
    conn = LocalConnector([sys.executable, "-c", script],
                          drain_grace_s=10.0)
    await conn.set_replicas(1)
    proc = conn.procs[0]
    await asyncio.sleep(0.3)  # let the handler install
    await conn.set_replicas(0)
    assert conn.drains_started == 1
    for _ in range(100):
        if marker.exists() and proc.poll() is not None:
            break
        await asyncio.sleep(0.1)
    assert marker.exists(), "worker was killed before it could drain"
    assert proc.poll() == 0  # clean exit, not SIGKILL
    await conn.shutdown()


async def test_local_connector_kills_after_drain_grace(tmp_path):
    """A worker that ignores SIGTERM is SIGKILLed after the grace."""
    import signal as _signal
    import sys

    from dynamo_tpu.planner import LocalConnector

    script = (
        "import signal, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "time.sleep(60)\n"
    )
    conn = LocalConnector([sys.executable, "-c", script],
                          drain_grace_s=0.4)
    await conn.set_replicas(1)
    proc = conn.procs[0]
    await asyncio.sleep(0.3)
    await conn.set_replicas(0)
    for _ in range(100):
        if proc.poll() is not None:
            break
        await asyncio.sleep(0.1)
    assert proc.poll() == -_signal.SIGKILL
    await conn.shutdown()


# ---------------------------------------------------------------------------
# chaos hooks


def test_chaos_configure_grammar():
    CHAOS.configure("kill_worker:p=0.5:after=3,delay:t=0.05,"
                    "stall_stream:t=2:once")
    k = CHAOS.points["kill_worker"]
    assert k.armed and k.probability == 0.5 and k.after_outputs == 3
    d = CHAOS.points["delay"]
    assert d.armed and d.delay_s == 0.05
    s = CHAOS.points["stall_stream"]
    assert s.armed and s.once
    assert not CHAOS.points["drop_response"].armed
    with pytest.raises(ValueError):
        CHAOS.configure("explode")


async def _numbers(n):
    for i in range(n):
        yield i


async def test_chaos_kill_worker_drops_stream():
    CHAOS.arm("kill_worker", after_outputs=2, once=True)
    got = []
    with pytest.raises(ConnectionResetError):
        async for item in CHAOS.wrap_stream(_numbers(6)):
            got.append(item)
    assert got == [0, 1]
    assert not CHAOS.points["kill_worker"].armed  # once: self-disarmed
    assert CHAOS.points["kill_worker"].injected_total == 1
    assert RESILIENCE.get(
        "dynamo_resilience_chaos_injections_total") == 1
    # disarmed: streams flow clean again
    assert [i async for i in CHAOS.wrap_stream(_numbers(3))] == [0, 1, 2]


async def test_chaos_drop_response_swallows_one():
    CHAOS.arm("drop_response", once=True)
    got = [i async for i in CHAOS.wrap_stream(_numbers(4))]
    assert got == [1, 2, 3]  # first output dropped, then disarmed


async def test_chaos_once_kill_fires_exactly_once_across_streams():
    """Regression: a once-fused kill latched by several CONCURRENT
    streams must fire on exactly one of them — the others re-check the
    armed fuse at injection time."""
    CHAOS.arm("kill_worker", after_outputs=1, once=True)
    g1 = CHAOS.wrap_stream(_numbers(4))
    g2 = CHAOS.wrap_stream(_numbers(4))
    assert await g1.__anext__() == 0   # both streams latch their trigger
    assert await g2.__anext__() == 0
    with pytest.raises(ConnectionResetError):
        await g1.__anext__()           # first injection disarms the fuse
    got = [0]
    async for item in g2:              # survivor streams to completion
        got.append(item)
    assert got == [0, 1, 2, 3]
    assert CHAOS.points["kill_worker"].injected_total == 1


async def test_disagg_wrapper_drain_rejects_before_remote_prefill():
    """Regression: a draining disagg decode worker must refuse BEFORE
    the remote-prefill decision — not after paying a cross-worker KV
    transfer for a request it then rejects."""
    from dynamo_tpu.disagg import DisaggDecodeEngine
    from dynamo_tpu.mocker import MockerArgs, MockerEngine

    inner = MockerEngine(MockerArgs(speedup_ratio=100.0, page_size=BS,
                                    num_pages=64))
    # rt=None: any touch of the control plane in the drained path would
    # raise AttributeError, failing the test
    eng = DisaggDecodeEngine(inner, rt=None)
    eng.begin_drain()
    with pytest.raises(WorkerDrainingError):
        async for _ in eng.generate(_req(list(range(1, 9)))):
            pass
    assert eng.drained()
    await inner.stop()


# ---------------------------------------------------------------------------
# trace sampling (--trace-sample-rate satellite)


def test_trace_sampling_shell_dropped_and_promotable():
    from dynamo_tpu.telemetry.trace import span_now
    import time as _time

    TRACES.clear()
    tr = TRACES.start("unsampled-1", sampled=False)
    assert not TRACES.add_span("unsampled-1",
                               span_now("route", _time.monotonic()))
    assert tr.spans == []
    assert TRACES.finish("unsampled-1") is not None
    assert TRACES.get("unsampled-1") is None  # dropped, not parked

    TRACES.start("promoted-1", sampled=False)
    assert TRACES.promote("promoted-1")
    assert TRACES.add_span("promoted-1",
                           span_now("migrate", _time.monotonic()))
    TRACES.finish("promoted-1")
    got = TRACES.get("promoted-1")
    assert got is not None and got.span_names() == ["migrate"]
    TRACES.clear()


def test_http_service_sampling_rate_zero_keeps_shells_out_of_ring():
    from dynamo_tpu.frontend.service import HttpService

    svc = HttpService(trace_sample_rate=0.0)
    assert svc.trace_sample_rate == 0.0


# ---------------------------------------------------------------------------
# metrics contract (families render with HELP/TYPE on every surface)


def test_resilience_metrics_render_families():
    RESILIENCE.inc("dynamo_migration_total")
    RESILIENCE.set("dynamo_resilience_draining", 1)
    text = RESILIENCE.render()
    assert "# HELP dynamo_migration_total" in text
    assert "# TYPE dynamo_migration_total counter" in text
    assert "dynamo_migration_total 1" in text
    assert "# TYPE dynamo_resilience_draining gauge" in text
    assert "dynamo_resilience_draining 1" in text


def _frontend_surface():
    from dynamo_tpu.frontend.service import HttpService

    return HttpService()


def _system_surface():
    from dynamo_tpu.runtime.system_server import SystemServer

    return SystemServer(None, worker_id="w0")


def _exporter_surface():
    from dynamo_tpu.metrics_exporter import MetricsExporter

    return MetricsExporter(kv=None)


@pytest.mark.parametrize("openmetrics", [False, True])
@pytest.mark.parametrize(
    "surface", [_frontend_surface, _system_surface, _exporter_surface])
async def test_resilience_metrics_on_all_three_surfaces(surface, openmetrics):
    """Each scrape surface answers a real GET /metrics with the
    resilience plane in it (through telemetry.metrics.render_planes)."""
    from aiohttp.test_utils import make_mocked_request

    RESILIENCE.reset()
    RESILIENCE.inc("dynamo_migration_total", 2)
    accept = "application/openmetrics-text" if openmetrics else "*/*"
    resp = await surface().handle_metrics(
        make_mocked_request("GET", "/metrics", headers={"Accept": accept}))
    text = resp.text
    assert "dynamo_migration_total 2" in text
    assert "# TYPE dynamo_resilience_breaker_trips_total counter" in text
    assert text.endswith("# EOF\n") == openmetrics
