"""The latent-attention + routed-expert block (models/mla_moe.py) against
its plain reference (benchmarks/references/mla_moe.py), on seeded random
weights at tiny widths on the CPU. Logits, not tokens: with random
weights the largest logit changes on rounding.

Every comparison here is float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ only in
the ORDER of float32 sums (absorbed against expanded attention, blocked
running softmax against one softmax, grouped against dense experts), so
logits of magnitude ~1 agree to ~1e-5 and the tolerances are 2e-4. A
dropped term, a wrong rope pairing, a bias leaking into the weights or a
mis-scaled expert moves logits by 1e-2 and more.
"""
import asyncio
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models import llama, mla_moe
from dynamo_tpu.models.config import _TINY_MHC, _TINY_MLA_MOE, ModelConfig
from dynamo_tpu.models.moe import grouped_experts
from dynamo_tpu.ops import latent_decode
from dynamo_tpu.ops.attention import REFERENCE, DecodeAttention
from dynamo_tpu.ops.latent_decode import latent_decode_attention
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.telemetry import metrics as tmetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = dict(rtol=2e-4, atol=2e-4)
PS = 16


def load_reference(name="mla_moe"):
    path = os.path.join(REPO, "benchmarks", "references", name + ".py")
    spec = importlib.util.spec_from_file_location("ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def served(cfg, seed=3):
    """Parameters as the engine hands them to the block's programs (the
    tests below call those directly): the published leaves, W_kvb by head
    beside them."""
    return llama.serving_params(cfg, llama.init_params(cfg, seed))


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny_mla_moe(dtype="float32")
    return cfg, served(cfg), load_reference()


@pytest.fixture(scope="module", params=["one-stream", "mhc"])
def block(request, setup):
    """The two blocks models/mla_moe.py serves, each with its reference
    and the config.json keys the reference reads: the plain residual, and
    four mixed streams with YaRN (factor 4 over 64 positions: ``long``
    is a prompt length that crosses it)."""
    if request.param == "one-stream":
        cfg, params, ref = setup
        return cfg, params, ref, dict(_TINY_MLA_MOE), 21
    cfg = ModelConfig.tiny_mla_moe_mhc()
    hf = dict(_TINY_MLA_MOE, **_TINY_MHC)
    return cfg, served(cfg), load_reference("mla_moe_mhc"), hf, 75


def padded(prompt, width):
    toks = np.zeros(width, np.int32)
    toks[: len(prompt)] = prompt
    return jnp.asarray(toks)


def ref_logits(ref, params, seq, positions, hf=None):
    """The reference's log-probs; the program's logits are compared after
    the same log-softmax."""
    return ref.logprobs(hf or dict(_TINY_MLA_MOE), params, list(seq),
                        positions)


def log_softmax(x):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(x), -1))


# the block's one decode entry (the engine's round calls it too)
DECODE_STEP = jax.jit(functools.partial(mla_moe.decode_step_impl,
                                        attn=REFERENCE), static_argnums=(0,))


def decode_steps(cfg, params, ctx, first_logits, seq_len, n):
    """n greedy decode steps through ring + flush; returns tokens and
    the logits that chose them."""
    toks = [int(np.argmax(first_logits))]
    rows = [np.asarray(first_logits)]
    ring = llama.init_ring(cfg, 1, 1, dtype=jnp.float32)
    for _ in range(n):
        seq_len += 1
        base = jnp.asarray([seq_len - 1], jnp.int32)
        ring, lg, _ = DECODE_STEP(
            cfg, params, ctx, ring, jnp.asarray([toks[-1]], jnp.int32),
            jnp.asarray([seq_len], jnp.int32), base, jnp.int32(0))
        ctx = llama.flush_ctx(ctx, ring, jnp.asarray([0], jnp.int32), base,
                              jnp.asarray([1], jnp.int32))
        rows.append(np.asarray(lg)[0])
        toks.append(int(np.argmax(rows[-1])))
    return toks, np.stack(rows), ctx


def test_prefill_then_decode_through_the_latent_cache(block):
    """(a) fresh (expanded) prefill, then 9 decode steps (absorbed, over
    region + ring) against the reference's full forward."""
    cfg, params, ref, hf, long = block
    prompt = np.random.RandomState(0).randint(1, 256, long).tolist()
    ctx = llama.init_ctx(cfg, 1, 128, jnp.float32)
    ctx, logits = llama.prefill(
        cfg, params, ctx, padded(prompt, -(-long // 32) * 32), jnp.int32(0),
        jnp.int32(0), jnp.int32(len(prompt)), fresh=True)
    toks, rows, _ = decode_steps(cfg, params, ctx, logits, len(prompt), 9)
    seq = prompt + toks[:-1]
    want = ref_logits(ref, params, seq,
                      [len(prompt) - 1 + i for i in range(10)], hf)
    np.testing.assert_allclose(log_softmax(rows), want, **TOL)


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_absorbed_decode_attention_equals_expanded(impl):
    """(b) the absorbed form over cached rows against K and V expanded
    per head: algebra, so float32 agrees to summation order. Both region
    implementations: the XLA loop and the kernel, interpreted."""
    rng = np.random.RandomState(1)
    B, nh, rank, rope, nope, vd, S, R = 3, 4, 24, 8, 16, 16, 40, 2
    row = rank + rope
    stored = 128
    lens = np.array([37, 5, 18], np.int32)       # incl. the current token
    base = np.maximum(lens - R, 0).astype(np.int32)
    rows = rng.randn(B, S + R, row).astype(np.float32)
    wk = rng.randn(rank, nh, nope).astype(np.float32) / 5
    wv = rng.randn(rank, nh, vd).astype(np.float32) / 5
    q_nope = rng.randn(B, nh, nope).astype(np.float32)
    q_rope = rng.randn(B, nh, rope).astype(np.float32)
    # region holds positions < base, the ring the rest; stored rows are
    # zero-padded and the region's tail is garbage
    ctx = rng.randn(1, 1, B + 1, S, stored).astype(np.float32)
    ring = np.zeros((1, 1, B, R, stored), np.float32)
    for b in range(B):
        ctx[0, 0, b, : base[b], :row] = rows[b, : base[b]]
        ctx[0, 0, b, : base[b], row:] = 0
        n = lens[b] - base[b]
        ring[0, 0, b, :n, :row] = rows[b, base[b]: lens[b]]
    scale = 1.0 / np.sqrt(nope + rope)
    q = np.concatenate([np.einsum("bhd,chd->bhc", q_nope, wk), q_rope,
                        np.zeros((B, nh, stored - row), np.float32)], -1)
    got = latent_decode_attention(
        DecodeAttention(impl, chunk=16), jnp.asarray(q * scale),
        jnp.asarray(ctx), jnp.asarray(ring), jnp.int32(0),
        jnp.asarray(lens), jnp.asarray(base), rank)
    got = np.einsum("bhc,chd->bhd", np.asarray(got), wv)
    for b in range(B):
        live = rows[b, : lens[b]]
        k = np.concatenate([
            np.einsum("sc,chd->shd", live[:, :rank], wk),
            np.broadcast_to(live[:, None, rank:], (lens[b], nh, rope))], -1)
        v = np.einsum("sc,chd->shd", live[:, :rank], wv)
        s = np.einsum("hd,shd->hs",
                      np.concatenate([q_nope[b], q_rope[b]], -1), k) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[b], np.einsum("hs,shd->hd", p, v),
                                   **TOL)


# lanes of a ragged batch, region of S = 64 rows read 16 at a time:
# (rows below the ring base, holds a request)
RAGGED = {
    "no_region_rows": (0, True),
    "on_a_chunk_boundary": (32, True),
    "one_past_a_boundary": (33, True),
    "at_the_regions_end": (64, True),
    "mid_chunk": (7, True),
    "idle_with_a_stale_length": (61, False),
}


@pytest.fixture(scope="module")
def ragged():
    """One batch with every lane of ``RAGGED``, garbage in each region
    tail and in the scratch lane, through both implementations."""
    rng = np.random.RandomState(3)
    B, nh, row, vw, S, R, L = len(RAGGED), 4, 128, 64, 64, 2, 2
    below = np.array([n for n, _ in RAGGED.values()], np.int32)
    live = np.array([ok for _, ok in RAGGED.values()])
    args = dict(
        q=jnp.asarray(rng.randn(B, nh, row) * 0.3, jnp.float32),
        ctx=rng.randn(L, 1, B + 1, S, row).astype(np.float32),
        ring=jnp.asarray(rng.randn(L, 1, B, R, row), jnp.float32),
        lens=jnp.asarray(below + 1), base=jnp.asarray(below),
        live=jnp.asarray(live))

    def run(impl, ctx, chunk=16):
        return np.asarray(latent_decode_attention(
            DecodeAttention(impl, chunk=chunk), args["q"], jnp.asarray(ctx),
            args["ring"], jnp.int32(1), args["lens"], args["base"], vw,
            args["live"]))

    return dict(args, below=below, run=run, S=S, vw=vw)


@pytest.mark.parametrize("lane", list(RAGGED))
def test_latent_decode_kernel_equals_the_xla_loop_lane_by_lane(ragged, lane):
    """The kernel reads each lane's own chunks and the XLA loop every lane
    to the longest: the same softmax over the same rows, lane by lane,
    whatever lies in the region past a lane's length."""
    b = list(RAGGED).index(lane)
    want = ragged["run"]("reference", ragged["ctx"])
    got = ragged["run"]("pallas_interpret", ragged["ctx"])
    np.testing.assert_allclose(got[b], want[b], **TOL)
    # the lane by hand: its region rows below its length, then the ring
    n, holds = RAGGED[lane]
    rows = np.concatenate([ragged["ctx"][1, 0, b, : n if holds else 0],
                           np.asarray(ragged["ring"])[1, 0, b, :1]])
    s = np.asarray(ragged["q"])[b] @ rows.T
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(
        got[b], (p / p.sum(-1, keepdims=True)) @ rows[:, : ragged["vw"]],
        **TOL)


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("chunk", [16, 24], ids=["tiles", "slides_back"])
def test_latent_decode_reads_nothing_past_a_lanes_rows(ragged, impl, chunk):
    """Poison in every region row a lane's attention may not use changes
    nothing: the other layer, the scratch lane, all of an idle lane's rows
    and a live lane's tail. The kernel never FETCHES past a lane's last
    whole chunk, so there the poison is NaN; inside that chunk, and for
    the XLA loop (which reads every lane to the longest), it is masked
    and has to be finite (0 x NaN in the value product). A chunk that
    does not tile the region slides its last block back and masks the
    rows it has seen."""
    kernel = impl != "reference"
    want = ragged["run"](impl, ragged["ctx"], chunk)
    ctx = ragged["ctx"].copy()
    poison = np.nan if kernel else 7.0
    ctx[0] = ctx[1, 0, len(RAGGED)] = poison
    for b, (n, holds) in enumerate(RAGGED.values()):
        n = n if holds else 0
        ctx[1, 0, b, n:] = 7.0
        ctx[1, 0, b, min(-(-n // chunk) * chunk, ragged["S"]):] = poison
    np.testing.assert_allclose(ragged["run"](impl, ctx, chunk), want, **TOL)


def test_region_trips_by_hand_and_the_kernels_own_walk(ragged):
    """The one function the kernel's wrapper and the engine's mirror both
    call: chunks a lane, by hand; rows read by each implementation; and
    the same counts from what the kernel itself walks: a NaN in the last
    row of chunk k of every lane spoils a lane's output (NaN through the
    value product where the row is masked, the "no visible row" guard's
    zeros where it is scored) exactly when the kernel fetched that
    chunk."""
    below, live = ragged["below"], np.asarray(ragged["live"])
    trips = latent_decode.region_trips(below, live, 16)
    assert trips.tolist() == [0, 2, 3, 4, 1, 0]
    want = ragged["run"]("pallas_interpret", ragged["ctx"])
    walked = np.zeros(len(RAGGED), int)
    for k in range(ragged["S"] // 16):
        ctx = ragged["ctx"].copy()
        ctx[1, 0, :, k * 16 + 15] = np.nan
        out = ragged["run"]("pallas_interpret", ctx)
        walked += ~np.isclose(out, want).all(axis=(1, 2))
    assert walked.tolist() == trips.tolist()
    assert latent_decode.region_rows_read("pallas", trips, 16) == 160
    assert latent_decode.region_rows_read("reference", trips, 16) == 384
    # the module's own chunk, a region shorter than it, an idle batch
    c = latent_decode.CHUNK
    assert latent_decode.chunk_rows(8 * c) == c
    assert latent_decode.chunk_rows(40) == 40
    assert latent_decode.chunk_rows(8 * c, 16) == 16
    assert latent_decode.region_trips(
        np.array([0, 1, c, c + 1, 5 * c]), True, c).tolist() == [
            0, 1, 1, 2, 5]
    idle = latent_decode.region_trips(below, np.zeros(6, bool), 16)
    assert latent_decode.region_rows_read("reference", idle, 16) == 0
    # traced in, traced out: what the wrapper hands the kernel
    np.testing.assert_array_equal(
        latent_decode.region_trips(jnp.asarray(below), jnp.asarray(live),
                                   16), trips)


def test_expert_layer_equals_a_dense_loop_over_experts(setup):
    """(c) uneven routing, a bias that changes the selection but not the
    weights, no token dropped, padded lanes routed nowhere."""
    cfg, params, _ = setup
    ep = dict(params["experts"][0])
    # a bias that pulls most tokens to experts 0 and 1: uneven load
    ep["bias"] = ep["bias"].at[:2].add(0.6)
    rng = np.random.RandomState(2)
    T = 14
    x = jnp.asarray(rng.randn(T, cfg.hidden_size), jnp.float32)
    valid = jnp.asarray(np.arange(T) < 11)
    got, load = mla_moe.expert_ffn(cfg, ep, x, valid)
    r = cfg.routed_dict
    s = np.asarray(jax.nn.sigmoid(x @ ep["wr"]), np.float64)
    biased = s + np.asarray(ep["bias"], np.float64)
    want = np.zeros((T, cfg.hidden_size))
    counts = np.zeros(r["n_routed_experts"], np.int64)

    def swiglu(v, g, u, d):
        return np.asarray((jax.nn.silu(v @ g) * (v @ u)) @ d, np.float64)

    selected_by_bias = 0
    for t in range(11):
        sel = np.argsort(-biased[t])[: r["num_experts_per_tok"]]
        selected_by_bias += set(sel) != set(
            np.argsort(-s[t])[: r["num_experts_per_tok"]])
        w = s[t, sel] / (s[t, sel].sum() + 1e-20) * r["routed_scaling_factor"]
        for e, we in zip(sel, w):
            counts[e] += 1
            want[t] += we * swiglu(x[t], ep["we_g"][e], ep["we_u"][e],
                                   ep["we_d"][e])
        want[t] += swiglu(x[t], ep["ws_g"], ep["ws_u"], ep["ws_d"])
    # padded lanes: the shared expert still runs on them (their rows are
    # discarded by the caller); the routed part is exactly zero
    routed_only, _ = grouped_experts(
        x, *mla_moe.route(cfg, ep, x), ep["we_g"], ep["we_u"], ep["we_d"],
        valid)
    assert np.all(np.asarray(routed_only)[11:] == 0)
    np.testing.assert_allclose(np.asarray(got)[:11], want[:11], **TOL)
    # dropless: every pick of every live token was computed
    np.testing.assert_array_equal(np.asarray(load), counts)
    assert counts.sum() == 11 * r["num_experts_per_tok"]
    assert counts.max() > 2 * counts.mean()        # the routing was uneven
    assert selected_by_bias > 0                    # and the bias mattered


@pytest.mark.parametrize("k,n,want", [
    (2048, 768, 768),     # cell 3's gate/up: whole, as before PR 37
    (768, 2048, 2048),    # and its down product
    (3584, 1024, 512),    # 7 MiB whole: half the output columns
    (1024, 3584, 1792),   # the down product: 14 lanes of 128
    (8192, 384, 256),     # 6 MiB and 384 has no half of whole lanes: its
                          # three lane columns are dealt 2 + 1 (PR 60)
], ids=["joyai-up", "joyai-down", "xing-up", "xing-down", "odd"])
def test_grouped_product_tile_follows_the_matrix(k, n, want):
    """The megablox kernel double-buffers one weight tile in 16 MiB of
    VMEM: whole matrices up to 4 MiB, else the output columns halved in
    whole 128-value lanes (an odd number of lane columns dealt over the
    fewest tiles that fit), the contraction never split."""
    from dynamo_tpu.models.moe import GMM_TILE_BYTES, gmm_tile_n

    tn = gmm_tile_n(k, n, 2)
    assert tn == want and (tn == n or tn % 128 == 0)
    assert k * tn * 2 <= GMM_TILE_BYTES


def test_chunked_prefill_and_reloaded_prefix_give_one_prefill(block):
    """(d) one FRESH chunk and one CONTINUING chunk (its prior rows
    expanded from the region) against the reference's full forward, and a
    prefix sealed to the pool then loaded into another lane, give the
    logits of one prefill: the pool and its movers carry the latent row.
    The sealed rows themselves move bit-exactly."""
    cfg, params, ref, hf, long = block
    n, first = long + 23, 32 if long < 32 else 64   # 44 or 98 tokens
    prompt = np.random.RandomState(4).randint(1, 256, n).tolist()
    S = 128
    ctx = llama.init_ctx(cfg, 2, S, jnp.float32)
    ctx, one = llama.prefill(
        cfg, params, ctx, padded(prompt, S), jnp.int32(0), jnp.int32(0),
        jnp.int32(len(prompt)), fresh=True)
    want = ref_logits(ref, params, prompt, [n - 1], hf)[0]
    np.testing.assert_allclose(log_softmax(one), want, **TOL)

    # two chunks through the batched program: fresh, then the rest over it
    ctx2 = llama.init_ctx(cfg, 2, S, jnp.float32)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    ctx2, _ = llama.batch_prefill(
        cfg, params, ctx2, padded(prompt[:first], first)[None], i32(1),
        i32(0), i32(first), 0)
    ctx2, two = llama.batch_prefill(
        cfg, params, ctx2, padded(prompt[first:], first)[None], i32(1),
        i32(first), i32(n), S)
    np.testing.assert_allclose(log_softmax(two[0]), want, **TOL)
    np.testing.assert_allclose(np.asarray(two[0]), np.asarray(one), **TOL)

    # seal lane 0's first two blocks, load them into lane 1 of a fresh
    # region, prefill the rest there
    cache = llama.init_cache(cfg, 8, PS, jnp.float32)
    cache = llama.seal_blocks(cache, ctx, i32(0, 0), i32(0, PS), i32(3, 5),
                              page_size=PS)
    row = mla_moe.ROW
    np.testing.assert_array_equal(
        np.asarray(cache[row][:, 0, 5]), np.asarray(ctx[row][:, 0, 0, PS:2 * PS]))
    ctx3 = llama.init_ctx(cfg, 2, S, jnp.float32)
    ctx3 = llama.load_ctx_pages(ctx3, cache, jnp.int32(1), i32(3, 5))
    np.testing.assert_array_equal(
        np.asarray(ctx3[row][:, 0, 1, : 2 * PS]),
        np.asarray(ctx[row][:, 0, 0, : 2 * PS]))
    ctx3, three = llama.prefill(
        cfg, params, ctx3, padded(prompt[32:], S - 32), jnp.int32(1),
        jnp.int32(32), jnp.int32(n))
    np.testing.assert_allclose(np.asarray(three), np.asarray(one), **TOL)


# (region rows S, chunk width T, [(q_start, chunk tokens)] a lane of the
# continuing program; a lane of (0, 0) is a dummy). The attention's blocks
# are 256 rows: in a 320-row region the second prior block slides back to
# 64, and the chunk ends where the region does.
CONTINUING = {
    "q_start_on_a_block": (512, 64, [(256, 50)]),
    "q_start_off_a_block": (512, 64, [(300, 50)]),
    "last_block_slides_back": (320, 32, [(288, 30)]),
    "fresh_lane_beside_continuing": (512, 64, [(0, 40), (300, 50)]),
    "dummy_lane_beside_continuing": (512, 64, [(300, 50), (0, 0)]),
    "two_lanes_at_their_own_q_start": (512, 64, [(256, 60), (300, 50)]),
    "chunk_fills_its_bucket": (512, 64, [(130, 64)]),
}


@pytest.mark.parametrize("case", sorted(CONTINUING))
def test_continuing_chunk_equals_one_fresh_prefill(block, case):
    """A chunk that continues a context in the region (its prior rows
    expanded once into the workspace, scored at the heads' width) gives
    what ONE fresh prefill of the whole prompt gives: the last token's
    logits and every row the chunk writes, in every layer (a row of layer
    l > 0 carries the attention of the layers under it at ITS position),
    to float32 summation order."""
    cfg, params, _, _, _ = block
    S, T, lanes = CONTINUING[case]   # fresh programs run S wide
    tight = dict(rtol=1e-5, atol=1e-5)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    row, B = mla_moe.ROW, 2
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 256, q + n).tolist() for q, n in lanes]

    def fresh(ctx, prompt, slot, upto):
        return llama.batch_prefill(
            cfg, params, ctx, padded(prompt[:upto], S)[None], i32(slot),
            i32(0), i32(upto), 0)

    whole = llama.init_ctx(cfg, B, S, jnp.float32)
    ctx = llama.init_ctx(cfg, B, S, jnp.float32)
    want = []
    for slot, ((q, n), prompt) in enumerate(zip(lanes, prompts)):
        if n == 0:
            want.append(None)
            continue
        whole, logits = fresh(whole, prompt, slot, q + n)
        want.append(logits[0])
        if q:
            ctx, _ = fresh(ctx, prompt, slot, q)
    K = len(lanes)
    slots = [B if n == 0 else i for i, (_, n) in enumerate(lanes)]
    toks = jnp.stack([padded(p[q:], T) for (q, _), p in zip(lanes, prompts)])
    ctx, got = llama.batch_prefill(
        cfg, params, ctx, toks, i32(*slots), i32(*[q for q, _ in lanes]),
        i32(*[q + n for q, n in lanes]), S)
    assert got.shape[0] == K
    for slot, (q, n) in enumerate(lanes):
        if n == 0:
            continue
        np.testing.assert_allclose(
            np.asarray(got[slot]), np.asarray(want[slot]), **tight)
        np.testing.assert_allclose(
            np.asarray(ctx[row][:, 0, slot, q:q + n]),
            np.asarray(whole[row][:, 0, slot, q:q + n]), **tight)


def test_continuing_chunk_equals_absorbed_decode_and_only_adds_rows(block):
    """The two attention forms over the SAME cached rows: a continuing
    chunk (prior rows expanded, scored at the heads' width) against the
    absorbed form, which is decode fed the chunk's tokens one at a time
    over region + ring; the last token's logits agree to float32
    summation order. The chunk only ADDS rows: what the region held below
    q_start, and every other lane, is unchanged bit for bit, so decode
    after it reads the rows a fresh prefill of the whole prompt wrote."""
    cfg, params, _, _, _ = block
    S, T, q, n = 512, 32, 300, 21     # q_start off a block, chunk < bucket
    tight = dict(rtol=1e-5, atol=1e-5)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    row = mla_moe.ROW
    prompt = np.random.RandomState(11).randint(1, 256, q + n).tolist()

    def fresh(upto):
        return llama.batch_prefill(
            cfg, params, llama.init_ctx(cfg, 1, S, jnp.float32),
            padded(prompt[:upto], S)[None], i32(0), i32(0), i32(upto), 0)

    prior, _ = fresh(q)
    whole, _ = fresh(q + n)
    before = np.asarray(prior[row])
    ctx, got = llama.batch_prefill(
        cfg, params, prior, padded(prompt[q:], T)[None], i32(0), i32(q),
        i32(q + n), S)
    after = np.asarray(ctx[row])
    np.testing.assert_array_equal(after[:, :, 0, :q], before[:, :, 0, :q])
    # (the bucket's padding rows, [q + n, q + T), are garbage by contract)
    np.testing.assert_array_equal(after[:, :, 0, q + T:],
                                  before[:, :, 0, q + T:])
    np.testing.assert_array_equal(after[:, :, 1:], before[:, :, 1:])

    # absorbed: the chunk's tokens through decode, teacher-forced
    absorbed = llama.init_ctx(cfg, 1, S, jnp.float32)
    absorbed = {row: absorbed[row].at[:].set(jnp.asarray(before))}
    ring = llama.init_ring(cfg, 1, 1, dtype=jnp.float32)
    for i, tok in enumerate(prompt[q:]):
        base = i32(q + i)
        ring, logits, _ = DECODE_STEP(
            cfg, params, absorbed, ring, i32(tok), i32(q + i + 1), base,
            jnp.int32(0))
        absorbed = llama.flush_ctx(absorbed, ring, i32(0), base, i32(1))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(logits[0]),
                               **tight)
    np.testing.assert_allclose(
        np.asarray(absorbed[row][:, 0, 0, q:q + n]), after[:, 0, 0, q:q + n],
        **tight)

    # decode after the chunk against decode after one fresh prefill
    _, rows_chunked, _ = decode_steps(cfg, params, ctx, got[0], q + n, 3)
    _, rows_whole, _ = decode_steps(cfg, params, whole, got[0], q + n, 3)
    np.testing.assert_allclose(rows_chunked, rows_whole, **tight)


def test_ring_flush_near_the_regions_end_keeps_every_row(setup):
    """The span flush shifts entries inside a span that never runs off
    the region; rows past valid_len stay as they were."""
    cfg, _, _ = setup
    S, R = 32, 4
    ctx = jax.tree.map(lambda a: a + 7.0, llama.init_ctx(cfg, 2, S,
                                                         jnp.float32))
    ring = llama.init_ring(cfg, 2, R, dtype=jnp.float32)
    row = mla_moe.ROW
    ring = {row: ring[row] + jnp.arange(1, R + 1, dtype=jnp.float32)[
        None, None, None, :, None]}
    out = llama.flush_ctx(ctx, ring, jnp.asarray([0, 1], jnp.int32),
                          jnp.asarray([30, 10], jnp.int32),
                          jnp.asarray([2, 3], jnp.int32))[row]
    lane0 = np.asarray(out[0, 0, 0, :, 0])
    np.testing.assert_array_equal(lane0[28:], [7, 7, 1, 2])
    lane1 = np.asarray(out[0, 0, 1, :, 0])
    np.testing.assert_array_equal(lane1[9:14], [7, 1, 2, 3, 7])


def test_from_hf_dict_reads_the_published_keys_and_refuses_the_unknown():
    """(e)"""
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mla-moe-joyai-d5.json")) as f:
        published = json.load(f)
    c = ModelConfig.from_hf_dict(published)
    d = mla_moe.dims(c)
    assert (d["nh"], d["q_rank"], d["kv_rank"], d["nope"], d["rope"],
            d["v"], d["row"], d["stored"]) == (32, 1536, 512, 128, 64, 128,
                                               576, 640)
    assert (d["E"], d["K"], d["I_e"], d["I_s"], d["n_dense"]) == (
        256, 8, 768, 768, 1)
    assert (c.vocab_size, c.hidden_size, c.intermediate_size,
            c.num_layers) == (129280, 2048, 7168, 5)
    assert c.rope_theta == 32e6 and c.rms_norm_eps == 1e-6
    assert c.routed_dict["routed_scaling_factor"] == 2.5
    # the dense decoder still reads its own keys
    assert ModelConfig.from_hf_dict({
        "model_type": "mistral", "vocab_size": 8, "hidden_size": 8,
        "intermediate_size": 8, "num_hidden_layers": 1,
        "num_attention_heads": 2, "sliding_window": None}).mla is None
    with pytest.raises(ValueError, match="no block this program builds"):
        ModelConfig.from_hf_dict({"model_type": "no_such_block",
                                  "vocab_size": 8})
    with pytest.raises(ValueError, match="refusing to read it as a Llama"):
        ModelConfig.from_hf_dict({
            "model_type": "llama", "vocab_size": 8, "hidden_size": 8,
            "intermediate_size": 8, "num_hidden_layers": 1,
            "num_attention_heads": 2, "num_local_experts": 8})
    for key, value in (("scoring_func", "softmax"), ("n_group", 8),
                       ("num_nextn_predict_layers", 1),
                       ("rope_scaling", {"type": "yarn", "factor": 40})):
        with pytest.raises(ValueError, match="does not implement"):
            ModelConfig.from_hf_dict(dict(published, **{key: value}))
    with pytest.raises(ValueError, match="missing"):
        ModelConfig.from_hf_dict(
            {k: v for k, v in published.items() if k != "q_lora_rank"})


@pytest.mark.parametrize("plane,kw", [
    ("int8 KV", {"kv_quant": "int8"}),
    ("offload", {"host_offload_pages": 8}),
    ("spec/", {"speculative": "ngram"}),
    ("LoRA", {"lora_adapters": 2}),
    ("sequence-parallel", {"sp_prefill_threshold": 64}),
])
def test_a_plane_that_cannot_carry_a_latent_row_refuses_at_start(
        setup, plane, kw):
    """(f) named refusals at engine start-up; nothing reinterprets the
    row."""
    cfg, _, _ = setup
    ecfg = EngineConfig(**{**dict(
        num_pages=16, page_size=PS, max_pages_per_seq=4, max_decode_slots=4,
        prefill_buckets=(32,), cache_dtype="float32"), **kw})
    with pytest.raises(ValueError, match="latent"):
        TpuEngine(cfg, ecfg, params=llama.init_params(cfg, 3),
                  mesh_config=MeshConfig(tp=1))


def test_fewer_lanes_than_counters_ride_more_rows(setup):
    """No plane: the routing counters ride home behind the round's tokens
    in rows ``max_decode_slots`` wide, as many rows as the columns fill
    (until PR 64 an engine of fewer lanes than counters was refused)."""
    cfg, _, _ = setup
    assert len(llama.stats_layout(cfg)) == 3
    eng = TpuEngine(cfg, EngineConfig(
        num_pages=16, page_size=PS, max_pages_per_seq=4, max_decode_slots=2,
        prefill_buckets=(32,), cache_dtype="float32"),
        params=llama.init_params(cfg, 3), mesh_config=MeshConfig(tp=1))

    async def serve():
        req = PreprocessedRequest(
            token_ids=list(range(5, 17)), model="t",
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        toks = [t async for out in eng.generate(req) for t in out.token_ids]
        await eng.stop()
        return toks

    assert len(asyncio.run(serve())) == 6
    snap = eng.telemetry.snapshot()
    assert snap[tmetrics.MOE_ROUTED[0]]["count"] > 0
    assert snap[tmetrics.MOE_LOAD_MAX[0]]["sum"] > 0      # the third column


def test_model_functions_of_other_planes_refuse_a_latent_row(setup):
    cfg, params, _ = setup
    cache = llama.init_cache(cfg, 4, PS, jnp.float32)
    z = jnp.zeros(1, jnp.int32)
    ctx1 = llama.init_ctx(cfg, 1, 32, jnp.float32)
    for call in (
        lambda: llama.decode_step_impl(
            cfg, params, ctx1, llama.init_ring(cfg, 1, 1, jnp.float32),
            z, z, z, jnp.int32(0), attn=REFERENCE),
        lambda: llama.gather_pages(cache, z),
        lambda: llama.encode_impl(cfg, params, z, jnp.int32(1)),
        lambda: llama.batch_score_impl(
            cfg, params, llama.init_ctx(cfg, 1, 32, jnp.float32),
            z[None], z, z, z, 32),
        lambda: llama.init_ctx(cfg, 1, 32, kv_quant="int8"),
    ):
        with pytest.raises(ValueError, match="latent"):
            call()
    with pytest.raises(ValueError, match="not sharded over"):
        from dynamo_tpu.parallel.mesh import make_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        llama.param_shardings(
            cfg, make_mesh(MeshConfig(tp=2), jax.devices()[:2]))


def test_the_lower_precision_controls_fail_the_references_tolerances(setup):
    """The reference's own controls (experts rounded to 8 bits; router
    scores in bfloat16) move the log-probs of a float32 program by more
    than a float32 program moves from the reference: what the chip check
    rests on, at tiny widths."""
    cfg, params, ref = setup
    seq = np.random.RandomState(5).randint(1, 256, 40).tolist()
    pos = list(range(20, 40))
    sound = ref_logits(ref, params, seq, pos)
    # 8 experts and 40 tokens hold few near-ties for a bf16 router to
    # flip, so that control only has to stand clear of the tolerance
    # here; at 256 experts it flips several a layer (PERF.md section 6)
    for control, times in (("experts_int8", 20), ("router_bf16", 3)):
        off = ref.logprobs(dict(_TINY_MLA_MOE), params, seq, pos,
                           control=control)
        assert np.abs(off - sound).max() > times * TOL["atol"], control


def test_the_reference_refuses_weights_that_are_not_the_stated_ones(setup):
    """What the engine holds is what its steps stream: a routed-expert
    matrix held in 8 bits, or a pytree of another block, stops the check
    instead of being dequantised or read as something else."""
    cfg, params, ref = setup
    hf, seq = dict(_TINY_MLA_MOE), list(range(1, 9))
    first = dict(params["experts"][0])
    first["we_g"] = jnp.round(first["we_g"] * 100).astype(jnp.int8)
    held8 = dict(params, experts=[first] + list(params["experts"][1:]))
    with pytest.raises(ValueError, match=r"we_g.*held as int8"):
        ref.logprobs(hf, held8, seq, [7])
    dense = llama.init_params(ModelConfig.tiny(dtype="float32"), 0)
    with pytest.raises(ValueError, match="not this block's"):
        ref.logprobs(hf, dense, seq, [7])


def test_the_local_fault_control_moves_one_position_only(setup):
    """``lane_swap`` (one compared position answers with its neighbour's
    state) is what the check's MAX limit is set against: the other
    positions do not move at all."""
    cfg, params, ref = setup
    seq = np.random.RandomState(6).randint(1, 256, 40).tolist()
    pos = list(range(28, 36))
    sound = ref_logits(ref, params, seq, pos)
    off = ref.logprobs(dict(_TINY_MLA_MOE), params, seq, pos,
                       control="lane_swap")
    moved = np.abs(off - sound).max(-1)
    assert moved[4] > 100 * TOL["atol"]
    np.testing.assert_array_equal(np.delete(off, 4, 0),
                                  np.delete(sound, 4, 0))
    np.testing.assert_array_equal(off[4], sound[3])


async def test_engine_serves_the_block_and_counts_its_routing(setup):
    """Through TpuEngine (prefill bucket, fused rounds, ring flush, fused
    seal, prefix reuse): greedy tokens equal the hand-driven loop, and
    the routing counters ride the round's fetch."""
    cfg, params, _ = setup
    ecfg = EngineConfig(num_pages=32, page_size=PS, max_pages_per_seq=8,
                        max_decode_slots=4, prefill_buckets=(32, 64),
                        cache_dtype="float32")
    # the engine takes the PUBLISHED leaves and makes the others itself
    eng = TpuEngine(cfg, ecfg, params=llama.init_params(cfg, 3),
                    mesh_config=MeshConfig(tp=1))
    assert set(eng.params["layers"]) == set(params["layers"])
    prompt = list(range(1, 41))

    async def collect():
        req = PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=10, ignore_eos=True))
        return [t async for out in eng.generate(req) for t in out.token_ids]

    toks = await collect()
    ctx = llama.init_ctx(cfg, 1, ecfg.max_context, jnp.float32)
    ctx, logits = llama.prefill(
        cfg, params, ctx, padded(prompt, 64), jnp.int32(0), jnp.int32(0),
        jnp.int32(len(prompt)), fresh=True)
    want, _, _ = decode_steps(cfg, params, ctx, logits, len(prompt), 9)
    assert toks == want
    assert await collect() == want          # served from the sealed prefix
    assert eng.allocator.hit_blocks >= 1
    snap = eng.telemetry.snapshot()
    rounds = snap["dynamo_moe_tokens_routed"]["count"]
    r = cfg.routed_dict
    per_round = (ecfg.flush_every * r["num_experts_per_tok"]
                 * (cfg.num_layers - r["first_k_dense_replace"]))
    assert rounds > 0
    assert snap["dynamo_moe_tokens_routed"]["sum"] == rounds * per_round
    assert 0 < snap["dynamo_moe_experts_touched"]["sum"] <= rounds * per_round
    assert snap["dynamo_kv_row_bytes"]["sum"] == mla_moe.kv_row_bytes(cfg, 4)
    await eng.stop()


def test_the_byte_count_and_the_kernels_roofline_reader_by_hand():
    """benchmarks/bytes/mla_moe.py and layer_metrics/kernel.gmm_roofline:
    plain arithmetic on counters and a reduced trace, no JAX; a parent
    program without the counters or the kernel reads nothing."""
    import json

    def load(*parts):
        path = os.path.join(REPO, "benchmarks", *parts)
        spec = importlib.util.spec_from_file_location(
            "bench_" + parts[-1][:-3].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mla-moe-joyai-d5.json")) as f:
        cfg = json.load(f)
    count = load("bytes", "mla_moe.py")
    reader = load("layer_metrics", "kernel.gmm_roofline.py").read
    s = count.shapes(cfg)
    assert s["expert"] == 3 * 2048 * 768 and s["n_expert_layers"] == 4
    assert s["attn"] == 26_345_472 and s["row"] == 576      # ISSUE's 26.35 M

    def hist(total, n):
        return {"sum": total, "count": n}

    # 100 rounds x 4 steps; 800 experts touched and 1600 picks a step
    src = {
        "config": cfg, "engine_up": {"flush_every": 4,
                                     "device_kind": "TPU v5 lite"},
        "before": {"histograms": {
            "dynamo_moe_experts_touched": hist(0.0, 0),
            "dynamo_moe_tokens_routed": hist(0.0, 0)}},
        "after": {"histograms": {
            "dynamo_moe_experts_touched": hist(320_000.0, 100),
            "dynamo_moe_tokens_routed": hist(640_000.0, 100)}},
        "peaks": load("peaks.py"), "byname": load("byname.py"),
        # 10 rounds in the span: 40 steps, 0.4 s in decode-shaped calls
        "trace": {"modules": {"jit_engine_round_seal": {"count": 10,
                                                        "seconds": 0.6}},
                  "kernels": {"gmm bf16[512,768]": 0.25,
                              "gmm bf16[512,2048]": 0.15,
                              "gmm bf16[8192,768]": 9.9}},
    }
    weights = (5 * s["attn"] + s["dense_mlp"] + 4 * (s["shared"] + s["router"])
               + s["head"] + 800 * s["expert"]) * 2
    assert count.decode_bytes_per_step(src, [100.0, 5000.0]) == (
        weights + (100 + 4096) * 576 * 5 * 2)
    gmm_bytes = 800 * s["expert"] * 2
    assert reader(src) == pytest.approx(
        gmm_bytes / 819e9 / (0.4 / 40) * 100.0)              # ~92 %
    # the parent: no counters, or no such kernel in its trace
    bare = dict(src, before={"histograms": {}}, after={"histograms": {}})
    assert reader(bare) is None
    assert count.decode_bytes_per_step(bare, [100.0]) == (
        weights - 800 * s["expert"] * 2 + 100 * 576 * 5 * 2)
    assert reader(dict(src, trace=dict(src["trace"], kernels={}))) is None
    assert reader(dict(src, config={"engine": {}})) is None
