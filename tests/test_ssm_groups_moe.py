"""The ONE-PART hybrid stack (models/ssm_moe.py with ``one_part``: a layer
is one norm and one part: a Mamba-2 mixer with B/C GROUPS and a grouped
gated norm, a NoPE GQA mixer, or an expert layer of UNGATED relu^2
experts + a shared one; ops/mamba2.py with groups; models/moe.py's
two-matrix grouped product and its tiles) against its plain reference
(benchmarks/references/ssm_groups_moe.py), on seeded random weights at
tiny widths on the CPU: thirteen layers ``MEMEM*EMEMEM*``, 8 Mamba heads
of 12 in 2 groups (inner 96 against hidden 64), a scan chunk of 8, 32
published experts top 6 of which this share holds 4, the head untied.

Comparisons are float32 against float32 under the suite's
``jax_default_matmul_precision=highest``: the two sides differ in the ORDER
of float32 sums (a chunked scan against the recurrence as written, grouped
against dense expert products), so log-probs agree to ~1e-5 and the
tolerance is 2e-4; the faults the controls inject move them by far more.

Time (this box, alone): ~70 s, most of it the one engine's programs.
"""
import asyncio
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.models import llama, moe, ssm_moe
from dynamo_tpu.models.config import _TINY_NEMOTRON_H, ModelConfig
from dynamo_tpu.ops import kda, mamba2
from dynamo_tpu.ops.attention import DecodeAttention
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.telemetry import metrics as tmetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
BUCKETS = (16, 32)
TOP = 5
LANES = 6
HF = dict(_TINY_NEMOTRON_H, engine={"prefill_buckets": list(BUCKETS)})
CELL = "nemotron3-nano-ep8"


def load(kind, name):
    path = os.path.join(REPO, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(dry_run=False):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           CELL + ".json")) as f:
        cfg = json.load(f)
    if dry_run:
        cfg.update(cfg["dry_run"])
    return cfg


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(1, 256, n).tolist()


# ---------------------------------------------------------------------------
# the served path against the reference: ONE engine, every case through it

SERVED = {
    # one padded bucket, no multiple of the scan's chunk of 8; 24 decode
    # steps are six flushes of 4
    "one-chunk": ([21], 24),
    # shorter than ONE chunk of the scan
    "below-a-chunk": ([5], 8),
    # 32 + 13: the continuing chunk resumes six states and six windows and
    # reads the attention layers' prior rows; padded bucket
    "two-chunks": ([45], 12),
    # 32 + 32 + 3: a continuing chunk shorter than the convolution + 1
    "three-chunks-a-short-tail": ([67], 8),
    # prompts arriving together: a batched prefill, then lanes of
    # different lengths in one round
    "a-batch": ([30, 6, 30, 6], 8),
}


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny_nemotron_h(dtype="float32")
    return cfg, llama.init_params(cfg, 3), load("references",
                                                "ssm_groups_moe")


@pytest.fixture(scope="module")
def served(setup):
    """{case: [(prompt, tokens, top log-probs) a prompt]} from one engine
    (prefill fresh, continuing, padded, batched; decode through ring,
    region, the recurrent leaves and the fused rounds)."""
    cfg, params, _ = setup
    eng = TpuEngine(cfg, EngineConfig(
        num_pages=16, page_size=8, max_pages_per_seq=16,
        max_decode_slots=LANES, prefill_buckets=BUCKETS, flush_every=4,
        cache_dtype="float32", max_logprobs=TOP),
        params=params, mesh_config=MeshConfig(tp=1))

    async def serve(prompt, n):
        req = PreprocessedRequest(
            token_ids=list(prompt), model="t",
            stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            output_options=OutputOptions(logprobs=TOP))
        toks, tops = [], []
        async for out in eng.generate(req):
            toks += out.token_ids
            tops += out.top_logprobs or []
        assert len(toks) == n and len(tops) == n
        return prompt, toks, tops

    async def every_case():
        out = {}
        for case, (lens, n) in SERVED.items():
            out[case] = await asyncio.gather(*(
                serve(prompt_of(m, 10 + i), n) for i, m in enumerate(lens)))
        out["metrics"] = {name: h["sum"] for name, h in
                          eng.telemetry.snapshot().items()}
        await eng.stop()
        return out

    return asyncio.run(every_case())


def distance(ref, params, prompt, toks, tops, control=None):
    """(max, mean) |log-prob difference| over the engine's top tokens,
    every step, against the reference's full forward of prompt + tokens."""
    want = ref.logprobs(HF, params, list(prompt) + toks,
                        [len(prompt) - 1 + i for i in range(len(toks))],
                        control=control)
    diffs = []
    for i, row in enumerate(tops):
        ids = np.asarray([p[0] for p in row])
        diffs.append(np.abs(np.asarray([p[1] for p in row]) - want[i, ids]))
    diffs = np.concatenate(diffs)
    return float(diffs.max()), float(diffs.mean())


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_path_equals_the_reference(setup, served, case):
    _, params, ref = setup
    for prompt, toks, tops in served[case]:
        assert distance(ref, params, prompt, toks, tops)[0] < TOL


def test_the_served_rounds_feed_the_parts_counter(served):
    """Every dispatched round said which parts its steps ran, by this
    stack's OWN layer counts (6 / 2 / 5 / 0), and the state-space counter
    by its six Mamba layers."""
    m = served["metrics"]
    parts = {p: m[name] for p, (name, _) in tmetrics.LAYER_PARTS_RUN.items()}
    steps = parts["mixer_attn"] / 2
    assert steps > 0 and parts == {
        "mixer_ssm": 6 * steps, "mixer_attn": 2 * steps,
        "experts": 5 * steps, "mlp": 0}
    assert m[tmetrics.SSM_STATE_ROWS_STEPPED[0]] > 0
    assert m[tmetrics.SSM_STATE_ROWS_STEPPED[0]] % 6 == 0
    assert m[tmetrics.SSM_SCAN_POSITIONS[0]] % 6 == 0
    assert m[tmetrics.MOE_PICKS_ROUTED[0]] % (5 * 6) == 0


def test_every_required_control_is_named_and_stated():
    ref = load("references", "ssm_groups_moe")
    assert set(ref.CONTROLS_REQUIRED) >= {
        "one_group", "norm_all", "gate_after_norm", "relu", "swiglu",
        "scale_1", "picks_wrapped", "no_shared", "state_zeroed", "fp8"}
    # inside the band sound seeds span at the published widths on the chip
    # (held HERE, in float32, by the test below)
    assert set(ref.CONTROLS_NAMED) >= {"bias_in_weights", "rotary",
                                       "state_bf16", "conv_zeroed"}
    for name in ref.CONTROLS_REQUIRED + ref.CONTROLS_NAMED:
        assert f'``"{name}"``' in ref.__doc__, name


CONTROLS = ("one_group", "norm_all", "gate_after_norm", "relu", "swiglu",
            "scale_1", "bias_in_weights", "picks_wrapped", "no_shared",
            "rotary", "state_zeroed", "conv_zeroed", "fp8", "state_bf16")


@pytest.mark.parametrize("control", CONTROLS)
def test_a_faulty_program_stands_ten_tolerances_off(setup, served, control):
    """What each control computes (one B/C group for all heads, the norm
    over all of inner, the norm before the gate, relu without the square,
    a gated unit in the experts' place, the routed scale, the bias in the
    weights, a share's own experts, the shared expert, a rotary, a state
    or a window lost at the chunk boundary, 8-bit operands) is not what
    the program served: the two-chunk prompt's log-probs stand >= 10 x the
    tolerance off it."""
    _, params, ref = setup
    (prompt, toks, tops), = served["two-chunks"]
    far = distance(ref, params, prompt, toks, tops, control)[0]
    assert far > 10 * TOL


# ---------------------------------------------------------------------------
# Mamba-2 with groups: the chunked scan, the step and the kernel against
# the recurrence as written

def recurrence(x, dt, A, B, C, S):
    """The recurrence as written, numpy, one position at a time: x [T, H,
    P], B and C [T, G, N]; head h reads group h // (H / G)."""
    T, H, _ = x.shape
    per = H // B.shape[1]
    ys = []
    for t in range(T):
        Bh, Ch = np.repeat(B[t], per, 0), np.repeat(C[t], per, 0)
        S = (np.exp(dt[t] * A)[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :])
        ys.append(np.einsum("hpn,hn->hp", S, Ch))
    return np.stack(ys), S


def scan_inputs(seed, T, H, P, G, N):
    r = np.random.RandomState(seed)
    f = lambda *s: r.randn(*s).astype(np.float32)  # noqa: E731
    return (f(T, H, P), r.uniform(0.01, 0.3, (T, H)).astype(np.float32),
            -r.uniform(0.5, 4, H).astype(np.float32), f(T, G, N), f(T, G, N))


@pytest.mark.parametrize("G", [1, 2, 8])
def test_chunk_scan_and_step_with_groups_are_the_recurrence(G):
    """T = 21 is no multiple of the chunk of 8; from a state that is not
    zero. One group given as [T, N] is the same again."""
    T, H, P, N = 21, 8, 4, 16
    x, dt, A, B, C = scan_inputs(G, T, H, P, G, N)
    S0 = np.random.RandomState(9).randn(H, P, N).astype(np.float32)
    want_y, want_S = recurrence(x, dt, A, B, C, S0)
    forms = [(B, C)] + ([(B[:, 0], C[:, 0])] if G == 1 else [])
    for Bf, Cf in forms:
        y, S = mamba2.chunk_scan(*map(jnp.asarray, (x, dt, A, Bf, Cf, S0)), 8)
        np.testing.assert_allclose(y, want_y, atol=2e-5)
        np.testing.assert_allclose(S, want_S, atol=2e-5)
        S = jnp.asarray(S0)[None]
        for t in range(T):
            y, S = mamba2.scan_step(
                *(jnp.asarray(a[t])[None] for a in (x, dt)), jnp.asarray(A),
                jnp.asarray(Bf[t])[None], jnp.asarray(Cf[t])[None], S)
            np.testing.assert_allclose(y[0], want_y[t], atol=2e-5)
        np.testing.assert_allclose(S[0], want_S, atol=2e-5)


@pytest.mark.parametrize("G,H,P", [(1, 8, 64), (2, 8, 64), (8, 64, 64)])
def test_m2_step_kernel_with_groups_follows_its_work_list(G, H, P):
    """The Pallas step (interpret) over a work list that skips lanes 1 and
    4: the listed lanes' states as ``scan_step`` moves them, the others'
    (and the scratch lane's) bit for bit as they were, their ``y`` 0."""
    L, N = 5, 128
    x, dt, A, B, C = scan_inputs(G + 10, L, H, P, G, N)
    S0 = np.random.RandomState(3).randn(L + 1, H, P, N).astype(np.float32)
    live = jnp.asarray([True, False, True, True, False])
    if G == 1:
        B, C = B[:, 0], C[:, 0]
    args = [jnp.asarray(a) for a in (x, dt, A, B, C)]
    y, S = mamba2.scan_step_pallas(*args, jnp.asarray(S0),
                                   *kda.work_list(live), interpret=True)
    args[1] = jnp.where(live[:, None], args[1], 0.0)
    want_y, want_S = mamba2.scan_step(*args, jnp.asarray(S0[:L]))
    np.testing.assert_allclose(
        y, jnp.where(live[:, None, None], want_y, 0), atol=2e-5)
    np.testing.assert_allclose(S[:L], want_S, atol=2e-6)
    for lane in (1, 4, 5):
        np.testing.assert_array_equal(S[lane], S0[lane])


# ---------------------------------------------------------------------------
# experts without a gate matrix

def expert_weights(seed, E, H, I, held=None):
    r = np.random.RandomState(seed)
    w = lambda *s: jnp.asarray(r.randn(*s) / np.sqrt(s[-2]), jnp.float32)  # noqa: E731
    return {"wr": w(H, E), "bias": jnp.asarray(r.randn(E) * 0.01,
                                               jnp.float32),
            "we_u": w(E, H, I), "we_d": w(E, I, H),
            "ws_u": w(H, 2 * I), "ws_d": w(2 * I, H)}


@pytest.mark.parametrize("first", [None, 8])
def test_grouped_experts_relu2_is_a_loop_over_experts(first):
    """Two grouped products and relu^2 between them, no gate matrix:
    against a loop over the held experts, all of them held (``first``
    None) or experts 8-11 of 16 with the other picks adding nothing."""
    E, held, H, I, T, K = 16, (16 if first is None else 4), 32, 24, 40, 6
    p = expert_weights(1, held, H, I)
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(T, H), jnp.float32)
    sel = jnp.asarray(np.stack([r.permutation(E)[:K] for _ in range(T)]))
    w = jnp.asarray(r.rand(T, K), jnp.float32)
    valid = jnp.asarray(r.rand(T) > 0.2)
    y, load = moe.grouped_experts(x, sel, w, None, p["we_u"], p["we_d"],
                                  valid, first=first, act="relu2")
    want, counts = np.zeros((T, H), np.float32), np.zeros(held, np.int64)
    for t in range(T):
        for k in range(K):
            e = int(sel[t, k]) - (first or 0)
            if valid[t] and 0 <= e < held:
                a = np.maximum(np.asarray(x[t] @ p["we_u"][e]), 0) ** 2
                want[t] += float(w[t, k]) * np.asarray(a @ p["we_d"][e])
                counts[e] += 1
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_array_equal(load, counts)


def test_experts_stored_wider_compute_the_published_width():
    """A width that is no whole number of 128-lane columns is STORED at
    the next one, zeros beyond: the layer's output is the published
    width's, to the bit of the sums' order."""
    assert [moe.stored_width(n) for n in (40, 128, 160, 1856, 1024, 3712)
            ] == [40, 128, 256, 1920, 1024, 3712]
    hf = dict(_TINY_NEMOTRON_H, moe_intermediate_size=160)
    c = ModelConfig.from_hf_dict(dict(hf, dtype="float32"))
    d = ssm_moe.dims(c)
    assert (d["I_e"], d["I_st"]) == (160, 256)
    lp = jax.eval_shape(lambda: ssm_moe.init_params(c, 0))["layers"][1]
    assert lp["we_u"].shape == (4, 64, 256) and lp["we_d"].shape == (
        4, 256, 64) and "we_g" not in lp
    lp = ssm_moe.init_params(c, 0)["layers"][1]
    assert not np.asarray(lp["we_u"][:, :, 160:]).any()
    assert not np.asarray(lp["we_d"][:, 160:]).any()
    x = jnp.asarray(np.random.RandomState(0).randn(24, 64), lp["we_u"].dtype)
    y, _ = ssm_moe._ffn(c, lp, x, None, ssm_moe.stats_zero(c))
    cut = dict(lp, we_u=lp["we_u"][:, :, :160], we_d=lp["we_d"][:, :160])
    want, _ = ssm_moe._ffn(c, cut, x, None, ssm_moe.stats_zero(c))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(setup):
    """One expert layer of 32 published experts cut eight ways: the
    program's routed part of each share (its own four experts, ``first`` =
    index x 4), plus the shared expert counted ONCE, is what the reference
    gives for the layer uncut (all 32 held by one chip)."""
    _, _, ref = setup
    E, held, H, I = 32, 4, 64, 40
    whole = expert_weights(5, E, H, I)
    x = jnp.asarray(np.random.RandomState(6).randn(24, H), jnp.float32)
    total = jnp.zeros_like(x)
    for index in range(E // held):
        c = ModelConfig.tiny_nemotron_h(dtype="float32", expert_share={
            "published_experts": E, "of": E // held, "index": index})
        lp = dict(whole, **{n: whole[n][index * held:(index + 1) * held]
                            for n in ("we_u", "we_d")})
        y, stats = ssm_moe._ffn(c, lp, x, None, ssm_moe.stats_zero(c))
        total = total + (y - ssm_moe._shared(lp, x))
        # every token's 6 picks counted, this share's among them
        assert int(stats[3]) == 24 * 6 and 0 < int(stats[1]) < 24 * 6
    total = total + ssm_moe._shared(whole, x)
    uncut = ref.hyper(dict(_TINY_NEMOTRON_H, n_routed_experts=E,
                           expert_share=None))
    want = ref.routed(uncut, whole, x) + ref.shared(whole, x)
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# a tile for every width

def old_gmm_tile_n(k, n, itemsize):
    """``moe.gmm_tile_n`` as it stood before odd widths were dealt."""
    tn = n
    while k * tn * itemsize > moe.GMM_TILE_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def expert_products():
    """(configuration, contraction, output columns) of both grouped
    products' weight tiles in every configuration of the benchmark that
    routes, at the widths the program STORES."""
    out = []
    folder = os.path.join(REPO, "benchmarks", "configs")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            cfg = json.load(f)
        c = ModelConfig.from_hf_dict(cfg)
        if c.hybrid is not None and ssm_moe.dims(c)["experts"]:
            H, I = c.hidden_size, ssm_moe.dims(c)["I_st"]
        elif c.routed is not None:
            H, I = c.hidden_size, c.routed_dict["moe_intermediate_size"]
        else:
            continue
        out += [(name[:-5], H, I), (name[:-5], I, H)]
    return out


@pytest.mark.parametrize("name,k,n", expert_products() + [
    ("odd-columns", 1856, 2688), ("no-whole-columns", 2688, 1856)])
def test_every_width_gets_a_tile_inside_the_budget(name, k, n):
    """A tile of whole 128-lane columns inside ``GMM_TILE_BYTES`` (the
    contraction never split); every configuration accepted before this
    one keeps its tile to the number."""
    tn = moe.gmm_tile_n(k, n, 2)
    assert k * tn * 2 <= moe.GMM_TILE_BYTES
    assert tn == n or tn % 128 == 0
    if not name.startswith((CELL, "odd", "no-whole")):
        assert tn == old_gmm_tile_n(k, n, 2)
    assert {(2688, 1920): 640, (1920, 2688): 896, (1856, 2688): 896,
            (2688, 1856): 640}.get((k, n), tn) == tn


# ---------------------------------------------------------------------------
# the reader

def test_the_reader_maps_the_published_keys():
    d = published()
    c = ModelConfig.from_hf_dict(d)
    k = c.hybrid_dict
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.intermediate_size, c.num_layers, c.vocab_size,
            c.tie_word_embeddings, c.rms_norm_eps) == (
        2688, 32, 2, 128, 1856, 52, 16384, False, 1e-5)
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert d["hybrid_override_pattern"] == pattern
    assert k["layer_types"] == tuple(
        {"M": "mamba", "E": "experts", "*": "attention"}[t] for t in pattern)
    assert k["one_part"] is True and k["expert_act"] == "relu2"
    assert (k["mamba_n_heads"], k["mamba_d_head"], k["mamba_n_groups"],
            k["mamba_d_state"], k["mamba_d_conv"], k["mamba_chunk_size"]
            ) == (64, 64, 8, 128, 4, 128)
    assert (k["published_experts"], k["num_local_experts"], k["share_of"],
            k["share_index"], k["num_experts_per_tok"], k["intermediate_size"],
            k["shared_intermediate_size"]) == (128, 16, 8, 0, 6, 1856, 3712)
    assert (k["router"], k["n_group"], k["topk_group"],
            k["routed_scaling_factor"]) == ("sigmoid_groups", 1, 1, 2.5)
    assert (k["embedding_multiplier"], k["residual_multiplier"],
            k["logits_scaling"], k["attention_multiplier"]) == (
        1.0, 1.0, 1.0, 128 ** -0.5)
    d = ssm_moe.dims(c)
    assert (d["n_ssm"], d["n_attn"], sum(d["routes"]), d["G"], d["inner"],
            d["conv"], d["I_e"], d["I_st"], d["first"]) == (
        23, 6, 23, 8, 4096, 6144, 1856, 1920, 0)
    assert [i for i, t in enumerate(d["kinds"]) if t == "attention"] == [
        5, 12, 19, 26, 33, 42]
    assert ssm_moe.layer_parts(c) == {"mixer_ssm": 23, "mixer_attn": 6,
                                      "experts": 23, "mlp": 0}


REFUSALS = {
    "letter": ({"hybrid_override_pattern": "MEMEM*EMEMEMX"},
               "letters other than"),
    "length": ({"hybrid_override_pattern": "MEMEM*"}, "length is not"),
    "no_attention": ({"hybrid_override_pattern": "MEMEMEEMEMEME"},
                     "without an attention layer"),
    "mlp_act": ({"mlp_hidden_act": "silu"}, "mlp_hidden_act 'silu'"),
    "mamba_act": ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act 'gelu'"),
    "attention_bias": ({"attention_bias": True}, "attention_bias true"),
    "mlp_bias": ({"mlp_bias": True}, "mlp_bias true"),
    "use_bias": ({"use_bias": True}, "use_bias true"),
    "proj_bias": ({"mamba_proj_bias": True}, "mamba_proj_bias true"),
    "conv_bias": ({"use_conv_bias": False}, "use_conv_bias false"),
    "n_group": ({"n_group": 4}, "n_group 4"),
    "topk_group": ({"topk_group": 2}, "topk_group 2"),
    "norm_topk": ({"norm_topk_prob": False}, "norm_topk_prob false"),
    "shared": ({"n_shared_experts": 2}, "n_shared_experts 2"),
    "groups": ({"n_groups": 3}, "n_groups 3"),
    "window": ({"sliding_window": 4096}, "sliding_window 4096"),
    "residual_fp32": ({"residual_in_fp32": True}, "residual_in_fp32"),
    "eps": ({"norm_eps": 1e-6}, "norm_eps"),
    "share": ({"expert_share": {"published_experts": 32, "of": 3,
                                "index": 0}}, "expert_share"),
    "share_index": ({"expert_share": {"published_experts": 32, "of": 8,
                                      "index": 8}}, "expert_share index"),
    "missing": ({"drop": "ssm_state_size"}, "missing"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_the_reader_refuses_by_name(case):
    change, match = REFUSALS[case]
    d = dict(_TINY_NEMOTRON_H, **change)
    d.pop(d.pop("drop", None), None)
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict(d)


def test_the_head_is_tied_or_not_by_what_the_file_says():
    for tied in (True, False):
        c = ModelConfig.tiny_nemotron_h(tie_word_embeddings=tied)
        assert c.tie_word_embeddings is tied
        shapes = jax.eval_shape(lambda: llama.init_params(c, 0))
        assert ("head" in shapes) is (not tied)


def test_the_two_part_reader_takes_groups_and_its_own_inner():
    """The two-part state-space reader no longer refuses B/C groups or an
    inner width that is not expand x hidden; a group count that does not
    divide the heads it still does."""
    from dynamo_tpu.models.config import _TINY_SSM_MOE

    c = ModelConfig.from_hf_dict(dict(_TINY_SSM_MOE, mamba_n_groups=4,
                                      mamba_d_head=12))
    d = ssm_moe.dims(c)
    assert (d["G"], d["inner"], d["conv"]) == (4, 96, 96 + 2 * 4 * 16)
    with pytest.raises(ValueError, match="mamba_n_groups 3"):
        ModelConfig.from_hf_dict(dict(_TINY_SSM_MOE, mamba_n_groups=3))


# ---------------------------------------------------------------------------
# the published widths, by shapes alone

def test_published_leaves_and_state_bytes():
    """At the published widths: a layer holds ONE norm and its part's
    leaves, every weight bfloat16 but the router's bias and the
    recurrence's A, D and dt_bias; two matrices an expert, stored at 1920;
    the state's and the rows' bytes as the configuration's ``memory``
    states them."""
    c = ModelConfig.from_hf_dict(published())
    shapes = jax.eval_shape(lambda: llama.init_params(c, 0))
    kinds = ssm_moe.dims(c)["kinds"]
    f32 = {"bias", "A_log", "D", "dt_bias"}
    for kind, lp in zip(kinds, shapes["layers"]):
        assert "ln2" not in lp and lp["ln1"].shape == (2688,)
        if kind == "mamba":
            assert set(lp) == {"ln1", "w_in", "conv_w", "conv_b", "A_log",
                               "dt_bias", "D", "norm", "w_out"}
            assert lp["w_in"].shape == (2688, 4096 + 6144 + 64)
            assert lp["conv_w"].shape == (4, 6144)
            assert lp["w_out"].shape == (4096, 2688)
        elif kind == "attention":
            assert set(lp) == {"ln1", "wq", "wk", "wv", "wo"}
            assert lp["wq"].shape == (2688, 4096)
            assert lp["wk"].shape == lp["wv"].shape == (2688, 256)
        else:
            assert set(lp) == {"ln1", "wr", "bias", "we_u", "we_d", "ws_u",
                               "ws_d"}
            assert lp["wr"].shape == (2688, 128)
            assert lp["we_u"].shape == (16, 2688, 1920)
            assert lp["we_d"].shape == (16, 1920, 2688)
            assert lp["ws_u"].shape == (2688, 3712)
        assert all((v.dtype == jnp.float32) == (n in f32)
                   for n, v in lp.items())
    assert shapes["head"].shape == (2688, 16384)
    assert shapes["embed"].shape == (16384, 2688)
    stored = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # the published parameters of this share, and the zeros stored beside
    # them (64 columns / rows of 23 x 16 x 2 matrices)
    assert stored - 23 * 16 * 2 * 2688 * 64 == 5258420544
    assert ssm_moe.kv_row_bytes(c, 2) == 6 * 2 * 2 * 128 * 2
    assert ssm_moe.state_bytes(c, 2) == 23 * (64 * 64 * 128 * 4
                                              + 3 * 6144 * 2)
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, 24, 9216))
    assert ctx["k"].shape == (6, 2, 25, 9216, 128)
    assert len(ctx["ssm_state"]) == 23 and ctx["ssm_state"][0].shape == (
        25, 64, 64, 128)
    assert ctx["conv_state"][0].shape == (25, 3, 6144)
    assert ssm_moe.stepped_kinds(c, ctx) == ("conv_state", "ssm_state")
    assert ssm_moe.prefill_rows_sorted(c, 4096) == 4096 * 6 * 23
    assert ssm_moe.live_row_block(c, 4096) == ssm_moe.LIVE_ROW_BLOCK


# ---------------------------------------------------------------------------
# the host's mirrors and the cell's readers, at the rehearsal's sizes

def test_decode_mirror_counts_parts_and_rows():
    c = ModelConfig.tiny_nemotron_h()
    mirror = ssm_moe.decode_mirror(c, 128, 4, DecodeAttention("pallas"))
    got = dict(mirror(np.asarray([3, 20, 100, 7]),
                      np.asarray([True, True, True, False]), 4))
    names = {p: n for p, (n, _) in tmetrics.LAYER_PARTS_RUN.items()}
    assert [got[names[p]] for p in ("mixer_ssm", "mixer_attn", "experts",
                                    "mlp")] == [24, 8, 20, 0]
    assert got["dynamo_decode_attn_rows_read"] > 0
    # a two-part stack runs a mixer AND a feed-forward part a layer
    two = ssm_moe.layer_parts(ModelConfig.tiny_ssm_moe())
    assert two == {"mixer_ssm": 4, "mixer_attn": 2, "experts": 6, "mlp": 0}


def test_the_cells_readers_read_what_the_program_counts():
    """The accepted readers the cell joined (their ``workloads`` lists) and
    the one it brings, on this configuration's own keys and byte count."""
    byname = load(".", "byname")
    here = os.path.join(REPO, "benchmarks", "layer_metrics")

    def hist(values, count):
        return {"histograms": {k: {"sum": v, "count": count}
                               for k, v in values.items()}}

    counted = {"dynamo_ssm_state_rows_stepped": 23 * 4 * 10 * 50,
               "dynamo_engine_round_live_lane_steps": 4 * 10 * 50,
               "dynamo_moe_experts_touched": 23 * 4 * 9 * 50,
               "dynamo_moe_tokens_routed": 23 * 4 * 8 * 50,
               "dynamo_moe_picks_routed": 23 * 4 * 60 * 50}
    trace = {"modules": {"jit_engine_round_seal": {"count": 40,
                                                   "seconds": 2.4}},
             "kernels": {"m2_step (f32[24,32,128], f32[25,64,64,128])": 0.3,
                         "gmm bf16[144,1920]": 0.2, "gmm bf16[144,2688]": 0.2}}
    sources = {"config": published(), "byname": byname,
               "peaks": load(".", "peaks"), "trace": trace,
               "engine_up": {"flush_every": 4, "device_kind": "TPU v5 lite"},
               "before": hist({k: 0 for k in counted}, 0),
               "after": hist(counted, 50)}
    read = lambda name: byname.module_with(here, name, "read").read(sources)  # noqa: E731
    assert read("moe.held_pick_share") == pytest.approx(8 / 60 * 100)
    assert read("ssm.states_stepped_over_live.ragdoc-open") == pytest.approx(
        1.0)
    # 23 layers x 4 steps x 10 lanes a round, each state 2.097 MB read and
    # written once: what a reader of ``m2_step``'s roofline would divide by
    # the kernel's seconds (no entry has room for one: per_layer is full)
    b = load("bytes", "ssm_groups_moe")
    states, rounds = b.m2_states_stepped(sources)
    assert (states, rounds) == (23 * 4 * 10 * 50, 50)
    assert b.m2_step_bytes(sources["config"], states / rounds) == (
        23 * 4 * 10 * 2 * 64 * 64 * 128 * 4)
    # 9 held experts a layer a step x two matrices at the PUBLISHED width
    want = 23 * 9 * 2 * 2688 * 1856 * 2 / 819e9 / (0.4 / 160) * 100
    assert read("kernel.gmm_roofline") == pytest.approx(want)
    # a program without the counters (the parent): nothing to read
    empty = dict(sources, before={"histograms": {}},
                 after={"histograms": {}})
    for name in ("kernel.gmm_roofline",
                 "moe.held_pick_share", "moe.load_max_over_mean",
                 "ssm.states_stepped_over_live.ragdoc-open"):
        assert byname.module_with(here, name, "read").read(empty) is None
    # the cell is on the list of every entry it reports, and brings ONE
    # (the mix's own TTFT tail, which the benchmark's contract asks for;
    # the list is full: a reader of ``m2_step``'s roofline waits for room)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["per_layer"]
            if CELL + ".agentthink" in m.get("workloads", ())]
    assert len(bench["per_layer"]) <= 128 and sorted(mine) == sorted([
        "ttft_ms_p50.chat-decode-open", "ttft_ms_p90.agentthink-open",
        "gen.late_ms_p90.chat-decode-open",
        "gen.carried_tok_s.chat-decode-open", "moe.load_max_over_mean",
        "moe.held_pick_share", "kernel.gmm_roofline",
        "step.decode_state_share", "ssm.states_stepped_over_live.ragdoc-open",
        "step.prefill_continued_share"])


def test_the_byte_count_counts_the_published_width():
    """``bytes/ssm_groups_moe.py``: two matrices an expert at the
    PUBLISHED 1856 (what is stored beyond it is not asked for), the state
    read and written once a live lane, low and never high."""
    b = load("bytes", "ssm_groups_moe")
    cfg = published()
    s = b.shapes(cfg)
    assert s["expert"] == 2 * 2688 * 1856
    assert s["shared"] == 2 * 2688 * 3712
    assert s["mamba"] == (2688 * (4096 + 6144 + 64) + 6144 * 5 + 4096
                          + 4096 * 2688)
    assert s["attn"] == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert s["state_lane"] == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert (s["n_ssm"], s["n_attn"], s["n_experts"]) == (23, 6, 23)
    assert b.m2_step_bytes(cfg, 230) == 230 * 2 * 64 * 64 * 128 * 4
    sources = {"config": cfg, "engine_up": {"flush_every": 4},
               "before": {"histograms": {
                   b.TOUCHED: {"sum": 0, "count": 0},
                   b.ROUTED: {"sum": 0, "count": 0}}},
               "after": {"histograms": {
                   b.TOUCHED: {"sum": 23 * 4 * 9 * 50, "count": 50},
                   b.ROUTED: {"sum": 23 * 4 * 12 * 50, "count": 50}}}}
    parts = b.decode_parts(sources, [1000.0] * 10)
    assert parts["experts"] == 23 * 9 * 2 * 2688 * 1856 * 2
    assert parts["state"] == 2 * 10 * 23 * s["state_lane"]
    assert parts["rows"] == 10 * 1000 * 6 * 2 * 256 * 2
    nbytes, ops, labels = b.gmm_decode(sources)
    assert nbytes == parts["experts"]
    assert ops == 23 * 12 * 2 * 2 * 2688 * 1856
    lanes = cfg["engine"]["max_decode_slots"]
    assert labels == (f"gmm bf16[{lanes * 6},1920]",
                      f"gmm bf16[{lanes * 6},2688]")
