#!/usr/bin/env python
"""Where the distance between a correct bfloat16 program and the float32
reference of the four-stream latent-attention block comes from: a plain
EMULATION on the CPU at the published widths, reference against
reference, no engine and nothing of the program but its weights' draw.

``references/mla_moe_mhc.py``'s own pieces (``mix``, ``attention``,
``rotary``, the plain block's ``rms_norm``, ``swiglu``) run one random
sequence twice: in float32, and ROUNDED where a bfloat16 program rounds
(every matmul's operands and result, each sublayer's input, output and
new streams; router scores, mixing coefficients, softmax and the head's
logits stay float32, as the program keeps them). The second pass is then
repeated with the router's PICKS, the mixing COEFFICIENTS, or both, held
to the float32 pass's: what is left is the distance that rounding alone
makes, without the discontinuity and without the steep function.

One JSON line a pass: mean and max |log-prob difference| against the
float32 pass over the rounded pass's top 20 tokens at 96 positions of a
1024-token sequence (the check's arithmetic,
``server.check_against_reference``);
per layer the share of (token, pick) pairs that differ, the streams'
relative distance after the layer and the largest |H_res difference|.

  python3 tools/mla_moe_mhc_cause.py --seed 3700370013       # ~4 min
  python3 tools/mla_moe_mhc_cause.py --seed 1 --dry-run      # tiny
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

TOP = 20          # tokens compared a position, the check's
TOKENS = 1024     # one random sequence
POSITIONS = 96    # compared, evenly spread over its last seven eighths


def forward(ref, hf, hp, params, tokens, *, rnd, held=None, hold=()):
    """log-probs [T, V] of one pass and its record (picks, coefficients,
    streams by layer). ``rnd`` rounds (identity: the float32 pass);
    ``hold`` names what is taken from the record ``held`` instead of
    computed: ``"picks"``, ``"mix"``."""
    plain = ref.plain
    n, n_dense = int(hf["hc_mult"]), hf["first_k_dense_replace"]
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    exact_mm = plain.mm
    rounded_mm = lambda x, w, control: rnd(rnd(x) @ w)  # noqa: E731
    rec = {"picks": [], "mix": [], "X": []}

    def coefficients(X, lp, sub):
        if "mix" in hold:
            pre, post, res = held["mix"][len(rec["mix"])]
        else:   # float32 on both sides, from the streams as they stand
            pre, post, res = ref.mix(hp, X, lp["hc_phi"][sub],
                                     lp["hc_a"][sub], lp["hc_b"][sub])
        rec["mix"].append((pre, post, res))
        return pre, post, res

    def close(X, f, post, res):
        return rnd(jnp.einsum("tij,tjc->tic", res, X)
                   + post[:, :, None] * f[:, None, :])

    def experts(x2, ep):
        s = jax.nn.sigmoid(x2 @ ep["wr"].astype(jnp.float32))
        if "picks" in hold:
            order = held["picks"][len(rec["picks"])]
        else:
            order = np.asarray(jnp.argsort(
                -(s + ep["bias"].astype(jnp.float32)), -1)[:, :hp["top_k"]])
        rec["picks"].append(order)
        picked = np.zeros(s.shape, bool)
        picked[np.arange(s.shape[0])[:, None], order] = True
        w = s * picked
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * hp["scale"]
        y = plain.swiglu(x2, *(ep[k].astype(jnp.float32)
                               for k in ("ws_g", "ws_u", "ws_d")))
        for e in range(s.shape[1]):
            rows = np.nonzero(picked[:, e])[0]
            if rows.size:
                ye = plain.swiglu(x2[rows], *(ep[k][e].astype(jnp.float32)
                                              for k in ("we_g", "we_u", "we_d")))
                y = y.at[rows].add(w[rows, e][:, None] * ye)
        return rnd(y)

    try:
        plain.mm = rounded_mm
        e = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        X = jnp.broadcast_to(e[:, None], (e.shape[0], n, e.shape[1]))
        for l in range(hf["num_hidden_layers"]):
            lp = f32(jax.tree.map(lambda a: a[l], params["layers"]))
            plain.mm = exact_mm
            pre, post, res = coefficients(X, lp, 0)
            plain.mm = rounded_mm
            u = rnd(jnp.einsum("ti,tic->tc", pre, X))
            X = close(X, ref.attention(hp, lp, u), post, res)
            plain.mm = exact_mm
            pre, post, res = coefficients(X, lp, 1)
            plain.mm = rounded_mm
            u = rnd(jnp.einsum("ti,tic->tc", pre, X))
            x2 = rnd(plain.rms_norm(u, lp["ln2"], hp["eps"]))
            if l < n_dense:
                f = plain.swiglu(x2, *(params["dense"][k][l].astype(
                    jnp.float32) for k in ("wg", "wu", "wd")))
            else:
                f = experts(x2, params["experts"][l - n_dense])
            X = close(X, f, post, res)
            rec["X"].append(np.asarray(X))
        h = rnd(plain.rms_norm(X.sum(axis=1), params["norm_f"].astype(
            jnp.float32), hp["eps"]))
        logits = h @ params["lm_head"].astype(jnp.float32)
        return np.asarray(jax.nn.log_softmax(logits, -1)), rec
    finally:
        plain.mm = exact_mm


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()
    import server  # benchmarks/server.py
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = server.load_config(
        os.path.join(REPO, "benchmarks", "configs", "xing4-mhc-d7.json"),
        args.dry_run)
    ref = server.byname.module_with(
        os.path.join(REPO, "benchmarks", "references"), cfg["reference"],
        "logprobs")
    mcfg = ModelConfig.from_hf_dict(cfg)   # bfloat16 weights, as served
    params = llama.init_params(mcfg, jax.random.PRNGKey(
        args.seed % (2 ** 31)))
    hp = {
        "heads": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "kv_rank": cfg["kv_lora_rank"], "eps": float(cfg["rms_norm_eps"]),
        "top_k": cfg["num_experts_per_tok"],
        "scale": float(cfg["routed_scaling_factor"]),
        "rotary": ref.rotary(cfg, None),
        "iters": int(cfg["hc_sinkhorn_iters"]),
        "hc_eps": float(cfg["hc_eps"]),
        "clamp": (float(cfg["mhc_h_res_clamp_min"]),
                  float(cfg["mhc_h_res_clamp_max"])),
    }
    T = 256 if args.dry_run else TOKENS
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(10, cfg["vocab_size"], T).tolist()
    at = np.linspace(T // 8, T - 1, POSITIONS).astype(int)
    with jax.default_matmul_precision("highest"):
        exact, rec32 = forward(ref, cfg, hp, params, tokens,
                               rnd=lambda z: z)
        for hold in ((), ("picks",), ("mix",), ("picks", "mix")):
            got, rec = forward(ref, cfg, hp, params, tokens,
                               rnd=ref.plain.to_bf16, held=rec32, hold=hold)
            ids = np.argsort(-got[at], -1)[:, :TOP]
            diff = np.abs(np.take_along_axis(got[at], ids, -1)
                          - np.take_along_axis(exact[at], ids, -1))
            flips = [float(np.mean([len(set(a) - set(b)) for a, b in
                                    zip(p[at], q[at])]) / hp["top_k"])
                     for p, q in zip(rec["picks"], rec32["picks"])]
            apart = [float(np.linalg.norm(a[at] - b[at])
                           / np.linalg.norm(b[at]))
                     for a, b in zip(rec["X"], rec32["X"])]
            h_res = [float(jnp.max(jnp.abs(a[2][at] - b[2][at])))
                     for a, b in zip(rec["mix"], rec32["mix"])]
            print(json.dumps({
                "seed": args.seed, "tokens": T, "positions": len(at),
                "held_to_float32": list(hold),
                "mean_abs_logprob_diff": float(diff.mean()),
                "max_abs_logprob_diff": float(diff.max()),
                "picks_differ_share_by_expert_layer": flips,
                "streams_apart_by_layer": apart,
                "h_res_apart_max_by_sublayer": h_res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
