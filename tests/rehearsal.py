"""What the tier-1 rehearsals of the benchmark share: the child-process
runner, a copied checkout at a rate the CPU holds, and the rehearsal of
one whole cell (``rehearse``), with every cell's case in ``CELLS``.

The driver's tier-1 run hands a FILE to one of its six workers
(``--dist loadfile``), so the cases of one file run one after another:
the six rehearsals in one file were 835 s of a run of 863 (PR 50). They
are spread over ``tests/test_rehearsal_*.py``, two cells a file, a long
one with a short one. A NEW CELL'S REHEARSAL joins the file that is
shortest then (the times stand in each file's head), or opens a new file
once every file holds two: add its case to ``CELLS`` here and its id to
that file's ``cells(...)``.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(cmd, limit_s, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cwd)
    env.pop("XLA_FLAGS", None)   # the rehearsal sets its own device count
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=limit_s)


def checkout_at_rate(tmp_path, cell, rate_rps):
    """A copy of what the benchmark reads (``BENCHMARK.json``,
    ``benchmarks/``; the program linked, not copied) whose cell offers
    ``rate_rps``: what ``tools/knee_sweep.py`` does to the chip machine's
    copy. The CPU computes a 4096-token continuing chunk of the TINY model
    in two seconds, so at a rate sized for the chip every stream of the
    rehearsal would outlast the drain grace."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "dynamo_tpu"),
               os.path.join(root, "dynamo_tpu"))
    with open(os.path.join(root, "benchmarks", "cells", cell + ".json"),
              "w") as f:
        json.dump({"rate_rps": rate_rps}, f)
    return root


# id -> (cell, seed, reference, rate_rps; None: the cell's own rate)
CELLS = {
    # 14.4 req/s is sized for the chip: ~170 streams of up to 512 tokens
    # in 12 s. Alone the CPU holds that; under the six workers of the
    # driver's run it did not (PR 40's run of the standing tree: streams
    # outlasted the drain grace, ``failed`` > 0). A third of it, as the
    # other rehearsals run at a rate the CPU holds
    "chat-decode": ("mla-moe-joyai-d5.chat-decode", "3100310031",
                    "benchmarks/references/mla_moe.py", 4.0),
    "longdoc": ("xing4-mhc-d7.longdoc", "3700370037",
                "benchmarks/references/mla_moe_mhc.py", 0.34),
    "longprompt": ("mistral7b-w8.longprompt", "3700370038",
                   "benchmarks/reference.py", None),
    "ragdoc": ("granite4h-ep2-d10.ragdoc", "4100410041",
               "benchmarks/references/ssm_moe.py", 1.0),
    # prompts of 8k-28k tokens: the CPU needs ~3 s a 4096-token chunk of
    # the tiny model, so one request in the pre-roll and one in the window
    "longctx": ("minicpm-sala-d16.longctx", "4500450045",
                "benchmarks/references/sala.py", 0.17),
    # answers of 384-2048 tokens from up to 48 lanes: six requests in the
    # 20 s pre-roll and two in the window are what the CPU drains in time
    "reasoning": ("ling3-flash-ep8-d12.reasoning", "4700470047",
                  "benchmarks/references/kda_mla_moe.py", 0.3),
    # a rate sized for the chip's 96 lanes; the CPU drains ~20 requests of
    # 32-768 tokens out in the 10 s pre-roll and the window
    "chat-rate": ("jamba2-3b.chat-rate", "5100510051",
                  "benchmarks/references/jamba.py", 1.2),
    # answers of 256-1536 tokens after prompts of 128-16384 (one to four
    # chunks of the tiny model): five requests in the 20 s pre-roll and one
    # or two in the window are what the CPU drains in time
    "thinklong": ("phi4-mini-flash.thinklong", "5400540054",
                  "benchmarks/references/sambay.py", 0.25),
    # prompts of 512-16384 (one to four 4096-token chunks of the tiny
    # model, ~2 s each on the CPU): two requests in the 10 s pre-roll and
    # one in the window
    "codeturn": ("laguna-s-ep8-d12.codeturn", "5800580058",
                 "benchmarks/references/window_gqa_moe.py", 0.2),
    # answers of 96-1024 tokens after prompts of 128-8192 (one or two
    # chunks of the tiny model's 13 one-part layers): three requests in the
    # 10 s pre-roll and one or two in the window
    "agentthink": ("nemotron3-nano-ep8.agentthink", "6000600060",
                   "benchmarks/references/ssm_groups_moe.py", 0.3),
}


def cells(*ids):
    """The parametrisation of ``test_the_new_cell_rehearses_on_the_cpu``
    for the cells a file holds, under the ids the one file gave them."""
    return pytest.mark.parametrize(
        "cell,seed,reference,rate_rps", [CELLS[i] for i in ids],
        ids=list(ids))


def rehearse(tmp_path, cell, seed, reference, rate_rps):
    """A cell's files end to end at the dry-run widths: configuration,
    reference by name, traffic mix, warm-up, window, result line; at the
    cell's own rate, or where the CPU cannot hold that (above) at one it
    can. The long-document cell's check and traffic run a fresh
    4096-token chunk, continuing chunks and decode over a 16384-token
    region here too, and its warm-up set has to leave the window nothing
    to compile. The long-context cell's check crosses the toy model's
    switch to the block selection (position 1024) in prefill and in
    decode, over a 32768-token region with its compressed-key rows. The
    reasoning cell's check carries the toy model's delta-rule state, its
    convolution windows and its latent rows over a chunk boundary at 4096
    and through 72 decode steps, under grouped routing with a share of
    eight. The chat-rate cell's check carries the toy model's Mamba-1
    state, window and K/V rows over a chunk boundary at 2048, through a
    looped 1024 bucket and a padded 256 one, and its warm-up fills all 96
    lanes once. The long-thought cell's check carries the toy model's
    Mamba-1 state, its window buffers (8 rows a lane: wrapped a thousand
    times), layer 5's rows and the gated memory unit's m over two chunk
    boundaries at 4096, with only each chunk's last row above layer 5, and
    its decode crosses multiples of the buffer's length in every round. The
    coding-turn cell's check carries the toy model's window buffers (8 rows
    a lane) and its three full layers' rows over two chunk boundaries at
    4096 at 18 and 12 query heads over two K/V heads, rotary by kind, under
    the one-group router with a share of eight. The agent-turn cell's
    check carries the toy model's six grouped Mamba-2 states and windows
    and its two attention layers' rows over a chunk boundary at 4096
    through one-part layers, with experts stored wider than published."""
    root = REPO if rate_rps is None else checkout_at_rate(
        tmp_path, cell, rate_rps)
    r = run([sys.executable, "benchmarks/run.py", "--workload", cell,
             "--seed", seed, "--seconds", "6", "--cpu-dry-run"], 900, root)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["dry_run"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0, r.stdout[-3000:]
    assert {"tpot_ms_p90", "setup_s"} <= set(line["metrics"])
    notes = next(json.loads(l[len("notes: "):])
                 for l in r.stdout.splitlines() if l.startswith("notes: "))
    assert notes["check"]["reference"] == reference
