"""What the tier-1 rehearsals of the benchmark share: the child-process
runner, a copied checkout at a rate the CPU holds, and the rehearsal of
one whole cell (``rehearse``), with every cell's case in ``CELLS``.

The driver's tier-1 run hands a FILE to one of its six workers
(``--dist loadfile``) and a worker draws its next file while two CASES of
this one are pending, so a rehearsal is one file of one case: a new cell
adds its line to ``CELLS`` here and ``tests/test_rehearsal_<id>.py``, the
three lines every such file is.
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
    in two seconds and, under the run's six workers, a decode step in
    ~100 ms, so at a rate and a drain grace sized for the chip the streams
    of the rehearsal would outlast the grace (1006 tokens in 96 s,
    ``thinklong``): the copy's mixes wait three times as long."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "dynamo_tpu"),
               os.path.join(root, "dynamo_tpu"))
    with open(os.path.join(root, "benchmarks", "cells", cell + ".json"),
              "w") as f:
        json.dump({"rate_rps": rate_rps}, f)
    traffic = os.path.join(root, "benchmarks", "traffic")
    for name in os.listdir(traffic):
        with open(os.path.join(traffic, name)) as f:
            mix = json.load(f)
        mix["drain_grace_s"] *= 3
        with open(os.path.join(traffic, name), "w") as f:
            json.dump(mix, f)
    return root


# id -> (cell, seed, reference, rate_rps), a line a cell with what its check
# carries at the dry-run widths. The rates are the CPU's, not the cells': a
# rate sized for the chip queues tens of streams that the tiny model drains
# at ~90 ms a token under the run's six workers, past the drain grace (PR
# 40, PR 61's run); what is asserted (``correct``, ``failed == 0``, the
# reference by name, the metrics' keys) needs ONE request in the window, so
# each rate leaves one or two there and as many in the pre-roll
CELLS = {
    # decode through the latent kernel's XLA loop from 64 lanes
    "chat-decode": ("mla-moe-joyai-d5.chat-decode", "3100310031",
                    "benchmarks/references/mla_moe.py", 0.5),
    # a fresh 4096-token chunk, continuing chunks and decode over a
    # 16384-token region; the warm-up set leaves the window nothing to
    # compile
    "longdoc": ("xing4-mhc-d7.longdoc", "3700370037",
                "benchmarks/references/mla_moe_mhc.py", 0.17),
    # the dense looped prefill at 2048 / 4096
    "longprompt": ("mistral7b-w8.longprompt", "3700370038",
                   "benchmarks/reference.py", 0.34),
    # Mamba-2 states and K/V rows over chunk boundaries, experts by halves
    "ragdoc": ("granite4h-ep2-d10.ragdoc", "4100410041",
               "benchmarks/references/ssm_moe.py", 0.17),
    # the switch to the block selection (position 1024) crossed in prefill
    # and in decode, over a 32768-token region with its compressed-key rows
    "longctx": ("minicpm-sala-d16.longctx", "4500450045",
                "benchmarks/references/sala.py", 0.17),
    # delta-rule state, convolution windows and latent rows over a chunk
    # boundary at 4096 and through 72 decode steps, grouped routing with a
    # share of eight; 0.06 req/s would leave the window empty
    "reasoning": ("ling3-flash-ep8-d12.reasoning", "4700470047",
                  "benchmarks/references/kda_mla_moe.py", 0.1),
    # Mamba-1 state, window and K/V rows over a chunk boundary at 2048,
    # through a looped 1024 bucket and a padded 256 one; the warm-up fills
    # all 96 lanes once
    "chat-rate": ("jamba2-3b.chat-rate", "5100510051",
                  "benchmarks/references/jamba.py", 0.1),
    # Mamba-1 state, window buffers of 8 rows wrapped a thousand times,
    # layer 5's rows and the gated memory unit's m over two chunk
    # boundaries at 4096; 0.06 req/s would leave the window empty
    "thinklong": ("phi4-mini-flash.thinklong", "5400540054",
                  "benchmarks/references/sambay.py", 0.1),
    # window buffers and three full layers' rows over two chunk boundaries
    # at 4096, at 18 and 12 query heads over two K/V heads, rotary by kind
    "codeturn": ("laguna-s-ep8-d12.codeturn", "5800580058",
                 "benchmarks/references/window_gqa_moe.py", 0.1),
    # six grouped Mamba-2 states and two attention layers' rows over a
    # chunk boundary at 4096 through one-part layers, experts stored wider
    # than published
    "agentthink": ("nemotron3-nano-ep8.agentthink", "6000600060",
                   "benchmarks/references/ssm_groups_moe.py", 0.1),
    # twelve cache planes (3 layers x 4 passes) through ring, region and a
    # four-page pool, the counter row in two rows of three lanes
    "chat-looped": ("ouro-2p6b-ut4.chat", "6400640064",
                    "benchmarks/references/looped.py", 0.3),
}


def cells(*ids):
    """The parametrisation of ``test_the_new_cell_rehearses_on_the_cpu``
    for the cell a file holds, under its id."""
    return pytest.mark.parametrize(
        "cell,seed,reference,rate_rps", [CELLS[i] for i in ids],
        ids=list(ids))


def rehearse(tmp_path, cell, seed, reference, rate_rps):
    """A cell's files end to end at the dry-run widths: configuration,
    reference by name, traffic mix, warm-up, window, result line."""
    root = checkout_at_rate(tmp_path, cell, rate_rps)
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
