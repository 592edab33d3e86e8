"""What the harness knows about a block is found through the configuration's
file: ``python3 -m pytest benchmarks/test_by_config.py -q`` (on the CPU, no
server; like ``test_contract.py`` outside tier-1, because a benchmark PR
may add no file under ``tests/``).

1. A configuration WITHOUT the keys resolves to ``benchmarks/
   reference.py``, ``peaks.decode_bytes_per_step`` with the arguments it
   always had, the prompts ``((96, 12), (180, 12))`` and the tolerances
   0.2 / 0.03. (One WITH them is held to 2 by files made for the test, so
   a later PR that adds such a configuration changes nothing here.)
2. One WITH them resolves to the named files, and a named file that is
   missing or lacks its function is an error, never a fall back.
3. The check holds the engine to the reference module's own prompts and
   tolerances and says which it used.
4. ``run.py``'s process imports no JAX, with every reader and a ``bytes/``
   file loaded.
"""
from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)     # server.py resolves dynamo_tpu.* by name


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


server = _load("bench_server", "server.py")
byname = _load("bench_byname", "byname.py")
peaks = _load("bench_peaks", "peaks.py")
roofline = _load("bench_roofline", "layer_metrics", "step.decode_roofline.py")

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CONFIGS = {}
for entry in BENCH["configs"]:
    with open(os.path.join(REPO, entry["file"])) as f:
        CONFIGS[entry["name"]] = json.load(f)
PLAIN = sorted(name for name, cfg in CONFIGS.items()
               if not {"reference", "bytes"} & set(cfg))

REFERENCE_WITH_ITS_OWN = '''
CHECK_PROMPTS = ((600, 9), (40, 8))   # crosses a 512-token window
CHECK_TOL_MAX = 0.5                   # a reason would stand here
CHECK_TOL_MEAN = 0.04
def logprobs(hf, params, tokens, positions):
    raise NotImplementedError
'''
REFERENCE_BARE = "def logprobs(hf, params, tokens, positions): return None\n"
BYTES_FILE = '''
CALLS = []
def decode_bytes_per_step(sources, ctx_lens):
    CALLS.append((sources, list(ctx_lens)))
    return 1000.0 * len(ctx_lens)
'''


@pytest.fixture
def plugin_dirs(tmp_path, monkeypatch):
    """An empty benchmarks/ of its own: references/ and bytes/ to fill."""
    (tmp_path / "references").mkdir()
    (tmp_path / "bytes").mkdir()
    monkeypatch.setattr(server, "HERE", str(tmp_path))
    monkeypatch.setattr(server, "REPO", str(tmp_path.parent))
    monkeypatch.setattr(roofline, "_BYTES", str(tmp_path / "bytes"))
    return tmp_path


class FakePeaks:
    def __init__(self):
        self.calls = []

    def decode_bytes_per_step(self, *args):
        self.calls.append(args)
        return 819e9 * 0.010            # 10 ms at the peak below

    def peaks_for(self, kind):
        return 197e12, 819e9


def sources_for(cfg: dict, peaks_mod) -> dict:
    """A traced second of 4-step rounds of 20 ms, two live requests."""
    log = [{"ok": True, "chunks": [0.0, 10.0], "tokens": 100,
            "prompt_tokens": 300},
           {"ok": True, "chunks": [0.5, 9.0], "tokens": 50,
            "prompt_tokens": 700},
           {"ok": False, "chunks": [], "tokens": 0}]
    return {"config": cfg, "peaks": peaks_mod, "byname": byname, "log": log,
            "trace_span": (4.0, 5.0),
            "engine_up": {"param_bytes": 7_000_000, "tp": cfg.get("tp", 1),
                          "device_kind": "TPU v5 lite", "flush_every": 4},
            "trace": {"modules": {"jit_engine_round_seal": {
                "count": 10.0, "seconds": 0.8}}}}


def settings(ref: dict) -> tuple:
    return ref["prompts"], ref["tol_max"], ref["tol_mean"]


# ---- 1. without the keys --------------------------------------------


@pytest.mark.parametrize("name", PLAIN)
def test_without_keys_the_reference_is_reference_py(name):
    ref = server.reference_for(CONFIGS[name])
    assert ref["file"] == os.path.join("benchmarks", "reference.py")
    assert os.path.samefile(ref["logprobs"].__code__.co_filename,
                            os.path.join(HERE, "reference.py"))
    assert settings(ref) == ([[96, 12], [180, 12]], 0.2, 0.03)


@pytest.mark.parametrize("name", PLAIN)
def test_without_keys_the_bytes_are_peaks_with_the_arguments_it_had(name):
    cfg, fake = CONFIGS[name], FakePeaks()
    sources = sources_for(cfg, fake)
    share = roofline.read(sources)
    # 10 ms of bytes at the peak over a 20 ms step
    assert share == pytest.approx(50.0)
    assert len(fake.calls) == 20
    eng = cfg["engine"]
    for hf, param_bytes, ctx_lens, tp, max_ctx in fake.calls:
        assert hf is cfg and param_bytes == 7_000_000 and tp == cfg["tp"]
        assert max_ctx == eng["max_pages_per_seq"] * eng["page_size"]
        assert len(ctx_lens) == 2 and 340 < ctx_lens[0] < 350
    # and through the real arithmetic it is what peaks gives
    real = roofline.bytes_counter(sources_for(cfg, peaks))
    assert real([300.0, 700.0]) == peaks.decode_bytes_per_step(
        cfg, 7_000_000, [300.0, 700.0], cfg["tp"],
        eng["max_pages_per_seq"] * eng["page_size"])


# ---- 2. with the keys -----------------------------------------------


@pytest.mark.parametrize("text, want", [
    (REFERENCE_WITH_ITS_OWN, ([[600, 9], [40, 8]], 0.5, 0.04)),
    (REFERENCE_BARE, ([[96, 12], [180, 12]], 0.2, 0.03)),
], ids=["states-its-own", "states-nothing"])
def test_a_named_reference_is_loaded_with_what_it_states(plugin_dirs, text,
                                                         want):
    (plugin_dirs / "references" / "other-block.py").write_text(text)
    ref = server.reference_for({"reference": "other-block"})
    assert ref["file"] == os.path.join(plugin_dirs.name, "references",
                                       "other-block.py")
    assert callable(ref["logprobs"]) and settings(ref) == want


@pytest.mark.parametrize("name, text, error", [
    ("absent", None, FileNotFoundError),
    ("no-logprobs", "CHECK_TOL_MAX = 0.1\n", AttributeError),
    ("no-prompts", REFERENCE_BARE + "CHECK_PROMPTS = ()\n", ValueError),
    ("../reference", None, FileNotFoundError),
    ("", None, FileNotFoundError),
    (7, None, FileNotFoundError),
    (None, None, FileNotFoundError),
], ids=["missing-file", "lacks-logprobs", "no-prompts", "path-not-name",
        "empty", "number", "null"])
def test_a_named_reference_that_cannot_be_had_is_an_error_not_a_fallback(
        plugin_dirs, name, text, error):
    # the default is within reach of a fall back: it must not be taken
    (plugin_dirs / "reference.py").write_text(REFERENCE_BARE)
    if text is not None:
        (plugin_dirs / "references" / f"{name}.py").write_text(text)
    with pytest.raises(error):
        server.reference_for({"reference": name})


def test_a_named_byte_count_is_called_with_sources_and_lengths(plugin_dirs):
    (plugin_dirs / "bytes" / "sparse.py").write_text(BYTES_FILE)
    fake = FakePeaks()
    sources = sources_for({"bytes": "sparse", "engine": {}}, fake)
    count = roofline.bytes_counter(sources)
    assert count([10.0, 20.0, 30.0]) == 3000.0
    share = roofline.read(sources)
    # 2 lanes x 1000 B over 819e9 B/s, against a 20 ms step
    assert share == pytest.approx(2000.0 / 819e9 / 0.020 * 100.0)
    assert fake.calls == []          # peaks' count was not consulted


@pytest.mark.parametrize("name, text, error", [
    ("absent", None, FileNotFoundError),
    ("../peaks", None, FileNotFoundError),
    ("empty", "X = 1\n", AttributeError),
    (None, None, FileNotFoundError),
], ids=["missing-file", "path-not-name", "lacks-function", "null"])
def test_a_named_byte_count_that_cannot_be_had_is_an_error_not_a_fallback(
        plugin_dirs, name, text, error):
    if text is not None:
        (plugin_dirs / "bytes" / f"{name}.py").write_text(text)
    fake = FakePeaks()
    with pytest.raises(error):
        roofline.read(sources_for({"bytes": name, "engine": {}}, fake))
    assert fake.calls == []


# ---- 3. the check is held to what the reference module states --------


VOCAB = 64
TABLE = np.log(np.arange(1, VOCAB + 1) / (VOCAB * (VOCAB + 1) / 2))


class FakeEngine:
    """Emits tokens whose top-20 log-probs are the reference's own plus
    ``off`` (and ``spike`` more on one entry of each prompt's first row)."""

    params = None

    def __init__(self, off: float, spike: float):
        self.off, self.spike = off, spike
        self.prompts = []

    async def generate(self, req):
        from dynamo_tpu.protocols.common import LLMEngineOutput

        self.prompts.append(len(req.token_ids))
        for i in range(req.stop_conditions.max_tokens):
            row = [(t, float(TABLE[t]) + self.off) for t in range(20)]
            if i == 0:
                row[3] = (3, row[3][1] + self.spike)
            yield LLMEngineOutput(token_ids=[5], top_logprobs=[row])


@pytest.mark.parametrize("off, spike, tol_max, tol_mean, ok", [
    (0.0, 0.0, 0.2, 0.03, True),
    (0.02, 0.1, 0.2, 0.03, True),
    (0.02, 0.3, 0.2, 0.03, False),      # one local fault: max
    (0.05, 0.0, 0.2, 0.03, False),      # a uniform loss of precision: mean
    (0.05, 0.3, 0.5, 0.06, True),       # the module's own, wider, hold
], ids=["exact", "inside", "max-fails", "mean-fails", "own-tolerances"])
def test_the_check_compares_at_the_modules_prompts_under_its_tolerances(
        off, spike, tol_max, tol_mean, ok):
    engine = FakeEngine(off, spike)
    reference = {
        "logprobs": lambda hf, params, tokens, positions: np.tile(
            TABLE, (len(positions), 1)),
        "file": "benchmarks/references/x.py", "prompts": [[600, 9], [40, 8]],
        "tol_max": tol_max, "tol_mean": tol_mean}
    verdict = asyncio.run(server.check_against_reference(
        engine, {"vocab_size": VOCAB}, 30, reference))
    assert verdict["ok"] is ok
    assert engine.prompts == [600, 40]
    assert verdict["prompts"] == [[600, 9], [40, 8]]
    assert verdict["compared"] == (9 + 8) * 20
    assert (verdict["tol_max"], verdict["tol_mean"]) == (tol_max, tol_mean)
    assert verdict["reference"] == "benchmarks/references/x.py"
    assert verdict["max_abs_logprob_diff"] == pytest.approx(off + spike)


# ---- 4. run.py's process stays off JAX ------------------------------


def test_run_py_with_every_reader_and_a_bytes_file_imports_no_jax(tmp_path):
    (tmp_path / "wrap.py").write_text(BYTES_FILE)
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {HERE!r})
        import run
        bench = run.load_json("BENCHMARK.json")
        readers = {{m["name"]: run.load_reader(m["name"])
                   for m in bench["per_layer"]}}
        mod = readers["step.decode_roofline"].__globals__
        mod["_BYTES"] = {str(tmp_path)!r}
        sources = {{"config": {{"bytes": "wrap", "engine": {{}}}},
                   "engine_up": {{}}, "peaks": run.peaks,
                   "byname": run.byname}}
        assert mod["bytes_counter"](sources)([1.0, 2.0]) == 2000.0
        bad = sorted(m for m in sys.modules
                     if m.split(".")[0] in ("jax", "jaxlib", "dynamo_tpu"))
        print(json.dumps({{"readers": len(readers), "bad": bad}}))
    """)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["readers"] == len(BENCH["per_layer"]) and out["bad"] == []
