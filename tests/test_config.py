"""Config layering + logging init tests (reference config.rs figment
layering and logging.rs DYN_LOG filters)."""
import json
import logging

from dynamo_tpu.config import (
    JsonlFormatter,
    RuntimeConfig,
    _apply_filters,
    load_config,
)


def test_defaults():
    cfg = load_config(env={})
    assert cfg == RuntimeConfig()
    assert cfg.store_host_port == ("127.0.0.1", 7111)


def test_toml_layer(tmp_path):
    p = tmp_path / "conf.toml"
    p.write_text("""
[runtime]
control_plane = "10.0.0.9:7222"
page_size = 32
""")
    cfg = load_config(path=str(p), env={})
    assert cfg.control_plane == "10.0.0.9:7222"
    assert cfg.page_size == 32
    assert cfg.num_pages == 512  # untouched default


def test_env_overrides_toml(tmp_path):
    p = tmp_path / "conf.toml"
    p.write_text('[runtime]\npage_size = 32\nnamespace = "from-toml"\n')
    cfg = load_config(env={
        "DYNTPU_CONFIG": str(p),
        "DYNTPU_PAGE_SIZE": "128",
        "DYNTPU_HOST_OFFLOAD_PAGES": "64",
    })
    assert cfg.page_size == 128          # env wins over toml
    assert cfg.namespace == "from-toml"  # toml wins over default
    assert cfg.host_offload_pages == 64


def test_log_filter_spec():
    root = logging.getLogger("test-root-sentinel")
    _apply_filters("debug", root)
    assert root.level == logging.DEBUG
    _apply_filters("dynamo_tpu.x=warning, other.y=error", root)
    assert logging.getLogger("dynamo_tpu.x").level == logging.WARNING
    assert logging.getLogger("other.y").level == logging.ERROR


def test_jsonl_formatter():
    rec = logging.LogRecord(
        "pkg.mod", logging.WARNING, "f.py", 1, "something %s", ("bad",), None
    )
    out = json.loads(JsonlFormatter().format(rec))
    assert out["level"] == "WARNING"
    assert out["logger"] == "pkg.mod"
    assert out["msg"] == "something bad"


# ---------------------------------------------------------------------------
# An engine option is written out by hand in three files (EngineConfig,
# RuntimeConfig, and launch/run.py's parser and EngineConfig(...) call;
# ROADMAP D6). Until they are one, these hold the copies to each other.


def _shared_fields():
    import dataclasses

    from dynamo_tpu.engine.config import EngineConfig

    engine = {f.name: f.default for f in dataclasses.fields(EngineConfig)}
    runtime = {f.name: f.default for f in dataclasses.fields(RuntimeConfig)}
    return {n: (engine[n], runtime[n]) for n in engine if n in runtime}


def _clean_parser(monkeypatch, tmp_path):
    """The launcher's parser with no DYNTPU_* layer and no
    ./dynamo_tpu.toml under it: its defaults are the dataclass's."""
    import os

    from dynamo_tpu.launch.run import build_parser

    for key in list(os.environ):
        if key.startswith("DYNTPU_"):
            monkeypatch.delenv(key)
    monkeypatch.chdir(tmp_path)
    return build_parser()


def test_runtime_and_engine_defaults_agree():
    shared = _shared_fields()
    assert len(shared) >= 30          # the mirror is there to be checked
    assert {n: e for n, (e, r) in shared.items() if e != r} == {}


def test_every_shared_option_has_a_flag_with_its_default(
        monkeypatch, tmp_path):
    """--<field> (less a unit suffix), with on/off standing for a bool;
    or --no-<field>, which is the negation."""
    actions = {a.dest: a
               for a in _clean_parser(monkeypatch, tmp_path)._actions}
    wrong = {}
    for name, (default, _) in _shared_fields().items():
        if "no_" + name in actions:
            got = not actions["no_" + name].default
        else:
            action = (actions.get(name)
                      or actions.get(name.removesuffix("_s")))
            if action is None:
                wrong[name] = "no flag"
                continue
            got = action.default
            if action.choices == ["on", "off"]:
                got = {"on": True, "off": False}[got]
        if got != default or type(got) is not type(default):
            wrong[name] = (got, default)
    assert wrong == {}


def test_no_flag_outlives_its_option(monkeypatch, tmp_path):
    """Every flag of the parser is read by some program file, and every
    keyword the launcher hands EngineConfig is a field of it: an option
    taken out of one copy cannot stay behind in another."""
    import ast
    import dataclasses
    import pathlib

    import dynamo_tpu
    from dynamo_tpu.engine.config import EngineConfig

    dests = {a.dest for a in _clean_parser(monkeypatch, tmp_path)._actions}
    dests.discard("help")
    read, handed = set(), set()
    root = pathlib.Path(dynamo_tpu.__file__).parent
    for path in root.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "args"):
                read.add(node.attr)
            elif (isinstance(node, ast.Call)
                    and getattr(node.func, "id", None) == "getattr"
                    and len(node.args) >= 2
                    and isinstance(node.args[1], ast.Constant)):
                read.add(node.args[1].value)
            elif (isinstance(node, ast.Call) and path.name == "run.py"
                    and getattr(node.func, "id", None) == "EngineConfig"):
                handed |= {k.arg for k in node.keywords}
    assert dests - read == set()
    assert handed and handed <= {
        f.name for f in dataclasses.fields(EngineConfig)}
