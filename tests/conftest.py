"""Test configuration: force a virtual 8-device CPU platform before JAX import.

All unit/integration tests run on CPU with 8 virtual devices so sharding
(tp/dp/sp/ep meshes) is exercised without TPU hardware, mirroring the
reference's strategy of testing distributed logic without GPUs
(reference: tests run against mock engines + docker-compose etcd/NATS;
see SURVEY.md §4.7).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

from dynamo_tpu import compile_cache  # noqa: E402

# XLA CPU dispatches f32 matmuls to reduced-precision paths by default;
# golden tests against torch need exact f32 accumulation.
jax.config.update("jax_default_matmul_precision", "highest")


_CACHE_DIR = pytest.StashKey[str]()


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    """xdist's controller, for each worker it starts: the run's directory."""
    node.workerinput["compile_cache"] = node.config.stash.get(_CACHE_DIR, "")


def pytest_unconfigure(config):
    if _CACHE_DIR in config.stash:
        shutil.rmtree(config.stash[_CACHE_DIR], ignore_errors=True)


def pytest_configure(config):
    # the order below is the scheduler's only if it is left alone: xdist
    # (3.8: ``--loadscope-reorder`` is its default) sorts files by their
    # NUMBER of cases, which runs the one-case rehearsals last of all,
    # three queued on one worker while five stand idle (a replay of PR 62's
    # run: 1246 s of cases on the longest worker, 966 as collected, 964
    # perfect)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    # ONE persistent compilation cache a run: the toy engines and models
    # (``ModelConfig.tiny*``, ``llama3_1b(num_layers=...)``) are built in
    # dozens of files by six processes, and a fresh engine a case compiles
    # the programs the case before it compiled (a whole run with it: 5782 s
    # of cases; without: 6820, no case's result another; my runs, PR 62).
    # Made new by the run (its controller, or the one process of a run
    # without xdist) and removed at its end, so no entry ever answers for
    # another tree's code. Where the machine places JAX's cache itself
    # (dynamo_tpu/compile_cache.py's rule), no path is set in code
    if not os.environ.get(compile_cache.ENV_VAR):
        if hasattr(config, "workerinput"):
            path = config.workerinput["compile_cache"]
        else:
            path = config.stash[_CACHE_DIR] = tempfile.mkdtemp(
                prefix="tier1-jax-")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    config.addinivalue_line("markers", "asyncio: async test")
    config.addinivalue_line(
        "markers", "asyncio_timeout(seconds): override the 120s default"
    )
    config.addinivalue_line(
        "markers",
        "slow: needs a real multi-layer model / long wall time — "
        "excluded from the tier-1 run (-m 'not slow')",
    )


# The seconds each file took in a whole run by the driver's command
# (``tools/test_seconds.py`` writes the table from that run's junit file).
with open(os.path.join(os.path.dirname(__file__), "seconds.json")) as _f:
    SECONDS = json.load(_f)


def pytest_collection_modifyitems(items):
    """The driver's run (``-n 6 --dist loadfile``) hands FILES to its six
    workers in collection order, so the order is the longest file first:
    the wall is then the work over six, whatever files there are. A file
    the table does not know (a new one) starts first of all; the sort is
    stable, so nothing moves inside a file."""
    for item in items:
        if inspect.iscoroutinefunction(getattr(item, "function", None)):
            item.add_marker(pytest.mark.asyncio)
    items.sort(key=lambda item: -SECONDS.get(
        os.path.basename(str(item.fspath)), float("inf")))


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio support (pytest-asyncio may not be installed)."""
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        marker = pyfuncitem.get_closest_marker("asyncio_timeout")
        timeout = marker.args[0] if marker else 120
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=timeout))
        return True
    return None
