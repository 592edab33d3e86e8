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

import jax  # noqa: E402
import pytest  # noqa: E402

# XLA CPU dispatches f32 matmuls to reduced-precision paths by default;
# golden tests against torch need exact f32 accumulation.
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test")
    config.addinivalue_line(
        "markers", "asyncio_timeout(seconds): override the 120s default"
    )
    config.addinivalue_line(
        "markers",
        "slow: needs a real multi-layer model / long wall time — "
        "excluded from the tier-1 run (-m 'not slow')",
    )


# The files whose cases take minutes (160-690 s each in the builder's run
# of PR 50's tree; fourteen since PR 51), in two waves. The driver's tier-1 run (`-n 6 --dist
# loadfile`) hands FILES to its six workers in collection order, two to a
# worker at the start and one more whenever a worker finishes one. In the
# alphabet's order a long file that sorts late was the run's clock alone
# (test_tpu_lowering.py began ~320 s in and ran ~540 s more), and twelve
# long files in a row pair the longest two on one worker. So: the first
# wave of six, then six short files (each worker's second), then the second
# wave, then the rest in the alphabet's order: the long files start early
# and the many short ones fill in behind them (a simulation of the
# scheduler over the measured seconds ends within 5 % of the sum / 6; the
# alphabet's order, 55 % over). A stable sort: nothing moves inside a file.
# One rehearsal of whole cells a wave's worker, not three at once. A NEW
# LONG FILE joins the second wave.
LONG_FILES = (
    "test_tpu_lowering.py", "test_kda.py", "test_hybrid_live_rows.py",
    "test_rehearsal_chat_decode_longctx.py", "test_mla_moe.py",
    "test_ssm_moe.py",
    "test_spec.py", "test_rehearsal_longdoc_reasoning.py", "test_sala.py",
    "test_spec_tree.py", "test_prefill_live_rows.py",
    "test_rehearsal_ragdoc_longprompt.py",
    "test_mamba1.py", "test_rehearsal_chat_rate.py",   # PR 51: ~3 min each
    "test_sambay.py",                                  # PR 54: ~2.5 min
    "test_window_gqa_moe.py",                          # PR 58: ~75 s
    "test_ssm_groups_moe.py",                          # PR 60: ~80 s
)


def pytest_collection_modifyitems(items):
    for item in items:
        if inspect.iscoroutinefunction(getattr(item, "function", None)):
            item.add_marker(pytest.mark.asyncio)
    file_of = lambda item: os.path.basename(str(item.fspath))  # noqa: E731
    seen = list(dict.fromkeys(map(file_of, items)))
    long = [name for name in LONG_FILES if name in seen]
    rest = [name for name in seen if name not in LONG_FILES]
    order = long[:6] + rest[:6] + long[6:] + rest[6:]
    rank = {name: i for i, name in enumerate(order)}
    items.sort(key=lambda item: rank[file_of(item)])


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
