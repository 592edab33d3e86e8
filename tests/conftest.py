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


def pytest_collection_modifyitems(items):
    for item in items:
        if inspect.iscoroutinefunction(getattr(item, "function", None)):
            item.add_marker(pytest.mark.asyncio)


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
