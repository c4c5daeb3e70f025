"""Test configuration: the tests run on the CPU.

jax is pinned to the CPU platform with 8 virtual devices
(xla_force_host_platform_device_count), so the multi-chip sharding tests
run on a simulated mesh.  The chip is exercised by ``chip_smoke.py``
through the chip tool; ``tests/test_tpu_compile.py`` compiles for a
described v5e chip without one.

The XLA flag must be in the environment before the CPU backend
initializes.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running soak tests")


import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Free compiled executables after each test module.

    The full suite compiles hundreds of XLA CPU programs in one
    process; with the r5 additions the accumulation started segfaulting
    the CPU compiler mid-suite (backend_compile_and_load SIGSEGV at
    ~50%, reproducible only under full-suite state — every test passes
    in isolation).  Dropping the in-process executable caches between
    modules bounds that state; the cost is re-compiling shared shapes a
    few times across the run."""
    yield
    import jax

    jax.clear_caches()
