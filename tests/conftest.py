"""Force a deterministic 8-device virtual CPU platform for all tests.

Multi-chip sharding tests run against a virtual CPU mesh
(xla_force_host_platform_device_count); the chip runs chip_smoke.py
(--devices 4 for a real mesh).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Entry points the suite drives in-process (cli.main, bench.main,
# chip_smoke) place the persistent compile cache at <checkout>/.jax_cache;
# tier-1 must not fill a directory inside the tree the chip tool copies.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running training/benchmark tests"
    )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables at module boundaries: with the full suite in
    one process, the accumulated compile state eventually segfaults XLA's CPU
    compiler inside a later (unrelated) jit compile — reproducible only with
    ~the whole suite's compile history, gone when any half runs alone. Costs
    some cross-module recompiles; keeps the 170-test process bounded."""
    yield
    jax.clear_caches()
