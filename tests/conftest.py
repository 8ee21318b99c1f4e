"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding paths are
exercised without TPU hardware — the same trick the reference uses for
"distributed without a cluster" (embedded Aeron MediaDriver + local[N] Spark;
SURVEY.md §4). Must set env vars before jax is imported anywhere.
"""

import os

# Force CPU: tests need determinism, fp32 precision, and 8 virtual devices —
# also on a host that has a TPU.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# x64 stays globally off (TPU-realistic dtypes); gradient checks get double
# precision locally via the jax.enable_x64() context manager in gradcheck.py.

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Bound the number of live compiled executables: a full-suite process
    accumulates ~1000 XLA:CPU executables, after which the compiler was
    observed to segfault on a trivial program (flaky, end-of-suite, not
    host OOM — 123 GB free at the time). Clearing per module keeps the
    working set small; per-module recompiles are already the norm since
    shapes differ between files."""
    yield
    jax.clear_caches()
