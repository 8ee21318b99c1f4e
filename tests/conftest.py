"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding paths are
exercised without TPU hardware — the same trick the reference uses for
"distributed without a cluster" (embedded Aeron MediaDriver + local[N] Spark;
SURVEY.md §4). Must set env vars before jax is imported anywhere.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

# Force CPU: tests need determinism, fp32 precision, and 8 virtual devices —
# also on a host that has a TPU.
os.environ["JAX_PLATFORMS"] = "cpu"
# LLVM's optimisation level for the HOST's code. Nobody runs this system on
# XLA:CPU: the suite is here to check arithmetic and control flow before a
# chip sees them, and no CPU timing stands as a result (PERF.md). Most of the
# suite's seconds are LLVM compiling programs that then run once, so the
# level that compiles fastest is the right one. Child processes inherit it
# through the environment (see ``child_env`` below).
XLA_CPU_OPT_LEVEL = "--xla_backend_optimization_level=0"

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    xla_flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in xla_flags:
    xla_flags += " " + XLA_CPU_OPT_LEVEL
os.environ["XLA_FLAGS"] = xla_flags.strip()
# x64 stays globally off (TPU-realistic dtypes); gradient checks get double
# precision locally via the jax.enable_x64() context manager in gradcheck.py.

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The files that take over 40 s by themselves, longest first (seconds in
# ROADMAP.md, "Tier-1"). ``--dist loadfile`` gives a whole file to one worker
# and, left alone, starts the files that hold the most tests first; these
# hold few, so they started last and one worker finished them alone while
# five idled (PR 27: 597 s of wall for 2,343 s of tests on six workers).
# Started first, the short files fill in behind them. A file that grows past
# a minute is split or shares its compiled programs before it is added here.
LONGEST_FILES = (
    "chipbench_tests/test_chipbench_run_train.py",
    "chipbench_tests/test_chipbench_reference.py",
    "test_import_corpus.py",
    "test_autotune.py",
    "test_kernels.py",
    "test_paged_decode.py",
    "test_elastic.py",
    "chipbench_tests/test_chipbench_run_serve.py",
    "test_op_coverage.py",
    "test_kimi_linear.py",
    "test_keras_import.py",
    "test_serving.py",
    "test_pipeline_fit.py",
    "chipbench_tests/test_chipbench_kimi.py",
    "test_compression.py",
    "test_zoo.py",
    "test_distributed.py",
    "test_prefix_cache.py",
)


def pytest_configure(config):
    # xdist would re-sort the files by their number of tests
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    if not hasattr(config, "workerinput"):
        # Build the native library (3 s, once per checkout) before any xdist
        # worker exists: test_native*.py ask for it at import, so six workers
        # raced to write one file while collecting, and the losers skipped
        # their 21 tests (PR 27's run at the parent, on a fresh clone).
        from deeplearning4j_tpu import native

        native.is_available()


def pytest_collection_modifyitems(items):
    here = os.path.dirname(os.path.abspath(__file__))
    rank = {os.path.join(here, f): i for i, f in enumerate(LONGEST_FILES)}
    items.sort(key=lambda item: rank.get(str(item.path), len(rank)))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def child_env():
    """``child_env(device_count=None)``: the environment for a child
    process that a test starts. This process's, with the repo importable and
    the parent's virtual-device count replaced (or dropped: one device), so
    that the rest of ``XLA_FLAGS`` — the optimisation level — reaches the
    child: a child that compiles at full level keeps the cost the parent
    shed."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def build(device_count=None):
        flags = [f for f in os.environ["XLA_FLAGS"].split()
                 if "xla_force_host_platform_device_count" not in f]
        if device_count:
            flags.append(
                f"--xla_force_host_platform_device_count={device_count}")
        path = os.pathsep.join(
            p for p in (repo, os.environ.get("PYTHONPATH")) if p)
        return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path,
                    XLA_FLAGS=" ".join(flags))

    return build


@pytest.fixture(scope="session")
def wait_until():
    """``wait_until(cond, timeout, what)``: poll until ``cond()`` holds. The
    event ends the wait; the deadline is only the failure."""

    def wait(cond, timeout, what):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, (
                f"{what}: not within {timeout} s")
            time.sleep(0.01)

    return wait


@pytest.fixture(scope="session")
def http_json():
    """``http_json(url, obj=None, request_id=None)``: POST ``obj`` as JSON
    (GET without one) and return (status, body, headers) whatever the
    status; the body parsed as JSON where it is JSON."""

    def call(url, obj=None, request_id=None, timeout=120):
        headers = {"Content-Type": "application/json"}
        if request_id is not None:
            headers["X-Request-Id"] = request_id
        req = urllib.request.Request(
            url, data=None if obj is None else json.dumps(obj).encode(),
            headers=headers)
        try:
            r = urllib.request.urlopen(req, timeout=timeout)
            raw, code, hdrs = r.read(), r.status, dict(r.headers)
        except urllib.error.HTTPError as e:
            raw, code, hdrs = e.read(), e.code, dict(e.headers)
        try:
            return code, json.loads(raw), hdrs
        except ValueError:
            return code, raw.decode(), hdrs

    return call


@pytest.fixture(scope="session")
def all_at_once():
    """``all_at_once(fn, args)``: ``fn(a)`` for every ``a`` of ``args``, each
    on a thread of its own and all in flight together; the results in the
    order of ``args``."""

    def run(fn, args, timeout=120):
        got = [None] * len(args)

        def fire(i):
            got[i] = fn(args[i])

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(args))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout)
        return got

    return run


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
