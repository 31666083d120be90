"""Test config: force CPU with 8 virtual devices so multi-chip sharding
tests run anywhere (the driver separately dry-runs the real-TPU path) and
f64 gradient checks work (TPU has no f64).

One persistent compilation cache a run: the process that starts the run
makes a fresh directory, and its workers and every subprocess inherit it
through ``JAX_COMPILATION_CACHE_DIR``, so a program that costs a second or
more to compile is compiled once a run, not once a case. The directory is
never the checkout's ``.jax_cache`` and goes when the run ends, so no entry
outlives the code it was compiled from. Tests that need another setting
make their own: ``test_tpu_compile.py`` keeps its compiles out of the cache,
``test_chip_smoke.py`` gives its subprocess a directory of its own.

Each test's call is cut after ``TEST_LIMIT_S`` by ``SIGALRM``, so a test
that hangs fails alone (``pytest-timeout`` is not installed)."""

import os
import shutil
import signal
import tempfile
import threading

# Belt: env vars (effective if jax is not yet imported).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# the run's cache: made here by the process that starts the run, inherited
# by the rest, removed by the maker alone when the run ends
_RUN_CACHE = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _RUN_CACHE = tempfile.mkdtemp(prefix="jax-cache-tests-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE

# Braces: some pytest plugins import jax before conftest runs, in which case
# only a config update before backend initialization still works.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# four times the slowest case of a whole run on six workers
# (``test_chip_smoke.py``, some 220 s), rounded up
TEST_LIMIT_S = 900
# files whose tests wait on subprocesses under a ``timeout=`` of their own
# as long as TEST_LIMIT_S: each is left to that limit
OWN_LIMITS = frozenset({"test_benchmark_dry.py"})


def pytest_configure(config):
    assert jax.devices()[0].platform == "cpu", jax.devices()
    assert len(jax.devices()) == 8, jax.devices()


def pytest_unconfigure(config):
    if _RUN_CACHE is not None:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if (item.path.name in OWN_LIMITS
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def cut(signum, frame):
        pytest.fail(f"{item.nodeid} ran past the per-test limit of "
                    f"{TEST_LIMIT_S} s (tests/conftest.py)", pytrace=False)

    before = signal.signal(signal.SIGALRM, cut)
    signal.alarm(TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
