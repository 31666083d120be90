"""The run's persistent compilation cache (``tests/conftest.py``): the
directory every test of the run compiles into is the run's own, not the
checkout's ``.jax_cache``, and a program compiled a second time from a
fresh ``jax.jit`` is read back from it."""

import os

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.profiling import CompileWatcher, MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_run_compiles_into_its_own_cache_and_reads_it_back():
    path = jax.config.jax_compilation_cache_dir
    assert path and path == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert not os.path.realpath(path).startswith(
        os.path.realpath(ROOT) + os.sep)        # not the checkout's

    def program():      # a new function each time: a new trace and compile
        return jax.jit(lambda x: jnp.cumsum(jnp.sin(x) * 3.0 + 1.0))

    x = jnp.arange(1000, dtype=jnp.float32)
    registry = MetricsRegistry()
    watcher = CompileWatcher(registry=registry).install()
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        hits = registry.counter("jax_compile_cache_hits_total")
        first = program()(x)
        read = hits.value
        second = program()(x)
        assert hits.value == read + 1
        assert os.listdir(path)         # the entry was written there
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before)
        watcher.uninstall()
    assert jnp.array_equal(first, second)
