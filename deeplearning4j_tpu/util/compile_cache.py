"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles at full size
(``chip_smoke.py``, ``benchmark/run.py``): the cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says — JAX reads that variable
itself, so nothing is set in code — and otherwise at ``.jax_cache`` in the
checkout. The path is part of the cache key's neighbourhood (a directory
that moves never hits), so it is fixed: no tempfile, pid or time in it.

The serving engine's AOT ``jit(...).lower(...).compile()`` and the
in-memory ``keras.batching.CompileCache`` sit on top of this unchanged: an
executable they ask XLA for is looked up here first.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Point JAX at the persistent cache (call before the first jit);
    returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
