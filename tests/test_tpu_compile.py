"""The kernels of the main path compiled for a v5e that is described and
not attached (the TPU's compiler is installed here): what Mosaic refuses at
the real widths (a tile it cannot lay out, more VMEM than a kernel may
use, an operand type a precision does not take) fails in tier-1 and not on
the chip. Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never while a module is
imported: only the worker that is given this file loads the TPU's library.
Keep such tests in this one file."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops.pallas_attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize(
    "shape,dtype",
    [((1, 30, 8192, 128), jnp.bfloat16), ((1, 30, 8192, 128), jnp.float32),
     ((1, 2, 32768, 128), jnp.bfloat16), ((2, 2, 300, 64), jnp.bfloat16)],
    ids=["hybrid_cell-bfloat16", "hybrid_cell-float32", "gate_edge-bfloat16",
         "padded-bfloat16"])
def test_flash_kernels_compile_for_a_v5e(one_chip, no_compile_cache, shape,
                                         dtype, precision):
    """Forward (one kernel) and backward (three) at the hybrid cell's
    shape in both widths, at the longest bfloat16 sequence the VMEM gate
    takes, and at a padded one; under the ambient precision "highest"
    too, which Mosaic takes for float32 operands alone."""
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fwd = functools.partial(flash_attention, causal=True)
    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                   argnums=(0, 1, 2))
    with jax.default_matmul_precision(precision):
        for fn, kernels in ((fwd, 1), (bwd, 3)):
            text = jax.jit(fn).lower(x, x, x).compile().as_text()
            assert text.count("tpu_custom_call") == kernels
