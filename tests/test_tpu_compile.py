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
from deeplearning4j_tpu.ops.pallas_delta_rule import (
    gdn_chunk_local, padded_chunks)
from deeplearning4j_tpu.ops.pallas_selective_scan import (
    selective_scan, selective_scan_ok)


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


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize(
    "shape,dtype",
    [((1, 30, 8192, 96, 192), jnp.bfloat16),
     ((1, 30, 8192, 96, 192), jnp.float32),
     ((1, 30, 8320, 96, 192), jnp.bfloat16), ((2, 2, 100, 8, 16), jnp.float32),
     ((1, 30, 2100, 96, 192), jnp.bfloat16),
     ((1, 30, 4200, 96, 192), jnp.float32)],
    ids=["hybrid_cell-bfloat16", "hybrid_cell-float32", "padded-bfloat16",
         "tiny-float32", "blocks_of_4-bfloat16", "blocks_of_8-float32"])
def test_delta_rule_kernels_compile_for_a_v5e(one_chip, no_compile_cache,
                                              shape, dtype, precision):
    """The chunk-local forward kernel and the backward one at the hybrid
    cell's shape with bfloat16 and with float32 operands, at a length that
    is padded to whole blocks of chunks (130 chunks run as 144), at the
    tests' own widths, and at lengths that run in blocks of 4 and of 8
    chunks (33 as 36, 66 as 72); under the ambient precision "highest" too (the
    inverse's float32 products name HIGHEST themselves, the bfloat16 ones
    the default)."""
    B, H, T, dk, dv = shape
    N = padded_chunks(-(-T // 64))
    arg = lambda last, dt: jax.ShapeDtypeStruct(
        (B, H, N, 64) + last, dt, sharding=one_chip)
    args = (arg((dk,), jnp.float32), arg((dk,), jnp.float32),
            arg((dv,), dtype), arg((), jnp.float32), arg((), jnp.float32))
    fwd = functools.partial(gdn_chunk_local, compute_dtype=dtype)
    bwd = jax.grad(lambda *a: sum(x.astype(jnp.float32).sum()
                                  for x in fwd(*a)), argnums=(0, 1, 2, 3, 4))
    with jax.default_matmul_precision(precision):
        for fn, name in ((fwd, "gdn_chunk_local_fwd"),
                         (bwd, "gdn_chunk_local_bwd")):
            text = jax.jit(fn).lower(*args).compile().as_text()
            assert text.count("tpu_custom_call") == 1 and name in text


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize(
    "heads,T,window,dtype",
    [(40, 8192, 512, jnp.bfloat16), (40, 8192, 512, jnp.float32),
     (40, 8192, None, jnp.bfloat16), (4, 1100, 512, jnp.bfloat16),
     (4, 8192, 1, jnp.bfloat16), (4, 8192, 700, jnp.float32)],
    ids=["sambay_window-bfloat16", "sambay_window-float32",
         "sambay_full-bfloat16", "padded_window-bfloat16",
         "window_of_one-bfloat16", "window_across_blocks-float32"])
def test_windowed_flash_kernels_compile_for_a_v5e(
        one_chip, no_compile_cache, heads, T, window, dtype, precision):
    """The flash kernels as a differential attention layer calls them: a
    key of 64 beside a value of 128, with the window's select and loop
    bounds (and without: the full and the cross layer), at the SambaY
    cell's shape in both widths, at a padded length, and at windows of one
    token and of no whole number of blocks."""
    qk = jax.ShapeDtypeStruct((1, heads, T, 64), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, heads, T, 128), dtype, sharding=one_chip)
    fwd = functools.partial(flash_attention, causal=True, window=window)
    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                   argnums=(0, 1, 2))
    with jax.default_matmul_precision(precision):
        for fn, kernels in ((fwd, 1), (bwd, 3)):
            text = jax.jit(fn).lower(qk, qk, v).compile().as_text()
            assert text.count("tpu_custom_call") == kernels


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize(
    "B,T,D,dtype",
    [(1, 256, 5120, jnp.bfloat16), (1, 300, 5120, jnp.bfloat16),
     (1, 256, 5120, jnp.float32), (2, 70, 1152, jnp.bfloat16)],
    ids=["sambay_cell-bfloat16", "padded-bfloat16", "sambay_cell-float32",
         "one_block_tiles_of_128-bfloat16"])
def test_selective_scan_kernels_compile_for_a_v5e(
        one_chip, no_compile_cache, B, T, D, dtype, precision):
    """The selective scan's forward kernel and its backward one at the
    SambaY cell's channel count (5,120 channels in tiles of 1,024, 16
    states, ``x`` in bfloat16 and in float32) over two time blocks (a
    kernel's body does not grow with the sequence, so the cell's 8,192
    tokens add nothing that Mosaic could refuse), at a length padded to
    three, and at a short one of a single block of 80 tokens whose 1,152
    channels run in tiles of 128; under the ambient precision "highest"
    too (the kernels multiply no matrices)."""
    N = 16
    assert selective_scan_ok(T, D, N, jnp.float32, dtype)
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    f32 = jnp.float32
    args = (arg((B, T, D), dtype), arg((B, T, D), f32), arg((N, D), f32),
            arg((B, T, N), f32), arg((B, T, N), f32))
    bwd = jax.grad(lambda *a: selective_scan(*a).sum(),
                   argnums=(0, 1, 2, 3, 4))
    with jax.default_matmul_precision(precision):
        for fn, names in ((selective_scan, ("selective_scan_fwd",)),
                          (bwd, ("selective_scan_fwd", "selective_scan_bwd"))):
            text = jax.jit(fn).lower(*args).compile().as_text()
            assert text.count("tpu_custom_call") == len(names)
            assert all(name in text for name in names)


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize(
    "heads,T,dtype",
    [(32, 8192, jnp.bfloat16), (32, 8192, jnp.float32),
     (4, 1100, jnp.bfloat16), (4, 300, jnp.float32)],
    ids=["keye_cell-bfloat16", "keye_cell-float32", "padded-bfloat16",
         "blocks_of_128-float32"])
def test_selected_flash_kernels_compile_for_a_v5e(
        one_chip, no_compile_cache, heads, T, dtype, precision):
    """The flash kernels under a selection (``select=``: int8 rows of
    ``[block, Tp]`` a grid step, the transpose for dk/dv) at the sparse
    cell's shape, 32 heads of 128 over 8,192 tokens, in both widths; at a
    padded length and in blocks of 128."""
    x = jax.ShapeDtypeStruct((1, heads, T, 128), dtype, sharding=one_chip)
    s = jax.ShapeDtypeStruct((1, T, T), jnp.int8, sharding=one_chip)
    fwd = lambda q, k, v, sel: flash_attention(q, k, v, causal=True,
                                               select=sel)
    bwd = jax.grad(lambda q, k, v, sel: fwd(q, k, v, sel).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))
    with jax.default_matmul_precision(precision):
        for fn, kernels in ((fwd, 1), (bwd, 3)):
            text = jax.jit(fn).lower(x, x, x, s).compile().as_text()
            assert text.count("tpu_custom_call") == kernels


def test_the_selection_compiles_for_a_v5e_without_a_sort(one_chip,
                                                          no_compile_cache):
    """``top_keys`` at the sparse cell's shape, a chunk of 512 queries over
    8,192 keys keeping 2,048: its threshold comes from the counting search,
    so the chip's program holds a loop, no sort and no kernel."""
    from deeplearning4j_tpu.nn.layers.attention import top_keys
    x = jax.ShapeDtypeStruct((1, 512, 8192), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda s: top_keys(s, 7680, 2048)).lower(
        x).compile().as_text()
    assert " while(" in text
    assert " sort(" not in text and "tpu_custom_call" not in text


def test_grouped_products_compile_for_a_v5e(one_chip, no_compile_cache):
    """The expert layer's grouped product at the sparse cell's shape:
    16,384 rows (twice a held share's) of 2,048 against sixteen experts'
    ``[2048, 768]``, forward and both transposes, as grouped-product custom
    calls and not as sixteen masked dense products."""
    x = jax.ShapeDtypeStruct((16384, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((16, 2048, 768), jnp.bfloat16,
                             sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    fwd = lambda x, w, s: jax.lax.ragged_dot(x, w, s)
    bwd = jax.grad(lambda x, w, s: fwd(x, w, s).astype(jnp.float32).sum(),
                   argnums=(0, 1))
    for fn, calls in ((fwd, 1), (bwd, 2)):
        compiled = jax.jit(fn).lower(x, w, sizes).compile()
        text = compiled.as_text()
        assert text.count("ragged-dot-none") + text.count(
            "ragged-dot-") >= calls, text[:2000]
        assert compiled.cost_analysis()["flops"] < 1.5 * calls * (
            2 * 16384 * 2048 * 768)
