"""``DevicePrefetchIterator`` uploads a batch as its base iterator gave it
and narrows the floating features and labels on the device. What the loop
receives is what the host's cast gave: the same bits."""

import threading

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    DevicePrefetchIterator, ListDataSetIterator,
)
from deeplearning4j_tpu.profiling import CompileWatcher
from deeplearning4j_tpu.profiling.metrics import (
    MetricsRegistry, set_registry,
)
from deeplearning4j_tpu.profiling.tracer import Tracer, set_tracer

N = 3


def _batches(n=N, shape=(4, 6, 6, 3), seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=shape).astype(np.float32)
        # ties of the rounding to bfloat16: 1 + 2^-8 lies half way between
        # 1 and 1 + 2^-7 (to even: down), 1 + 3 * 2^-8 half way between
        # 1 + 2^-7 and 1 + 2^-6 (to even: up); and what overflows and
        # underflows a narrower exponent would
        x.flat[:6] = [1 + 2.0 ** -8, 1 + 3 * 2.0 ** -8, -1 - 2.0 ** -8,
                      3.0e38, 1.0e-40, 0.0]
        y = rng.uniform(size=(shape[0], 5)).astype(np.float32)
        out.append(DataSet(x, y, np.ones(shape[:1], np.float32),
                           np.arange(shape[0], dtype=np.int32)))
    return out


def _bits(a):
    return np.asarray(a).view(np.uint16)


def test_narrowed_on_the_device_to_the_bits_of_the_hosts_cast():
    batches = _batches()
    got = list(DevicePrefetchIterator(ListDataSetIterator(batches),
                                      dtype="bfloat16"))
    assert len(got) == N
    for ds, host in zip(got, batches):
        for leaf in (ds.features, ds.labels, ds.features_mask,
                     ds.labels_mask):
            assert isinstance(leaf, jax.Array)
        assert ds.features.dtype == ds.labels.dtype == jnp.bfloat16
        assert ds.features.shape == host.features.shape
        np.testing.assert_array_equal(_bits(ds.features), _bits(
            np.asarray(host.features).astype(ml_dtypes.bfloat16)))
        np.testing.assert_array_equal(_bits(ds.labels), _bits(
            np.asarray(host.labels).astype(ml_dtypes.bfloat16)))
        # masks keep their dtype, float or not
        assert ds.features_mask.dtype == np.float32
        assert ds.labels_mask.dtype == np.int32
        np.testing.assert_array_equal(ds.labels_mask, host.labels_mask)


def test_integer_features_and_missing_masks_pass_through():
    ds = DataSet(np.arange(12, dtype=np.int32).reshape(4, 3),
                 np.ones((4, 2), np.float32))
    (got,) = list(DevicePrefetchIterator(ListDataSetIterator([ds]),
                                         dtype="bfloat16"))
    assert got.features.dtype == np.int32
    np.testing.assert_array_equal(got.features, ds.features)
    assert got.labels.dtype == jnp.bfloat16
    assert got.features_mask is None and got.labels_mask is None


def test_float64_input_is_rounded_by_way_of_float32_as_the_host_did():
    """numpy's default float64 goes up as float32 (``device_put`` with x64
    off) and is rounded to bfloat16 there: two roundings. The host's cast
    made the same two (``ml_dtypes`` takes a double through float32), so
    the bits are the host's here too: 1 + 2^-8 + 2^-40 lies above the tie
    of bfloat16 and would round up alone; as float32 it is the tie and goes
    down to even, on either path."""
    x = np.random.default_rng(2).normal(size=(4, 6)).astype(np.float64)
    x.flat[0] = 1 + 2.0 ** -8 + 2.0 ** -40
    ds = DataSet(x, np.ones((4, 2)))
    (got,) = list(DevicePrefetchIterator(ListDataSetIterator([ds]),
                                         dtype="bfloat16"))
    assert got.features.dtype == got.labels.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(got.features),
                                  _bits(x.astype(ml_dtypes.bfloat16)))
    assert _bits(got.features).flat[0] == _bits(
        np.ones(1, ml_dtypes.bfloat16))[0]
    # without a dtype it is float32 on the device, as ever
    (plain,) = list(DevicePrefetchIterator(ListDataSetIterator([ds])))
    assert plain.features.dtype == np.float32


def test_a_leaf_that_is_a_list_goes_up_as_one_array():
    ds = DataSet([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]], [[1.0, 0.0], [0.0, 1.0]])
    (got,) = list(DevicePrefetchIterator(ListDataSetIterator([ds]),
                                         dtype="bfloat16"))
    assert isinstance(got.features, jax.Array)
    assert got.features.shape == (2, 3) and got.labels.shape == (2, 2)
    assert got.features.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got.features, np.float32),
                                  ds.features)


def test_without_a_dtype_the_batch_is_uploaded_untouched():
    batches = _batches()
    got = list(DevicePrefetchIterator(ListDataSetIterator(batches)))
    for ds, host in zip(got, batches):
        for name in ("features", "labels", "features_mask", "labels_mask"):
            leaf, want = getattr(ds, name), getattr(host, name)
            assert isinstance(leaf, jax.Array) and leaf.dtype == want.dtype
            np.testing.assert_array_equal(leaf, want)


@pytest.fixture
def recorded():
    tracer, registry = Tracer(), MetricsRegistry()
    prev_tracer, prev_registry = set_tracer(tracer), set_registry(registry)
    watcher = CompileWatcher(registry=registry, tracer=tracer).install()
    try:
        yield tracer, registry
    finally:
        watcher.uninstall()
        set_tracer(prev_tracer)
        set_registry(prev_registry)


def test_the_feeds_thread_still_records_the_cast(recorded):
    tracer, registry = recorded
    it = DevicePrefetchIterator(ListDataSetIterator(_batches()),
                                dtype="bfloat16")
    while it.has_next():        # one pass (``iter`` would start a second)
        it.next()
    events = tracer.export()["traceEvents"]
    by_id = {e["id"]: e for e in events}
    casts = [e for e in events if e["name"] == "input:cast"]
    uploads = [e for e in events if e["name"] == "input:h2d"]
    assert len(casts) == len(uploads) == N
    for cast, upload in zip(casts, uploads):
        assert by_id[cast["parent"]]["name"] == "input:produce"
        assert cast["parent"] == upload["parent"]
        assert cast["tid"] != threading.get_ident()
        # the convert is dispatched on what the upload returned
        assert upload["ts_ns"] + upload["dur_ns"] <= cast["ts_ns"]
    spans_s = sum(e["dur_ns"] for e in casts) / 1e9
    assert spans_s > 0
    assert abs(registry.counter("input_cast_seconds_total").value
               - spans_s) < 1e-6


def test_no_compilation_after_the_first_batch_of_a_shape(recorded):
    _, registry = recorded
    counts = lambda: (registry.counter("jax_compile_total").value,
                      registry.counter("jax_trace_total").value)
    shape = (4, 5, 5, 2)        # no other test narrows this shape
    list(DevicePrefetchIterator(ListDataSetIterator(_batches(1, shape)),
                                dtype="bfloat16"))
    first = counts()
    assert first[0] >= 1        # the convert of this shape compiled, once
    # more batches, and a new iterator over the same shapes: nothing new
    list(DevicePrefetchIterator(ListDataSetIterator(_batches(N, shape, 1)),
                                dtype="bfloat16"))
    it = DevicePrefetchIterator(ListDataSetIterator(_batches(N, shape, 2)),
                                dtype="bfloat16")
    list(it)
    list(it)                    # reset and a second epoch
    assert counts() == first
