"""DataSet iterators.

Mirrors the reference's iterator stack: the ``DataSetIterator`` contract
(ND4J interface), ``AsyncDataSetIterator`` (background prefetch thread +
BlockingQueue — ref: deeplearning4j-nn/.../datasets/iterator/
AsyncDataSetIterator.java:33-75), and the adapters under
datasets/iterator/ (ListDataSetIterator, SamplingDataSetIterator,
MultipleEpochsIterator, ExistingDataSetIterator).

On TPU the async iterator's job is keeping the host→device feed ahead of the
step; ``fit()`` wraps any iterator in AsyncDataSetIterator exactly as
MultiLayerNetwork.fit does (ref: MultiLayerNetwork.java:951).

The async iterators record what both of their threads do, as spans of the
process's tracer and counters of its registry, under the ``input:*`` names
``datasets/pipeline.py`` has: the producer's ``input:produce`` (children
``input:read``, ``input:h2d``, ``input:cast``, ``input:put_wait``) and the
consumer's ``input:wait``. The k-th item produced and the k-th taken carry
``batch=k`` (the queue is FIFO); the item that ends the stream is one too.
"""

from __future__ import annotations

import functools
import itertools
import queue
import threading
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.profiling.metrics import get_registry
from deeplearning4j_tpu.profiling.tracer import get_tracer


class DataSetIterator:
    """Iterator contract (ref: ND4J DataSetIterator interface, incl.
    setPreProcessor — a DataSetPreProcessor applied to every emitted
    batch, e.g. the VGG16 mean-subtraction preprocessor)."""

    def reset(self) -> None:
        raise NotImplementedError

    def has_next(self) -> bool:
        raise NotImplementedError

    def next(self) -> DataSet:
        raise NotImplementedError

    def batch_size(self) -> int:
        raise NotImplementedError

    def total_examples(self) -> Optional[int]:
        return None

    def async_supported(self) -> bool:
        return True

    def set_pre_processor(self, pre_processor) -> "DataSetIterator":
        """(ref: DataSetIterator.setPreProcessor) ``pre_processor`` is a
        callable DataSet -> DataSet-or-None (None = mutated in place).

        Wraps this instance's ``next`` so EVERY consumption path applies
        it — direct ``next()`` calls, ``__next__``, and ``__iter__``."""
        self._pre_processor = pre_processor
        if not getattr(self, "_pp_wrapped", False):
            raw_next = self.next

            def wrapped() -> DataSet:
                ds = raw_next()
                pp = getattr(self, "_pre_processor", None)
                if pp is not None:
                    out = pp(ds)
                    ds = ds if out is None else out
                return ds

            self.next = wrapped  # instance attr shadows the class method
            self._pp_wrapped = True
        return self

    # Python iteration protocol
    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        while self.has_next():
            yield self.next()

    def __next__(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        return self.next()


class ListDataSetIterator(DataSetIterator):
    """Iterate over a pre-built list of minibatches
    (ref: datasets/iterator/impl/ListDataSetIterator.java)."""

    def __init__(self, batches: List[DataSet]):
        self._batches = list(batches)
        self._pos = 0

    @staticmethod
    def from_dataset(ds: DataSet, batch_size: int) -> "ListDataSetIterator":
        return ListDataSetIterator(ds.batch_by(batch_size))

    def reset(self):
        self._pos = 0

    def has_next(self):
        return self._pos < len(self._batches)

    def next(self):
        b = self._batches[self._pos]
        self._pos += 1
        return b

    def batch_size(self):
        return self._batches[0].num_examples() if self._batches else 0

    def total_examples(self):
        return sum(b.num_examples() for b in self._batches)


class ExistingDataSetIterator(DataSetIterator):
    """Wrap any Python iterable of DataSets
    (ref: datasets/iterator/ExistingDataSetIterator.java)."""

    def __init__(self, iterable):
        self._iterable = iterable
        self._it = None
        self._peek: Optional[DataSet] = None

    def reset(self):
        self._it = iter(self._iterable)
        self._peek = None

    def _ensure(self):
        if self._it is None:
            self.reset()
        if self._peek is None:
            try:
                self._peek = next(self._it)
            except StopIteration:
                self._peek = None

    def has_next(self):
        self._ensure()
        return self._peek is not None

    def next(self):
        self._ensure()
        if self._peek is None:
            raise StopIteration
        out, self._peek = self._peek, None
        return out

    def batch_size(self):
        return 0


class SamplingDataSetIterator(DataSetIterator):
    """Sample minibatches with replacement from a full DataSet
    (ref: datasets/iterator/SamplingDataSetIterator.java)."""

    def __init__(self, dataset: DataSet, batch_size: int, total_batches: int,
                 seed: int = 0):
        self._ds = dataset
        self._bs = batch_size
        self._total = total_batches
        self._count = 0
        self._rng = np.random.default_rng(seed)

    def reset(self):
        self._count = 0

    def has_next(self):
        return self._count < self._total

    def next(self):
        idx = self._rng.integers(0, self._ds.num_examples(), size=self._bs)
        self._count += 1
        return DataSet(self._ds.features[idx], self._ds.labels[idx])

    def batch_size(self):
        return self._bs


class MultipleEpochsIterator(DataSetIterator):
    """Repeat an underlying iterator for N epochs
    (ref: datasets/iterator/MultipleEpochsIterator.java)."""

    def __init__(self, epochs: int, base: DataSetIterator):
        self._epochs = epochs
        self._base = base
        self._epoch = 0

    def reset(self):
        self._epoch = 0
        self._base.reset()

    def has_next(self):
        if self._base.has_next():
            return True
        if self._epoch + 1 < self._epochs:
            self._epoch += 1
            self._base.reset()
            return self._base.has_next()
        return False

    def next(self):
        if not self.has_next():
            raise StopIteration
        return self._base.next()

    def batch_size(self):
        return self._base.batch_size()


class AsyncDataSetIterator(DataSetIterator):
    """Background prefetch thread + bounded queue
    (ref: AsyncDataSetIterator.java:33-75 — same structure: producer thread
    fills a BlockingQueue of size ``queue_size``; poison pill on exhaustion)."""

    def __init__(self, base: DataSetIterator, queue_size: int = 8):
        self._base = base
        self._queue_size = queue_size
        self._queue: "queue.Queue" = queue.Queue(maxsize=queue_size)
        self._thread: Optional[threading.Thread] = None
        self._peek = None  # ("data", ds) | ("error", exc) | ("end", None)
        self._done = False
        self._taken = 0    # items taken off the queue since the last start
        self._start()

    def _stage(self, ds: DataSet) -> DataSet:
        """What the producer does to a batch between reading and
        queueing it: nothing here."""
        return ds

    def _producer(self, q: "queue.Queue"):
        # In-order tagged items: already-produced batches are consumed before
        # an error is raised, and the stream always terminates cleanly.
        try:
            for k in itertools.count():
                tracer = get_tracer()
                with tracer.span("input:produce", batch=k):
                    with tracer.span("input:read"):
                        more = self._base.has_next()
                        ds = self._base.next() if more else None
                    item = ("data", self._stage(ds)) if more else ("end", None)
                    with tracer.span("input:put_wait") as put:
                        q.put(item)
                    get_registry().counter(
                        "input_backpressure_seconds_total",
                        help="producer seconds held back by a full "
                             "prefetch queue").inc(put.dur_ns / 1e9)
                if not more:
                    return
        except BaseException as e:  # surfaced, in order, on the consumer side
            q.put(("error", e))

    def async_supported(self) -> bool:
        return False    # already async — wrapping would double-thread

    def _start(self):
        self._done = False
        self._taken = 0
        self._thread = threading.Thread(target=self._producer,
                                        args=(self._queue,), daemon=True)
        self._thread.start()

    def reset(self):
        if self._thread is not None and self._thread.is_alive():
            # drain so the producer can exit; terminal item ends the stream
            while True:
                tag, _ = self._queue.get()
                if tag in ("end", "error"):
                    break
            self._thread.join()
        self._queue = queue.Queue(maxsize=self._queue_size)
        self._peek = None
        self._base.reset()
        self._start()

    def close(self):
        """Release the producer thread — it may be parked on a full
        queue — and join it. The iterator is exhausted afterwards; use
        reset() instead to start another epoch."""
        if self._thread is not None and self._thread.is_alive():
            # drain until the terminal item UNLESS it was already pulled
            # into _peek (then the producer is already exiting and the
            # queue may be empty — draining would block forever)
            if self._peek is None or self._peek[0] == "data":
                while True:
                    tag, _ = self._queue.get()
                    if tag in ("end", "error"):
                        break
            self._thread.join()
        self._thread = None
        self._peek = None
        self._done = True

    def _ensure(self):
        if self._peek is not None or self._done:
            return
        # the consumer's wait, measured and attributed: ready says whether
        # the item was there when the consumer came for it
        depth = self._queue.qsize()
        with get_tracer().span("input:wait", batch=self._taken,
                               ready=int(depth > 0), depth=depth) as wait:
            self._peek = self._queue.get()
        self._taken += 1
        reg = get_registry()
        reg.counter("input_stall_seconds_total",
                    help="consumer seconds blocked waiting on the input "
                         "pipeline (the chip-starvation measure)"
                    ).inc(wait.dur_ns / 1e9)
        if self._peek[0] == "data":
            reg.counter("input_batches_total",
                        help="batches emitted by the input pipeline").inc()
        if not depth:
            reg.counter("input_empty_takes_total",
                        help="takes that found the prefetch queue empty"
                        ).inc()

    def has_next(self):
        if self._done:
            return False
        self._ensure()
        tag, payload = self._peek
        if tag == "error":  # propagate instead of silently ending the epoch
            self._done = True
            raise payload
        return tag == "data"

    def next(self):
        if self._done:
            raise StopIteration
        self._ensure()
        tag, payload = self._peek
        if tag == "data":
            self._peek = None
            return payload
        # terminal item: mark exhausted so subsequent calls never block
        self._done = True
        if tag == "error":
            raise payload
        raise StopIteration

    def batch_size(self):
        return self._base.batch_size()


@functools.partial(jax.jit, static_argnames="dtype")
def _narrow_floats(arrays, dtype):
    """``arrays`` with every floating leaf converted to ``dtype``, other
    leaves as they are. One jit for the module: every
    ``DevicePrefetchIterator`` shares the compiled convert of a shape, so a
    new iterator over shapes already seen compiles nothing."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, arrays)


class DevicePrefetchIterator(AsyncDataSetIterator):
    """Async prefetch that also stages each batch in DEVICE memory (with
    optional dtype cast) from the producer thread — double-buffered
    host→device feed (SURVEY §7: "double-buffered device prefetch"; the
    reference's device-affinity prefetch is AsyncDataSetIterator.java:45
    + MagicQueue device buckets in ParallelWrapper).

    ``jax.device_put`` is asynchronous: the transfer overlaps the previous
    training step, so fit() sees device-resident arrays and the step time
    excludes the host-to-device transfer.

    ``dtype`` narrows the floating features and labels ON THE DEVICE: the
    batch goes up as the base iterator gave it and one jitted convert,
    dispatched from the producer thread behind the upload, rounds it to
    nearest even, as numpy's ``astype`` does: the host cast's bits, for
    float64 input too (``device_put`` makes it float32 first, x64 being
    off, and so did ``ml_dtypes`` on the host). Masks and integer leaves
    keep their dtype; ``dtype=None`` uploads untouched.

    Measured in one cell on one host (ResNet-50, 77 MB of float32 a batch,
    one v5e; PERF.md §6): the host's ``astype`` held the producer thread
    49.2 ms a batch and paced a 52.6 ms step; uploading the wide batch and
    converting there holds it 1.7 ms. The price: twice the bytes cross the
    host link, and each batch waits on the device in its WIDE dtype until
    the convert's turn comes behind the steps already queued, up to
    ``queue_size + 2`` of them (+0.7 to 1.1 GB of peak memory in that
    cell). Where the link is slower than the host's cast (a remote device)
    that is the wrong trade; no such link was measured.
    """

    def __init__(self, base: DataSetIterator, queue_size: int = 2,
                 dtype: Optional[str] = None, device=None):
        self._dtype = None if dtype is None else jnp.dtype(dtype)
        # device=None stages on the DEFAULT device UNCOMMITTED
        # (device_put with no target). An explicit device would commit the
        # arrays (SingleDeviceSharding in the jit cache key) while params
        # fresh from init() are uncommitted (UnspecifiedValue) — the first
        # step then compiles against the mixed signature and the SECOND
        # step, whose params come back committed, recompiles the whole
        # train step (~13s LeNet / ~60s ResNet-50 on a v5e, measured).
        # Pass a device only to pin a non-default chip.
        self._device = device
        super().__init__(base, queue_size=queue_size)

    def _stage(self, ds: DataSet) -> DataSet:
        tracer, reg = get_tracer(), get_registry()
        # a leaf that is no array (a list of rows) goes up as one array,
        # not as a pytree of scalars
        batch = tuple(a if a is None or isinstance(a, (np.ndarray, jax.Array))
                      else np.asarray(a) for a in (
                          ds.features, ds.labels, ds.features_mask,
                          ds.labels_mask))
        # device_put returns before the bytes have crossed: the span reads
        # the upload's dispatch (1 ms for 77 MB), not the transfer
        with tracer.span("input:h2d") as span:
            staged = (jax.device_put(batch) if self._device is None
                      else jax.device_put(batch, self._device))
        reg.counter("input_h2d_seconds_total",
                    help="wall seconds staging batches on device"
                    ).inc(span.dur_ns / 1e9)
        # the convert runs on the device behind the upload; this thread
        # only dispatches it, so the span reads next to nothing once the
        # shape has compiled
        with tracer.span("input:cast") as span:
            if self._dtype is not None:
                staged = _narrow_floats(staged[:2],
                                        dtype=self._dtype) + staged[2:]
        reg.counter("input_cast_seconds_total",
                    help="producer seconds narrowing batches (the dispatch "
                         "of the device's convert)").inc(span.dur_ns / 1e9)
        return DataSet(*staged)
