"""Continuous-batching scheduler for the Keras gateway.

KerasServer used to dispatch one request = one compiled call, so
concurrent predicts on one model serialized on the per-model op lock
and serving throughput was bounded by single-request latency — and
every new input shape paid a recompile. This module is the serving-edge
analog of the µ-cuDNN micro-batching trick (arXiv 1804.04806): predict
requests admitted for the same model land in a per-model queue, a
dispatcher thread coalesces them into padded, shape-bucketed batches,
executes ONE ahead-of-time-compiled step per bucket, and splits the
result back to per-request futures with the padding rows dropped.

Batching discipline:

- **Bucket** = next power-of-two row count up to ``max_batch`` (the
  "precompile the shapes you'll actually run" discipline of arXiv
  1410.0759); the non-batch feature shape and dtype are exact-matched —
  only same-shaped requests coalesce. A request larger than
  ``max_batch`` runs alone in its own (still cached) bucket.
- **AOT compile cache**: one compiled executable per (model, bucket,
  feature-shape) triple via ``jit(infer).lower(...).compile()`` —
  params/states stay arguments, so fit updates never invalidate the
  executable. The cache is keyed like the server's LRU model cache and
  evicted with it (``evict_model``). Per-request recompiles are dead:
  after warmup, a wave of identical-bucket requests adds zero traces.
- **Deadline-aware flush**: a batch flushes when it is full
  (``reason=full``), when a member's ``deadline_ms`` budget is nearly
  spent (``reason=deadline`` — the margin covers dispatch), or when
  ``max_wait_ms`` elapses at low load (``reason=idle``), so worst-case
  added latency is bounded.
- **Per-row nonfinite guard**: the sentinel check runs per request,
  not per batch — one poisoned request gets ``NONFINITE`` alone; its
  batchmates are served. A *batch-level* execution failure falls back
  to singleton re-execution before any request surfaces an error, so
  the circuit breaker is only charged for requests that fail alone.

Everything is observable: ``serving_batch_size`` histogram,
``serving_batched_requests_total`` / ``serving_batch_flushes_total``
(by flush reason) / ``serving_batch_fallbacks_total`` counters,
``serving_compile_seconds_total``, p50/p99 predict-latency gauges, and
``serve:batch`` tracer spans.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.profiling.metrics import get_registry
from deeplearning4j_tpu.profiling.tracer import get_tracer
from deeplearning4j_tpu.resilience import faultinject
from deeplearning4j_tpu.resilience.service import (Deadline,
                                                   DeadlineExceeded,
                                                   DrainingError,
                                                   NonFiniteOutput)
from deeplearning4j_tpu.util.math_utils import next_pow_of_2

# row-count edges for the serving_batch_size histogram (requests per
# executed batch — NOT seconds, hence not DEFAULT_TIME_BUCKETS)
BATCH_SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

# sub-second-focused edges for predict latency (the default time
# buckets are compile-scale and would put every predict in one bucket)
PREDICT_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                           0.1, 0.25, 0.5, 1.0, 2.5, 10.0)

FLUSH_REASONS = ("full", "deadline", "idle")

#: priority classes for the batch queue (ISSUE 15): an INTERACTIVE
#: request is inserted ahead of every queued BULK request, so a latency-
#: sensitive predict/generate never waits behind a bulk scorer's
#: backlog. Ordering is stable within a class (FIFO).
PRIORITIES = {"interactive": 0, "bulk": 1}


def priority_rank(priority: str) -> int:
    try:
        return PRIORITIES[priority]
    except KeyError:
        raise ValueError(f"unknown priority {priority!r}; "
                         f"one of {tuple(PRIORITIES)}") from None


def priority_insert(queue, item, *, front_of_class: bool = False) -> None:
    """Insert ``item`` (anything with a ``priority`` rank) into a
    priority-ordered deque: ahead of every lower-priority entry, FIFO
    within its class — the ONE insert discipline both batch queues
    (predict and generate) share. ``front_of_class`` puts the item
    ahead of its own class too (an evicted victim that already waited
    its turn)."""
    if front_of_class:
        idx = next((i for i, q in enumerate(queue)
                    if q.priority >= item.priority), len(queue))
        queue.insert(idx, item)
        return
    if queue and queue[-1].priority > item.priority:
        idx = next(i for i, q in enumerate(queue)
                   if q.priority > item.priority)
        queue.insert(idx, item)
    else:
        queue.append(item)


class CompileCache:
    """Cross-model AOT compile cache with a GLOBAL entry/bytes budget
    (ISSUE 15 satellite). PR 6 cached one compiled executable per
    (model, bucket, shape) with no bound except the model LRU — a
    gateway serving many models with ragged traffic could accumulate
    executables without limit. This cache is shared by every scheduler
    in the process (predict buckets AND generation prefill/decode
    buckets): entries are LRU-ordered across models, the budget counts
    entries and compiled bytes (XLA's own memory analysis where the
    backend reports it), and evictions land in
    ``serving_compile_cache_evictions_total``. A model evicted from the
    server LRU still drops all of its entries at once
    (``evict_model`` — the cache is evicted WITH the model cache)."""

    def __init__(self, max_entries: int = 128,
                 max_bytes: Optional[int] = 512 * 1024 * 1024):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.max_entries = int(max_entries)
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._entries = collections.OrderedDict()  # key -> (value, nbytes)
        self._bytes = 0

    @staticmethod
    def compiled_nbytes(compiled) -> int:
        """Budget-relevant footprint of one XLA executable: generated
        code + scratch. Backends without memory analysis cost 0 bytes
        (the entry budget still bounds them)."""
        try:
            ma = compiled.memory_analysis()
            return int(getattr(ma, "generated_code_size_in_bytes", 0)
                       + getattr(ma, "temp_size_in_bytes", 0))
        except Exception:  # noqa: BLE001 — sizing is best-effort
            return 0

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            return entry[0]

    def put(self, key, value, nbytes: int = 0) -> None:
        evicted = 0
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._entries[key] = (value, int(nbytes))
            self._bytes += int(nbytes)
            while len(self._entries) > 1 and (
                    len(self._entries) > self.max_entries
                    or (self.max_bytes is not None
                        and self._bytes > self.max_bytes)):
                _, (_, nb) = self._entries.popitem(last=False)
                self._bytes -= nb
                evicted += 1
            self._publish_locked()
        if evicted:
            get_registry().counter(
                "serving_compile_cache_evictions_total",
                help="AOT-compiled steps evicted by the cross-model "
                     "compile-cache budget").inc(evicted)

    def _publish_locked(self) -> None:
        reg = get_registry()
        reg.gauge("serving_compile_cache_entries",
                  help="AOT-compiled steps resident in the cross-model "
                       "compile cache").set(len(self._entries))
        reg.gauge("serving_compile_cache_bytes",
                  help="estimated compiled bytes resident in the "
                       "cross-model compile cache").set(self._bytes)

    def remove(self, key) -> None:
        """Drop one entry (a put that lost a race with eviction)."""
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
                self._publish_locked()

    def evict_model(self, owner: int, model_key: str) -> None:
        """Drop every entry one scheduler cached for one model key —
        called when the server LRU evicts the model."""
        with self._lock:
            for k in [k for k in self._entries
                      if k[0] == owner and k[1] == model_key]:
                self._bytes -= self._entries.pop(k)[1]
            self._publish_locked()

    def evict_owner(self, owner: int) -> None:
        """Drop every entry a (stopped) scheduler owns — owner serials
        are never reused, so a dead scheduler's executables would
        otherwise sit in the GLOBAL cache until the budget pushes them
        out."""
        with self._lock:
            for k in [k for k in self._entries if k[0] == owner]:
                self._bytes -= self._entries.pop(k)[1]
            self._publish_locked()

    def keys(self) -> List[tuple]:
        with self._lock:
            return list(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes}


_compile_cache_lock = threading.Lock()
_compile_cache: Optional[CompileCache] = None
_owner_serial = 0


def next_cache_owner() -> int:
    """Monotonic owner id for compile-cache keys. ``id(scheduler)``
    would be reused after garbage collection, letting a new scheduler
    hit a dead scheduler's stale executables (compiled against another
    model's shapes)."""
    global _owner_serial
    with _compile_cache_lock:
        _owner_serial += 1
        return _owner_serial


def get_compile_cache() -> CompileCache:
    """The process-global compile cache every scheduler shares — ONE
    budget across models, buckets, and predict/generate kinds."""
    global _compile_cache
    with _compile_cache_lock:
        if _compile_cache is None:
            _compile_cache = CompileCache()
        return _compile_cache


def set_compile_cache(cache: Optional[CompileCache]
                      ) -> Optional[CompileCache]:
    """Swap the global cache (tests / budget reconfiguration); returns
    the previous one."""
    global _compile_cache
    with _compile_cache_lock:
        prev, _compile_cache = _compile_cache, cache
        return prev


def bucket_rows(rows: int) -> int:
    """The padded row count for a ``rows``-row batch: the next power of
    two. The scheduler caps COALESCED rows at ``max_batch`` before
    calling (max_batch is normalized to a power of two, so coalesced
    buckets never exceed it); a single oversize request gets its own
    larger pow2 bucket — it can never coalesce, but its compile is
    still cached."""
    if rows < 1:
        raise ValueError(f"rows must be >= 1, got {rows}")
    return next_pow_of_2(rows)


def _pow2_floor(n: int) -> int:
    p = next_pow_of_2(n)
    return p if p == n else p >> 1


def quantile(ordered, q: float) -> float:
    """Nearest-rank quantile of an already-sorted sequence — the ONE
    convention the p50/p99 gauges and ``stats()`` share."""
    return ordered[min(len(ordered) - 1, int(len(ordered) * q))]


class _Pending:
    """One queued predict: the request's features, its deadline, and the
    future (event + result/error) its handler thread waits on."""

    __slots__ = ("features", "deadline", "event", "result", "error",
                 "rows", "shape_key", "t0", "priority")

    def __init__(self, features: np.ndarray, deadline: Deadline,
                 priority: int = 0):
        self.features = features
        self.deadline = deadline
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.rows = int(features.shape[0])
        # only exact non-batch shape + dtype matches may share a batch
        self.shape_key = (tuple(features.shape[1:]), str(features.dtype))
        self.t0 = time.monotonic()
        self.priority = priority


class _LatencyWindow:
    """Bounded reservoir of recent latencies; publishes p50/p99 gauges
    on every observation (a scrape of ``/api/metrics`` sees the current
    quantiles without histogram interpolation). The metric family is
    parameterized so the generation scheduler's TTFT window shares the
    machinery (``serving_ttft_*``) with the predict window."""

    # republish the gauges every Nth observation: a per-request sort of
    # the whole reservoir would serialize the serving hot path for
    # quantiles that only matter at scrape cadence
    REFRESH_EVERY = 16

    def __init__(self, maxlen: int = 1024,
                 hist_name: str = "serving_predict_seconds",
                 hist_help: str = "end-to-end predict latency "
                                  "(admission to response), successful "
                                  "requests",
                 gauge_prefix: str = "serving_predict",
                 gauge_what: str = "predict latency"):
        self._lock = threading.Lock()
        self._window = collections.deque(maxlen=maxlen)
        self._since_refresh = 0
        self._hist_name = hist_name
        self._hist_help = hist_help
        self._gauge_prefix = gauge_prefix
        self._gauge_what = gauge_what

    def observe(self, seconds: float) -> None:
        get_registry().histogram(
            self._hist_name, help=self._hist_help,
            buckets=PREDICT_LATENCY_BUCKETS).observe(seconds)
        with self._lock:
            self._window.append(seconds)
            self._since_refresh += 1
            refresh = (self._since_refresh >= self.REFRESH_EVERY
                       or len(self._window) == 1)
            if refresh:
                self._since_refresh = 0
        if refresh:
            self._publish(*self.quantiles())

    def _publish(self, p50: float, p99: float) -> None:
        reg = get_registry()
        reg.gauge(f"{self._gauge_prefix}_p50_ms",
                  help=f"median {self._gauge_what} over the recent "
                       "window (ms)").set(p50 * 1000.0)
        reg.gauge(f"{self._gauge_prefix}_p99_ms",
                  help=f"p99 {self._gauge_what} over the recent window "
                       "(ms)").set(p99 * 1000.0)

    def quantiles(self) -> Tuple[Optional[float], Optional[float]]:
        with self._lock:
            if not self._window:
                return None, None
            ordered = sorted(self._window)
        return quantile(ordered, 0.5), quantile(ordered, 0.99)


class BatchScheduler:
    """Per-server continuous-batching engine. ``submit()`` is called by
    an admitted handler thread (holding its ServiceGuard slot) and
    blocks until the request's rows come back; a per-model dispatcher
    thread forms and executes the batches. The caller resolves the
    model key ONCE at admission and threads it through — eviction or an
    LRU swap can never retarget a queued request."""

    def __init__(self, max_batch: int = 32, max_wait_ms: float = 5.0,
                 deadline_margin_ms: float = 50.0,
                 idle_thread_s: float = 30.0,
                 compile_cache: Optional[CompileCache] = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        # buckets are powers of two "up to max_batch": normalize down so
        # no bucket ever exceeds the configured cap
        self.max_batch = _pow2_floor(int(max_batch))
        self.max_wait_s = max(0.0, float(max_wait_ms)) / 1000.0
        self.deadline_margin_s = max(0.0, float(deadline_margin_ms)) / 1000.0
        self.idle_thread_s = idle_thread_s
        self._cond = threading.Condition()
        self._queues: Dict[str, collections.deque] = {}
        self._backends: Dict[str, tuple] = {}  # key -> (model, lock)
        self._dispatchers: Dict[str, threading.Thread] = {}
        # compiled steps live in the budgeted CROSS-MODEL cache (global
        # by default): per-scheduler keys, one process-wide budget
        self._compiled = (compile_cache if compile_cache is not None
                          else get_compile_cache())
        self._cache_owner = next_cache_owner()
        # observed request-size mix: (shape_key, bucket) -> batches
        # executed — the speculative-prewarm signal
        self._bucket_mix: collections.Counter = collections.Counter()
        self._stopping = False
        # per-scheduler stats (/api/metrics has the process-global ones)
        self._stats_lock = threading.Lock()
        self.compile_s = 0.0
        self._batch_sizes: collections.Counter = collections.Counter()
        self.latency = _LatencyWindow()

    # ------------------------------------------------------------- metrics
    @staticmethod
    def _flush_counter(reason: str):
        return get_registry().labeled_counter(
            "serving_batch_flushes_total",
            help="batches dispatched, by flush reason").labels(
                reason=reason)

    # -------------------------------------------------------------- submit
    def submit(self, key: str, model, lock: threading.Lock,
               features: np.ndarray, deadline: Deadline,
               priority: str = "interactive") -> np.ndarray:
        """Queue one predict for ``key`` and block until its rows are
        back. Raises the request's own structured error (DEADLINE /
        NONFINITE / the singleton re-execution's failure).
        ``priority``: queue class — an ``interactive`` request is
        inserted ahead of every queued ``bulk`` request."""
        features = np.asarray(features)
        if features.ndim < 1 or features.shape[0] < 1:
            raise ValueError(
                f"predict features must have a leading batch axis with "
                f">= 1 rows, got shape {features.shape}")
        deadline.check("predict enqueue")
        pending = _Pending(features, deadline, priority_rank(priority))
        with self._cond:
            if self._stopping:
                raise DrainingError("batch scheduler stopped")
            # the model/lock pair travels with the KEY, pinned by the
            # caller for the life of this op: a cache swap mid-queue
            # cannot retarget the request
            self._backends[key] = (model, lock)
            queue = self._queues.setdefault(key, collections.deque())
            priority_insert(queue, pending)
            worker = self._dispatchers.get(key)
            if worker is None or not worker.is_alive():
                worker = threading.Thread(
                    target=self._dispatch_loop, args=(key,), daemon=True,
                    name=f"batch-dispatch-{len(self._dispatchers)}")
                self._dispatchers[key] = worker
                worker.start()
            self._cond.notify_all()
        while not pending.event.is_set():
            remaining = deadline.remaining()
            timeout = 5.0 if remaining is None else max(0.0,
                                                        remaining) + 0.05
            if pending.event.wait(timeout):
                break
            # budget gone while the batch is still in flight: report
            # DEADLINE now; the dispatcher completes (and discards) the
            # orphan later. No-deadline requests loop until completion.
            deadline.check("predict batched dispatch")
        if pending.error is not None:
            raise pending.error
        if pending.result is None:  # stop() raced the wait
            raise DrainingError("batch scheduler stopped")
        return pending.result

    # ----------------------------------------------------------- dispatcher
    def _dispatch_loop(self, key: str) -> None:
        idle_until = time.monotonic() + self.idle_thread_s
        while True:
            with self._cond:
                queue = self._queues.get(key)
                while not self._stopping and not queue:
                    left = idle_until - time.monotonic()
                    if left <= 0:
                        # nothing queued for a while: retire the thread
                        # and its empty queue (a later submit recreates
                        # both — without this a long-lived server leaks
                        # a deque per model key ever served)
                        if (self._dispatchers.get(key)
                                is threading.current_thread()):
                            del self._dispatchers[key]
                            if not self._queues.get(key):
                                self._queues.pop(key, None)
                        return
                    self._cond.wait(left)
                    queue = self._queues.get(key)
                if self._stopping:
                    # queue may be None here: stop() can race the idle
                    # retirement above (another pass popped the deque
                    # between our wait and this re-fetch)
                    for p in (queue or ()):
                        p.error = DrainingError("batch scheduler stopped")
                        p.event.set()
                    if queue is not None:
                        queue.clear()
                    return
                batch, reason = self._form_batch(queue)
            try:
                self._execute(key, batch, reason)
            except Exception as e:  # noqa: BLE001 — the dispatcher must
                # survive anything: a dead dispatcher would strand every
                # queued request behind a still-alive-looking thread
                for p in batch:
                    if not p.event.is_set():
                        p.error = e
                        p.event.set()
            idle_until = time.monotonic() + self.idle_thread_s

    def _form_batch(self, queue) -> Tuple[List[_Pending], str]:
        """Collect one flushable batch from ``queue`` (held lock).
        Blocks on the condition while the flush conditions say wait."""
        while True:
            head = queue[0]
            batch, rows = [], 0
            for p in queue:
                if p.shape_key != head.shape_key:
                    continue  # different feature shape: a later batch
                if batch and rows + p.rows > self.max_batch:
                    break  # bucket capacity; an oversize HEAD runs alone
                batch.append(p)
                rows += p.rows
            if rows >= self.max_batch:
                reason = "full"
            else:
                now = time.monotonic()
                wait_idle = (head.t0 + self.max_wait_s) - now
                wait_deadline = float("inf")
                for p in batch:
                    remaining = p.deadline.remaining()
                    if remaining is not None:
                        wait_deadline = min(
                            wait_deadline,
                            remaining - self.deadline_margin_s)
                wait = min(wait_idle, wait_deadline)
                if wait > 0:
                    self._cond.wait(wait)
                    if self._stopping:
                        # the outer loop fails the queue; flush nothing
                        return [], "idle"
                    continue  # re-collect: new arrivals may have landed
                reason = "deadline" if wait_deadline < wait_idle else "idle"
            for p in batch:
                queue.remove(p)
            return batch, reason

    # ------------------------------------------------------------ execution
    def _execute(self, key: str, batch: List[_Pending],
                 reason: str) -> None:
        # members whose WHOLE budget is already gone get DEADLINE
        # without paying for execution (their submitters have raised
        # and left — running the step would burn exactly the backend
        # capacity an overloaded server needs to recover). No counter
        # here: the submitter's own deadline.check already counted.
        live = []
        for p in batch:
            if p.deadline.expired():
                p.error = DeadlineExceeded("predict: batch member "
                                           "expired before dispatch")
                p.event.set()
            else:
                live.append(p)
        batch = live
        if not batch:
            return
        with self._cond:
            backend = self._backends.get(key)
        if backend is None:
            # every queued request pins its model, so a missing backend
            # means only orphans remained and the LRU moved on — fail
            # them cleanly instead of KeyError-ing the dispatcher
            for p in batch:
                p.error = DrainingError(f"model {key!r} evicted with "
                                        "only abandoned requests queued")
                p.event.set()
            return
        model, lock = backend
        rows = sum(p.rows for p in batch)
        bucket = bucket_rows(rows)
        shape_key = batch[0].shape_key
        tracer = get_tracer()
        with tracer.span("serve:batch", model=key, size=len(batch),
                         rows=rows, bucket=bucket, reason=reason):
            # slow_batch chaos seam: stall THIS batch (outside every
            # lock — a stalled batch must not freeze the scheduler)
            faultinject.on_batch_dispatch(key)
            x = np.concatenate([p.features for p in batch], axis=0)
            if bucket > rows:
                pad = np.zeros((bucket - rows,) + x.shape[1:], x.dtype)
                x = np.concatenate([x, pad], axis=0)
            try:
                runner = self._runner(key, model, bucket, shape_key)
                with lock:  # predict and fit on one model never interleave
                    y = np.asarray(runner(model, x))[:rows]
            except Exception:  # noqa: BLE001 — isolate batchmates
                # batch-level failure (compile error, backend fault):
                # re-execute each request ALONE before surfacing
                # anything — only a request that fails by itself may
                # charge the caller's circuit breaker
                get_registry().counter(
                    "serving_batch_fallbacks_total",
                    help="batches that fell back to singleton "
                         "re-execution after a batch-level failure").inc()
                self._singleton_fallback(model, lock, batch)
                self._account(batch, reason)
                return
            offset = 0
            for p in batch:
                self._finish_rows(p, y[offset:offset + p.rows])
                offset += p.rows
        self._account(batch, reason)

    def _singleton_fallback(self, model, lock,
                            batch: List[_Pending]) -> None:
        for p in batch:
            try:
                with lock:
                    y = np.asarray(model.output(p.features))
                self._finish_rows(p, y)
            except Exception as e:  # noqa: BLE001 — per-request verdict
                p.error = e
                p.event.set()

    def _finish_rows(self, p: _Pending, y: np.ndarray) -> None:
        """Per-ROW sentinel: a poisoned request fails alone — its
        batchmates' rows are served."""
        from deeplearning4j_tpu.resilience.sentinel import host_nonfinite
        if host_nonfinite(y):
            get_registry().counter(
                "serving_nonfinite_outputs_total",
                help="predictions refused because the model output "
                     "carried NaN/Inf").inc()
            p.error = NonFiniteOutput("prediction contains NaN/Inf")
        else:
            p.result = y
        p.event.set()

    def _account(self, batch: List[_Pending], reason: str) -> None:
        reg = get_registry()
        reg.histogram("serving_batch_size",
                      help="requests coalesced per executed batch",
                      buckets=BATCH_SIZE_BUCKETS).observe(len(batch))
        reg.counter("serving_batched_requests_total",
                    help="predict requests served through the "
                         "batching scheduler").inc(len(batch))
        self._flush_counter(reason).inc()
        with self._stats_lock:
            self._batch_sizes[len(batch)] += 1
            rows = sum(p.rows for p in batch)
            self._bucket_mix[(batch[0].shape_key,
                              bucket_rows(rows))] += 1

    # ------------------------------------------------------- compile cache
    def _runner(self, key: str, model, bucket: int, shape_key):
        """The AOT-compiled step for (model key, bucket, feature shape)
        — compiled once, reused until the model is evicted. Runners
        take ``(model, x)``: the executable binds only SHAPES, never a
        model object, so a fit or an evict-and-reload of the same key
        can never serve stale weights from a cache hit. Falls back to
        the model's own jitted ``output`` when the container exposes no
        AOT seam (jit still caches per shape: one trace per bucket)."""
        cache_key = (self._cache_owner, key, bucket, shape_key)
        runner = self._compiled.get(cache_key)
        if runner is not None:
            return runner
        t0 = time.perf_counter()
        runner, nbytes = self._aot_compile(model, bucket, shape_key)
        if runner is None:
            runner, nbytes = (lambda m, x: m.output(x)), 0  # noqa: E731
        elapsed = time.perf_counter() - t0
        get_registry().counter(
            "serving_compile_seconds_total",
            help="seconds spent AOT-compiling per-bucket predict "
                 "steps").inc(elapsed)
        with self._stats_lock:
            self.compile_s += elapsed
        with self._cond:
            current = self._backends.get(key)
            if current is not None and current[0] is model:
                # put UNDER the cond: an evict_model racing between the
                # check and the put could otherwise land a stale
                # executable for a gone model (the cache's own lock is
                # a leaf — no path nests it around the cond)
                self._compiled.put(cache_key, runner, nbytes)
            # else: the key was evicted (or swapped to a fresh load)
            # while we compiled — serve this batch with the uncached
            # runner and let the next batch compile against the
            # current object, rather than caching for a gone model
        return runner

    @staticmethod
    def _aot_compile(model, bucket: int, shape_key):
        """``jit(infer).lower(spec).compile()`` against the container's
        cached jitted inference forward; params/states remain call
        arguments so fit updates keep the executable valid. Returns
        ``(runner, compiled_bytes)`` — the bytes charge the cross-model
        compile-cache budget."""
        import jax

        shape, dtype = shape_key
        spec = jax.ShapeDtypeStruct((bucket,) + tuple(shape), dtype)
        try:
            jitted = model._infer_fn()
            if hasattr(model, "layers"):  # MultiLayerNetwork
                compiled = jitted.lower(model.params, model.states,
                                        spec, None).compile()
                return (lambda m, x: compiled(m.params, m.states,
                                              x, None),
                        CompileCache.compiled_nbytes(compiled))
            # ComputationGraph: dict input map, list of outputs
            name = model.conf.network_inputs[0]
            compiled = jitted.lower(model.params, model.states,
                                    {name: spec}, None).compile()
            return (lambda m, x: compiled(m.params, m.states,
                                          {name: x}, None)[0],
                    CompileCache.compiled_nbytes(compiled))
        except Exception:  # noqa: BLE001 — AOT is an optimization
            return None, 0

    # ----------------------------------------------------------- prewarming
    def prewarm(self, key: str, model, top: int = 4) -> int:
        """Speculatively AOT-compile the ``top`` most-observed
        (feature shape, bucket) combinations for a freshly loaded
        model, so the first real wave against it pays zero compiles.
        The signal is the scheduler's OBSERVED request-size mix across
        every model it has served (traffic shape is a gateway property,
        not a model property). Returns the number of buckets compiled;
        call from a background thread — compiles are slow."""
        with self._stats_lock:
            mix = self._bucket_mix.most_common()
        done = 0
        # pin the backend so _runner may cache against it — but
        # remember OUR insertion: if the server LRU evicts this model
        # while we compile and no request re-registers it, the pin
        # must come back out or the dead model object leaks in
        # _backends forever
        pin = (model, threading.Lock())
        with self._cond:
            if self._stopping:
                return 0
            pinned = key not in self._backends
            if pinned:
                self._backends[key] = pin
        try:
            for (shape_key, bucket), _ in mix:
                if done >= top:
                    break
                cache_key = (self._cache_owner, key, bucket, shape_key)
                if self._compiled.get(cache_key) is not None:
                    continue
                with self._cond:
                    if self._stopping:
                        break
                try:
                    self._runner(key, model, bucket, shape_key)
                except Exception:  # noqa: BLE001 — speculative
                    continue
                done += 1
        finally:
            if pinned:
                with self._cond:
                    if (self._backends.get(key) is pin
                            and not self._queues.get(key)):
                        self._backends.pop(key)
        if done:
            get_registry().counter(
                "serving_prewarmed_buckets_total",
                help="AOT buckets compiled speculatively from the "
                     "observed request-size mix").inc(done)
        return done

    # ------------------------------------------------------------ lifecycle
    def evict_model(self, key: str) -> None:
        """Drop the compiled-step cache for an evicted model — the AOT
        cache is keyed like the server's LRU and dies with it. Purge
        and backend-pop happen under ONE cond hold so they serialize
        against _runner's check-and-put (purging first would let a
        concurrent put re-land a stale executable)."""
        with self._cond:
            self._compiled.evict_model(self._cache_owner, key)
            self._backends.pop(key, None)
            if not self._queues.get(key):  # drop the empty deque too
                self._queues.pop(key, None)

    def stop(self, grace_s: float = 5.0) -> None:
        """Fail queued work with DRAINING, wake and join dispatchers;
        release this scheduler's slice of the global compile cache."""
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
            workers = list(self._dispatchers.values())
        for w in workers:
            w.join(grace_s)
        self._compiled.evict_owner(self._cache_owner)

    def stats(self) -> dict:
        """Per-scheduler serve stats."""
        p50, p99 = self.latency.quantiles()
        with self._stats_lock:
            return {
                "compile_s": round(self.compile_s, 3),
                "batch_size_mix": {str(k): v for k, v in
                                   sorted(self._batch_sizes.items())},
                "p50_ms": None if p50 is None else round(p50 * 1000, 2),
                "p99_ms": None if p99 is None else round(p99 * 1000, 2),
            }
