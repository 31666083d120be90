"""Feeders for the metrics registry: compile watcher + memory watermark.

``CompileWatcher`` hooks ``jax.monitoring``'s duration events —
``/jax/core/compile/jaxpr_trace_duration`` (trace),
``jaxpr_to_mlir_module_duration`` (lower), and
``backend_compile_duration`` (XLA compile) — counting and timing each
into the registry, mirroring every compile into the span tracer's
timeline, and (via ``wrap()``) warning when a watched function
recompiles because its argument *shapes* changed — the silent
minutes-per-recompile failure mode that corrupted bench round 3.

``DeviceMemoryWatermark`` is a background sampler over the
``memory_stats()`` probe (the same probe ``ui/stats.py`` polls per
iteration): bytes-in-use gauge plus a ratcheted high-watermark gauge,
at a fixed interval, so an OOM post-mortem has the curve that led to it.

Both are jax-optional: importing this module never imports jax; on a
jax-free (or memory_stats-less) runtime everything degrades to no-ops.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, Optional

from deeplearning4j_tpu.profiling.metrics import MetricsRegistry, get_registry
from deeplearning4j_tpu.profiling.tracer import Tracer, get_tracer

logger = logging.getLogger(__name__)

# event suffix -> (metric stem, short span name)
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jax_trace", "jit:trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jax_lower",
                                                        "jit:lower"),
    "/jax/core/compile/backend_compile_duration": ("jax_compile",
                                                   "jit:compile"),
}

# persistent-compilation-cache events -> counter name
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jax_compile_cache_hits_total",
    "/jax/compilation_cache/cache_misses": "jax_compile_cache_misses_total",
}

_COMPILE_TIME_BUCKETS = (0.01, 0.05, 0.2, 1.0, 5.0, 20.0, 60.0, 300.0)


class CompileWatcher:
    """Counts and times jit traces / lowers / compiles.

    ``install()`` registers jax.monitoring listeners (process-wide;
    jax offers no per-listener removal, so ``uninstall()`` deactivates
    this watcher's callbacks instead of deregistering them). Counters:
    ``jax_{trace,lower,compile}_total`` and ``..._seconds_total``, plus
    a ``jax_compile_seconds`` histogram, and
    ``jax_compile_cache_{hits,misses}_total`` for the persistent
    compilation cache (a hit still counts as a compile event — jax
    times the cache lookup under the same name). Compiles longer than
    ``warn_compile_s`` log a warning — in a steady-state loop a
    surprise recompile IS the incident.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 warn_compile_s: float = 30.0):
        self.registry = registry or get_registry()
        self.tracer = tracer or get_tracer()
        self.warn_compile_s = warn_compile_s
        self._active = False
        self._installed = False
        self._lock = threading.Lock()
        self._wrapped_sigs: Dict[str, set] = {}

    # ------------------------------------------------------------ listeners
    def install(self) -> "CompileWatcher":
        with self._lock:
            self._active = True
            if self._installed:
                return self
            try:
                import jax.monitoring as monitoring
                monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                monitoring.register_event_listener(self._on_event)
                self._installed = True
            except Exception:  # noqa: BLE001 — jax-free runtime: no-op
                logger.debug("jax.monitoring unavailable; CompileWatcher "
                             "counts only wrapped calls")
        return self

    def uninstall(self) -> None:
        with self._lock:
            self._active = False

    def _on_event(self, event: str, **_kw) -> None:
        name = _CACHE_EVENTS.get(event)
        if self._active and name is not None:
            self.registry.counter(
                name, help="persistent compilation cache lookups").inc()

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if not self._active:
            return
        hit = _COMPILE_EVENTS.get(event)
        if hit is None:
            return
        stem, span_name = hit
        self.registry.counter(
            f"{stem}_total", help=f"number of {span_name} events").inc()
        self.registry.counter(
            f"{stem}_seconds_total",
            help=f"cumulative seconds in {span_name}").inc(duration)
        if stem == "jax_compile":
            self.registry.histogram(
                "jax_compile_seconds", help="per-program XLA compile time",
                buckets=_COMPILE_TIME_BUCKETS).observe(duration)
            # mirror into the trace timeline, backdated by the duration
            self.tracer.complete(span_name, int(duration * 1e9))
            if duration >= self.warn_compile_s:
                logger.warning("XLA compile took %.1fs — if this step "
                               "already ran, something changed its "
                               "shapes/dtypes", duration)

    # ------------------------------------------------------- recompile guard
    @staticmethod
    def _signature(args, kwargs):
        """Hashable (shape, dtype) tree of the array-like leaves; python
        scalars keep their type (they are trace constants too)."""
        def leaf(x):
            shape = getattr(x, "shape", None)
            if shape is not None:
                return ("arr", tuple(shape), str(getattr(x, "dtype", "?")))
            if isinstance(x, (list, tuple)):
                return tuple(leaf(v) for v in x)
            if isinstance(x, dict):
                return tuple(sorted((k, leaf(v)) for k, v in x.items()))
            return ("py", type(x).__name__)
        return (tuple(leaf(a) for a in args),
                tuple(sorted((k, leaf(v)) for k, v in kwargs.items())))

    def wrap(self, fn, label: str):
        """Wrap a (jitted) callable: each NEW argument shape signature
        after the first is a shape-change recompile — counted
        (``jit_shape_recompiles_total``) and warned once per new
        signature. The call itself is passed through untouched."""
        def wrapped(*args, **kwargs):
            sig = self._signature(args, kwargs)
            with self._lock:
                seen = self._wrapped_sigs.setdefault(label, set())
                fresh = sig not in seen
                n_seen = len(seen)
                if fresh:
                    seen.add(sig)
            if fresh and n_seen >= 1:
                self.registry.counter(
                    "jit_shape_recompiles_total",
                    help="watched functions re-traced on a new shape "
                         "signature").inc()
                logger.warning(
                    "%s: argument shapes changed (signature #%d) — this "
                    "call pays a full re-trace + XLA recompile", label,
                    n_seen + 1)
            return fn(*args, **kwargs)

        wrapped.__name__ = getattr(fn, "__name__", label)
        return wrapped


# ---------------------------------------------------------------------------
# device memory
# ---------------------------------------------------------------------------

def device_memory_stats(device=None) -> Optional[dict]:
    """``memory_stats()`` probe (the ui/stats.py probe, shared): returns
    the raw dict, or None when jax is absent / uninitialized / the
    backend doesn't report (CPU returns None)."""
    try:
        import sys
        if "jax" not in sys.modules and device is None:
            return None  # never force a backend init from a sampler
        import jax
        d = device if device is not None else jax.devices()[0]
        return d.memory_stats()
    except Exception:  # noqa: BLE001 — telemetry must never raise
        return None


class DeviceMemoryWatermark:
    """Background device-memory sampler feeding the registry.

    Gauges: ``device_bytes_in_use`` (latest sample) and
    ``device_bytes_in_use_watermark`` (ratcheted max across samples —
    catches the between-iterations peak the per-iteration StatsListener
    probe misses). ``sample()`` is also callable directly without
    starting the thread.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 interval_s: float = 0.5, device=None):
        self.registry = registry or get_registry()
        self.interval_s = interval_s
        self.device = device
        self.watermark_bytes = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def sample(self) -> Optional[dict]:
        ms = device_memory_stats(self.device)
        if not ms or "bytes_in_use" not in ms:
            return None
        in_use = int(ms["bytes_in_use"])
        # the backend's own lifetime peak when exposed, else our ratchet
        peak = int(ms.get("peak_bytes_in_use", 0)) or in_use
        self.watermark_bytes = max(self.watermark_bytes, peak, in_use)
        self.registry.gauge(
            "device_bytes_in_use",
            help="device memory in use (memory_stats probe)").set(in_use)
        self.registry.gauge(
            "device_bytes_in_use_watermark",
            help="high watermark of device memory in use").set_max(
                self.watermark_bytes)
        return ms

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.sample()

    def start(self) -> "DeviceMemoryWatermark":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="device-mem-watermark", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
