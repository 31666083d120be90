"""Thread-safe span tracer exporting Chrome trace-event JSON.

Spans nest via ``with tracer.span("shard"):`` (per-thread stacks) or run
explicitly via ``begin()``/``end()`` for async work that starts on one
thread and finishes on another (the AsyncDataSetIterator prefetch
pattern). Export is the Chrome trace-event format — ``"X"`` complete
events with microsecond timestamps — which Perfetto and chrome://tracing
open directly; one process = one ``pid``, one thread = one ``tid``.

The part the bench rounds were missing: ``open_span_stack()`` returns
the names of every span currently in flight, start-ordered. When a rung
hangs, the failure record carries that stack — "warmup" vs "stage
batches" vs "backend init" is the whole diagnosis (VERDICT r5: three
rounds dead with zero diagnostics).

A process-global default tracer (``get_tracer()``) is what the
containers and the parallel trainers emit into; the
buffer is bounded (oldest events drop, counted) so a week-long training
run cannot leak memory into the tracer. Timing is host wall time
(``perf_counter_ns``, whole nanoseconds): a span around an unsynced jit
dispatch measures dispatch, not device compute — sync first (as the
TrainingStats phases do) when the device time is the question.

Every span has an ``id`` and a ``parent`` (the innermost span open on
the same thread when it began), so ``self_times`` can give each span
its duration less what its children cover. ``with tracer.span(...)``
also enters a ``jax.profiler.TraceAnnotation`` named ``dl4j:<name>``
when jax is loaded: under a profiler session the program's spans then
lie in the profiler's own trace, on the device trace's clock. This
module never imports jax.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

# prefix of the spans mirrored into jax.profiler's trace
ANNOTATION_PREFIX = "dl4j:"


class _SpanHandle:
    """Token returned by ``Tracer.begin`` — pass it back to ``end``."""

    __slots__ = ("name", "id", "parent", "t0_ns", "dur_ns", "tid", "args",
                 "closed")

    def __init__(self, name: str, span_id: int, t0_ns: int, tid: int,
                 args: dict):
        self.name = name
        self.id = span_id
        self.parent: Optional[int] = None
        self.t0_ns = t0_ns
        self.dur_ns = 0         # set by end(): counters read it
        self.tid = tid
        self.args = args
        self.closed = False


class _SpanCtx:
    """Context manager wrapping one begin/end pair on one thread, and
    its mirror in jax.profiler's trace (re-entrant safe: every ``with``
    creates a fresh instance)."""

    __slots__ = ("_tracer", "_name", "_args", "_handle", "_mirror")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._handle = self._tracer.begin(self._name, **self._args)
        jax = sys.modules.get("jax")
        if jax is None:
            self._mirror = None
        else:   # an atomic load unless a profiler session is running
            self._mirror = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + self._name, **self._args)
            self._mirror.__enter__()
        return self._handle

    def __exit__(self, exc_type, exc, tb):
        if self._mirror is not None:
            self._mirror.__exit__(exc_type, exc, tb)
        if exc is not None:
            # record the span stack the exception unwound through —
            # `open_span_stack()` is empty by the time an outer handler
            # runs, because these exits already closed the spans
            self._tracer._note_error(self._handle, exc)
        self._tracer.end(self._handle)
        return False


class Tracer:
    """Bounded-buffer span recorder with Chrome trace-event export."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._dropped = 0
        self._ids = itertools.count(1)
        # tid -> open-span stack (list of _SpanHandle, outermost first);
        # a dict (not threading.local) so open_span_stack() can see every
        # thread's in-flight spans — the hang diagnosis requirement
        self._open: Dict[int, List[_SpanHandle]] = {}
        self._error_key: Optional[int] = None
        self._error_stack: List[str] = []

    # ------------------------------------------------------------ recording
    def begin(self, name: str, **args) -> _SpanHandle:
        """Open a span explicitly (async work); close with ``end()``.
        ``end`` may run on a different thread than ``begin``, so such a
        pair is not mirrored into jax.profiler's trace."""
        tid = threading.get_ident()
        h = _SpanHandle(name, next(self._ids), time.perf_counter_ns(), tid,
                        args)
        with self._lock:
            stack = self._open.setdefault(tid, [])
            if stack:
                h.parent = stack[-1].id
            stack.append(h)
        return h

    def end(self, handle: _SpanHandle) -> None:
        if handle.closed:
            return
        handle.closed = True
        handle.dur_ns = max(time.perf_counter_ns() - handle.t0_ns, 0)
        ev = _event(handle.name, "X", handle.t0_ns, handle.tid, handle.args,
                    handle.id, handle.parent, handle.dur_ns)
        with self._lock:
            stack = self._open.get(handle.tid)
            if stack and handle in stack:
                stack.remove(handle)
                if not stack:
                    del self._open[handle.tid]
            dropped = self._append_locked(ev)
        self._count_dropped(dropped)

    def _append_locked(self, ev: dict) -> int:
        """Bounded append (caller holds the lock): every event source —
        end/instant/complete — shares the same drop-oldest-half trim.
        Returns how many events this append evicted so the caller can
        publish the count AFTER releasing the lock (the registry has its
        own locks; never nest them under the tracer's)."""
        dropped = 0
        if len(self._events) >= self.max_events:
            # drop the OLDEST half in one go: per-event pop(0) would
            # make the full-buffer steady state quadratic
            self._events = self._events[self.max_events // 2:]
            dropped = self.max_events - len(self._events)
            self._dropped += dropped
        self._events.append(ev)
        return dropped

    def _record(self, ev: dict) -> None:
        with self._lock:
            dropped = self._append_locked(ev)
        self._count_dropped(dropped)

    def _count_dropped(self, dropped: int) -> None:
        """Publish buffer evictions as ``tracer_events_dropped`` so
        bounded-buffer truncation shows up on the same ``/api/metrics``
        surface as everything else (lazy import: keep this module free
        of load-time dependencies)."""
        if not dropped:
            return
        from deeplearning4j_tpu.profiling.metrics import get_registry
        get_registry().counter(
            "tracer_events_dropped",
            help="trace events evicted from the bounded buffer"
        ).inc(dropped)

    def span(self, name: str, **args) -> _SpanCtx:
        """``with tracer.span("shard") as h:`` — nested spans stack per
        thread; after the block ``h.dur_ns`` is the span's duration."""
        return _SpanCtx(self, name, args)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event (ph "i")."""
        ev = _event(name, "i", time.perf_counter_ns(), threading.get_ident(),
                    args)
        ev["s"] = "t"
        self._record(ev)

    def complete(self, name: str, dur_ns: int, **args) -> None:
        """Record an interval of ``dur_ns`` that ended now, measured by
        someone else (e.g. a compile duration reported after the fact by
        jax.monitoring)."""
        dur_ns = max(int(dur_ns), 0)
        self._record(_event(
            name, "X", time.perf_counter_ns() - dur_ns,
            threading.get_ident(), args, next(self._ids), None, dur_ns))

    def _note_error(self, handle: _SpanHandle, exc: BaseException) -> None:
        """Called by span contexts as an exception unwinds through them
        (innermost first). One stack per exception object."""
        with self._lock:
            if self._error_key != id(exc):
                self._error_key = id(exc)
                self._error_stack = []
            self._error_stack.append(handle.name)

    # ------------------------------------------------------------ inspection
    def error_span_stack(self) -> List[str]:
        """The span stack the most recent exception unwound through,
        outermost first (the failure-record diagnosis for raises, as
        ``open_span_stack`` is for hangs)."""
        with self._lock:
            return list(reversed(self._error_stack))

    def open_span_stack(self) -> List[str]:
        """Names of every in-flight span, across all threads, ordered by
        start time (outermost/oldest first) — the hang diagnosis."""
        with self._lock:
            live = [h for stack in self._open.values() for h in stack]
        return [h.name for h in sorted(live, key=lambda h: h.t0_ns)]

    def open_spans_by_thread(self) -> Dict[int, List[dict]]:
        """Per-thread in-flight spans, outermost first: tid -> list of
        ``{name, t0_ns, args}``. The diagnostic-bundle form — the stall
        culprit is the DEEPEST open span of the stale subsystem's
        thread, which the flat ``open_span_stack`` cannot attribute."""
        with self._lock:
            return {tid: [{"name": h.name, "t0_ns": h.t0_ns,
                           "args": dict(h.args)} for h in stack]
                    for tid, stack in self._open.items() if stack}

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    @property
    def dropped(self) -> int:
        return self._dropped

    # --------------------------------------------------------------- export
    def export(self) -> dict:
        """Chrome trace-event JSON object (the ``traceEvents`` wrapper
        form both Perfetto and chrome://tracing accept). Beside Chrome's
        microsecond ``ts``/``dur`` every event carries ``ts_ns`` (and a
        span ``dur_ns``, ``id``, ``parent``): whole nanoseconds of
        ``time.perf_counter_ns()``."""
        with self._lock:
            events = [dict(e) for e in self._events]
        return {"traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self._dropped}}

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.export(), indent=indent)

    def save(self, path: str) -> str:
        """Write the trace to ``path`` (open it in Perfetto)."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json())
        os.replace(tmp, path)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self._dropped = 0
            self._error_key = None
            self._error_stack = []


def _event(name: str, ph: str, ts_ns: int, tid: int, args: dict,
           span_id: Optional[int] = None, parent: Optional[int] = None,
           dur_ns: Optional[int] = None) -> dict:
    """One exported event; a span (``span_id`` given) carries its id, its
    parent and its duration, an instant does not."""
    ev = {"name": name, "ph": ph, "ts": ts_ns / 1e3, "ts_ns": ts_ns,
          "pid": os.getpid(), "tid": tid}
    if span_id is not None:
        ev.update(id=span_id, parent=parent, dur_ns=dur_ns, dur=dur_ns / 1e3)
    if args:
        ev["args"] = dict(args)
    return ev


def self_times(events: List[dict]) -> Dict[int, int]:
    """``{span id: nanoseconds}``: each span's duration less the part of
    its interval that its children (by ``parent``) cover. ``events`` are
    exported events; those without an ``id`` (instants) are passed over."""
    spans = [e for e in events if "id" in e]
    children: Dict[int, List[dict]] = {}
    for e in spans:
        if e["parent"] is not None:
            children.setdefault(e["parent"], []).append(e)
    out = {}
    for e in spans:
        lo, hi = e["ts_ns"], e["ts_ns"] + e["dur_ns"]
        covered, edge = 0, lo
        for c in sorted(children.get(e["id"], ()), key=lambda c: c["ts_ns"]):
            c_lo = max(c["ts_ns"], edge)
            c_hi = min(c["ts_ns"] + c["dur_ns"], hi)
            if c_hi > c_lo:
                covered += c_hi - c_lo
                edge = c_hi
        out[e["id"]] = e["dur_ns"] - covered
    return out


# ---------------------------------------------------------------------------
# process-global default tracer
# ---------------------------------------------------------------------------

_default = Tracer()
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    """The process-global tracer the containers and trainers emit into."""
    return _default


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer (tests, per-run capture). Returns
    the previous one."""
    global _default
    with _default_lock:
        prev, _default = _default, tracer
    return prev
