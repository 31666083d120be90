"""The program's own spans, read by the benchmark.

The program records spans inside ``fit`` and inside the prefetch feed's
thread (``deeplearning4j_tpu/nn/netcommon.py``, ``datasets/iterator.py``) in
the ring of ``profiling/tracer.py``: exported events with ``name``, ``id``,
``parent``, ``ts_ns``, ``dur_ns``, ``tid`` and ``args``, on the clock of
``time.perf_counter_ns()``. ``trace.load_xplane`` keeps only the host events
whose names start ``bench:`` and ``run.py`` deletes the profiler's files
before a reader runs, so the readers take the spans from the ring, in the
same process, and ``on_trace_clock`` puts those of the traced stretch on the
trace's clock by one anchor: the benchmark's ``bench:fit`` span is entered
immediately round the call that the program's ``fit`` span covers.

Every function that reads gives ``None`` where there is nothing to read: a
program from before these spans (no ``id`` on its events, no ``fit`` span)
makes every reader leave its metric out, and none raises.

A ``run`` may carry its spans itself, as ``run.spans`` (the recorded pair of
the tests does); otherwise they are the ring's.
"""

from __future__ import annotations

import statistics

from benchmark import trace

FIT, FIT_SPAN = "fit", trace.SPAN_PREFIX + "fit"
AGREE = 0.05            # a fit span against the window it should be
ANCHOR_NS = 1_000_000   # the anchor's two ends may differ by this much
NONE = "none"           # idle time that no span of the thread covers


def program_spans() -> list:
    """The spans in the ring of the program's tracer, oldest first."""
    from deeplearning4j_tpu.profiling.tracer import get_tracer
    return sorted((e for e in get_tracer().export()["traceEvents"]
                   if "id" in e and "dur_ns" in e),
                  key=lambda e: (e["ts_ns"], e["id"]))


def _spans(run) -> list:
    """The run's spans; read from the ring once and kept on the run."""
    if getattr(run, "spans", None) is None:
        run.spans = program_spans()
    return run.spans


def fit_spans(run) -> tuple:
    """``(window, traced)``: the ``fit`` span of the measured window (the
    last but one) and of the traced stretch (the last), each ``None`` if it
    is not there or is not what the benchmark measured. The window's has to
    agree within 5 % with the seconds measured round it. The stretch's may
    only be shorter than its seconds: under the profiler the device lags
    the host, and ``block_until_ready`` after ``fit`` took 1.9 of 8.1 s
    (my chip runs, PR 25); ``on_trace_clock`` holds it to ``bench:fit``
    within a millisecond instead."""
    fits = [e for e in _spans(run) if e["name"] == FIT]
    traced_s = run.measures.get("traced", {}).get("window_s")
    if len(fits) < 2 or traced_s is None:
        return None, None
    window_s = run.measures["window_s"]
    window, traced = fits[-2], fits[-1]
    if abs(window["dur_ns"] / 1e9 - window_s) > AGREE * window_s:
        window = None
    if traced["dur_ns"] / 1e9 > (1 + AGREE) * traced_s:
        traced = None
    return window, traced


def under(run, fit) -> tuple:
    """``(loop, feed)``: the spans below ``fit`` on its own thread (``fit``
    among them), and the spans of the other threads that lie in its
    interval or reach into it (the feed's thread is started just before
    the call)."""
    lo, hi = fit["ts_ns"], fit["ts_ns"] + fit["dur_ns"]
    inside = [e for e in _spans(run)
              if e["ts_ns"] < hi and e["ts_ns"] + e["dur_ns"] > lo]
    below, loop = {fit["id"]}, [fit]
    for e in inside:            # oldest first: a parent before its children
        if e["parent"] in below:
            below.add(e["id"])
            loop.append(e)
    return loop, [e for e in inside if e["tid"] != fit["tid"]]


def window(run):
    """``(fit, loop, feed)`` of the measured (untraced) window, or None."""
    fit, _ = fit_spans(run)
    return None if fit is None else (fit, *under(run, fit))


def on_trace_clock(run):
    """``(fit, loop, feed)`` of the traced stretch with every ``ts_ns``
    shifted onto the trace's clock: by the start of ``bench:fit`` in the
    trace less the start of the program's ``fit`` span. ``None`` if the
    two spans' ends then differ by more than 1 ms."""
    _, fit = fit_spans(run)
    anchors = trace.spans(run.trace, FIT_SPAN)
    if fit is None or len(anchors) != 1:
        return None
    _, start, dur = anchors[0]
    if abs(dur - fit["dur_ns"]) > ANCHOR_NS:
        return None
    shift = start - fit["ts_ns"]
    moved = lambda events: [dict(e, ts_ns=e["ts_ns"] + shift) for e in events]
    loop, feed = under(run, fit)
    return moved([fit])[0], moved(loop), moved(feed)


def device_gaps(tr: dict, min_gap_ns: int = 20_000) -> list:
    """The idle gaps of the first device inside the window, ``[start, end]``
    each, as ``trace.idle_gaps`` finds them."""
    lo, hi = trace.window_ns(tr)
    ops = trace.line_events(trace.device_planes(tr)[0], trace.OPS_LINE)
    busy = trace.clip(trace.merge([e[1], e[1] + e[2]] for e in ops), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= min_gap_ns]


def covering(events: list, g0: int, g1: int):
    """The innermost (shortest) of ``events`` that covers half of the gap
    ``[g0, g1]`` or more, or None."""
    best = None
    for e in events:
        over = min(g1, e["ts_ns"] + e["dur_ns"]) - max(g0, e["ts_ns"])
        if 2 * over >= g1 - g0 and (best is None
                                    or e["dur_ns"] < best["dur_ns"]):
            best = e
    return best


def idle_by_span(run, thread: str):
    """``{span name: seconds}``: each idle gap of the first device in the
    traced stretch given to the innermost program span of ``thread``
    (``"loop"`` or ``"feed"``) that covers half of it or more, ``"none"``
    where no span does. ``None`` without the anchor."""
    placed = on_trace_clock(run)
    if placed is None:
        return None
    events = placed[1] if thread == "loop" else placed[2]
    acc: dict = {}
    for g0, g1 in device_gaps(run.trace):
        near = [e for e in events
                if e["ts_ns"] < g1 and e["ts_ns"] + e["dur_ns"] > g0]
        owner = covering(near, g0, g1)
        name = NONE if owner is None else owner["name"]
        acc[name] = acc.get(name, 0) + (g1 - g0) / 1e9
    return acc


def idle_share(run, name: str):
    """Of the device's idle time in the traced stretch, the per cent given
    to the loop's spans called ``name``."""
    acc = idle_by_span(run, "loop")
    if not acc:
        return None
    return 100.0 * acc.get(name, 0.0) / sum(acc.values())


# ---------------------------------------------------------------------------
# small sums, for the readers in metrics/
# ---------------------------------------------------------------------------

def named(events: list, name: str) -> list:
    return [e for e in events if e["name"] == name]


def total_ns(events: list, name: str) -> int:
    return sum(e["dur_ns"] for e in named(events, name))


def median_ms(durations_ns: list):
    return statistics.median(durations_ns) / 1e6 if durations_ns else None


def loop_self_ns(loop: list):
    """The loop's own host time: the self times of every span below
    ``fit`` but the waits in the feed's queue and the step's dispatch, so
    that the three add up to the ``fit`` span."""
    try:
        from deeplearning4j_tpu.profiling.tracer import self_times
    except ImportError:         # a program from before the spans
        return None
    own = self_times(loop)
    return sum(own[e["id"]] for e in loop
               if e["name"] not in ("input:wait", "fit:dispatch"))


def feed_host_work_ns(feed: list) -> list:
    """For each batch the feed produced (an ``input:produce`` with an
    upload), its duration less its ``input:h2d`` and ``input:put_wait``:
    read, cast and the thread's own time."""
    less: dict = {}
    uploads = set()
    for e in feed:
        if e["name"] in ("input:h2d", "input:put_wait"):
            less[e["parent"]] = less.get(e["parent"], 0) + e["dur_ns"]
            if e["name"] == "input:h2d":
                uploads.add(e["parent"])
    return [e["dur_ns"] - less.get(e["id"], 0)
            for e in named(feed, "input:produce") if e["id"] in uploads]
