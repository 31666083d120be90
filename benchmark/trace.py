"""The reduction from a profiler trace to numbers.

``load_xplane`` reads what ``jax.profiler`` wrote (``*.xplane.pb``) into a
small plain form, ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}]}``; ``load_json`` reads the same form
from a file, which is how the recorded trace of the tests is kept. Every
function below works on that form, so the arithmetic is the same on the
chip and in the tests.

On a TPU the device planes are ``/device:TPU:<n>``. Their line ``XLA Ops``
holds one event for each operation that ran, ``XLA Modules`` one for each
run of a compiled program (named ``jit_<function>(<fingerprint>)``). Host
threads are lines of the plane ``/host:CPU``; the benchmark's own
``TraceAnnotation`` spans are there under names that start ``bench:``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


def load_xplane(trace_dir: str) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir``, lines without events
    left out."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        keep_all = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name), int(e.start_ns),
                       int(e.duration_ns)] for e in line.events
                      if keep_all or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO text, ``%fusion.72 =
    (f32[256]...) fusion(...)``: keep the name before `` = `` and, for a
    custom call (a Pallas kernel among them), what says which it is."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    head = head.lstrip("%")
    if "custom-call" in head or "custom_call" in head:
        target = re.search(r'custom_call_target="([^"]+)"', rest)
        kernel = re.search(r'kernel_name[^A-Za-z0-9_]+([A-Za-z0-9_]+)', rest)
        op = re.search(r'op_name="([^"]+)"', rest)
        tail = [m.group(1) for m in (target, kernel, op) if m]
        return head + (" " + " ".join(tail) if tail else "")
    return head


def save_json(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def cut(trace: dict, lo: int, hi: int) -> dict:
    """The events that lie wholly inside ``[lo, hi)``, and the spans that
    reach into it clipped to it: how a recorded trace is made small."""
    planes = []
    for p in trace["planes"]:
        device = bool(DEVICE_PLANE.match(p["name"]))
        lines = []
        for line in p["lines"]:
            if device:
                ev = [e for e in line["events"]
                      if e[1] >= lo and e[1] + e[2] <= hi]
            else:
                ev = [[e[0], max(e[1], lo), min(e[1] + e[2], hi) - max(e[1], lo)]
                      for e in line["events"] if e[1] < hi and e[1] + e[2] > lo]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def load_json(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# selecting events
# ---------------------------------------------------------------------------

def device_planes(trace: dict) -> list:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes, key=lambda p: int(DEVICE_PLANE.match(
        p["name"]).group(1)))


def line_events(plane: dict, line_name: str) -> list:
    return [e for line in plane["lines"] if line["name"] == line_name
            for e in line["events"]]


def spans(trace: dict, name: str | None = None) -> list:
    """The benchmark's own host spans, ``[name, start_ns, dur_ns]``."""
    return [e for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
            for line in p["lines"] for e in line["events"]
            if e[0].startswith(SPAN_PREFIX) and (name is None or e[0] == name)]


def window_ns(trace: dict) -> tuple:
    """The measured window on the trace's clock: the ``bench:window`` span,
    or, where the host's and the devices' clocks do not overlap, from the
    first to the last device operation."""
    ops = [e for p in device_planes(trace) for e in line_events(p, OPS_LINE)]
    if not ops:
        raise ValueError("no operation ran on a device in the traced window")
    lo = min(e[1] for e in ops)
    hi = max(e[1] + e[2] for e in ops)
    win = spans(trace, SPAN_PREFIX + "window")
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
        if w0 < hi and lo < w1:
            return w0, w1
    return lo, hi


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same instants."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list:
    """Of two merged lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _iv(events) -> list:
    return [[e[1], e[1] + e[2]] for e in events]


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------

def busy_seconds(trace: dict) -> list:
    """For each device, the seconds of the window in which an operation ran:
    the union of the ``XLA Ops`` intervals."""
    lo, hi = window_ns(trace)
    return [total(clip(merge(_iv(line_events(p, OPS_LINE))), lo, hi)) / 1e9
            for p in device_planes(trace)]


def window_seconds(trace: dict) -> float:
    lo, hi = window_ns(trace)
    return (hi - lo) / 1e9


def idle_share(trace: dict) -> float:
    """1 less busy over the window, on the device that idles most."""
    return 1.0 - min(busy_seconds(trace)) / window_seconds(trace)


def module_runs(trace: dict, pattern: str) -> list:
    """``[seconds, ...]`` of each run, inside the window, of the compiled
    programs whose name matches ``pattern``, on the first device."""
    lo, hi = window_ns(trace)
    rx = re.compile(pattern)
    return [e[2] / 1e9 for e in line_events(device_planes(trace)[0],
                                            MODULES_LINE)
            if rx.search(e[0]) and e[1] >= lo and e[1] + e[2] <= hi]


def op_seconds(trace: dict, pattern: str) -> float:
    """Device time, inside the window and on the first device, of the
    operations whose name matches ``pattern`` (overlaps counted once)."""
    lo, hi = window_ns(trace)
    rx = re.compile(pattern)
    ev = [e for e in line_events(device_planes(trace)[0], OPS_LINE)
          if rx.search(e[0])]
    return total(clip(merge(_iv(ev)), lo, hi)) / 1e9


def exposed_collective_seconds(trace: dict) -> float:
    """Time of collective operations during which no other operation runs on
    that device, on the device where it is longest."""
    lo, hi = window_ns(trace)
    worst = 0.0
    for p in device_planes(trace):
        ops = line_events(p, OPS_LINE)
        coll = clip(merge(_iv(e for e in ops if COLLECTIVE.match(e[0]))),
                    lo, hi)
        rest = merge(_iv(e for e in ops if not COLLECTIVE.match(e[0])))
        worst = max(worst, (total(coll) - total(intersect(coll, rest))) / 1e9)
    return worst


def top_device_ops(trace: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the operations of the first device that
    took most time inside the window, numbered twins (``fusion.12``)
    counted under their own names."""
    lo, hi = window_ns(trace)
    acc: dict = {}
    for name, start, dur in line_events(device_planes(trace)[0], OPS_LINE):
        if start >= lo and start + dur <= hi:
            acc[name] = acc.get(name, 0) + dur
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(trace: dict, n: int = 10, min_gap_ns: int = 20_000) -> list:
    """``[[what the host was doing, seconds], ...]``: the idle gaps of the
    first device inside the window, each given to the innermost of the
    benchmark's host spans that covers half of it or more, summed by span
    name."""
    lo, hi = window_ns(trace)
    busy = clip(merge(_iv(line_events(device_planes(trace)[0], OPS_LINE))),
                lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= min_gap_ns]
    host = sorted((e for e in spans(trace)
                   if e[0] != SPAN_PREFIX + "window"), key=lambda e: e[1])
    acc: dict = {}
    for g0, g1 in gaps:
        best, shortest = "host:unattributed", None
        for name, start, dur in host:
            if start >= g1:
                break
            over = min(g1, start + dur) - max(g0, start)
            # the innermost span that covers half of the gap or more
            if 2 * over >= g1 - g0 and (shortest is None or dur < shortest):
                best, shortest = name[len(SPAN_PREFIX):], dur
        acc[best] = acc.get(best, 0) + (g1 - g0)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def summary(trace: dict, n: int = 40) -> dict:
    """What one looks at by hand first: planes, lines, counts and the most
    frequent names of each line."""
    out = {}
    for p in trace["planes"]:
        for line in p["lines"]:
            names: dict = {}
            for e in line["events"]:
                names[e[0]] = names.get(e[0], 0) + e[2]
            top = sorted(names.items(), key=lambda kv: -kv[1])[:n]
            out[f"{p['name']}|{line['name']}"] = {
                "events": len(line["events"]),
                "top_ns": top}
    return out
