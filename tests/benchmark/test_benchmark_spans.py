"""The benchmark's reading of the program's spans (``benchmark/spans.py`` and
the eight readers of ``program_span`` metrics): on spans and a trace written
by hand, whose answers are worked out in the comments, and on a pair recorded
on the chip and kept with the benchmark."""

import importlib
import json
import os
import types

import pytest

from benchmark import manifest, spans, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000
LOOP, FEED = 1, 2       # thread ids


def S(i, parent, name, t0_ms, dur_ms, tid=LOOP, **args):
    ev = {"name": name, "id": i, "parent": parent, "ts_ns": t0_ms * MS,
          "dur_ns": dur_ms * MS, "tid": tid}
    if args:
        ev["args"] = args
    return ev


# The ring, on the program's clock (milliseconds here). Three fit calls: one
# of set-up, the measured window (ids 10 to 42) and the traced stretch (ids
# 50 to 61).
#
# The window's fit [1000,1100) runs two steps:
#   waits 30 + 30 + 15 (the take that ends the stream) = 75, one of three
#   found its item ready; dispatches 5 and 7; so the loop's own time is
#   100 - 75 - 12 = 13 ms, 6.5 a step. By self times: fit 1, the two
#   fit_batch 1 and 3, splits 4, keys 2, listeners 2.
# Its feed produced two batches and the end: host work 30 - 6 - 1 = 23 and
#   40 - 8 - 7 = 25; uploads 6 and 8; held back 1 + 7 + 5 = 13 of 100 ms.
RING = [
    S(1, None, "fit", 0, 10),
    S(2, 1, "input:wait", 0, 9, batch=0, ready=0, depth=0),
    # ---- the measured window
    S(30, None, "input:produce", 998, 30, FEED, batch=0),
    S(31, 30, "input:read", 998, 2, FEED),
    S(10, None, "fit", 1000, 100),
    S(11, 10, "input:wait", 1000, 30, batch=0, ready=0, depth=0),
    S(32, 30, "input:cast", 1000, 20, FEED),
    S(33, 30, "input:h2d", 1020, 6, FEED),
    S(34, 30, "input:put_wait", 1026, 1, FEED),
    S(35, None, "input:produce", 1028, 40, FEED, batch=1),
    S(36, 35, "input:read", 1028, 2, FEED),
    S(12, 10, "fit_batch", 1030, 10, it=4, batch=0),
    S(13, 12, "fit:split", 1030, 2),
    S(37, 35, "input:cast", 1030, 22, FEED),
    S(14, 12, "fit:rng", 1032, 1),
    S(15, 12, "fit:dispatch", 1033, 5),
    S(16, 12, "fit:listeners", 1038, 1),
    S(17, 10, "input:wait", 1040, 30, batch=1, ready=1, depth=1),
    S(38, 35, "input:h2d", 1052, 8, FEED),
    S(39, 35, "input:put_wait", 1060, 7, FEED),
    S(40, None, "input:produce", 1068, 7, FEED, batch=2),
    S(41, 40, "input:read", 1068, 1, FEED),
    S(42, 40, "input:put_wait", 1069, 5, FEED),
    S(18, 10, "fit_batch", 1070, 14, it=5, batch=1),
    S(19, 18, "fit:split", 1070, 2),
    S(20, 18, "fit:rng", 1072, 1),
    S(21, 18, "fit:dispatch", 1073, 7),
    S(22, 18, "fit:listeners", 1080, 1),
    S(23, 10, "input:wait", 1084, 15, batch=2, ready=0, depth=0),
    # ---- the traced stretch
    S(60, None, "input:produce", 1999, 36, FEED, batch=0),
    S(50, None, "fit", 2000, 100),
    S(51, 50, "input:wait", 2000, 40, batch=0, ready=0, depth=0),
    S(61, 60, "input:h2d", 2020, 10, FEED),
    S(52, 50, "fit_batch", 2040, 20, it=6, batch=0),
    S(53, 52, "fit:dispatch", 2042, 16),
    S(54, 50, "input:wait", 2060, 32, batch=1, ready=0, depth=0),
]

# The trace's clock runs 1500 ms behind the program's: bench:fit starts at
# 500 ms, and lasts 0.2 ms longer than the program's fit span inside it. The
# device is busy over [510,530) and [556,590), so inside the window
# [500,600) it idles over [500,510), [530,556) and [590,600): 46 ms.
#   [500,510): the loop is in its first input:wait ([500,540) there)
#   [530,556): 14 of its 26 ms lie under fit:dispatch [542,558), 16 under
#              fit_batch, 10 under the wait: the innermost that covers half
#              is the dispatch
#   [590,600): the last wait ends at 592; only fit covers half
# The feed's thread is in input:produce over [499,535): it covers the first
# gap, 5 ms of the second and nothing of the third.
TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["a", 510 * MS, 20 * MS], ["b", 556 * MS, 34 * MS]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            ["bench:window", 500 * MS, 100 * MS],
            ["bench:fit", 500 * MS, 100 * MS + 200_000],
            ["bench:feed_wait", 500 * MS, 40 * MS]]}]},
]}
MEASURES = {"window_s": 0.101, "steps": 2, "traced": {"window_s": 0.1005}}

WANT = {
    "fit_loop_self_ms": 6.5,
    "fit_dispatch_ms": 6.0,
    "feed_host_work_ms": 24.0,
    "feed_h2d_ms": 7.0,
    "feed_backpressure_share": 13.0,
    "input_ready_share": 100.0 / 3,
    "idle_in_input_wait_share": 100.0 * 10 / 46,
    "idle_in_dispatch_share": 100.0 * 26 / 46,
}


def make_run(ring=RING, tr=TRACE, **measures):
    return types.SimpleNamespace(spans=ring, trace=tr,
                                 measures=dict(MEASURES, **measures))


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


def ids(events):
    return sorted(e["id"] for e in events)


def test_the_window_is_the_last_fit_but_one_and_the_stretch_the_last():
    window, traced = spans.fit_spans(make_run())
    assert (window["id"], traced["id"]) == (10, 50)
    fit, loop, feed = spans.window(make_run())
    assert fit["id"] == 10
    assert ids(loop) == list(range(10, 24))
    # the feed's first turn began before the call and reaches into it (its
    # read, 31, was over by then); the other fits' spans do not
    assert ids(feed) == [30] + list(range(32, 43))


OF_THE_STRETCH = {"idle_in_input_wait_share", "idle_in_dispatch_share"}


@pytest.mark.parametrize("ring,measures,window,stretch", [
    (RING, {"window_s": 0.2}, False, True),     # the window took twice that
    # the stretch's seconds hold what follows fit too: longer is no fault,
    (RING, {"traced": {"window_s": 0.2}}, True, True),
    (RING, {"traced": {"window_s": 0.09}}, True, False),    # shorter is
    (RING, {"traced": {}}, False, False),       # no traced stretch was run
    # the last fit is then the window's: neither is what was measured
    ([e for e in RING if e["id"] != 50], {"traced": {"window_s": 0.05}},
     False, False),
    ([e for e in RING if e["name"] != "fit"], {}, False, False),
    ([], {}, False, False),
], ids=["window_disagrees", "stretch_longer", "stretch_shorter", "untraced",
        "fit_missing", "no_fit", "empty_ring"])
def test_a_fit_span_that_does_not_fit_the_measures_is_refused(
        ring, measures, window, stretch):
    """Each of the two is checked against its own seconds, and silences
    only the readers that read it."""
    run = make_run(ring, **measures)
    found = spans.fit_spans(run)
    assert [f is not None for f in found] == [window, stretch]
    assert (spans.window(run) is not None) == window
    assert (spans.on_trace_clock(run) is not None) == stretch
    assert (spans.idle_by_span(run, "loop") is not None) == stretch
    for name in WANT:
        want = stretch if name in OF_THE_STRETCH else window
        assert (reader(name).read(run) is not None) == want, name


def test_the_anchor_puts_the_stretch_on_the_traces_clock():
    fit, loop, feed = spans.on_trace_clock(make_run())
    assert (fit["ts_ns"], fit["dur_ns"]) == (500 * MS, 100 * MS)
    at = {e["id"]: e["ts_ns"] // MS for e in loop + feed}
    assert at == {50: 500, 51: 500, 52: 540, 53: 542, 54: 560,
                  60: 499, 61: 520}
    assert RING[-1]["ts_ns"] == 2060 * MS       # the ring's copy is not moved


def _with_fit_anchor(*anchors):
    host = [e for e in TRACE["planes"][1]["lines"][0]["events"]
            if e[0] != "bench:fit"] + [list(a) for a in anchors]
    return {"planes": [TRACE["planes"][0], {"name": "/host:CPU", "lines": [
        {"name": "main", "events": host}]}]}


@pytest.mark.parametrize("anchors,placed", [
    ([("bench:fit", 500 * MS, 101 * MS - 1)], True),     # 1 ms less 1 ns
    ([("bench:fit", 500 * MS, 101 * MS + 1)], False),    # 1 ms and 1 ns
    ([("bench:fit", 500 * MS, 98 * MS)], False),
    ([], False),                                         # nothing to anchor to
    ([("bench:fit", 400 * MS, 100 * MS),
      ("bench:fit", 500 * MS, 100 * MS)], False),        # which of the two?
], ids=["inside_1ms", "over_1ms", "short", "no_anchor", "two_anchors"])
def test_the_anchor_is_refused_when_its_ends_differ_by_over_1ms(anchors,
                                                                placed):
    run = make_run(tr=_with_fit_anchor(*anchors))
    assert (spans.on_trace_clock(run) is not None) == placed
    assert (spans.idle_by_span(run, "loop") is not None) == placed
    for name in ("idle_in_input_wait_share", "idle_in_dispatch_share"):
        assert (reader(name).read(run) is not None) == placed
    # the readers of the untraced window do not need the anchor
    assert reader("fit_dispatch_ms").read(run) == pytest.approx(6.0)


def test_idle_gaps_go_to_the_innermost_span_of_the_thread_asked_for():
    assert spans.device_gaps(TRACE) == [
        [500 * MS, 510 * MS], [530 * MS, 556 * MS], [590 * MS, 600 * MS]]
    loop = spans.idle_by_span(make_run(), "loop")
    assert loop == {"input:wait": pytest.approx(0.010),
                    "fit:dispatch": pytest.approx(0.026),
                    "fit": pytest.approx(0.010)}
    feed = spans.idle_by_span(make_run(), "feed")
    assert feed == {"input:produce": pytest.approx(0.010),
                    spans.NONE: pytest.approx(0.036)}
    # the same gaps that trace.idle_gaps gives to the benchmark's own spans
    assert sum(loop.values()) == pytest.approx(
        sum(seconds for _, seconds in trace.idle_gaps(TRACE)))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_the_spans_written_by_hand(name):
    assert reader(name).read(make_run()) == pytest.approx(WANT[name])


def test_wait_dispatch_and_the_loops_own_time_make_up_the_fit_span():
    fit, loop, _ = spans.window(make_run())
    parts = (spans.total_ns(loop, "input:wait")
             + spans.total_ns(loop, "fit:dispatch") + spans.loop_self_ns(loop))
    assert parts == fit["dur_ns"] == 100 * MS


def test_program_spans_reads_the_programs_ring_oldest_first():
    from deeplearning4j_tpu.profiling import Tracer, set_tracer
    mine = Tracer()
    prev = set_tracer(mine)
    try:
        with mine.span("fit"):
            with mine.span("fit_batch", batch=0):
                pass
        mine.instant("mark")        # no span: left out
        got = spans.program_spans()
    finally:
        set_tracer(prev)
    assert [e["name"] for e in got] == ["fit", "fit_batch"]
    assert got[1]["parent"] == got[0]["id"]
    run = types.SimpleNamespace(measures=MEASURES, trace=TRACE)
    # this process's ring has no pair
    assert spans.fit_spans(run) == (None, None)


def test_a_program_from_before_the_spans_makes_every_reader_leave_its_metric(
        monkeypatch):
    """The driver runs the parent commit under this benchmark: its ring
    holds events without ``id`` or ``ts_ns`` and its tracer module has no
    ``self_times``. No reader may raise."""
    from deeplearning4j_tpu.profiling import tracer
    old = {"traceEvents": [{"name": "fit_batch", "ph": "X", "ts": 1.0,
                            "dur": 2.0, "pid": 1, "tid": 1,
                            "args": {"it": 1}}]}
    monkeypatch.setattr(tracer, "get_tracer", lambda: types.SimpleNamespace(
        export=lambda: old))
    monkeypatch.delattr(tracer, "self_times")
    run = types.SimpleNamespace(measures=MEASURES, trace=TRACE)
    assert spans.program_spans() == []
    assert {name: reader(name).read(run) for name in WANT} == dict.fromkeys(
        WANT)
    assert spans.loop_self_ns(RING) is None


def test_manifest_has_the_eight_program_span_metrics_and_passes():
    m = manifest.load(ROOT)
    assert manifest.problems(m, ROOT) == []
    added = [p for p in m["per_layer"] if p["source"] == "program_span"]
    assert [p["name"] for p in added] == [
        "fit_loop_self_ms", "fit_dispatch_ms", "feed_host_work_ms",
        "feed_h2d_ms", "feed_backpressure_share", "input_ready_share",
        "idle_in_input_wait_share", "idle_in_dispatch_share"]
    assert sorted(p["name"] for p in added) == sorted(WANT)
    assert m["per_layer"][-8:] == added         # appended, nothing between
    for p in added:
        assert p["workloads"] == ["resnet50_train_1chip"]
        assert p["moves"] == "train_samples_per_s_chip"
    assert {p["layer"] for p in added} == {"entry_training", "input_feed",
                                           "device"}


# ---------------------------------------------------------------------------
# the pair recorded on the chip
# ---------------------------------------------------------------------------

RECORDED = os.path.join(ROOT, "benchmark", "testdata",
                        "resnet50_train_spans_v5e")


def recorded_run():
    with open(RECORDED + ".spans.json") as f:
        kept = json.load(f)
    run = types.SimpleNamespace(
        spans=kept["spans"], measures=kept["measures"],
        trace=trace.load_json(RECORDED + ".trace.json.gz"))
    return run, kept["readings"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_on_the_recorded_pair_what_the_chip_run_printed(name):
    run, readings = recorded_run()
    assert reader(name).read(run) == pytest.approx(readings[name], rel=1e-9)


def test_recorded_pair_hangs_together():
    """A second or so of the ResNet-50 cell on the v5e (PR 25): the anchor
    holds, the three parts make up the window's fit span, and the batch
    numbers of the two threads agree."""
    run, _ = recorded_run()
    fit, loop, feed = spans.window(run)
    assert (spans.total_ns(loop, "input:wait")
            + spans.total_ns(loop, "fit:dispatch")
            + spans.loop_self_ns(loop)) == fit["dur_ns"]
    steps = len(spans.named(loop, "fit_batch"))
    assert steps == run.measures["steps"] >= 3
    batch = lambda events, name: [e["args"]["batch"]
                                  for e in spans.named(events, name)]
    assert batch(loop, "fit_batch") == list(range(steps))
    assert batch(loop, "input:wait") == list(range(steps + 1))
    assert batch(feed, "input:produce") == list(range(steps + 1))
    placed = spans.on_trace_clock(run)
    assert placed is not None
    anchor, = trace.spans(run.trace, "bench:fit")
    assert placed[0]["ts_ns"] == anchor[1]
    assert abs(placed[0]["dur_ns"] - anchor[2]) <= spans.ANCHOR_NS
    idle = spans.idle_by_span(run, "loop")
    assert sum(idle.values()) == pytest.approx(sum(
        seconds for _, seconds in trace.idle_gaps(run.trace, n=100)))
