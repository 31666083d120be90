"""Profiling subsystem: span tracer (Chrome trace-event schema), metrics
registry (JSON + Prometheus text), compile watcher, memory watermark,
compiled-step cost analysis (analytic MFU vs a hand-computed LeNet FLOP
count), and the black-box
diagnostics leg — flight recorder ring, stall watchdog bundles (the
ISSUE-17 acceptance gates: a wedged trainer step and a hung backend
probe must both leave a bundle naming the stalled phase), and the
postmortem reader."""

import json
import os
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.profiling import (
    CompileWatcher, Counter, DeviceMemoryWatermark, FlightRecorder, Gauge,
    Histogram, MetricsRegistry, StallWatchdog, Tracer, analytic_mfu,
    assemble_bundle, get_flightrec, get_registry, get_tracer, peak_flops,
    set_flightrec, set_tracer, train_step_cost,
)
from deeplearning4j_tpu.profiling import watchdog as watchdog_mod
from deeplearning4j_tpu.profiling.tracer import ANNOTATION_PREFIX, self_times
from deeplearning4j_tpu.profiling.metrics import set_registry
from deeplearning4j_tpu.profiling.watchdog import (
    BUNDLE_FORMAT, beat, clear_beats, heartbeat_ages,
)


@pytest.fixture
def fresh_diag():
    """Isolated tracer + flight recorder + registry + heartbeats for the
    watchdog/bundle tests, restored afterwards."""
    tr, rec, reg = Tracer(), FlightRecorder(), MetricsRegistry()
    prev_tr = set_tracer(tr)
    prev_rec = set_flightrec(rec)
    prev_reg = set_registry(reg)
    clear_beats()
    try:
        yield tr, rec, reg
    finally:
        set_tracer(prev_tr)
        set_flightrec(prev_rec)
        set_registry(prev_reg)
        clear_beats()


# ---------------------------------------------------------------- tracer

def test_span_nesting_and_chrome_schema_roundtrip():
    tr = Tracer()
    with tr.span("outer", rung="lenet"):
        with tr.span("inner"):
            pass
    blob = json.loads(tr.to_json())  # schema round-trip through JSON
    evs = blob["traceEvents"]
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    for e in evs:
        # the Chrome trace-event contract Perfetto parses: complete
        # events with numeric microsecond ts/dur and pid/tid
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    assert outer["args"] == {"rung": "lenet"}
    # containment: inner lies within outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_open_span_stack_names_the_hang():
    tr = Tracer()
    h1 = tr.begin("rung:full")
    h2 = tr.begin("warmup")
    assert tr.open_span_stack() == ["rung:full", "warmup"]
    tr.end(h2)
    assert tr.open_span_stack() == ["rung:full"]
    tr.end(h1)
    assert tr.open_span_stack() == []


def test_error_span_stack_survives_context_unwind():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("rung:lenet"):
            with tr.span("warmup"):
                raise RuntimeError("boom")
    assert tr.open_span_stack() == []  # contexts closed on unwind...
    # ...but the stack the exception unwound through is preserved
    assert tr.error_span_stack() == ["rung:lenet", "warmup"]


def test_begin_end_across_threads():
    tr = Tracer()
    h = tr.begin("prefetch")  # async-work pattern: end on another thread
    t = threading.Thread(target=tr.end, args=(h,))
    t.start()
    t.join()
    assert tr.open_span_stack() == []
    assert [e["name"] for e in tr.export()["traceEvents"]] == ["prefetch"]


def _by_name(tr):
    return {e["name"]: e for e in tr.export()["traceEvents"]}


def test_span_ids_and_parents_nested():
    tr = Tracer()
    with tr.span("outer") as outer:
        with tr.span("first") as first:
            with tr.span("leaf"):
                pass
        with tr.span("second"):
            pass
    with tr.span("alone"):
        pass
    ev = _by_name(tr)
    ids = [e["id"] for e in ev.values()]
    assert all(isinstance(i, int) for i in ids) and len(set(ids)) == 5
    assert ev["outer"]["parent"] is None and ev["alone"]["parent"] is None
    assert ev["first"]["parent"] == ev["second"]["parent"] == outer.id
    assert ev["leaf"]["parent"] == first.id == ev["first"]["id"]
    json.dumps(tr.export())     # id and parent survive the exporter


def test_span_parent_is_per_thread():
    """A span's parent is the innermost span open on ITS thread: a span
    open on another thread at the same time is no parent."""
    tr = Tracer()
    started, release = threading.Event(), threading.Event()

    def feed():
        with tr.span("feed:outer"):
            started.set()
            assert release.wait(5)
            with tr.span("feed:inner"):
                pass

    t = threading.Thread(target=feed)
    with tr.span("main:outer"):
        t.start()
        assert started.wait(5)
        with tr.span("main:inner"):
            release.set()
            t.join(5)
    assert not t.is_alive()
    ev = _by_name(tr)
    assert ev["main:inner"]["parent"] == ev["main:outer"]["id"]
    assert ev["feed:inner"]["parent"] == ev["feed:outer"]["id"]
    assert ev["feed:outer"]["parent"] is None
    assert ev["feed:outer"]["tid"] != ev["main:outer"]["tid"]


def test_begin_end_across_threads_keeps_id_parent_and_thread():
    tr = Tracer()
    with tr.span("outer") as outer:
        h = tr.begin("prefetch")        # parent: open here, on this thread
        t = threading.Thread(target=tr.end, args=(h,))
        t.start()
        t.join(5)
        with tr.span("after") as after:     # the ended span is off the stack
            pass
    ev = _by_name(tr)
    assert ev["prefetch"]["parent"] == outer.id
    assert ev["prefetch"]["tid"] == threading.get_ident()
    assert ev["after"]["parent"] == outer.id and after.id > h.id


def test_tracer_clock_is_integer_nanoseconds():
    tr = Tracer()
    before = time.perf_counter_ns()
    with tr.span("s") as h:
        time.sleep(0.002)
    tr.instant("mark")
    tr.complete("compile", 5_000_000)
    after = time.perf_counter_ns()
    s, mark, done = tr.export()["traceEvents"]
    for e in (s, mark, done):
        assert type(e["ts_ns"]) is int
        assert e["ts"] == e["ts_ns"] / 1e3      # Chrome's microseconds
    assert before <= s["ts_ns"] <= mark["ts_ns"] <= after
    assert type(s["dur_ns"]) is int and s["dur_ns"] >= 2_000_000
    assert s["dur"] == s["dur_ns"] / 1e3 and h.dur_ns == s["dur_ns"]
    assert done["dur_ns"] == 5_000_000 and done["parent"] is None
    # backdated by its duration from the instant it was reported
    assert mark["ts_ns"] <= done["ts_ns"] + 5_000_000 <= after
    assert "dur_ns" not in mark and "id" not in mark


def _ev(i, parent, t0, dur):
    return {"name": f"s{i}", "id": i, "parent": parent, "ts_ns": t0,
            "dur_ns": dur}


@pytest.mark.parametrize("events,want", [
    # a leaf's self time is its duration; a parent's is less its children
    ([_ev(1, None, 0, 100), _ev(2, 1, 10, 30), _ev(3, 1, 50, 20)],
     {1: 50, 2: 30, 3: 20}),
    # grandchildren count against their parent only
    ([_ev(1, None, 0, 100), _ev(2, 1, 10, 60), _ev(3, 2, 20, 40)],
     {1: 40, 2: 20, 3: 40}),
    # children that overlap (ended on another thread) cover once, and a
    # child that outlives its parent covers only the parent's interval
    ([_ev(1, None, 0, 100), _ev(2, 1, 10, 50), _ev(3, 1, 40, 30),
      _ev(4, 1, 90, 40)], {1: 30, 2: 50, 3: 30, 4: 40}),
    # an instant has no id and is passed over
    ([_ev(1, None, 0, 10), {"name": "mark", "ph": "i", "ts_ns": 5}],
     {1: 10}),
])
def test_self_times(events, want):
    assert self_times(events) == want


def test_self_times_of_recorded_spans_sum_to_the_root():
    tr = Tracer()
    with tr.span("root"):
        for _ in range(3):
            with tr.span("step"):
                with tr.span("leaf"):
                    time.sleep(0.001)
    events = tr.export()["traceEvents"]
    self_ns = self_times(events)
    root = next(e for e in events if e["name"] == "root")
    assert sum(self_ns.values()) == root["dur_ns"]
    assert all(v >= 0 for v in self_ns.values())


def test_spans_are_mirrored_into_the_profilers_trace(tmp_path):
    """Under ``jax.profiler.trace`` a ``with tracer.span`` lands in the
    host plane as ``dl4j:<name>``; a begin/end pair does not."""
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("mirrored", batch=3):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
        tr.end(tr.begin("not_mirrored"))
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert ANNOTATION_PREFIX + "mirrored" in names
    assert not any("not_mirrored" in n for n in names)
    assert {e["name"] for e in tr.export()["traceEvents"]} == {
        "mirrored", "not_mirrored"}


def test_tracer_imports_and_records_without_jax():
    """``profiling/tracer.py`` never imports jax, and records with jax
    absent from ``sys.modules``: a fresh interpreter proves both."""
    import subprocess
    import sys
    from deeplearning4j_tpu.profiling import tracer as tracer_mod
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('tracer', "
        f"{tracer_mod.__file__!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "sys.modules['tracer'] = m\n"
        "spec.loader.exec_module(m)\n"
        "t = m.Tracer()\n"
        "with t.span('outer'):\n"
        "    with t.span('inner', k=1):\n"
        "        pass\n"
        "ev = t.export()['traceEvents']\n"
        "assert [e['name'] for e in ev] == ['inner', 'outer'], ev\n"
        "assert ev[0]['parent'] == ev[1]['id']\n"
        "assert 'jax' not in sys.modules, 'tracer imported jax'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_tracer_bounded_buffer_drops_and_counts():
    tr = Tracer(max_events=10)
    for i in range(25):
        with tr.span(f"s{i}"):
            pass
    assert tr.event_count() <= 10
    assert tr.dropped >= 10
    assert tr.export()["otherData"]["dropped_events"] == tr.dropped
    # every event source is bounded, not just end(): a compile-watcher
    # recompile storm (complete) or marker flood (instant) can't leak
    for i in range(30):
        tr.complete(f"c{i}", 1000)
        tr.instant(f"i{i}")
    assert tr.event_count() <= 10


def test_tracer_thread_safety_smoke():
    tr = Tracer()

    def work(n):
        for i in range(200):
            with tr.span(f"t{n}"):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.event_count() == 800
    assert tr.open_span_stack() == []


def test_global_tracer_swap():
    mine = Tracer()
    prev = set_tracer(mine)
    try:
        assert get_tracer() is mine
    finally:
        set_tracer(prev)
    assert get_tracer() is prev


def test_trainers_emit_into_global_tracer():
    """The containers and ParallelTrainer emit spans into the default
    tracer during a real fit."""
    from deeplearning4j_tpu import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater("sgd", learning_rate=0.05).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(8, 6)).astype(np.float32),
                 np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])
    mine = Tracer()
    prev = set_tracer(mine)
    try:
        net = MultiLayerNetwork(conf).init()
        net.fit_batch(ds)
        names = {e["name"] for e in mine.export()["traceEvents"]}
        assert "fit_batch" in names
        tr = ParallelTrainer(MultiLayerNetwork(conf).init(),
                             MeshContext.create(n_data=2, n_model=1))
        tr.fit_batch(ds)
        names = {e["name"] for e in mine.export()["traceEvents"]}
        assert {"shard", "step", "listener"} <= names
    finally:
        set_tracer(prev)


# --------------------------------------------------------------- metrics

def test_counter_gauge_histogram_math():
    reg = MetricsRegistry()
    c = reg.counter("steps_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("bytes_in_use")
    g.set(100)
    g.set_max(40)   # ratchet keeps the max
    assert g.value == 100
    g.set_max(250)
    assert g.value == 250
    h = reg.histogram("step_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.7, 5.0, 99.0):
        h.observe(v)
    assert h.count == 5 and abs(h.sum - 105.25) < 1e-9
    cum = dict(h.cumulative())
    assert cum[0.1] == 1 and cum[1.0] == 3 and cum[10.0] == 4
    assert cum[float("inf")] == 5


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1.0, 1.0, 2.0))  # non-increasing


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("jax_compile_total", help="compiles").inc(3)
    reg.gauge("device_bytes_in_use").set(2048)
    h = reg.histogram("lat", buckets=(0.5, 2.0))
    h.observe(0.3)
    h.observe(1.0)
    text = reg.to_prometheus()
    assert "# TYPE jax_compile_total counter" in text
    assert "jax_compile_total 3" in text
    assert "# HELP jax_compile_total compiles" in text
    assert "device_bytes_in_use 2048" in text
    assert 'lat_bucket{le="0.5"} 1' in text
    assert 'lat_bucket{le="2"} 2' in text
    assert 'lat_bucket{le="+Inf"} 2' in text
    assert "lat_sum 1.3" in text and "lat_count 2" in text
    d = reg.to_dict()
    assert d["jax_compile_total"] == 3
    assert d["lat"]["count"] == 2


def test_registry_timed_context():
    reg = MetricsRegistry()
    with reg.timed("op_seconds"):
        time.sleep(0.01)
    h = reg.get("op_seconds")
    assert h.count == 1 and h.sum >= 0.01


# -------------------------------------------------------------- watchers

def test_compile_watcher_counts_compiles():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    w = CompileWatcher(registry=reg, tracer=Tracer()).install()
    try:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,)))
    finally:
        w.uninstall()
    assert reg.counter("jax_trace_total").value >= 1
    assert reg.counter("jax_compile_total").value >= 1
    assert reg.counter("jax_compile_seconds_total").value > 0
    assert reg.get("jax_compile_seconds").count >= 1


def test_compile_watcher_wrap_warns_on_shape_change(caplog):
    import logging

    reg = MetricsRegistry()
    w = CompileWatcher(registry=reg, tracer=Tracer())
    calls = []
    fn = w.wrap(lambda x: calls.append(np.shape(x)), "train_step")
    with caplog.at_level(logging.WARNING,
                         logger="deeplearning4j_tpu.profiling.watchers"):
        fn(np.zeros((4, 2)))
        fn(np.zeros((4, 2)))   # same signature: silent
        assert reg.counter("jit_shape_recompiles_total").value == 0
        fn(np.zeros((8, 2)))   # shape change: counted + warned
    assert reg.counter("jit_shape_recompiles_total").value == 1
    assert any("argument shapes changed" in r.message
               for r in caplog.records)
    assert len(calls) == 3  # pass-through untouched


def test_memory_watermark_sampler_cpu_safe():
    # CPU memory_stats() returns None: the sampler degrades to a no-op
    # without touching the registry or raising
    reg = MetricsRegistry()
    s = DeviceMemoryWatermark(registry=reg, interval_s=0.01)
    assert s.sample() is None or isinstance(s.sample(), dict)
    s.start()
    time.sleep(0.05)
    s.stop()  # clean shutdown, no exception


def test_memory_watermark_ratchets(monkeypatch):
    import deeplearning4j_tpu.profiling.watchers as W
    seq = iter([{"bytes_in_use": 100}, {"bytes_in_use": 900},
                {"bytes_in_use": 300}])
    monkeypatch.setattr(W, "device_memory_stats", lambda device=None:
                        next(seq))
    reg = MetricsRegistry()
    s = DeviceMemoryWatermark(registry=reg)
    for _ in range(3):
        s.sample()
    assert reg.gauge("device_bytes_in_use").value == 300  # latest
    assert reg.gauge("device_bytes_in_use_watermark").value == 900


# ------------------------------------------------- cost analysis / MFU

def test_analytic_mfu_arithmetic():
    # 1e12 FLOPs in 0.5s on a 2e12-peak chip = 100% MFU
    assert analytic_mfu(1e12, 0.5, 2e12) == pytest.approx(1.0)
    assert analytic_mfu(1e12, 1.0, 2e12) == pytest.approx(0.5)
    assert analytic_mfu(1e12, 1.0, 2e12, n_chips=2) == pytest.approx(0.25)
    assert analytic_mfu(0, 1.0, 2e12) is None
    assert analytic_mfu(1e12, 0.0, 2e12) is None
    assert analytic_mfu(1e12, 1.0, None) is None


def test_peak_flops_table():
    assert peak_flops("TPU v5 lite") == 197e12
    assert peak_flops("TPU v4") == 275e12
    assert peak_flops("cpu") is None  # a CPU has no MFU
    with pytest.raises(ValueError, match="quantum abacus"):
        peak_flops("quantum abacus")  # unknown accelerator: no default


def test_lenet_train_step_cost_matches_hand_count():
    """XLA's cost model for the REAL LeNet train step vs the
    hand-computed forward FLOPs: conv towers + dense head, valid
    convolutions (28->24->12->8->4), 2 FLOPs per MAC. A training step
    is fwd + bwd ~= 3x forward; the XLA count must land in that band —
    the arithmetic pin for every MFU this subsystem reports."""
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.models.lenet import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    B = 8
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(B, 28, 28, 1)).astype(np.float32),
                 np.eye(10, dtype=np.float32)[rng.integers(0, 10, B)])
    net = MultiLayerNetwork(lenet_mnist()).init()
    cost = net.cost_analysis(ds)
    # hand count, MACs per example (2 FLOPs each):
    #   conv1: 24*24*20 outputs x 5*5*1  kernel = 288,000
    #   conv2:   8*8*50 outputs x 5*5*20 kernel = 1,600,000
    #   dense:  800 -> 500                      = 400,000
    #   head:   500 -> 10                       = 5,000
    fwd = 2 * (288_000 + 1_600_000 + 400_000 + 5_000) * B
    flops = cost["flops_per_step"]
    assert flops is not None
    # fwd+bwd is ~3x fwd; allow pooling/softmax/optimizer slack
    assert 2.5 * fwd <= flops <= 4.0 * fwd, (flops, fwd)
    assert cost["flops_per_example"] == pytest.approx(flops / B)
    assert cost["bytes_accessed"] and cost["bytes_accessed"] > 0
    assert cost["arithmetic_intensity"] == pytest.approx(
        flops / cost["bytes_accessed"])
    assert cost["batch"] == B
    # CPU run: no peak, so no MFU; against a stated peak it is defined
    assert cost["peak_flops_per_chip"] is None
    assert analytic_mfu(flops, 0.01, cost["peak_flops_per_chip"]) is None
    assert analytic_mfu(flops, 0.01, 1e12) == pytest.approx(flops / 1e10)


def test_graph_container_cost_analysis():
    """ComputationGraph surfaces the same cost analysis."""
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.conf.inputs import InputType

    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater("sgd", learning_rate=0.1).weight_init("xavier")
            .graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(8)).build())
    rng = np.random.default_rng(1)
    ds = DataSet(rng.normal(size=(4, 8)).astype(np.float32),
                 np.eye(4, dtype=np.float32)[rng.integers(0, 4, 4)])
    net = ComputationGraph(conf).init()
    cost = net.cost_analysis(ds)
    # dense 8->16 + head 16->4: tiny but nonzero and batch-scaled
    assert cost["flops_per_step"] and cost["flops_per_step"] > 0
    assert cost["batch"] == 4


def test_training_stats_folds_cost_analysis():
    from deeplearning4j_tpu.optimize.training_stats import TrainingStats

    s = TrainingStats()
    s.record("step", 0.01)
    s.record("step", 0.01)
    s.set_cost({"flops_per_step": 2e9, "peak_flops_per_chip": 1e12,
                "bytes_accessed": 1e6})
    e = s.export()
    assert e["cost_analysis"]["flops_per_step"] == 2e9
    # mean step 0.01s: 2e9 / (0.01 * 1e12) = 0.2
    assert e["analytic_mfu"] == pytest.approx(0.2)
    # without a step phase there is no MFU (nothing measured)
    s2 = TrainingStats()
    s2.set_cost({"flops_per_step": 2e9, "peak_flops_per_chip": 1e12})
    assert "analytic_mfu" not in s2.export()


def test_ui_server_serves_metrics_endpoints():
    import urllib.request

    from deeplearning4j_tpu.ui.server import UIServer

    reg = get_registry()
    reg.counter("bench_smoke_total").inc(7)
    srv = UIServer(port=0).start()
    try:
        base = srv.url
        text = urllib.request.urlopen(f"{base}/api/metrics").read().decode()
        assert "bench_smoke_total 7" in text
        assert "# TYPE bench_smoke_total counter" in text
        blob = json.loads(urllib.request.urlopen(
            f"{base}/api/metrics.json").read().decode())
        assert blob["bench_smoke_total"] == 7
        # the live diagnostic-bundle endpoint: same shape as the
        # watchdog's on-disk bundle, reason "live"
        dbg = json.loads(urllib.request.urlopen(
            f"{base}/api/debug").read().decode())
        assert dbg["format"] == BUNDLE_FORMAT
        assert dbg["reason"] == "live"
        assert "heartbeats" in dbg and "threads" in dbg
    finally:
        srv.stop()


# --------------------------------------------------- histogram quantiles

def test_histogram_quantile_interpolation():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 3.5, 9.0):   # cum: 1, 2, 4, inf->5
        h.observe(v)
    # rank 2.5 lands in the (2, 4] bucket: 2 + 2 * (0.5 / 2) = 2.5
    assert h.quantile(0.5) == pytest.approx(2.5)
    # rank 1.0 is exactly the first bucket's cum; lower bound is 0
    assert h.quantile(0.2) == pytest.approx(1.0)
    # the +Inf bucket clamps to the highest finite edge
    assert h.quantile(1.0) == 4.0
    assert h.quantile(0.99) == 4.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    blob = h._json()
    assert blob["p50"] == pytest.approx(2.5)
    assert blob["p99"] == 4.0


def test_histogram_quantile_empty_is_none():
    h = Histogram("lat", buckets=(1.0, 2.0))
    assert h.quantile(0.5) is None
    assert h._json()["p50"] is None


# ------------------------------------------------------- flight recorder

def test_flightrec_ring_bounds_under_concurrent_emit():
    rec = FlightRecorder(max_events=256)

    def emit(n):
        for i in range(1000):
            rec.record("sub%d" % n, "tick", i=i)

    threads = [threading.Thread(target=emit, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec) == 256              # bounded, never grows past cap
    assert rec.total_recorded == 8000   # but every emit was counted
    tail = rec.tail(16)
    assert len(tail) == 16
    for ev in tail:
        assert set(ev) == {"ts", "subsystem", "kind", "detail"}
    # oldest-first ordering within the tail
    assert all(a["ts"] <= b["ts"] for a, b in zip(tail, tail[1:]))


def test_flightrec_detail_is_json_clean():
    rec = FlightRecorder(max_events=8)
    rec.record("serving", "kv_evicted", row=3, reason="lru",
               obj=object())           # non-JSON value -> repr()'d
    ev = rec.tail()[-1]
    assert ev["detail"]["row"] == 3
    assert isinstance(ev["detail"]["obj"], str)
    json.dumps(rec.tail())             # whole tail JSON-serializable
    with pytest.raises(ValueError):
        FlightRecorder(max_events=0)


def test_flightrec_global_swap_and_module_record(fresh_diag):
    from deeplearning4j_tpu.profiling import flightrec as fr
    _tr, rec, _reg = fresh_diag
    assert get_flightrec() is rec
    fr.record("bench", "probe_started", timeout_s=5)
    assert rec.tail()[-1]["kind"] == "probe_started"


# --------------------------------------------- tracer drop accounting

def test_tracer_dropped_events_feed_registry_counter(fresh_diag):
    _tr, _rec, reg = fresh_diag
    tr = Tracer(max_events=5)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped >= 15
    assert reg.counter("tracer_events_dropped").value == tr.dropped


def test_tracer_open_spans_by_thread(fresh_diag):
    tr, _rec, _reg = fresh_diag
    h1 = tr.begin("outer")
    h2 = tr.begin("inner")
    spans = tr.open_spans_by_thread()
    me = threading.get_ident()
    assert [s["name"] for s in spans[me]] == ["outer", "inner"]
    tr.end(h2)
    tr.end(h1)
    assert tr.open_spans_by_thread() == {}


# ------------------------------------------------------- stall watchdog

def test_watchdog_heartbeat_ages(fresh_diag):
    beat("elastic")
    ages = heartbeat_ages()
    assert 0.0 <= ages["elastic"] < 5.0


def test_watchdog_stale_heartbeat_writes_bundle(tmp_path, fresh_diag):
    """A wedged thread (open spans + stale beat) must produce a bundle
    on disk whose culprit names the deepest open span of THAT thread."""
    tr, rec, _reg = fresh_diag
    release = threading.Event()
    armed = threading.Event()

    def wedge():
        h1 = tr.begin("train:step")
        h2 = tr.begin("train:collective")
        beat("trainer")               # last sign of life, then hang
        rec.record("trainer", "dispatch", step=7)
        armed.set()
        release.wait(20)
        tr.end(h2)
        tr.end(h1)

    wd = StallWatchdog(str(tmp_path), interval_s=0.05)
    t = threading.Thread(target=wedge, name="wedged-trainer")
    try:
        wd.watch("trainer", deadline_s=0.25)
        t.start()
        assert armed.wait(5)
        deadline = time.monotonic() + 8
        while wd.last_bundle_path is None and time.monotonic() < deadline:
            time.sleep(0.02)
        path = wd.last_bundle_path
        assert path is not None, "watchdog never fired on the stale beat"
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["format"] == BUNDLE_FORMAT
        assert bundle["reason"] == "stalled_heartbeat"
        assert bundle["stale"]["subsystem"] == "trainer"
        assert bundle["stale"]["age_s"] > 0.25
        assert "trainer" in bundle["heartbeats"]
        # the culprit chain: stale beat -> its tid -> deepest open span
        assert bundle["culprit"]["span"] == "train:collective"
        assert bundle["culprit"]["via"] == "stale_thread"
        spans = bundle["open_spans"][str(bundle["stale"]["tid"])]
        assert [s["name"] for s in spans] == ["train:step",
                                              "train:collective"]
        # the wedged thread's Python stack is in the dump
        names = {th["name"] for th in bundle["threads"]}
        assert "wedged-trainer" in names
        assert any(ev["kind"] == "dispatch"
                   for ev in bundle["flight_tail"])
        assert isinstance(bundle["metrics"], dict)
        # one bundle per episode: no second dump while still stale
        seq_before = wd.last_bundle_path
        time.sleep(0.3)
        assert wd.last_bundle_path == seq_before
    finally:
        release.set()
        t.join(5)
        wd.close()


def test_watchdog_threads_return_to_baseline(tmp_path):
    """Teardown hygiene: close() joins the monitor; enumerate() returns
    to baseline (the contract test_thread_hygiene enforces stack-wide)."""
    baseline = set(threading.enumerate())
    wd = StallWatchdog(str(tmp_path), interval_s=0.05)
    assert any(t.name == "stall-watchdog" for t in threading.enumerate())
    wd.watch("x", 10.0)
    wd.close()
    wd.close()                         # idempotent
    deadline = time.monotonic() + 8
    while time.monotonic() < deadline:
        if set(threading.enumerate()) <= baseline:
            break
        time.sleep(0.02)
    leaked = [t.name for t in set(threading.enumerate()) - baseline]
    assert not leaked, f"leaked threads: {leaked}"


def test_watchdog_recovered_heartbeat_rearms(tmp_path, fresh_diag):
    wd = StallWatchdog(str(tmp_path), interval_s=0.05)
    try:
        wd.watch("svc", deadline_s=0.15)
        deadline = time.monotonic() + 8
        while wd.last_bundle_path is None and time.monotonic() < deadline:
            time.sleep(0.02)
        first = wd.last_bundle_path
        assert first is not None
        beat("svc")                    # recovery re-arms the episode
        time.sleep(0.1)
        deadline = time.monotonic() + 8
        while wd.last_bundle_path == first \
                and time.monotonic() < deadline:
            time.sleep(0.02)           # goes stale again -> second dump
        assert wd.last_bundle_path != first
    finally:
        wd.close()


def test_assemble_bundle_without_watchdog(fresh_diag):
    tr, _rec, _reg = fresh_diag
    with tr.span("serve:decode"):
        bundle = assemble_bundle(reason="live")
    assert bundle["format"] == BUNDLE_FORMAT
    assert bundle["stale"] is None
    me = str(threading.get_ident())
    # no stale heartbeat: falls back to the most recent open span
    assert bundle["culprit"]["span"] == "serve:decode"
    assert me in bundle["open_spans"]
    json.dumps(bundle, default=repr)


# ---------------------------------------------- acceptance: wedged runs

def test_wedged_trainer_step_bundle_names_straggle(tmp_path, fresh_diag):
    """ISSUE-17 acceptance, half 1: a faultinject stall inside a trainer
    step goes stale against the elastic heartbeat and the bundle's
    deepest open span names the stalled phase (elastic:straggle)."""
    from deeplearning4j_tpu.resilience import faultinject
    from deeplearning4j_tpu.resilience.elastic import ElasticTrainer
    from deeplearning4j_tpu.resilience.faultinject import (Fault,
                                                           FaultSchedule)
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    def net():
        return MultiLayerNetwork(
            NeuralNetConfiguration.builder().seed(7)
            .updater("sgd", learning_rate=0.05).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build()).init()

    rng = np.random.default_rng(0)
    batches = [DataSet(rng.normal(size=(8, 6)).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])
               for _ in range(3)]
    ckpt = tmp_path / "ckpt"
    bundles = tmp_path / "bundles"
    trainer = ElasticTrainer(net, ckpt, checkpoint_every=10,
                             step_timeout_s=30.0,
                             heartbeat_interval_s=0.05)
    wd = StallWatchdog(str(bundles), interval_s=0.05)
    try:
        # step 1 warm-up OUTSIDE the watch: the jit compile is itself
        # slower than the deadline and would fire first, and episode
        # dedup would then swallow the straggle's dump
        trainer.fit(batches[:1], epochs=1)
        faultinject.set_schedule(FaultSchedule(
            [Fault(kind="slow_host", step=3, duration=1.2)]))
        wd.watch("elastic", deadline_s=0.3)
        trainer.fit(batches, epochs=1)   # steps 2, 3 (straggles), 4
        path = wd.last_bundle_path
        assert path is not None, \
            "the straggle never tripped the elastic heartbeat"
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["stale"]["subsystem"] == "elastic"
        # the acceptance bar: the deepest open span names the phase
        assert bundle["culprit"]["span"] == "elastic:straggle"
        kinds = {ev["kind"] for ev in bundle["flight_tail"]
                 if ev["subsystem"] == "elastic"}
        assert "step" in kinds
    finally:
        faultinject.clear()
        wd.close()
        trainer.close()


# ----------------------------------------------------- postmortem reader

def test_postmortem_summarize_names_culprit(tmp_path, fresh_diag):
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "postmortem_cli",
        Path(__file__).resolve().parents[1] / "tools" / "postmortem.py")
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)

    tr, rec, _reg = fresh_diag
    h = tr.begin("serve:decode")
    beat("serving_decode")
    rec.record("serving", "decode_dispatch", rows=4)
    bundle = assemble_bundle(
        reason="stalled_heartbeat",
        stale={"subsystem": "serving_decode", "age_s": 3.0,
               "deadline_s": 1.0, "tid": threading.get_ident()})
    tr.end(h)
    path = tmp_path / "b.json"
    path.write_text(json.dumps(bundle, default=repr))
    loaded = pm.load_bundle(str(path))
    text = pm.summarize(loaded)
    assert "CULPRIT" in text and "serve:decode" in text
    assert "serving_decode" in text
    with pytest.raises(ValueError):
        pm.load_bundle(__file__)       # not a bundle
    assert pm.main(["--self-check"]) == 0
