"""The reduction from a trace to numbers: on a trace written by hand, whose
answers are worked out in the comments, and on a small trace recorded on
the chip and kept with the benchmark."""

import glob
import os

import pytest

from benchmark import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# One device, a window of 1000 ns. Operations: a [0,100), b [50,150) (they
# overlap: busy 0-150), c [300,400), an all-reduce [400,450) of which
# [430,450) lies under d [430,500). Busy: 150 + 200 = 350 ns. The idle gaps
# are [150,300) and [500,1000). The host waited on the feed over [150,290).
HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["a", 0, 100], ["b", 50, 100], ["c", 300, 100],
            ["all-reduce.1", 400, 50], ["d", 430, 70]]},
        {"name": "XLA Modules", "events": [
            ["jit_train_step(123)", 0, 150], ["jit_train_step(123)", 300, 200],
            ["jit_other(9)", 600, 10]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            ["bench:window", 0, 1000], ["bench:fit", 0, 1000],
            ["bench:feed_wait", 150, 140]]}]},
]}


def test_busy_union_and_idle_share():
    assert trace.window_ns(HAND) == (0, 1000)
    assert trace.busy_seconds(HAND) == [pytest.approx(350e-9)]
    assert trace.window_seconds(HAND) == pytest.approx(1000e-9)
    assert trace.idle_share(HAND) == pytest.approx(0.65)


def test_a_programs_runs_and_a_kernels_time():
    assert trace.module_runs(HAND, r"^jit_train_step\b") == [
        pytest.approx(150e-9), pytest.approx(200e-9)]
    assert trace.module_runs(HAND, r"^jit_nothing\b") == []
    # a and b overlap: their union is counted once
    assert trace.op_seconds(HAND, r"^[ab]$") == pytest.approx(150e-9)
    assert trace.op_seconds(HAND, r"^zzz") == 0.0


def test_exposed_collective_time():
    # [400,450) less the part under d, [430,450): 30 ns
    assert trace.exposed_collective_seconds(HAND) == pytest.approx(30e-9)


def test_breakdown_names_the_top_ops_and_gives_gaps_to_host_spans():
    ops = dict(trace.top_device_ops(HAND))
    assert ops["a"] == pytest.approx(100e-9) and len(ops) == 5
    gaps = dict(trace.idle_gaps(HAND, min_gap_ns=10))
    # [150,300) goes to the innermost span that covers most of it
    assert gaps["feed_wait"] == pytest.approx(150e-9)
    assert gaps["fit"] == pytest.approx(500e-9)


def test_window_falls_back_to_the_devices_clock():
    shifted = {"planes": [HAND["planes"][0], {
        "name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench:window", 10**9, 1000]]}]}]}
    assert trace.window_ns(shifted) == (0, 500)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.window_ns({"planes": [HAND["planes"][1]]})


def test_interval_arithmetic():
    assert trace.merge([[5, 7], [0, 2], [1, 3], [7, 9]]) == [[0, 3], [5, 9]]
    assert trace.total(trace.intersect([[0, 3], [5, 9]], [[2, 6]])) == 2
    assert trace.clip([[0, 3], [5, 9]], 2, 6) == [[2, 3], [5, 6]]


RECORDED = sorted(glob.glob(os.path.join(
    ROOT, "benchmark", "testdata", "*.trace.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_reduces(path):
    """A trace recorded on the v5e (cut to a fraction of a second): the
    reductions run on it and give shares inside their ranges."""
    t = trace.load_json(path)
    assert trace.device_planes(t)
    busy, window = trace.busy_seconds(t), trace.window_seconds(t)
    assert all(0 < b <= window for b in busy)
    assert 0.0 <= trace.idle_share(t) < 1.0
    assert len(trace.top_device_ops(t)) == 10
    assert trace.idle_gaps(t)
    assert trace.module_runs(t, r"^jit_")


def test_recorded_resnet_trace_gives_the_numbers_worked_out_for_it():
    """Half a second of the ResNet-50 cell's first trace on the v5e (PR 24):
    four runs of ``jit_train_step`` of 52.3 ms each and, between the third
    and the fourth, 0.26 s in which the host sat in ``fit`` under the
    profiler."""
    t = trace.load_json(os.path.join(
        ROOT, "benchmark", "testdata", "resnet50_train_v5e.trace.json.gz"))
    runs = trace.module_runs(t, r"^jit_train_step\b")
    assert len(runs) == 4
    assert all(r == pytest.approx(0.0523, abs=5e-4) for r in runs)
    assert trace.window_seconds(t) == pytest.approx(0.5)
    assert trace.busy_seconds(t) == [pytest.approx(0.219413145)]
    assert trace.idle_share(t) == pytest.approx(0.56117371)
    assert trace.idle_gaps(t)[0][0] == "fit"
    assert trace.exposed_collective_seconds(t) == 0.0
    assert trace.op_seconds(t, r"^fusion\.72$") > 0
