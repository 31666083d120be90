"""The spans and counters inside the fit loop and the prefetch feed
(``nn/netcommon.py`` ``FitLoopMixin``, ``datasets/iterator.py``): a tiny net
over a ``DevicePrefetchIterator`` on the CPU gives, in both containers,
exactly the tree that ``PERF.md`` section 3 lists; the batch identifiers
agree between the two threads; the counters count what the spans time; and
the jitted step keeps the name, and its operations the layer names, that the
benchmark's readers look for."""

import re
import threading
from collections import Counter

import numpy as np
import pytest

from deeplearning4j_tpu import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    AsyncDataSetIterator, DataSetIterator, DevicePrefetchIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiling import (
    MetricsRegistry, Tracer, self_times, set_tracer,
)
from deeplearning4j_tpu.profiling.metrics import set_registry

N_BATCHES = 5

# table B: span -> parent (None: a root of its thread)
TREE = {
    "fit": None,
    "input:wait": "fit",
    "fit_batch": "fit",
    "fit:split": "fit_batch",
    "fit:rng": "fit_batch",
    "fit:dispatch": "fit_batch",
    "fit:listeners": "fit_batch",
    "input:produce": None,
    "input:read": "input:produce",
    "input:cast": "input:produce",
    "input:h2d": "input:produce",
    "input:put_wait": "input:produce",
}
# the item that ends the stream is read, queued and taken like a batch
PER_ITEM = {"input:wait", "input:produce", "input:read", "input:put_wait"}
# counter -> the span whose durations it sums
SECONDS = {
    "input_stall_seconds_total": "input:wait",
    "fit_dispatch_seconds_total": "fit:dispatch",
    "input_cast_seconds_total": "input:cast",
    "input_h2d_seconds_total": "input:h2d",
    "input_backpressure_seconds_total": "input:put_wait",
}


def _multilayer():
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater("sgd", learning_rate=0.05).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(DenseLayer(n_out=8, activation="tanh", name="second"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf).init(), {"layer0", "second"}


def _graph():
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater("sgd", learning_rate=0.05)
            .graph_builder()
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_out=8, activation="tanh"), "in")
            .add_layer("d2", DenseLayer(n_out=8, activation="tanh"), "d1")
            .add_vertex("add", ElementWiseVertex(op="add"), "d1", "d2")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax"),
                       "add")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(6)).build())
    return ComputationGraph(conf).init(), {"d1", "d2", "add"}


CONTAINERS = pytest.mark.parametrize("build", [_multilayer, _graph],
                                     ids=["multilayer", "graph"])


def _batches(n=N_BATCHES):
    rng = np.random.default_rng(0)
    return [DataSet(rng.normal(size=(8, 6)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])
            for _ in range(n)]


class OnePass(DataSetIterator):
    """Round a feed that is already running: ``fit``'s reset (which would
    drain the feed and start it again) is answered with nothing, as the
    benchmark's proxy answers it."""

    def __init__(self, inner):
        self.inner = inner

    def reset(self):
        pass

    def has_next(self):
        return self.inner.has_next()

    def next(self):
        return self.inner.next()

    def batch_size(self):
        return self.inner.batch_size()

    def async_supported(self):
        return False


@pytest.fixture
def recorded():
    tracer, registry = Tracer(), MetricsRegistry()
    prev_tracer, prev_registry = set_tracer(tracer), set_registry(registry)
    try:
        yield tracer, registry
    finally:
        set_tracer(prev_tracer)
        set_registry(prev_registry)


def _fit_once(net, tracer, batches):
    """One warm fit (the step compiles), then one recorded."""
    net.fit(OnePass(DevicePrefetchIterator(ListDataSetIterator(batches[:1]))))
    tracer.clear()
    net.fit(OnePass(DevicePrefetchIterator(
        ListDataSetIterator(batches), dtype="bfloat16")))
    return tracer.export()["traceEvents"]


@CONTAINERS
def test_fit_over_the_prefetch_feed_gives_the_tree_of_spans(build, recorded):
    tracer, _ = recorded
    net, _ = build()
    events = _fit_once(net, tracer, _batches())
    by_id = {e["id"]: e for e in events}
    assert {e["name"] for e in events} == set(TREE)
    for e in events:
        parent = None if e["parent"] is None else by_id[e["parent"]]["name"]
        assert parent == TREE[e["name"]], (e["name"], parent)
        if parent is not None:      # a child lies on its parent's thread
            assert e["tid"] == by_id[e["parent"]]["tid"]
    counts = Counter(e["name"] for e in events)
    for name in TREE:
        want = 1 if name == "fit" else (
            N_BATCHES + 1 if name in PER_ITEM else N_BATCHES)
        assert counts[name] == want, (name, counts[name])
    # two threads: the loop's and the feed's
    fit_tid = next(e["tid"] for e in events if e["name"] == "fit")
    feed_tid = {e["tid"] for e in events if e["name"] == "input:produce"}
    assert fit_tid == threading.get_ident() and feed_tid != {fit_tid}
    assert len(feed_tid) == 1


@CONTAINERS
def test_batch_ids_agree_between_the_two_threads(build, recorded):
    tracer, _ = recorded
    net, _ = build()
    events = _fit_once(net, tracer, _batches())
    ids = lambda name: [e["args"]["batch"] for e in sorted(
        (e for e in events if e["name"] == name), key=lambda e: e["ts_ns"])]
    assert ids("input:produce") == list(range(N_BATCHES + 1))
    assert ids("input:wait") == list(range(N_BATCHES + 1))
    assert ids("fit_batch") == list(range(N_BATCHES))
    # FIFO: batch k is queued before it is taken, and taken before its step
    at = lambda name, k, edge: next(
        e["ts_ns"] + (e["dur_ns"] if edge == "end" else 0) for e in events
        if e["name"] == name and e["args"]["batch"] == k)
    for k in range(N_BATCHES):
        assert at("input:produce", k, "start") < at("input:wait", k, "end")
        assert at("input:wait", k, "end") <= at("fit_batch", k, "start")
    its = [e["args"]["it"] for e in events if e["name"] == "fit_batch"]
    assert its == list(range(its[0], its[0] + N_BATCHES))
    waits = [e["args"] for e in events if e["name"] == "input:wait"]
    assert all(w["ready"] == int(w["depth"] > 0) for w in waits)


@CONTAINERS
def test_counters_count_what_the_spans_time(build, recorded):
    tracer, registry = recorded
    net, _ = build()
    net.fit(OnePass(DevicePrefetchIterator(
        ListDataSetIterator(_batches()[:1]))))
    names = list(SECONDS) + ["input_batches_total", "fit_steps_total",
                             "input_empty_takes_total"]
    before = {n: registry.counter(n).value for n in names}
    tracer.clear()
    net.fit(OnePass(DevicePrefetchIterator(ListDataSetIterator(_batches()))))
    events = tracer.export()["traceEvents"]
    grew = {n: registry.counter(n).value - before[n] for n in names}
    for counter, span in SECONDS.items():
        spans_s = sum(e["dur_ns"] for e in events if e["name"] == span) / 1e9
        assert abs(grew[counter] - spans_s) < 1e-3, (counter, span)
    assert grew["input_batches_total"] == N_BATCHES
    assert grew["fit_steps_total"] == N_BATCHES
    assert grew["input_empty_takes_total"] == sum(
        1 for e in events
        if e["name"] == "input:wait" and not e["args"]["ready"])


@CONTAINERS
def test_wait_dispatch_and_loop_self_time_sum_to_the_fit_span(build,
                                                              recorded):
    tracer, _ = recorded
    net, _ = build()
    events = _fit_once(net, tracer, _batches())
    fit = next(e for e in events if e["name"] == "fit")
    on_loop = [e for e in events if e["tid"] == fit["tid"]]
    total = lambda name: sum(e["dur_ns"] for e in on_loop
                             if e["name"] == name)
    own = self_times(on_loop)
    loop_self = sum(own[e["id"]] for e in on_loop
                    if e["name"] not in ("input:wait", "fit:dispatch"))
    assert (total("input:wait") + total("fit:dispatch") + loop_self
            == fit["dur_ns"])
    assert loop_self > 0 and total("fit:dispatch") > 0


@CONTAINERS
def test_jitted_step_is_named_and_its_operations_name_their_layer(build):
    """The benchmark's reader finds the step's runs by ``jit_train_step``,
    and ``op_name`` carries the node's or layer's ``jax.named_scope``."""
    net, scopes = build()
    batch = _batches(1)[0]
    net.fit_batch(batch)
    split = net._split if hasattr(net, "_split") else net._batch_args
    lowered = net._train_step_fn.lower(
        net.params, net.opt_state, net.states, *split(batch), net._rng)
    assert re.search(r"\bmodule @jit_train_step\b", lowered.as_text())
    assert re.match(r"^jit_(train_)?step\b",
                    lowered.compile().runtime_executable().hlo_modules()[0]
                    .name)
    text = lowered.as_text(debug_info=True)
    forward = set(re.findall(r'"jit\(train_step\)/jvp\((\w+)\)/', text))
    backward = set(re.findall(
        r'"jit\(train_step\)/transpose\(jvp\((\w+)\)\)/', text))
    assert scopes <= forward, (scopes, sorted(forward))
    # (a sum's backward pass has no operation of its own)
    assert scopes - {"add"} <= backward, (scopes, sorted(backward))


@CONTAINERS
def test_an_async_feed_handed_to_fit_is_not_wrapped_again(build, recorded):
    """``fit`` wraps what is not async in an ``AsyncDataSetIterator``; a
    feed that already has its thread is taken as it is, so one wait is
    one ``input:wait`` span and counted once."""
    tracer, registry = recorded
    net, _ = build()
    feed = DevicePrefetchIterator(ListDataSetIterator(_batches()))
    assert not feed.async_supported()
    assert not AsyncDataSetIterator(ListDataSetIterator([])).async_supported()
    net.fit(feed)
    events = tracer.export()["traceEvents"]
    fit = next(e for e in events if e["name"] == "fit")
    waits = [e for e in events if e["name"] == "input:wait"]
    assert waits and all(e["parent"] == fit["id"] for e in waits)
    stall_s = sum(e["dur_ns"] for e in waits) / 1e9
    assert abs(registry.counter("input_stall_seconds_total").value
               - stall_s) < 1e-3
    # and a plain iterator still gets its one feed thread from fit
    tracer.clear()
    net.fit(ListDataSetIterator(_batches()))
    events = tracer.export()["traceEvents"]
    fit = next(e for e in events if e["name"] == "fit")
    waits = [e for e in events if e["name"] == "input:wait"]
    assert len(waits) == N_BATCHES + 1
    assert all(e["parent"] == fit["id"] for e in waits)
    assert "input:h2d" not in {e["name"] for e in events}
