"""A training cell: ``net.fit`` over the
program's ``DevicePrefetchIterator``, fed host batches that cycle until the
window's clock runs out.

Set-up builds one network from the seed's weights, drives it through its
first ``check_steps`` steps by the window's own call and feed (which is
also the warm-up: the step compiles in the first), and hands the same
object to the window. What those steps gave (each loss, the first gradient
as the optimizer got it, the parameters' change) is compared with the
plain reference once the window has closed and the program's state is
freed.
"""

from __future__ import annotations

import time

from benchmark import compare, program, traffic


def _dataset_types():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import (
        DataSetIterator, DevicePrefetchIterator)
    return DataSet, DataSetIterator, DevicePrefetchIterator


def make_feed(datasets, seconds=None):
    """The benchmark's base iterator: ``datasets`` once in order
    (``seconds`` None), or cycled until ``seconds`` after it was made. It
    counts what it yielded."""
    _, DataSetIterator, _ = _dataset_types()

    class Feed(DataSetIterator):
        def __init__(self):
            self.yielded = 0
            self.deadline = (None if seconds is None
                             else time.perf_counter() + seconds)

        def reset(self):
            pass        # one pass: a window is one epoch

        def has_next(self):
            if self.deadline is None:
                return self.yielded < len(datasets)
            return time.perf_counter() < self.deadline

        def next(self):
            ds = datasets[self.yielded % len(datasets)]
            self.yielded += 1
            return ds

        def batch_size(self):
            return datasets[0].num_examples()

    return Feed()


def make_stall_proxy(inner):
    """Round the program's prefetch iterator: the time the fit loop spends
    waiting inside ``has_next``/``next``, as a span and as a sum."""
    import jax
    _, DataSetIterator, _ = _dataset_types()

    class StallProxy(DataSetIterator):
        stall_s = 0.0

        def _timed(self, call):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:feed_wait"):
                out = call()
            self.stall_s += time.perf_counter() - t0
            return out

        def reset(self):
            pass        # the fit loop resets before its pass; the feed is new

        def has_next(self):
            return self._timed(inner.has_next)

        def next(self):
            return self._timed(inner.next)

        def batch_size(self):
            return inner.batch_size()

        def async_supported(self):
            return False    # or the fit loop would wait in a thread of its own

    return StallProxy()


def run(ctx) -> dict:
    import jax

    DataSet, _, DevicePrefetchIterator = _dataset_types()
    cfg, mix = ctx.cfg, ctx.mix
    weights = ctx.reference.make_weights(cfg, ctx.seed)
    net = program.build_net(cfg, weights)
    host = traffic.train_batches(cfg, mix, ctx.seed)
    datasets = [DataSet(x, y) for x, y, _ in host]
    fit = net.fit

    def fit_feed(feed):
        proxy = make_stall_proxy(DevicePrefetchIterator(
            feed, dtype=mix.get("feed_dtype")))
        with jax.profiler.TraceAnnotation("bench:fit"):
            fit(proxy)
        return proxy

    # the first steps, by the window's own call and feed
    n = mix["check_steps"]
    prog = {"losses": []}
    for i in range(n):
        fit_feed(make_feed(datasets[i:i + 1]))
        prog["losses"].append(float(net.score_value))
        if i == 0:
            prog["grad_norm"] = program.leaf_norms(
                program.first_moment(net.opt_state),
                cfg["first_moment_scale"])
    prog["delta_norm"] = program.change_norms(
        program.flatten(net.params), weights)
    del weights

    # the window (and, in a traced run, a traced stretch after it)
    rotated = datasets[n % len(datasets):] + datasets[:n % len(datasets)]

    def window(seconds):
        compiles = program.counter("jax_compile_total")
        t0 = time.perf_counter()
        feed = make_feed(rotated, seconds)
        proxy = fit_feed(feed)
        jax.block_until_ready(net.params)
        window_s = time.perf_counter() - t0
        return {"window_s": window_s, "steps": feed.yielded,
                "samples": feed.yielded * mix["batch"],
                "stall_s": proxy.stall_s, "compiles_in_window":
                program.counter("jax_compile_total") - compiles}

    ctx.open_window()
    measures = window(ctx.window_seconds)
    if ctx.trace:
        with ctx.traced():
            measures["traced"] = window(ctx.trace_seconds)
    steps, window_s = measures["steps"], measures["window_s"]
    peak = ctx.memory_peak_bytes()

    # free the program's state, then follow the same steps in the reference
    del net, fit, datasets, rotated
    ref_batches = [(b[2] if b[2] is not None else (b[0], b[1]))
                   for b in host[:n]]
    del host
    ref = reference_steps(ctx, ref_batches)
    numbers = compare.training_numbers(prog, ref)
    # asked for by tools/readings.py alone, never by a run of the benchmark:
    # the reference in the precision below the configuration's, and the
    # reference with a planted fault, each put in the program's place
    extras = {}
    if "control" in ctx.extra:
        extras["control"] = compare.training_numbers(reference_steps(
            ctx, ref_batches, precision=cfg["control_precision"]), ref)
    if "half_batch" in ctx.extra:
        extras["half_batch"] = compare.training_numbers(reference_steps(
            ctx, ref_batches, fault="half_batch"), ref)
    return {
        "end_to_end": {"train_samples_per_s_chip":
                       measures["samples"] / window_s / ctx.chips},
        "measures": measures, "attempted": steps, "failed": 0,
        "numbers": numbers, "extras": extras, "memory_peak_bytes": peak,
    }


def reference_steps(ctx, ref_batches, precision="float32", fault=None) -> dict:
    """The plain reference over the same first steps, from the same seed's
    weights, at HIGHEST precision unless a control asks otherwise."""
    import jax
    with jax.default_matmul_precision("highest"):
        weights = ctx.reference.make_weights(ctx.cfg, ctx.seed)
        return ctx.reference.train_steps(ctx.cfg, weights, ref_batches,
                                         precision=precision, fault=fault)
