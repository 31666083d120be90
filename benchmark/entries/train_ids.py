"""A training cell fed integer token ids: ``net.fit`` over the program's
``DevicePrefetchIterator``, as ``entries/train.py`` runs it, for a language
model whose input is ``[B, T]`` int32 ids and whose targets are the same ids
shifted by one (``traffic.train_batches`` knows images and one-hot rows
only).

The feed, the proxy round it and the reference's driver are ``train.py``'s,
imported; the run follows its ``run()`` step for step: the first
``check_steps`` steps by the window's own call and feed, the window, the
traced stretch, and the plain reference once the program's state is freed.
Two things differ, both for a model of a billion parameters: the seed's
weights wait on the host from before the program is built until the
parameters' change has been read, a leaf at a time (beside float32
parameters and their momentum a third copy of 3.7 GB would leave the step
no room, and read as 11.1 GB of live arrays before the first step), and the
program's builder is imported before anything is made, so that a program
without the model leaves at once.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import compare, program
from benchmark.entries.train import (
    make_feed, make_stall_proxy, reference_steps)


def id_batches(cfg: dict, mix: dict, seed: int) -> list:
    """``host_batches`` pairs ``(ids, targets)``, int32 ``[batch, seq_len]``
    each: ids uniform over the vocabulary from ``seed``, the targets the
    same ids one place on. No padding, no document boundary."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(mix["host_batches"]):
        ids = rng.integers(0, cfg["vocab_size"],
                           (mix["batch"], mix["seq_len"] + 1), dtype=np.int32)
        out.append((np.ascontiguousarray(ids[:, :-1]),
                    np.ascontiguousarray(ids[:, 1:])))
    return out


def run(ctx) -> dict:
    import jax

    cfg, mix = ctx.cfg, ctx.mix
    importlib.import_module(cfg["program"]["builder"].split(":")[0])
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import DevicePrefetchIterator

    # the seed's weights wait on the host: made on the device, brought
    # down, and only then handed to the program, which uploads its copies
    start = jax.device_get(ctx.reference.make_weights(cfg, ctx.seed))
    net = program.build_net(cfg, start)
    host = id_batches(cfg, mix, ctx.seed)
    datasets = [DataSet(x, y) for x, y in host]
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
    prog["delta_norm"] = {}
    for leaf, value in program.flatten(net.params).items():
        # a leaf at a time: the whole start beside parameters and momentum
        # would be a third copy of 3.7 GB on the device
        prog["delta_norm"].update(program.change_norms(
            {leaf: value}, {leaf: start[leaf]}))
    del start

    # the window (and, in a traced run, a traced stretch after it)
    rotated = datasets[n % len(datasets):] + datasets[:n % len(datasets)]

    def window(seconds):
        compiles = program.counter("jax_compile_total")
        tokens = program.counter("train_tokens_total")
        t0 = time.perf_counter()
        feed = make_feed(rotated, seconds)
        proxy = fit_feed(feed)
        jax.block_until_ready(net.params)
        window_s = time.perf_counter() - t0
        return {"window_s": window_s, "steps": feed.yielded,
                "samples": feed.yielded * mix["batch"],
                "tokens": program.counter("train_tokens_total") - tokens,
                "stall_s": proxy.stall_s, "compiles_in_window":
                program.counter("jax_compile_total") - compiles}

    ctx.open_window()
    measures = window(ctx.window_seconds)
    if ctx.trace:
        with ctx.traced():
            measures["traced"] = window(ctx.trace_seconds)
    measures["gate_fallbacks"] = gate_fallbacks()
    steps, window_s = measures["steps"], measures["window_s"]
    peak = ctx.memory_peak_bytes()

    # free the program's state, then follow the same steps in the reference
    del net, fit, datasets, rotated
    ref_batches = host[:n]
    del host
    ref = reference_steps(ctx, ref_batches)
    numbers = compare.training_numbers(prog, ref)
    # asked for by tools/readings.py alone, never by a run of the benchmark
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


def gate_fallbacks() -> float:
    """``pallas_gate_fallbacks_total`` over the run: the layers a Pallas
    shape gate sent to the XLA path, all layers and kernels together."""
    from deeplearning4j_tpu.profiling.metrics import get_registry
    return float(get_registry().labeled_counter(
        "pallas_gate_fallbacks_total").value)
