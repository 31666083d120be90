#!/usr/bin/env python
"""Profiling smoke stage (tools/run_checks.sh): a 3-step LeNet fit on
CPU must produce (1) a Chrome trace-event JSON that parses and carries
the expected spans, (2) compile-watcher metrics in the registry and a
valid Prometheus rendering, and (3) a cost analysis whose FLOPs and
analytic MFU are present and positive. Exit 0 = healthy subsystem.
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    import numpy as np

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.models.lenet import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.profiling import (
        CompileWatcher, Tracer, analytic_mfu, get_registry, set_tracer,
    )

    tracer = Tracer()
    prev = set_tracer(tracer)
    watcher = CompileWatcher().install()
    try:
        rng = np.random.default_rng(0)
        batches = [DataSet(
            rng.normal(size=(8, 28, 28, 1)).astype(np.float32),
            np.eye(10, dtype=np.float32)[rng.integers(0, 10, 8)])
            for _ in range(3)]
        net = MultiLayerNetwork(lenet_mnist()).init()
        with tracer.span("lenet_fit", steps=3):
            for b in batches:
                net.fit_batch(b)
        cost = net.cost_analysis(batches[0])
    finally:
        watcher.uninstall()
        set_tracer(prev)

    failures = []

    # 1) trace exports, round-trips through JSON, and carries the spans
    with tempfile.TemporaryDirectory() as td:
        path = tracer.save(os.path.join(td, "trace.json"))
        with open(path) as f:
            blob = json.load(f)
    events = blob.get("traceEvents")
    if not isinstance(events, list) or not events:
        failures.append("trace has no traceEvents")
    else:
        names = {e.get("name") for e in events}
        for want in ("lenet_fit", "fit_batch"):
            if want not in names:
                failures.append(f"span {want!r} missing from trace "
                                f"(got {sorted(names)})")
        bad = [e for e in events
               if e.get("ph") not in ("X", "i")
               or not isinstance(e.get("ts"), (int, float))]
        if bad:
            failures.append(f"{len(bad)} malformed trace events")

    # 2) compile watcher fed the registry; Prometheus text renders
    reg = get_registry()
    if reg.counter("jax_compile_total").value < 1:
        failures.append("CompileWatcher counted no compiles")
    text = reg.to_prometheus()
    if "jax_compile_total" not in text or "# TYPE" not in text:
        failures.append("Prometheus rendering incomplete")

    # 3) cost analysis: FLOPs and a defined analytic MFU
    flops = cost.get("flops_per_step")
    if not flops or flops <= 0:
        failures.append(f"cost analysis flops_per_step={flops!r}")
    if cost.get("peak_flops_per_chip") is not None:
        failures.append("a CPU run reports a peak FLOP/s "
                        f"({cost['peak_flops_per_chip']!r}): a CPU has no MFU")
    # the MFU arithmetic, against the v5e's published peak
    mfu = analytic_mfu(flops or 0, 0.05, 197e12)
    if mfu is None or mfu <= 0:
        failures.append("analytic MFU undefined against a stated peak")

    if failures:
        print("profiling smoke FAILED:")
        for f in failures:
            print("  " + f)
        return 1
    print(f"profiling smoke OK: {len(events)} trace events, "
          f"{int(reg.counter('jax_compile_total').value)} compiles "
          f"watched, {flops:.3e} FLOPs/step (= {mfu:.6f} of a v5e's peak at "
          "50 ms/step; arithmetic only, nothing was timed)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
