"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that checks ``BENCHMARK.json`` against itself, finds its
chips or fails (there is no fallback to the CPU), makes weights and inputs
from ``--seed``, warms up, measures for ``--seconds``, compares what the
timed path produced with the plain reference, and prints one JSON object as
the last line of standard output. With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` a further stretch of at most
``TRACE_SECONDS`` runs under ``jax.profiler`` after the window and the
metrics are the cell's per-layer metrics, each from a reader of its own in
``metrics/``.

``--dry-cpu`` rehearses the same flow on the CPU at the tiny sizes the data
files give under ``dry_cpu``: every line is tagged, no device metric and no
result line is printed. It proves nothing about the chip.

This file holds no cell's name: a cell is an entry of ``BENCHMARK.json``,
a configuration file, a traffic file, a limits file and, for its per-layer
metrics, a reader each.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # as near to the process's start as we get

import argparse          # noqa: E402
import contextlib        # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import types             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 6       # the traced stretch after the window: traces are large
WORK_DIR = os.path.join(ROOT, ".bench_work")


class Context:
    """What an entry needs of the run: sizes, seed, the window's clock."""

    def __init__(self, args, cell, cfg, mix, reference):
        self.cfg, self.mix, self.reference = cfg, mix, reference
        self.seed, self.chips = args.seed, cell["chips"]
        self.trace = bool(args.trace) and not args.dry_cpu
        self.window_seconds = args.seconds
        self.trace_seconds = min(args.seconds, TRACE_SECONDS)
        self.setup_s = None
        self.extra = ()         # further checks, for tools/readings.py alone
        self.trace_dir = os.path.join(WORK_DIR, "trace")
        self.trace_options = load_json("benchmark", "profiler.json")["options"]

    def open_window(self):
        """The first instant of the measured window: set-up ends here."""
        self.setup_s = time.perf_counter() - T_START
        print(f"window open after {self.setup_s:.1f} s of set-up",
              file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def traced(self):
        """A stretch under ``jax.profiler``, after the measured window: the
        profiler slows a large program (ResNet-50's step runs in bursts
        under it), so what the host's clock measures is taken from the
        window before, and only device times from here."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        for key, value in self.trace_options.items():
            setattr(options, key, value)
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench:window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def memory_peak_bytes(self):
        """The peak on the fullest chip, as the device reports it: the peak
        of live arrays (``peak_bytes_in_use``) and the peak that running
        programs reserved for their temporaries (``peak_bytes_reserved``;
        4.32 GB for the ResNet-50 step at batch 128, which is the
        ``temp_size_in_bytes`` its compilation for a described chip gives)."""
        import jax
        def peak(d):
            s = d.memory_stats() or {}
            return (s.get("peak_bytes_in_use", 0)
                    + s.get("peak_bytes_reserved", 0))
        return max(map(peak, jax.devices()[:self.chips])) or None


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def main(argv=None) -> int:
    from benchmark import compare, manifest, traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-cpu", action="store_true")
    ap.add_argument("--look", metavar="DIR", help="with --trace 1: write "
                    "what one looks at by hand first (planes, lines, the "
                    "names that took most time, memory_stats) to DIR")
    args = ap.parse_args(argv)
    tag = "[DRY-CPU] " if args.dry_cpu else ""
    say = lambda msg: print(f"{tag}{msg}", file=sys.stderr, flush=True)

    m = manifest.load(ROOT)
    bad = manifest.problems(m, ROOT)
    if bad:
        for b in bad:
            say(f"BENCHMARK.json: {b}")
        return 2
    cell = manifest.cell(m, args.workload)
    cfg = traffic.with_dry(load_json(
        manifest.config_entry(m, cell["config"])["file"]), args.dry_cpu)
    mix = traffic.load(ROOT, cell["traffic"], args.dry_cpu)

    if args.dry_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DL4J_TPU_PALLAS"] = "interpret"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from deeplearning4j_tpu.profiling import CompileWatcher
    from deeplearning4j_tpu.util.compile_cache import use_compile_cache

    if not args.dry_cpu:
        cache = use_compile_cache()     # a fixed path inside the checkout
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        say(f"compile cache: {cache}")
    devices = jax.devices()
    dev = devices[0]
    if not args.dry_cpu and (dev.platform != "tpu"
                             or len(devices) < cell["chips"]):
        say(f"{args.workload} needs {cell['chips']} TPU chip(s); jax found "
            f"{len(devices)} x {dev.platform}. Nothing was run.")
        return 3
    peaks = None
    if not args.dry_cpu:
        table = load_json("benchmark", "peaks.json")
        if dev.device_kind not in table:
            say(f"no peaks for device_kind {dev.device_kind!r} in "
                "benchmark/peaks.json")
            return 3
        peaks = table[dev.device_kind]
    CompileWatcher().install()

    ctx = Context(args, cell, cfg, mix, importlib.import_module(
        f"benchmark.reference.{cfg['reference']}"))
    entry = importlib.import_module(f"benchmark.entries.{mix['entry']}")
    out = entry.run(ctx)

    limits = compare.load_limits(ROOT, cell["name"], args.dry_cpu)
    correct, compared = compare.decide(out["numbers"], limits)
    correct = correct and out["failed"] == 0 and out["attempted"] > 0
    notes = {k: v for k, v in out["numbers"].items() if k not in compared}
    notes.update(attempted=out["attempted"], failed=out["failed"])

    if args.dry_cpu:
        counts = {k: v for k, v in out["measures"].items()
                  if isinstance(v, (int, float)) and not k.endswith(
                      ("_s", "_ms", "_share"))}
        print(f"{tag}{args.workload}: correct={correct} counts={counts}",
              flush=True)
        compare.report(compared, notes, tag)
        return 0 if correct else 1

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if ctx.trace:
        from benchmark import trace as tr
        t = tr.load_xplane(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if args.look:
            os.makedirs(args.look, exist_ok=True)
            tr.save_json(t, os.path.join(
                args.look, f"{args.workload}.trace.json.gz"))
            with open(os.path.join(args.look, f"{args.workload}.json"),
                      "w") as f:
                json.dump({"summary": tr.summary(t),
                           "memory_stats": dev.memory_stats(),
                           "measures": out["measures"]}, f, default=str)
        run = types.SimpleNamespace(
            trace=t, measures=out["measures"], cfg=cfg, mix=mix, peaks=peaks,
            chips=cell["chips"], reference=ctx.reference)
        metrics = {}
        for spec in manifest.metrics_of(m, "per_layer", cell["name"]):
            reader = importlib.import_module(
                "benchmark.metrics." + spec["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value),
                                         "unit": spec["unit"]}
        busy = tr.busy_seconds(t)[:cell["chips"]]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = tr.window_seconds(t)
        result["breakdown"] = {"device_ops": tr.top_device_ops(t),
                               "idle_gaps": tr.idle_gaps(t)}
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        metrics = {spec["name"]: {"value": float(values[spec["name"]]),
                                  "unit": spec["unit"]}
                   for spec in manifest.metrics_of(m, "end_to_end",
                                                   cell["name"])}
    result.update(metrics=metrics, device=device)
    result["compared"] = {
        k: {"value": v if v == v else None, "limit": lim}   # NaN is no JSON
        for k, (v, lim) in compared.items()}
    say(f"measures: {json.dumps(out['measures'], default=str)}")
    compare.report(compared, notes)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
