"""One traced run of a cell with the program's spans kept beside its trace:

    python3 benchmark/tools/record_spans.py --workload <cell> --seed 7 \\
        --seconds 1 --out chiprun_out/record_spans

It runs ``run.py --trace 1 --look <out>`` in this process (so the program's
ring is still there afterwards) and writes ``<out>/<cell>.spans.json``
beside the ``<out>/<cell>.trace.json.gz`` that ``--look`` leaves:

- ``spans``: the program's spans of the measured window and of the traced
  stretch, as ``benchmark/spans.py`` reads them;
- ``measures``: the seconds and steps the benchmark measured round them;
- ``readings``: what the run's result line printed for each metric whose
  source is ``program_span``;
- ``idle_by_span``: the traced stretch's idle time by the loop's and by the
  feed's innermost span; ``longest_gaps``: for each of the longest idle
  gaps of the device, where both threads were; ``dispatch_to_device_ms``:
  for each step of the stretch, from the start of its ``fit:dispatch`` to
  the start of its run on the device (which is how to say whether the
  device waits for the host or lags it).

The trace is saved again with only the lines the reductions read (``XLA
Ops``, ``XLA Modules`` and the host's ``bench:*`` spans), and with a short
``--seconds`` the pair is small enough to keep under ``benchmark/testdata/``
(``tests/benchmark/test_benchmark_spans.py`` reads
``resnet50_train_spans_v5e.*``). No run of the benchmark calls this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, run, spans, trace  # noqa: E402

KEPT = ("name", "id", "parent", "ts_ns", "dur_ns", "tid", "args")


def where(events: list, g0: int, g1: int) -> dict:
    """Where one thread was during the gap ``[g0, g1]``: the innermost span
    covering half of it, and every span's milliseconds inside it."""
    inside: dict = {}
    for e in events:
        over = min(g1, e["ts_ns"] + e["dur_ns"]) - max(g0, e["ts_ns"])
        if over > 0:
            inside[e["name"]] = inside.get(e["name"], 0) + over / 1e6
    owner = spans.covering(events, g0, g1)
    return {"innermost": None if owner is None else dict(
        name=owner["name"], args=owner.get("args"),
        ms=owner["dur_ns"] / 1e6), "ms_inside": inside}


def thin(tr: dict) -> dict:
    """``tr`` with only the lines that ``trace.py`` and ``spans.py`` read."""
    keep = lambda plane, line: (not trace.DEVICE_PLANE.match(plane["name"])
                                or line["name"] in (trace.OPS_LINE,
                                                    trace.MODULES_LINE))
    return {"planes": [dict(p, lines=[l for l in p["lines"] if keep(p, l)])
                       for p in tr["planes"]]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default="chiprun_out/record_spans")
    ap.add_argument("--gaps", type=int, default=8)
    args = ap.parse_args()

    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1",
                       "--look", args.out])
    sys.stdout.write(printed.getvalue())
    if rc:
        return rc
    result = json.loads(printed.getvalue().strip().splitlines()[-1])
    with open(os.path.join(args.out, f"{args.workload}.json")) as f:
        measures = json.load(f)["measures"]
    trace_path = os.path.join(args.out, f"{args.workload}.trace.json.gz")
    one = types.SimpleNamespace(measures=measures, trace=thin(
        trace.load_json(trace_path)))
    trace.save_json(one.trace, trace_path)
    fits = spans.fit_spans(one)
    if None in fits:
        print("record_spans: the ring holds no pair of fit spans that "
              "agrees with the measures: " + json.dumps(
                  [e for e in spans.program_spans() if e["name"] == "fit"]),
              file=sys.stderr)
        return 1
    kept = {}
    for fit in fits:
        loop, feed = spans.under(one, fit)
        kept.update((e["id"], {k: e[k] for k in KEPT if k in e})
                    for e in loop + feed)
    one.spans = sorted(kept.values(), key=lambda e: (e["ts_ns"], e["id"]))
    named = {p["name"] for p in manifest.load(ROOT)["per_layer"]
             if p["source"] == "program_span"}
    out = {"spans": one.spans, "measures": measures,
           "readings": {k: v["value"] for k, v in result["metrics"].items()
                        if k in named}}
    placed = spans.on_trace_clock(one)
    if placed is not None:
        _, loop, feed = placed
        out["idle_by_span"] = {t: spans.idle_by_span(one, t)
                               for t in ("loop", "feed")}
        gaps = sorted(spans.device_gaps(one.trace),
                      key=lambda g: g[0] - g[1])[:args.gaps]
        lo, _ = trace.window_ns(one.trace)
        from benchmark.metrics.train_step_device_ms import PATTERN
        runs = sorted(e[1] for e in trace.line_events(
            trace.device_planes(one.trace)[0], trace.MODULES_LINE)
            if re.search(PATTERN, e[0]))
        out["dispatch_to_device_ms"] = [
            (start - e["ts_ns"]) / 1e6 for e, start in zip(
                spans.named(loop, "fit:dispatch"), runs)]
        out["longest_gaps"] = [
            {"at_ms": (g0 - lo) / 1e6, "ms": (g1 - g0) / 1e6,
             "loop": where(loop, g0, g1), "feed": where(feed, g0, g1)}
            for g0, g1 in sorted(gaps)]
    path = os.path.join(args.out, f"{args.workload}.spans.json")
    with open(path, "w") as f:
        json.dump(out, f, separators=(",", ":"))
    shown = {k: out.get(k) for k in ("readings", "idle_by_span",
                                     "dispatch_to_device_ms", "longest_gaps")}
    print(f"record_spans: {len(one.spans)} spans -> {path}\n"
          + json.dumps(shown, indent=1), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
