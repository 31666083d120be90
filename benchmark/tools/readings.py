"""The readings that a cell's limits are set from, several seeds in one
process (so that set-up is paid once where it can be):

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        --seconds 2 --extra control,half_batch --out chiprun_out/readings

For each seed it drives the cell's own entry at the cell's own sizes with a
short window and prints, as one JSON line, the numbers the check compares
(the program against the plain reference: the lower readings) and, for each
``--extra``, the same numbers with the control or a planted fault in the
program's place (the upper readings). Each set of numbers goes through
``compare.decide`` with the cell's own limits: ``verdicts`` has to read true
for the program and false for every control and fault, and the exit code is
1 where it does not. No run of the benchmark calls this.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import compare, manifest, run, traffic

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--extra", default="control")
    ap.add_argument("--out", default="chiprun_out/readings")
    ap.add_argument("--dry-cpu", action="store_true")
    args = ap.parse_args()
    m = manifest.load(ROOT)
    cell = manifest.cell(m, args.workload)
    cfg = traffic.with_dry(run.load_json(manifest.config_entry(
        m, cell["config"])["file"]), args.dry_cpu)
    mix = traffic.load(ROOT, cell["traffic"], args.dry_cpu)
    if args.dry_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DL4J_TPU_PALLAS"] = "interpret"
    import jax

    from deeplearning4j_tpu.profiling import CompileWatcher
    from deeplearning4j_tpu.util.compile_cache import use_compile_cache
    if not args.dry_cpu:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        if jax.devices()[0].platform != "tpu":
            print("readings: no TPU", file=sys.stderr)
            return 3
    CompileWatcher().install()
    reference = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    entry = importlib.import_module(f"benchmark.entries.{mix['entry']}")
    os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
    path = os.path.join(ROOT, args.out, args.workload + ".jsonl")
    limits = compare.load_limits(ROOT, args.workload, args.dry_cpu)
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                dry_cpu=args.dry_cpu)
        ctx = run.Context(ns, cell, cfg, mix, reference)
        ctx.extra = tuple(x for x in args.extra.split(",") if x)
        out = entry.run(ctx)
        verdicts = {"program": compare.decide(out["numbers"], limits)[0]
                    and out["failed"] == 0 and out["attempted"] > 0}
        for name, numbers in out["extras"].items():
            verdicts[name] = compare.decide(numbers, limits)[0]
        as_expected &= verdicts["program"] and not any(
            v for k, v in verdicts.items() if k != "program")
        line = json.dumps({
            "workload": args.workload, "seed": seed, "verdicts": verdicts,
            "device": jax.devices()[0].device_kind,
            "numbers": out["numbers"], "extras": out["extras"],
            "attempted": out["attempted"], "failed": out["failed"],
            "end_to_end": out["end_to_end"]})
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
        del out, ctx
        gc.collect()
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
