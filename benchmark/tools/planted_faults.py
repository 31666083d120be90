"""A configuration's own planted faults, read against its plain reference
and held to the cell's limits:

    python3 benchmark/tools/planted_faults.py --workload <cell> --seeds 7,8 \\
        --faults dense,raw_weights --out chiprun_out/readings

``benchmark/tools/readings.py`` reads the control and the half sequence
through the cell's entry; a fault of one mechanism (a sparse attention's
selection left out, routed experts' weights not renormalised) is a
``fault=`` of the reference's ``train_steps`` that the entry does not know.
This follows the cell's first steps in the reference twice, sound and with
the fault, from the same seed's weights and batches, and puts the faulted
run in the program's place: every fault has to fail at least one of the
cell's limits, and the exit code is 1 where one passes them all.
``control`` (the reference in the configuration's ``control_precision``)
and ``half_batch`` may be named among the faults too: neither needs the
program, so a further seed of them costs no program run. Every seed goes
through one fault before the next fault is begun, so that a reference
which keeps its last compiled step compiles each fault once; each line is
written as it is read, so a call that is cut keeps what it got. No run of
the benchmark calls this.
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--faults", required=True)
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
    import jax

    reference = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    entry = importlib.import_module(f"benchmark.entries.{mix['entry']}")
    limits = compare.load_limits(ROOT, args.workload, args.dry_cpu)
    os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
    path = os.path.join(ROOT, args.out, args.workload + ".faults.jsonl")
    seeds = [int(s) for s in args.seeds.split(",")]

    def steps(seed, fault=None):
        precision = "float32"
        if fault == "control":
            precision, fault = cfg["control_precision"], None
        batches = entry.id_batches(cfg, mix, seed)[:mix["check_steps"]]
        with jax.default_matmul_precision("highest"):
            return reference.train_steps(
                cfg, reference.make_weights(cfg, seed), batches,
                precision=precision, fault=fault)

    sound = {seed: steps(seed) for seed in seeds}
    caught = True
    for fault in args.faults.split(","):
        for seed in seeds:
            numbers = compare.training_numbers(steps(seed, fault),
                                               sound[seed])
            passes, compared = compare.decide(numbers, limits)
            caught &= not passes
            line = json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "passes_every_limit": passes, "numbers": numbers,
                "failed": sorted(k for k, (v, lim) in compared.items()
                                 if not v <= lim),
                "device": jax.devices()[0].device_kind})
            print(line, flush=True)
            with open(path, "a") as f:
                f.write(line + "\n")
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
