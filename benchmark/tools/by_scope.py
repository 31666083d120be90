"""One traced run of a cell and its step's device time by the program's own
scopes: the by-part table of ``PERF.md`` section 5, made by the program's
table and not by hand.

    python3 benchmark/tools/by_scope.py --workload <cell> --seed 7 \\
        --seconds 6 --out chiprun_out/by_scope

It runs ``run.py --trace 1 --look <out>`` in this process (so the registry
of ``deeplearning4j_tpu/profiling/scopes.py`` still holds the step that
ran), meets the trace's whole steps with the step's table
(``benchmark/metrics/step_scopes.py``) and prints, in milliseconds a step:
the time by scope (every phase and node together), by node and scope with
the phases side by side, the matrix products' part (a convolution or dot,
or a fusion holding one) and a node's digits starred (``b*_mix``: the
blocks of one kind together),
the seconds under names the table does not hold (``unknown``), under an
``op_name`` that names no node and no scope (``unlabelled``, with the
instructions that take most of it) and under an ``op_name`` inherited from
a fused computation (``inherited``); and what the table cost, by this
tool's own stopwatch round the program's calls: the capture at the step's
first dispatch in milliseconds, what the process holds for it until a
reader asks (the kept module's size as a serialized proto, and the growth
of the process's resident memory over the capture), the seconds to make the
table and the table's own bytes. ``<out>/<cell>.by_scope.json`` keeps every
row by (node, scope, phase) unstarred beside the trace that ``--look``
leaves.

A ``perf_opt`` builder runs it on parent and change. No run of the
benchmark calls this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run, trace  # noqa: E402
from benchmark.metrics import step_scopes, train_step_device_ms  # noqa: E402
from benchmark.tools.record_spans import thin  # noqa: E402


PHASES = ("fwd", "remat", "bwd")
TOP = 60        # rows of the table by node kind and scope


def starred(node):
    return None if node is None else re.sub(r"\d+", "*", node)


def report(tr: dict, table) -> dict:
    """The join of one trace with one table, every time in milliseconds a
    step."""
    one = types.SimpleNamespace(trace=tr, step_table=table)
    read = step_scopes.steps(one)
    if read is None:
        raise SystemExit("by_scope: no whole step in the trace, or over "
                         f"{step_scopes.STALE:.0%} of its time under names "
                         "the table does not hold")
    ms = lambda seconds: 1e3 * seconds / read.steps
    rows: dict = {}
    unlabelled: dict = {}
    inherited: dict = {}                # scope -> ms under an inherited name
    for name, op in read.ops.items():
        value = ms(read.seconds[name])
        if op.node is None and op.scope is None:
            unlabelled[name] = value
            continue
        row = rows.setdefault((op.node, op.scope, op.phase), [0.0, 0, 0.0])
        row[0] += value
        row[1] += 1
        row[2] += value if op.product else 0.0
        if name in table.inherited:
            where = op.scope or starred(op.node)
            inherited[where] = inherited.get(where, 0.0) + value
    module = train_step_device_ms.read(one)
    return {
        "steps": read.steps, "train_step_device_ms": module,
        "scopes_sum_ms": ms(sum(read.seconds.values())),
        "unknown_ms": ms(read.unknown), "unlabelled_ms": ms(read.unlabelled),
        "inherited_ms": sum(inherited.values()),
        "inherited": sorted(inherited.items(), key=lambda kv: -kv[1]),
        "rows": [[*k, *v] for k, v in sorted(
            rows.items(), key=lambda kv: -kv[1][0])],
        "unlabelled": sorted(unlabelled.items(), key=lambda kv: -kv[1]),
    }


def show(out: dict) -> str:
    lines = []
    say = lines.append
    total = out["scopes_sum_ms"]
    say(f"{out['steps']} whole steps; train_step_device_ms "
        f"{out['train_step_device_ms']:.3f}, the scopes' sum {total:.3f} "
        f"({100 * (total / out['train_step_device_ms'] - 1):+.3f} %)")
    named = total - out["unknown_ms"] - out["unlabelled_ms"]
    say(f"unknown {out['unknown_ms']:.3f}  unlabelled "
        f"{out['unlabelled_ms']:.3f}  inherited {out['inherited_ms']:.3f}  "
        f"coverage {100 * named / total:.2f} %")
    by_scope: dict = {}
    by_kind: dict = {}
    for node, scope, phase, value, n, products in out["rows"]:
        by_scope[scope] = by_scope.get(scope, 0.0) + value
        kind = by_kind.setdefault((starred(node), scope),
                                  {"n": 0, "products": 0.0})
        kind[phase] = kind.get(phase, 0.0) + value
        kind["n"] += n
        kind["products"] += products
    say("-- by scope (ms a step)")
    for scope, value in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        say(f"{value:10.3f}  {scope or '(a node, no scope)'}")
    say("-- by node kind and scope (ms a step): all = fwd + remat + bwd; "
        "of all, matrix products; instructions")
    whole = lambda kind: sum(kind.get(p, 0.0) for p in PHASES)
    for (node, scope), kind in sorted(
            by_kind.items(), key=lambda kv: -whole(kv[1]))[:TOP]:
        say(f"{whole(kind):10.3f} = " + " + ".join(
            f"{kind.get(p, 0.0):8.3f}" for p in PHASES)
            + f"; {kind['products']:8.3f} {kind['n']:6d}  {node or '-'}  "
            f"{scope or '-'}")
    say("-- inherited, by the scope (or node kind) it went to (ms a step)")
    for where, value in out["inherited"][:8]:
        say(f"{value:10.3f}  {where}")
    say("-- unlabelled, the largest (ms a step)")
    for name, value in out["unlabelled"][:12]:
        say(f"{value:10.3f}  {name}")
    return "\n".join(lines)


def resident_bytes() -> int:
    """The process's resident memory, as Linux counts it."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def stopwatch(scopes, cost: dict) -> None:
    """This tool's clock round the program's two calls: what the capture
    took and left in the process, and what the first request for the table
    took (it parses; the later ones find it made), into ``cost``."""
    record, table_of = scopes.record_step, scopes.step_table

    def timed_record(name, fn, args):
        resident, t0 = resident_bytes(), time.perf_counter()
        done = record(name, fn, args)
        if name == step_scopes.PROGRAM:
            cost["capture_ms"] = 1e3 * (time.perf_counter() - t0)
            cost["resident_growth_bytes"] = resident_bytes() - resident
            module = scopes.kept().get(name)
            if hasattr(module, "as_serialized_hlo_module_proto"):
                cost["module_proto_bytes"] = len(
                    module.as_serialized_hlo_module_proto())
        return done

    def timed_table(name=step_scopes.PROGRAM):
        t0 = time.perf_counter()
        table = table_of(name)
        cost.setdefault("table_s", time.perf_counter() - t0)
        return table

    scopes.record_step, scopes.step_table = timed_record, timed_table


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default="chiprun_out/by_scope")
    args = ap.parse_args()
    stem = os.path.join(args.out, args.workload)

    from deeplearning4j_tpu.profiling import scopes
    cost: dict = {}
    stopwatch(scopes, cost)
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1", "--look", args.out])
    sys.stdout.write(printed.getvalue())
    if rc:
        return rc
    table = scopes.step_table(step_scopes.PROGRAM)
    if table is None:
        print("by_scope: the program kept no step", file=sys.stderr)
        return 1
    cost.update(table_bytes=sum(len(k) + len(v) for k, v in table.items()),
                instructions=len(table),
                inherited_instructions=len(table.inherited))
    tr = thin(trace.load_json(stem + ".trace.json.gz"))
    trace.save_json(tr, stem + ".trace.json.gz")
    out = report(tr, table)
    out["cost"] = cost
    with open(stem + ".by_scope.json", "w") as f:
        json.dump(out, f, separators=(",", ":"))
    print(f"by_scope: {args.workload} -> {stem}.by_scope.json\n"
          f"the table's cost: {json.dumps(cost)}\n" + show(out),
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
