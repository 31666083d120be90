"""Device time of a train step by the program's own scopes: what the
readers of a scope's milliseconds share. No metric of its own.

The program keeps the HLO module of the step it ran
(``deeplearning4j_tpu/profiling/scopes.py``: ``record_step`` in the fit
loop, once a compiled step) and makes of it a table ``{instruction:
op_name}``; the trace's operation names are that module's instruction
names, so a number-named event (``fusion.14``) is met with its ``op_name``
and that read as (node, scope, phase). The readers take the table from the
program's registry in the same process, as ``benchmark/spans.py`` takes the
tracer's ring, and the whole steps of the traced stretch as
``step_ops.seconds_per_step`` finds them. An event's time is its self
time: a ``while`` less the operations of its body, which lie on the same
line inside its interval.

Every reader gives ``None``, and none raises, where there is nothing to
read: a program from before the registry (no module ``scopes``, or nothing
kept under ``jit_train_step``), no whole step in the trace, or a table that
is not the traced program's (more than ``STALE`` of the steps' device time
under names it does not hold).

A ``run`` may carry its table itself, as ``run.step_table`` (the tests'
synthetic runs do); otherwise it is the registry's newest.
"""

from __future__ import annotations

import bisect
import re
from typing import NamedTuple, Optional

from benchmark import trace
from benchmark.metrics import train_step_device_ms

PROGRAM = "jit_train_step"
STALE = 0.01


class Op(NamedTuple):
    """What a reader's predicate sees of one instruction."""
    node: Optional[str]     # the container's node, None in the step's shell
    scope: Optional[str]    # the innermost ``<family>:<part>``
    phase: str              # "fwd", "remat" or "bwd"
    opcode: str             # the HLO opcode: "fusion", "custom-call", ...
    primitive: str          # the op_name's last level: the jax primitive
    product: bool           # a convolution or dot, or a fusion holding one


class Steps(NamedTuple):
    seconds: dict           # {instruction: self seconds over all the steps}
    ops: dict               # {instruction: Op}, those the table holds
    steps: int
    unknown: float          # seconds under names the table does not hold
    unlabelled: float       # seconds whose op_name names no node, no scope


def _scopes():
    try:
        from deeplearning4j_tpu.profiling import scopes
    except ImportError:             # a program from before the registry
        return None
    return scopes


def _table(run, scopes):
    table = getattr(run, "step_table", None)
    return table if table is not None else scopes.step_table(PROGRAM)


def steps(run) -> Optional[Steps]:
    """The whole steps of the traced stretch met with the table (a
    ``scopes.StepTable``); read once and kept on the run. ``None`` as the
    module's docstring says."""
    if not hasattr(run, "_step_scopes"):
        run._step_scopes = _steps(run)
    return run._step_scopes


def _steps(run) -> Optional[Steps]:
    scopes = _scopes()
    table = scopes and _table(run, scopes)
    if not table:
        return None
    plane = trace.device_planes(run.trace)[0]
    lo, hi = trace.window_ns(run.trace)
    step = re.compile(train_step_device_ms.PATTERN)
    runs = sorted((e[1], e[1] + e[2])
                  for e in trace.line_events(plane, trace.MODULES_LINE)
                  if step.search(e[0]) and e[1] >= lo and e[1] + e[2] <= hi)
    if not runs:
        return None
    starts = [s for s, _ in runs]

    def in_a_step(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < runs[i][1]

    seconds = scopes.self_seconds(
        e for e in trace.line_events(plane, trace.OPS_LINE)
        if in_a_step(e[1]))
    ops = {}
    for name in seconds:
        op_name = table.get(name)
        if op_name is not None:
            ops[name] = Op(*scopes.split(op_name), table.opcode.get(name, ""),
                           op_name.partition(";")[0].rpartition("/")[2],
                           name in table.products)
    unknown = sum(v for k, v in seconds.items() if k not in ops)
    if unknown > STALE * sum(seconds.values()):
        return None
    unlabelled = sum(seconds[k] for k, op in ops.items()
                     if op.node is None and op.scope is None)
    return Steps(seconds, ops, len(runs), unknown, unlabelled)


def ms_per_step(run, want):
    """Device milliseconds a step of the instructions for which
    ``want(Op)`` holds; ``None`` where no event of the steps is such an
    instruction's (a program without that scope reads nothing)."""
    read = steps(run)
    if read is None:
        return None
    hits = [read.seconds[k] for k, op in read.ops.items() if want(op)]
    return 1e3 * sum(hits) / read.steps if hits else None


def scope_ms(run, *names, products: bool = True):
    """``ms_per_step`` of the instructions whose innermost scope is one of
    ``names``; without ``products``, of those that are no matrix product."""
    return ms_per_step(run, lambda op: op.scope in names
                       and (products or not op.product))
