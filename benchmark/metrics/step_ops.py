"""Device time of some of a train step's operations, by the step: what the
readers of a kernel's or a loop's milliseconds share. No metric of its own."""

import re

from benchmark import trace
from benchmark.metrics import train_step_device_ms


def seconds_per_step(t: dict, op_pattern: str):
    """Mean over the runs of the train step's program that lie wholly in
    the traced window of the summed durations of the operations, on the
    first device, that match ``op_pattern`` and start inside the run; with
    how many they were a run. ``(None, 0)`` where no run of the step is in
    the trace. A step cut by the window's edge is left out with its
    operations, so that eight steps are not read as eight and a half."""
    plane = trace.device_planes(t)[0]
    lo, hi = trace.window_ns(t)
    step = re.compile(train_step_device_ms.PATTERN)
    runs = [(e[1], e[1] + e[2])
            for e in trace.line_events(plane, trace.MODULES_LINE)
            if step.search(e[0]) and e[1] >= lo and e[1] + e[2] <= hi]
    if not runs:
        return None, 0
    op = re.compile(op_pattern)
    hits = [e for e in trace.line_events(plane, trace.OPS_LINE)
            if op.search(e[0])
            and any(s <= e[1] < end for s, end in runs)]
    return (sum(e[2] for e in hits) / 1e9 / len(runs),
            len(hits) / len(runs))
