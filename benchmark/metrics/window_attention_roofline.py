"""Least time the chip could take over the attention of a train step's
window layers, forward and backward (``reference/<family>.
window_attention_cost``: the scores inside each window, ``min(t + 1,
window)`` a query and a head, whatever a kernel pads or skips; the larger
of operations over the peak and bytes over the bandwidth), over the device
time of the flash kernels under ``attn:window`` in a step (the kernels by
name, as ``flash_attention_ms`` reads them, met with the step's scope
table). A program without the scope, or a configuration without
``window_attention_cost``, reads nothing."""

import re

from benchmark.metrics import flash_attention_ms, step_scopes


def read(run):
    cost = getattr(run.reference, "window_attention_cost", None)
    read = step_scopes.steps(run)
    if cost is None or read is None:
        return None
    kernel = re.compile(flash_attention_ms.PATTERN)
    hits = [read.seconds[k] for k, op in read.ops.items()
            if op.scope == "attn:window" and kernel.search(k)]
    if not hits:
        return None
    ms = 1e3 * sum(hits) / read.steps
    cost = cost(run.cfg, run.mix)
    least = max(cost["flops"] / run.peaks["flops_per_s"],
                cost["bytes"] / run.peaks["bytes_per_s"])
    return 100.0 * least * 1e3 / ms
