"""Least time the chip could take over the selective scans of a train step,
forward and backward (``reference/<family>.selective_scan_cost``, once a
state-space layer: the larger of its operations over the peak and of the
bytes of ``x, Delta, B, C`` in and ``s`` out, and backward those with the
five cotangents, over the bandwidth), over the device time of the scans in
a step. It reads the same work whatever implements it: no state is counted,
because a scan need not send one through HBM, so a path that does (the XLA
one moves its block's state a token step) reads low, and a forward run
twice under ``remat`` is time and no further work. The floor is of bytes:
1.23 ms a layer at the cell's shape beside 0.04 ms of operations at the
MXU's peak, which the scan's elementwise work cannot use."""

from benchmark.metrics import selective_scan_ms


def read(run):
    ms = selective_scan_ms.read(run)
    cost = getattr(run.reference, "selective_scan_cost", None)
    if not ms or cost is None:
        return None
    layers = run.reference.layer_kinds(run.cfg).count("mamba")
    cost = cost(run.cfg, run.mix)
    least = layers * max(cost["flops"] / run.peaks["flops_per_s"],
                         cost["bytes"] / run.peaks["bytes_per_s"])
    return 100.0 * least * 1e3 / ms
