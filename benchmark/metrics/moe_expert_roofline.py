"""Least time the chip could take over the grouped products of a train
step's held experts, forward and backward
(``reference/<family>.moe_expert_cost``, once a layer, at the load a uniform
router sends a held share: the larger of its operations over the peak, 1.18
ms a layer at the cell's shape, and of the bytes of the experts' matrices,
their gradients and the assignments' rows over the bandwidth, 0.89 ms),
over the device time of the grouped products in a step. It reads the same
work whatever implements it: a product run twice under ``remat`` is time
and no further work, and rows a kernel pads or multiplies in vain are time
too, so the share can only read lower for them.

The work is fixed and the time follows the routing: from layer 2 on the
tokens of a model with random weights lean to a few experts, held here or
not by the weights' draw, so the share moves from seed to seed (23.4 to
38.4 % over 8 traced seeds, ``PERF.md`` section 5). Layers 0 and 1 route
near uniformly on every seed and their products alone take more than the
whole step's least (10.8 ms against 9.4), so the share stays under 100 %
whatever the other six hold: about 51 % if they held nothing.
The assignments actually held are the layer's ``assigned`` state, which no
reader can reach (``PERF.md`` section 7 says what the entry would hand
on)."""

from benchmark.metrics import moe_expert_ms


def read(run):
    ms = moe_expert_ms.read(run)
    cost = getattr(run.reference, "moe_expert_cost", None)
    if not ms or cost is None:
        return None
    cost = cost(run.cfg, run.mix)
    least = run.cfg["num_hidden_layers"] * max(
        cost["flops"] / run.peaks["flops_per_s"],
        cost["bytes"] / run.peaks["bytes_per_s"])
    return 100.0 * least * 1e3 / ms
