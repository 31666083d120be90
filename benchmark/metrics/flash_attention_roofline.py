"""Least time the chip could take over the causal attention of a train step,
forward and backward (``reference/<family>.flash_attention_cost``:
``T (T + 1) / 2`` scores a head whatever a kernel skips or rebuilds; the
larger of operations over the peak and bytes over the bandwidth), over the
device time of the flash kernels in a step. A forward kernel run twice
under ``remat`` is time and no further operation, so the share can only
read lower for it."""

from benchmark.metrics import flash_attention_ms


def read(run):
    ms = flash_attention_ms.read(run)
    if not ms:
        return None
    cost = run.reference.flash_attention_cost(run.cfg, run.mix)
    least = max(cost["flops"] / run.peaks["flops_per_s"],
                cost["bytes"] / run.peaks["bytes_per_s"])
    return 100.0 * least * 1e3 / ms
