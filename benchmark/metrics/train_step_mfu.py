"""The whole training step's share of the chips' peak: the operations the
forward and backward passes need per sample, from the configuration's
shapes (``reference/<family>.train_flops_per_sample``), times samples per
second, over chips times the peak."""


def read(run):
    m = run.measures
    if not m.get("samples"):
        return None
    flops = run.reference.train_flops_per_sample(run.cfg, run.mix)
    rate = m["samples"] / m["window_s"]
    return 100.0 * flops * rate / (run.chips * run.peaks["flops_per_s"])
