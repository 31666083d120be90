"""Device milliseconds a train step spends in the Pallas kernels for the
gated delta rule's chunk-local work, by the names
``ops/pallas_delta_rule.py`` gives them: the forward kernel (twice a linear
layer under ``remat``: the layer is rebuilt in the backward) and the
backward kernel (once). A program whose chunk-local work runs as XLA
operations holds neither, and the metric reads nothing."""

from benchmark.metrics import step_ops

KERNELS = ("gdn_chunk_local_fwd", "gdn_chunk_local_bwd")


def read(run):
    seconds, _ = step_ops.seconds_per_step(run.trace, "|".join(KERNELS))
    return 1e3 * seconds if seconds else None
