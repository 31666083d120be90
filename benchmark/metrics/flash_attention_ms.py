"""Device milliseconds a train step spends in the Pallas flash-attention
kernels: the forward kernel (twice a step under ``remat``: the layer is
recomputed in the backward) and the two backward kernels, by the names
``ops/pallas_attention.py`` gives them."""

from benchmark.metrics import step_ops

PATTERN = r"flash_attention_(fwd|dq|dkv)"


def read(run):
    seconds, _ = step_ops.seconds_per_step(run.trace, PATTERN)
    return 1e3 * seconds if seconds else None
