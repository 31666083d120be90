"""1 less the union of the device's operation intervals over the traced
window, on the chip that idles most (serving cells)."""

from benchmark import trace


def read(run):
    return 100.0 * trace.idle_share(run.trace)
