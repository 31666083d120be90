"""Median host time of one batch's ``jax.device_put`` calls in the feed's
thread in the measured window: the ``input:h2d`` spans (the program's
spans)."""

from benchmark import spans


def read(run):
    found = spans.window(run)
    if found is None:
        return None
    return spans.median_ms([e["dur_ns"] for e in spans.named(
        found[2], "input:h2d")])
