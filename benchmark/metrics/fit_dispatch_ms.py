"""Median host time of one dispatch of the jitted train step in the measured
window: the ``fit:dispatch`` spans (the program's spans)."""

from benchmark import spans


def read(run):
    found = spans.window(run)
    if found is None:
        return None
    return spans.median_ms([e["dur_ns"] for e in spans.named(
        found[1], "fit:dispatch")])
