"""The fit loop's own host time a step in the measured window: the ``fit``
span less its waits in the feed's queue (``input:wait``) and the step's
dispatch (``fit:dispatch``), over the steps: splitting the batch, the step's
key, the listeners, the loop itself (the program's spans)."""

from benchmark import spans


def read(run):
    found = spans.window(run)
    if found is None:
        return None
    _, loop, _ = found
    own, steps = spans.loop_self_ns(loop), len(spans.named(loop, "fit_batch"))
    return own / 1e6 / steps if own is not None and steps else None
