"""Median host work of the feed's thread for one batch in the measured
window: ``input:produce`` less its ``input:h2d`` and ``input:put_wait``, so
read, cast and the thread's own time (the program's spans). Read it against
``train_step_device_ms``: at or over it, the feed sets the pace."""

from benchmark import spans


def read(run):
    found = spans.window(run)
    if found is None:
        return None
    return spans.median_ms(spans.feed_host_work_ns(found[2]))
