"""Share of the measured window in which the feed's thread was held back by
a full queue: the ``input:put_wait`` spans over the ``fit`` span (the
program's spans). High: the feed runs ahead and the loop or the device sets
the pace; near 0: the feed does."""

from benchmark import spans


def read(run):
    found = spans.window(run)
    if found is None:
        return None
    fit, _, feed = found
    lo, hi = fit["ts_ns"], fit["ts_ns"] + fit["dur_ns"]
    held = sum(min(hi, e["ts_ns"] + e["dur_ns"]) - max(lo, e["ts_ns"])
               for e in spans.named(feed, "input:put_wait"))
    return 100.0 * held / fit["dur_ns"]
