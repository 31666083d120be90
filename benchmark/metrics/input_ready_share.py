"""Of the fit loop's takes from the feed's queue in the measured window, the
share that found their item waiting: ``input:wait`` spans with ``ready=1``
over all of them (the program's spans)."""

from benchmark import spans


def read(run):
    found = spans.window(run)
    if found is None:
        return None
    takes = spans.named(found[1], "input:wait")
    if not takes:
        return None
    return 100.0 * sum(e["args"]["ready"] for e in takes) / len(takes)
