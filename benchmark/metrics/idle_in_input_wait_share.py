"""Of the first device's idle time in the traced stretch, the share whose
gaps the fit loop spent in the feed's queue (``input:wait``; the program's
spans on the trace's clock, ``spans.idle_by_span``)."""

from benchmark import spans


def read(run):
    return spans.idle_share(run, "input:wait")
