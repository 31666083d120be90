"""Of the first device's idle time in the traced stretch, the share whose
gaps the fit loop spent dispatching the jitted step (``fit:dispatch``; the
program's spans on the trace's clock, ``spans.idle_by_span``). What this and
``idle_in_input_wait_share`` leave belongs to the loop's other spans or to
none."""

from benchmark import spans


def read(run):
    return spans.idle_share(run, "fit:dispatch")
