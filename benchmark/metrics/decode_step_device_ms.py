"""Mean device time of one run of the paged decode program (``XLA Modules``
events named ``jit_paged_decode``) in the traced window."""

from benchmark import trace

PATTERN = r"^jit_paged_decode\b"


def read(run):
    runs = trace.module_runs(run.trace, PATTERN)
    return 1e3 * sum(runs) / len(runs) if runs else None
