"""Mean device time of one run of the train step's compiled program
(``XLA Modules`` events named ``jit_train_step``) in the traced window."""

from benchmark import trace

PATTERN = r"^jit_(train_)?step\b"


def read(run):
    runs = trace.module_runs(run.trace, PATTERN)
    return 1e3 * sum(runs) / len(runs) if runs else None
