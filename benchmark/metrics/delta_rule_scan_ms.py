"""Device milliseconds a train step spends inside the delta rule's
recurrences across chunks (``gdn:chunk_scan``).

What it matches: the ``while`` operations of the step. On the XLA path a
``lax.scan`` over the chunks lowers to one ``while`` whose event spans all
its turns, and ``trace.short_name`` keeps an operation's name and drops its
``op_name``, so the loops are told by kind and not by scope. A step of the
hybrid decoder has nine, three a linear layer: the forward scan, the same
scan once more under ``remat``, and its transpose. Nothing else in that step
is a ``while``: the triangular inverse is unrolled, the full layer runs
Pallas kernels (custom calls), and the step compiled for a described v5e
holds the nine and no other (``PERF.md`` section 5 gives the count read from
the chip's trace). A Pallas kernel for the recurrence takes the loops off
the path; its reader then goes by the kernel's name."""

from benchmark.metrics import step_ops

PATTERN = r"^while(\.\d+)?$"


def read(run):
    seconds, _ = step_ops.seconds_per_step(run.trace, PATTERN)
    return 1e3 * seconds if seconds else None
