"""Device milliseconds a train step spends in the selective scans of its
state-space layers (``ssm:scan``), and in nothing else.

What it matches: on the XLA path, the ``while`` operations of the step. The
chunked scan (``nn/layers/state_space.selective_scan_chunked``) is a
``lax.scan`` across blocks of tokens, which lowers to ONE ``while`` whose
event spans all its turns, and the work inside a block is unrolled (no loop
within the loop), so each pass over a layer is one event. ``trace.short_name``
keeps an operation's name and drops its ``op_name``, so the loops are told
by kind and not by scope, as ``delta_rule_scan_ms`` tells its own. A step of
the cell has nine, three a state-space layer: the forward scan, the same
scan once more under ``remat``, and its transpose (which rebuilds each
block's states before it walks them back). Nothing else in that step is a
``while``: the attention layers run Pallas kernels (custom calls), the
memory units and the head are products, and the step compiled for a
described v5e holds the nine and no other (``PERF.md`` section 5 gives the
count read from the chip's trace). The relayouts into blocks and the
``D x`` term lie outside the loops and are not counted; they are passes
over ``[T, d_in]``, not over the state. With a Pallas kernel for the scan
the loops leave the path and the kernel's events are read by the names it
is to carry, ``selective_scan_fwd`` and ``selective_scan_bwd``."""

from benchmark.metrics import step_ops

PATTERN = r"^while(\.\d+)?$|selective_scan_(fwd|bwd)"


def read(run):
    seconds, _ = step_ops.seconds_per_step(run.trace, PATTERN)
    return 1e3 * seconds if seconds else None
