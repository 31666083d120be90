"""Device milliseconds a train step spends in the grouped products of its
routed experts (``moe:experts``), and in nothing else of the expert layer.

What it matches: the operations the compiled step names ``ragged-dot-*``.
``jax.lax.ragged_dot`` lowers on a TPU to a grouped-product custom call
(``ragged-dot-none`` forward; the backward's two transposes carry the same
stem) behind a small ``ragged-dot-metadata`` call that lays the groups out
in tiles, and both are counted: the second is part of what a grouped
product costs. A step of the cell holds, a layer: three products forward,
the same three once more under ``remat``, and six backward (each product's
two transposes), each with its metadata call; ``PERF.md`` section 5 gives
the count read from the chip's trace. Not counted, because they carry a
fusion's name and ``trace.short_name`` drops the ``op_name``: the router's
product and top-8 (``moe:route``), the sort, gather and scatter-add of the
assignments (``moe:dispatch``, ``moe:combine``) and the SiLU gate between
the products; the join of section 5 reads those by scope. A Pallas kernel
for the grouped products, if one takes their place, is read by the name it
is to carry, ``moe_grouped_*``."""

from benchmark.metrics import step_ops

PATTERN = r"^ragged-dot|moe_grouped_"


def read(run):
    seconds, _ = step_ops.seconds_per_step(run.trace, PATTERN)
    return 1e3 * seconds if seconds else None
