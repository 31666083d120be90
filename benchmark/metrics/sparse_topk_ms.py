"""Device milliseconds a train step spends sorting: the selection of a
sparse attention layer's keys (``dsa:topk``) and what shares its kind.

What it matches: the ``sort`` operations of the step, by kind, because
``trace.short_name`` keeps an operation's name and drops its ``op_name``.
The selection finds each query's ``topk``-th largest index score by
``lax.top_k``, which the compiler lowers to a sort of the row with its
indices (``[query_chunk, T]`` float32 a turn of the indexer's loop over
query chunks: 16 turns a layer at the cell's shape, once a step,
since the indexer is frozen and the attention node keeps the selection
under ``remat``). Two smaller sorts share the kind and ARE counted, a
layer: the experts' stable sort of the ``N k`` assignments by expert
(``moe:dispatch``), forward and once more under ``remat``, and the router's
top-8 of 128 where the compiler lowers it to a sort. ``PERF.md`` section 5
gives the events a step and the selection's share of them, read from the
chip's trace. Not counted: the index scores' products and the running
count that settles equal scores (fusions, read by scope in the join of
section 5). A program without a selection or a sort reads nothing."""

from benchmark.metrics import step_ops

PATTERN = r"^sort(\.\d+)?$"


def read(run):
    seconds, _ = step_ops.seconds_per_step(run.trace, PATTERN)
    return 1e3 * seconds if seconds else None
