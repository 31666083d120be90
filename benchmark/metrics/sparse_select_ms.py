"""Device milliseconds a train step spends selecting a sparse attention
layer's keys: ``dsa:index`` (the index scores of a chunk of queries) and
``dsa:topk`` (the threshold's counting search, the running count of equal
scores and the selects) inside ``SparseIndexerLayer``'s loop
(``nn/layers/attention.py``, ``ops/topk_threshold.py``): fusions inside
each layer's ``lax.map``, which no reader by kind or by kernel name can
tell from their neighbours. ``sparse_topk_ms`` reads the step's sorts,
which since PR 36 are the experts' and not the selection's."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.scope_ms(run, "dsa:index", "dsa:topk")
