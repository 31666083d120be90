"""Device milliseconds a train step spends under ``attn:rope``: the norm
by head and the rotation of the queries and keys of
``GroupedQueryAttentionLayer`` and of the indexer's (``nn/layers/
attention.py``), in float32, forward, rebuilt and backward."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.scope_ms(run, "attn:rope")
