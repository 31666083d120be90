"""Device milliseconds a train step spends under ``attn:window``: the
attention of the window layers of ``GroupedQueryAttentionLayer``
(``nn/layers/attention.py``), their flash kernels with what the kernels'
wrapper pads and lays out round them, forward, rebuilt and backward. Not
the projections or the rotation (``attn:rope``), and not a full layer's
kernels, which ``flash_attention_ms`` reads with these."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.scope_ms(run, "attn:window")
