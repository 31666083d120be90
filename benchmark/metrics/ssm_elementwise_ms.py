"""Device milliseconds a train step spends in a Mamba mixer before its
scan: ``ssm:in_conv`` (``W_in``, the causal depthwise convolution and SiLU
in float32) and ``ssm:dt_bc`` (``W_x``, ``W_dt`` and the step's softplus),
forward, rebuilt and backward (``nn/layers/state_space.py``), less the
scopes' matrix products (``W_in``, ``W_x``, ``W_dt`` and their transposes:
every instruction that is a ``convolution`` or ``dot`` or a fusion holding
one), which the MXU makes at its rate."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.scope_ms(run, "ssm:in_conv", "ssm:dt_bc",
                                products=False)
