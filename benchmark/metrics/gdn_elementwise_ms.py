"""Device milliseconds a train step spends in the gated delta-rule mixers'
float32 elementwise passes: ``gdn:conv`` (the depthwise convolutions as
shifted sums, SiLU and the l2 norms of q and k) and ``gdn:gate_norm`` (the
RMSNorm over ``d_v`` and the SiLU gate), forward, rebuilt and backward
(``nn/layers/linear_attention.py``). The two scopes also hold the products
``x W_q``, ``x W_k``, ``x W_v`` and ``x W_g``, which the MXU makes at its
rate and which are left out: every instruction of the scopes that is no
matrix product (no ``convolution`` or ``dot``, no fusion holding one)."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.scope_ms(run, "gdn:conv", "gdn:gate_norm",
                                products=False)
