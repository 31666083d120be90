"""Device milliseconds a train step spends in its backward convolutions:
the instructions whose ``op_name`` ends in ``conv_general_dilated`` in the
backward phase (a convolution's gradient by its input and by its filter are
both that primitive, transposed), whatever XLA fused into them."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.ms_per_step(
        run, lambda op: op.phase == "bwd"
        and op.primitive == "conv_general_dilated")
