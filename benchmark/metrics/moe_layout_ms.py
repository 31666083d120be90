"""Device milliseconds a train step spends laying the routed experts'
tokens out and bringing them back: ``moe:dispatch`` (the assignments'
two sorts, the count by expert, the gather of the tokens' rows in expert
order) and ``moe:combine`` (each token gathers its assignments' outputs and
adds them by weight), forward, rebuilt and backward, in
``nn/layers/experts.py``. Not the grouped products (``moe_expert_ms``) and
not the router (``moe:route``)."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.scope_ms(run, "moe:dispatch", "moe:combine")
