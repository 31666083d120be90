"""Everything the benchmark takes from the program under test, in one
place: the model built from a configuration's sizes, its state read back
by leaf, and the counters of its registry. The entries (``entries/``) call
the program's public entry points themselves."""

from __future__ import annotations

import importlib


def build_net(cfg: dict, weights: dict):
    """The program's network for ``cfg``, initialised with copies of
    ``weights`` (flat ``"<node>/<param>"``, float32) in the configuration's
    dtype. The program donates its parameters to its step, so it gets
    copies and ``weights`` stays whole."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph import ComputationGraph

    spec = cfg["program"]
    module, func = spec["builder"].split(":")
    kwargs = dict(spec.get("kwargs", {}))
    kwargs.update({k: cfg[v] for k, v in spec.get("kwargs_from", {}).items()})
    conf = getattr(importlib.import_module(module), func)(**kwargs)
    for field, key in spec.get("training", {}).items():
        setattr(conf.training, field, cfg[key])    # what the builder sets
    net = ComputationGraph(conf)
    dtype = jnp.dtype(cfg["dtype"])
    params = {name: {} for name in net._layer_nodes}
    for key, value in weights.items():
        node, leaf = key.split("/")
        params[node][leaf] = jnp.array(value, dtype=dtype)   # a copy
    return net.init(params=params)


def flatten(tree: dict) -> dict:
    """``{node: {param: x}}`` -> ``{"node/param": x}``."""
    return {f"{node}/{leaf}": x for node, leaves in tree.items()
            for leaf, x in leaves.items()}


def first_moment(opt_state) -> dict:
    """The optimizer's first moment by leaf (optax's ``trace`` of momentum
    or ``mu`` of Adam), from which the gradient it was given follows after
    one step."""
    import jax
    holds = lambda s: hasattr(s, "trace") or hasattr(s, "mu")
    for s in jax.tree_util.tree_leaves(opt_state, is_leaf=holds):
        if holds(s):
            return flatten(s.trace if hasattr(s, "trace") else s.mu)
    raise ValueError("the optimizer's state holds no first moment")


def leaf_norms(flat: dict, scale: float = 1.0) -> dict:
    """The Euclidean norm of every leaf, computed where the leaf lives."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: {k: scale * jnp.sqrt(jnp.sum(
        v.astype(jnp.float32) ** 2)) for k, v in t.items()})(flat)
    return {k: float(v) for k, v in norms.items()}


def change_norms(flat: dict, before: dict) -> dict:
    """The norm of every leaf's change from ``before`` (float32, on the
    default device), computed where the program keeps the leaf."""
    import jax
    import jax.numpy as jnp
    before = {k: jax.device_put(before[k], flat[k].sharding) for k in flat}
    norms = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(
        (a[k].astype(jnp.float32) - b[k]) ** 2)) for k in a})(flat, before)
    return {k: float(v) for k, v in norms.items()}


def counter(name: str) -> float:
    from deeplearning4j_tpu.profiling.metrics import get_registry
    return float(get_registry().counter(name).value)
