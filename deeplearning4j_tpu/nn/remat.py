"""Rematerialisation that the compiler cannot run early.

``jax.checkpoint`` keeps a function's inputs and rebuilds its intermediates
in the backward pass. The rebuilt forward depends on those inputs alone, so
a scheduler is free to run it long before the cotangent it will meet has
been computed; the v5e's compiler did, for three feed-forward layers at
once while the forward pass was still under way (the hybrid decoder's step
at 8,192 tokens, compiled for a described chip: the intermediates the remat
was to save were all live at the peak). ``checkpoint_after_cotangent`` ties
the two: the saved inputs and the cotangent pass one optimization barrier
together, so the rebuild cannot start before the backward pass has reached
the function. The cotangents it returns pass a second one together, so the
backward pass cannot move on to the layer before while this one's weight
gradients, and the rebuilt intermediates they read, wait for a later turn
(the same compiler kept three layers' alive that way).

What a node keeps beyond its inputs is said here and nowhere else
(``kept``). A kernel's wrapper inside a node may name values that are dear
to rebuild (the flash kernels' output and logsumexp: a second run of the
forward kernel, 5.6 ms, for 201 MB); they leave the node's forward as residuals
beside its inputs, pass the first barrier with them, and the same call
site, met again in the rebuild, is handed them back.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import lax


def _together(tree):
    """``tree`` with its floating leaves through one optimization barrier:
    none is there before all are. (A whole-number input's cotangent is a
    ``float0`` placeholder, which no program holds.)"""
    leaves, treedef = jax.tree.flatten(tree)
    held = [i for i, x in enumerate(leaves)
            if jnp.issubdtype(x.dtype, jnp.inexact)]
    for i, x in zip(held, lax.optimization_barrier(
            [leaves[i] for i in held])):
        leaves[i] = x
    return jax.tree.unflatten(treedef, leaves)


class _Node:
    """One trace of a remat node's function: its forward, which collects
    what the call sites inside name as kept, or its rebuild, which hands
    ``values`` back to the same call sites in the same order."""

    def __init__(self, values=None):
        self.rebuilding = values is not None
        self.values = [] if values is None else list(values)
        self.read = 0
        self.trace = None


class _Tracing(threading.local):
    """What this thread is tracing, innermost last. Trace time only."""

    def __init__(self):
        self.nodes = []


_tracing = _Tracing()


def _traced_as(node, fn):
    """``fn``, traced as ``node``: its forward or its rebuild, or None for
    a plain call, which keeps nothing. The node notes the trace its
    function runs under: a call site under another one (the body of a scan,
    a function differentiated inside the node) holds values that cannot
    leave through the node."""
    def traced(*args):
        _tracing.nodes.append(node)
        try:
            if node is not None:
                node.trace = jax.core.get_opaque_trace_state()
            return fn(*args)
        finally:
            _tracing.nodes.pop()
    return traced


def kept(kernel: str, compute):
    """What the remat node being traced keeps for this call site, or None
    where there is none to keep it (no remat, a node's plain call, a call
    site under a trace of its own): the caller then takes its own path.

    In a node's forward ``compute()`` gives the values (a tuple of
    arrays) and they are kept beside the node's inputs. In its rebuild the
    call site, met in the same order, gets them back and ``compute`` does
    not run; ``remat_kept_total{kernel=}`` counts that, once a trace."""
    node = _tracing.nodes[-1] if _tracing.nodes else None
    if node is None or node.trace != jax.core.get_opaque_trace_state():
        return None
    if not node.rebuilding:
        node.values.append(tuple(compute()))
        return node.values[-1]
    if node.read == len(node.values):
        raise RuntimeError(_NOT_THE_SAME_TRACE.format(
            f"met a {kernel} call site more than"))
    node.read += 1
    from deeplearning4j_tpu.profiling.metrics import get_registry
    get_registry().labeled_counter(
        "remat_kept_total",
        "call sites whose rebuild under remat read values the forward "
        "kept and ran no kernel for them, by kernel (per trace)",
    ).labels(kernel=kernel).inc()
    return node.values[node.read - 1]


_NOT_THE_SAME_TRACE = (
    "the rebuild of a remat node {} its forward kept values for: the "
    "node's function has to meet the same call sites both times (outside "
    "jit a custom_vjp function round a kernel does not: its plain call "
    "runs under the node's own trace and its forward rule does not)")


def checkpoint_after_cotangent(fn):
    """``fn`` with its inputs kept and its intermediates rebuilt in the
    backward pass, not before the output's cotangent is there. Beside the
    inputs the node keeps what a call site inside names through
    :func:`kept`, and the rebuild reads it. Reverse mode only; every
    argument is a pytree of arrays (or ``None``)."""

    @jax.custom_vjp
    def node(*args):
        return _traced_as(None, fn)(*args)

    def forward(*args):
        tr = _Node()
        out = _traced_as(tr, fn)(*args)
        return out, (args, tuple(tr.values))

    def backward(res, ct):
        args, values, ct = _together((*res, ct))
        tr = _Node(values)
        _, vjp = jax.vjp(_traced_as(tr, fn), *args)
        if tr.read != len(values):
            raise RuntimeError(_NOT_THE_SAME_TRACE.format(
                f"read {tr.read} of the {len(values)} call sites"))
        return _together(vjp(ct))

    node.defvjp(forward, backward)
    return node


def backward_after_cotangent(fn):
    """``fn`` with its own backward rule held between the same two
    barriers: for a function that already keeps its inputs alone and
    rebuilds the rest backward (a kernel under its own ``custom_vjp``).
    Reverse mode only."""

    @jax.custom_vjp
    def held(*args):
        return fn(*args)

    def forward(*args):
        return jax.vjp(fn, *args)       # the rule, with what fn keeps

    def backward(vjp, ct):
        vjp, ct = _together((vjp, ct))
        return _together(vjp(ct))

    held.defvjp(forward, backward)
    return held
