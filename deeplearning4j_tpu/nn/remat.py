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
"""

from __future__ import annotations

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


def checkpoint_after_cotangent(fn):
    """``fn`` with its inputs kept and its intermediates rebuilt in the
    backward pass, not before the output's cotangent is there. Reverse mode
    only; every argument is a pytree of arrays (or ``None``)."""

    @jax.custom_vjp
    def kept(*args):
        return fn(*args)

    def forward(*args):
        return fn(*args), args

    def backward(args, ct):
        args, ct = _together((args, ct))
        _, vjp = jax.vjp(fn, *args)
        return _together(vjp(ct))

    kept.defvjp(forward, backward)
    return kept


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
