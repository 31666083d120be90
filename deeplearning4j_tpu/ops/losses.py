"""Loss functions.

Mirrors the reference's ND4J ``LossFunctions.LossFunction`` enum consumed by
output-layer confs (ref: nn/conf/layers/OutputLayer.java,
nn/layers/BaseOutputLayer.java `computeScore`). Every loss takes
``(labels, preout, activation_name, mask)`` and returns the **per-example
summed** loss vector of shape ``[batch]``; containers average over batch to
produce the reference's ``score`` semantics (score = mean per-example loss
+ L1/L2 — ref: nn/multilayer/MultiLayerNetwork.java:1840).

Softmax+MCXENT and sigmoid+XENT are fused for numerical stability, matching
the reference's special-cased "softmax with loss fn" gradient shortcut
(ref: org.nd4j.linalg.lossfunctions.impl.LossMCXENT).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.activations import get_activation

Array = jax.Array

_EPS = 1e-7


def _apply_act(preout: Array, activation: str) -> Array:
    return get_activation(activation)(preout)


def is_class_ids(labels: Array) -> bool:
    """Integer targets: one class id where a one-hot row would stand, so
    ``labels`` has one axis fewer than the logits (``[B, T]`` against
    ``[B, T, V]``: 64 KB a sequence of 8,192 where the rows take 411 MB at
    a vocabulary of 12,544)."""
    return jnp.issubdtype(labels.dtype, jnp.integer)


def promote_loss_dtype(preout: Array, labels: Array):
    """Mixed precision: losses compute in >= f32 (promote, don't hard-cast,
    so f64 gradient checks stay f64). Class ids stay whole numbers."""
    dt = jnp.promote_types(preout.dtype, jnp.float32)
    if is_class_ids(labels):
        return preout.astype(dt), labels
    return preout.astype(dt), labels.astype(dt)


def _reduce(per_elem: Array, mask: Optional[Array]) -> Array:
    """Sum per-element losses over feature axes -> [batch]; apply mask."""
    if mask is not None:
        # mask broadcasting: [batch] or [batch, 1] or full shape
        while mask.ndim < per_elem.ndim:
            mask = mask[..., None]
        per_elem = per_elem * mask
    axes = tuple(range(1, per_elem.ndim))
    return jnp.sum(per_elem, axis=axes)


def mse(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = _apply_act(preout, activation)
    # ref LossMSE: mean over output features of squared error
    n = labels.shape[-1]
    return _reduce((out - labels) ** 2, mask) / n


def l2(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = _apply_act(preout, activation)
    return _reduce((out - labels) ** 2, mask)


def mae(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = _apply_act(preout, activation)
    n = labels.shape[-1]
    return _reduce(jnp.abs(out - labels), mask) / n


def l1(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = _apply_act(preout, activation)
    return _reduce(jnp.abs(out - labels), mask)


def mcxent(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    """Multi-class cross entropy. Fused when activation == softmax.
    ``labels`` are one-hot (or soft) rows, or class ids (``is_class_ids``):
    the id picks the one term a one-hot row would keep, so the two give the
    same float32 bits."""
    if activation == "softmax":
        logp = jax.nn.log_softmax(preout, axis=-1)
    else:
        logp = jnp.log(jnp.clip(_apply_act(preout, activation), _EPS,
                                1.0 - _EPS))
    if is_class_ids(labels):
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return _reduce(-picked[..., 0], mask)
    return _reduce(-labels * logp, mask)


def negativeloglikelihood(labels, preout, activation, mask=None):
    return mcxent(labels, preout, activation, mask)


def xent(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    """Binary cross entropy. Fused when activation == sigmoid."""
    if activation == "sigmoid":
        # stable: max(z,0) - z*y + log(1+exp(-|z|))
        z = preout
        per = jnp.maximum(z, 0.0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z)))
        return _reduce(per, mask)
    out = jnp.clip(_apply_act(preout, activation), _EPS, 1.0 - _EPS)
    per = -(labels * jnp.log(out) + (1.0 - labels) * jnp.log(1.0 - out))
    return _reduce(per, mask)


def hinge(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = _apply_act(preout, activation)
    # labels in {-1, +1} or {0,1} -> map to ±1 like the reference does
    y = jnp.where(labels > 0, 1.0, -1.0)
    return _reduce(jnp.maximum(0.0, 1.0 - y * out), mask)


def squared_hinge(labels, preout, activation, mask=None):
    out = _apply_act(preout, activation)
    y = jnp.where(labels > 0, 1.0, -1.0)
    return _reduce(jnp.maximum(0.0, 1.0 - y * out) ** 2, mask)


def kl_divergence(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = jnp.clip(_apply_act(preout, activation), _EPS, 1.0)
    lab = jnp.clip(labels, _EPS, 1.0)
    return _reduce(lab * (jnp.log(lab) - jnp.log(out)), mask)


def poisson(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = jnp.clip(_apply_act(preout, activation), _EPS, None)
    return _reduce(out - labels * jnp.log(out), mask)


def cosine_proximity(labels: Array, preout: Array, activation: str, mask=None) -> Array:
    out = _apply_act(preout, activation)
    ln = jnp.linalg.norm(labels, axis=-1, keepdims=True)
    on = jnp.linalg.norm(out, axis=-1, keepdims=True)
    cos = jnp.sum(labels * out, axis=-1, keepdims=True) / jnp.maximum(ln * on, _EPS)
    return _reduce(-cos, mask)


def mean_squared_logarithmic_error(labels, preout, activation, mask=None):
    out = _apply_act(preout, activation)
    n = labels.shape[-1]
    per = (jnp.log1p(jnp.maximum(out, -1 + _EPS)) - jnp.log1p(labels)) ** 2
    return _reduce(per, mask) / n


def mean_absolute_percentage_error(labels, preout, activation, mask=None):
    out = _apply_act(preout, activation)
    n = labels.shape[-1]
    per = jnp.abs((labels - out) / jnp.where(jnp.abs(labels) < _EPS, _EPS, labels)) * 100.0
    return _reduce(per, mask) / n


LOSSES: Dict[str, Callable] = {
    "mse": mse,
    "l2": l2,
    "mae": mae,
    "l1": l1,
    "mcxent": mcxent,
    "negativeloglikelihood": negativeloglikelihood,
    "nll": negativeloglikelihood,
    "xent": xent,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "kl_divergence": kl_divergence,
    "reconstruction_crossentropy": xent,
    "poisson": poisson,
    "cosine_proximity": cosine_proximity,
    "msle": mean_squared_logarithmic_error,
    "mape": mean_absolute_percentage_error,
}


def get_loss(name: str) -> Callable:
    try:
        return LOSSES[name.lower()]
    except KeyError:
        raise ValueError(f"Unknown loss {name!r}; available: {sorted(LOSSES)}") from None
