"""Activation functions.

Mirrors the set the reference exposes through ND4J ``Activation`` enum /
``IActivation`` implementations (consumed by layer confs as
``.activation("relu")`` — ref: nn/conf/layers/Layer.java builder). Implemented
as pure jnp functions so XLA fuses them into the preceding matmul/conv.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

Array = jax.Array


def identity(x: Array) -> Array:
    return x


def sigmoid(x: Array) -> Array:
    return jax.nn.sigmoid(x)


def hardsigmoid(x: Array) -> Array:
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


def tanh_(x: Array) -> Array:
    return jnp.tanh(x)


def hardtanh(x: Array) -> Array:
    return jnp.clip(x, -1.0, 1.0)


def rationaltanh(x: Array) -> Array:
    # 1.7159 * tanh(2x/3) approximation via rational function, as in ND4J
    ax = jnp.abs(x)
    a = 1.0 + ax + 0.58576695 * ax * ax + 0.11442251 * ax * ax * ax
    return 1.7159 * jnp.sign(x) * (1.0 - 1.0 / a)


def rectifiedtanh(x: Array) -> Array:
    return jnp.maximum(0.0, jnp.tanh(x))


def relu(x: Array) -> Array:
    return jax.nn.relu(x)


def relu6(x: Array) -> Array:
    return jnp.clip(x, 0.0, 6.0)


def leakyrelu(x: Array, alpha: float = 0.01) -> Array:
    return jax.nn.leaky_relu(x, negative_slope=alpha)


def elu(x: Array) -> Array:
    return jax.nn.elu(x)


def selu(x: Array) -> Array:
    return jax.nn.selu(x)


def gelu(x: Array) -> Array:
    return jax.nn.gelu(x)


def softmax(x: Array) -> Array:
    return jax.nn.softmax(x, axis=-1)


def logsoftmax(x: Array) -> Array:
    return jax.nn.log_softmax(x, axis=-1)


def softplus(x: Array) -> Array:
    return jax.nn.softplus(x)


def softsign(x: Array) -> Array:
    return jax.nn.soft_sign(x)


def cube(x: Array) -> Array:
    return x * x * x


def swish(x: Array) -> Array:
    return jax.nn.silu(x)


ACTIVATIONS: Dict[str, Callable[[Array], Array]] = {
    "identity": identity,
    "linear": identity,
    "sigmoid": sigmoid,
    "hardsigmoid": hardsigmoid,
    "tanh": tanh_,
    "hardtanh": hardtanh,
    "rationaltanh": rationaltanh,
    "rectifiedtanh": rectifiedtanh,
    "relu": relu,
    "relu6": relu6,
    "leakyrelu": leakyrelu,
    "elu": elu,
    "selu": selu,
    "gelu": gelu,
    "softmax": softmax,
    "logsoftmax": logsoftmax,
    "softplus": softplus,
    "softsign": softsign,
    "cube": cube,
    "swish": swish,
    "silu": swish,
}

# Activations smooth enough for finite-difference gradient checking
# (ref: gradientcheck/GradientCheckUtil.java:47-58 whitelist).
SMOOTH_ACTIVATIONS = frozenset(
    {"identity", "linear", "sigmoid", "tanh", "softmax", "logsoftmax",
     "softplus", "softsign", "cube", "elu", "selu", "gelu", "swish", "silu",
     "rationaltanh"}
)


def get_activation(name: str) -> Callable[[Array], Array]:
    try:
        return ACTIVATIONS[name.lower()]
    except KeyError:
        raise ValueError(
            f"Unknown activation {name!r}; available: {sorted(ACTIVATIONS)}"
        ) from None
