"""What the plain references share: a key from any seed, and the rounding
that turns a reference into a control of the check (the same arithmetic in
the precision below the configuration's), never into a reference."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (PRNGKey alone takes 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _fp8(a, dtype, top):
    scale = jnp.max(jnp.abs(a)) / top + 1e-30
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


def rounders(precision: str):
    """``(operand, product)``: what the operands of a matrix product or
    convolution, and its result, pass through.

    Operands are rounded to ``precision`` with the gradient passing straight
    through (a cast's own transpose would round the cotangent as well, and
    fp8 without a scale underflows to nought). The configuration keeps its
    activations in its low precision between operations (ResNet-50:
    bfloat16), so the control keeps them in its own: the result is rounded
    too, and its cotangent to e5m2, so that the backward products read fp8
    operands as an fp8 training path's do (e4m3 forward, e5m2 gradients, a
    scale per tensor)."""
    same = lambda a: a
    if precision == "float32":
        return same, same
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    low = lambda a: _fp8(a, jnp.float8_e4m3fn, 448.0)
    low_ct = lambda ct: _fp8(ct, jnp.float8_e5m2, 57344.0)

    @jax.custom_vjp
    def product(a):
        return low(a)
    product.defvjp(lambda a: (product(a), None), lambda _, ct: (low_ct(ct),))
    return (lambda a: a + lax.stop_gradient(low(a) - a)), product
