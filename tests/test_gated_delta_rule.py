"""The chunked gated delta rule (``nn/layers/linear_attention.py``) against
the recurrence written token by token (``benchmark/reference/olmo_hybrid.py``
``delta_rule_recurrent``): outputs and the gradients of every input, in
float32 and in float64, at lengths that are less than a chunk, one chunk,
not a multiple of the chunk and several chunks, with the gates in their
hard corners.

Tolerances. float64: 1e-9 of the largest entry. The two forms are the same
algebra, so all that parts them is rounding, and 1e-9 is what shows the
algebra exact (a wrong decay index or a missing diagonal reads 1e-2 and
more). float32: 2e-4 of the largest entry. The chunked form solves a 64 x
64 triangular system a chunk and sums its rows in another order than the
recurrence does, 64 to 192 float32 roundings of 6e-8 each along a row
(about 1e-5 read in the easy regime); with beta near 2 and the decay near 1
the system is the worst conditioned it gets and the same rounding reads up
to 1e-4. A float32 gradient is held to 2e-4 of its largest entry or of
1e-3, whichever is larger, and the gradient of log alpha to 2e-4 of 1e-2:
where the decay wipes the state at every token (alpha 1e-6 to 1e-3, a
regime the model's own gates do not reach) that gradient is 1e-5 and less,
and the chunk's running sum of log alpha reaches -800, whose float32
rounding of 5e-5 stands in every decay ratio: 2e-6 of error was read, where
float64 reads 1e-9 of the same entries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.olmo_hybrid import delta_rule_recurrent
from deeplearning4j_tpu.nn.layers.linear_attention import (
    gated_delta_rule_chunked, unit_lower_inverse)

B, H, DK, DV = 2, 2, 8, 16

# (beta, alpha) corners: how hard a token overwrites, how much state stays
REGIMES = {
    "plain": ((0.2, 1.8), (0.5, 0.99)),
    "beta_near_2": ((1.9, 2.0), (0.9, 0.999)),
    "alpha_near_0": ((0.2, 1.8), (1e-6, 1e-3)),
    "alpha_near_1": ((1.0, 2.0), (0.9999, 1.0)),
}


def inputs(T, regime, dtype, seed=0):
    rng = np.random.default_rng(seed)
    (b_lo, b_hi), (a_lo, a_hi) = REGIMES[regime]
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((B, T, H, DK))) / np.sqrt(DK)
    k = unit(rng.standard_normal((B, T, H, DK)))
    v = rng.standard_normal((B, T, H, DV))
    beta = rng.uniform(b_lo, b_hi, (B, T, H))
    log_alpha = np.log(rng.uniform(a_lo, a_hi, (B, T, H)))
    return tuple(jnp.asarray(a, dtype) for a in (q, k, v, log_alpha, beta))


def close(got, want, tol, floor=1e-30):
    scale = max(float(jnp.max(jnp.abs(want))), floor)
    return float(jnp.max(jnp.abs(got - want))) / scale <= tol


def _out_and_grads(fn):
    def run(*args):
        weight = jnp.cos(jnp.arange(args[2].size, dtype=args[2].dtype)
                         ).reshape(args[2].shape)
        loss = lambda *a: jnp.sum(fn(*a) * weight)
        return fn(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    return jax.jit(run)     # one compilation a length and dtype, all regimes


CHUNKED = _out_and_grads(gated_delta_rule_chunked)
RECURRENT = _out_and_grads(delta_rule_recurrent)


def both(args):
    """(outputs, gradients of a weighted sum of the outputs) of the two."""
    (got, g_got), (want, g_want) = CHUNKED(*args), RECURRENT(*args)
    return (got, want), (g_got, g_want)


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("T", [7, 64, 100, 192])
def test_chunked_is_the_recurrence_in_float32(T, regime):
    (got, want), (g_got, g_want) = both(inputs(T, regime, jnp.float32))
    assert got.dtype == jnp.float32 and got.shape == (B, T, H, DV)
    assert close(got, want, 2e-4)
    for name, a, b in zip("q k v log_alpha beta".split(), g_got, g_want):
        assert close(a, b, 2e-4, floor=1e-2 if name == "log_alpha" else 1e-3
                     ), name


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("T", [7, 64, 100, 192])
def test_chunked_is_the_recurrence_in_float64(T, regime):
    with jax.enable_x64(True):
        (got, want), (g_got, g_want) = both(inputs(T, regime, jnp.float64))
        assert got.dtype == jnp.float64
        assert close(got, want, 1e-9)
        for name, a, b in zip("q k v log_alpha beta".split(), g_got, g_want):
            assert close(a, b, 1e-9), name


def test_compute_dtype_narrows_the_products_not_the_state():
    """bfloat16 operands, float32 result: within bfloat16's 2^-8 of the
    float32 form, times the few dozen terms a row sums."""
    args = inputs(100, "plain", jnp.float32)
    wide = gated_delta_rule_chunked(*args)
    narrow = gated_delta_rule_chunked(*args, compute_dtype=jnp.bfloat16)
    assert narrow.dtype == jnp.float32
    assert close(narrow, wide, 5e-2) and not close(narrow, wide, 1e-6)


@pytest.mark.parametrize("n", [5, 16, 24, 64])
def test_unit_lower_inverse(n):
    """Against numpy's inverse, where blocks are put together (64) and
    where the size sends it to plain substitution (5, 16, 24); the keys of
    one chunk all alike and beta 2 is the worst it meets."""
    rng = np.random.default_rng(n)
    for a in (np.tril(rng.uniform(-1, 1, (3, n, n)), -1),
              np.tril(2.0 * np.ones((1, n, n)), -1)):
        want = np.linalg.inv(np.eye(n) + a)
        with jax.enable_x64(True):
            got = np.asarray(jax.jit(unit_lower_inverse)(
                jnp.asarray(a, jnp.float64)))
        assert np.allclose(got, want, rtol=0, atol=1e-9 * np.abs(want).max())
