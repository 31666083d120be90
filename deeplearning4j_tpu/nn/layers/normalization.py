"""Normalization layers: BatchNormalization, LocalResponseNormalization.

References:
- nn/layers/normalization/BatchNormalization.java (+ conf
  nn/conf/layers/BatchNormalization.java): train vs inference stats,
  running mean/var decay, optional lock of gamma/beta.
  CudnnBatchNormalizationHelper → here XLA fuses the normalization chain.
- nn/layers/normalization/LocalResponseNormalization.java (AlexNet LRN).

BN running statistics are layer *state*, threaded functionally through the
container (the reference mutates globalMean/globalVar params in place).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import BaseLayerConf, Params, State, register_layer


def rms_normalize(x, eps: float):
    """``x / sqrt(mean(x^2) + eps)`` over the last axis, the statistics and
    the result in float32 or wider."""
    x = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _batch_mean(a, axes):
    """``mean(a, axes)`` as the mean over the examples of each example's
    own mean. A backend that adds a reduction's terms one after another
    (XLA's CPU one does) then rounds over H*W + N additions and not over
    N*H*W of them, which the difference of two raw moments feels: float32
    at 8x16x16 values a channel reads the one-pass variance 4e-7 off where
    one reduction over all three axes reads 2.5e-6 and the two-pass form
    1.4e-7 (tests/test_batchnorm_onepass.py). On the v5e each moment's
    first reduction rides in the convolution's fusion as the whole one
    did, and the second is one operation of a microsecond (PERF.md §5)."""
    return jnp.mean(jnp.mean(a, axis=axes[1:]), axis=0)


@register_layer
@dataclass
class BatchNormalization(BaseLayerConf):
    """Batch norm over the channel/feature axis (last axis in NHWC/FF).

    Training mode takes the batch's statistics in ONE pass: the two moments
    ``m1 = mean(x - c)`` and ``m2 = mean((x - c)**2)`` are sibling
    reductions of the layer's input, ``var = m2 - m1**2``. Neither waits
    for the other, so XLA computes both where the input is produced (a
    convolution's epilogue) instead of reading the tensor again for a
    variance centred on a mean it had to finish first. The output is
    ``(x - mean) * scale + beta`` with ``scale = gamma * rsqrt(var + eps)``
    folded first and the mean's derivative routed through the per-channel
    shift (``-scale * sum(dy)``, which ``beta``'s sum already gives), so
    that under plain autodiff, in either mode, the backward has two
    reductions over the activation and no more, ``sum(dy)`` and
    ``sum(dy * (x - mean))``: siblings that ride in the next convolution's
    fusion; everything else there is per-channel arithmetic. The two-pass
    form chains four (mean, variance, ``sum(dy * xc)``, the mean's
    cotangent), and ``gamma * xhat + beta`` asks for four sums of which two
    differ from the others by per-channel factors the compiler cannot see.

    Raw moments have three weaknesses, each guarded where it arises:

    - ``E[x**2] - E[x]**2`` cancels when a feature's mean dwarfs its
      spread, so the moments are taken about ``c =
      stop_gradient(state["mean"])``, a per-channel constant known before
      the step runs (the variance does not depend on it, ``m1`` gets it
      back). Mean 100, spread 1 in float32 agrees with the two-pass
      variance to 1e-4 relative once the running mean has followed the
      feature (5e-7 read), and to 2e-2 from a fresh state, where c = 0
      (4.4e-3 read; tests/test_batchnorm_onepass.py). The shift costs the
      v5e nothing: both reductions sit in the convolution's fusion with it
      (PERF.md §5).
    - The difference feels the rounding of ``m2``'s whole sum, not of the
      spread's, so each moment is the mean over the examples of each
      example's own mean (``_batch_mean``).
    - Where the spread is under what ``m2`` resolves the difference is
      noise of either sign, and a variance clamped at 0 there would emit
      ``(x - mean) * 316 * gamma``, without bound (two values a channel an
      ulp apart, a constant feature far from 0); where the squares
      overflow float32 it is ``inf - inf``. The variance is held at
      ``8 * eps * m2`` or over: the output then keeps the two-pass form's
      bound of sqrt(N) to a factor 1.2, and is ``beta``, as the two-pass
      form's, where ``m2`` is inf (a diverged net: ``chip_smoke.py
      --dry-cpu`` at its batch of 2 reaches 3.8e23 by its fifth step).
    """
    decay: float = 0.9
    eps: float = 1e-5
    is_minibatch: bool = True
    lock_gamma_beta: bool = False
    gamma: float = 1.0
    beta: float = 0.0
    # filled by builder:
    n_features: int = 0

    def set_n_in(self, in_type: InputType) -> None:
        self.n_in = in_type.flat_size()
        self.n_features = (in_type.channels if in_type.kind == "cnn"
                           else in_type.flat_size())

    def infer_output_type(self, in_type: InputType) -> InputType:
        return in_type

    def param_order(self) -> List[str]:
        return [] if self.lock_gamma_beta else ["gamma", "beta"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.full((self.n_features,), self.gamma, dtype),
                "beta": jnp.full((self.n_features,), self.beta, dtype)}

    def init_state(self) -> State:
        return {"mean": jnp.zeros((self.n_features,)),
                "var": jnp.ones((self.n_features,))}

    def apply(self, params, x, *, state, train, rng, mask=None):
        axes = tuple(range(x.ndim - 1))  # all but channel/feature
        in_dtype = x.dtype
        # statistics in >= f32 for stability (standard mixed-precision BN);
        # promote (not hard-cast) so f64 gradient checks stay f64
        xs = x.astype(jnp.promote_types(in_dtype, jnp.float32))
        gamma, beta = ((self.gamma, self.beta) if self.lock_gamma_beta
                       else (params["gamma"], params["beta"]))
        if not (train and self.is_minibatch):
            xhat = (xs - state["mean"]) * jax.lax.rsqrt(
                state["var"] + self.eps)
            return (gamma * xhat + beta).astype(in_dtype), state
        # both moments reduce the same input, side by side (see the class
        # docstring); c is a constant of the step, so it costs the
        # reductions nothing and the variance is indifferent to it
        c = jax.lax.stop_gradient(state["mean"]).astype(xs.dtype)
        xc = xs - c
        m1, m2 = _batch_mean(xc, axes), _batch_mean(xc * xc, axes)
        # never under what m2's own rounding resolves: the difference is
        # noise there, of either sign, and nan where the squares overflow
        # (a comparison with nan is false, so that reads inf as well)
        spread, resolved = m2 - m1 * m1, 8 * jnp.finfo(xs.dtype).eps * m2
        var = jnp.where(spread > resolved, spread, resolved)
        new_state = {
            "mean": self.decay * state["mean"] + (1 - self.decay) * (m1 + c),
            "var": self.decay * state["var"] + (1 - self.decay) * var,
        }
        # gamma * (x - mean) * inv + beta, centred as ever, with m1's
        # derivative routed through the per-channel shift (the bracket is
        # exactly beta): the cotangents of scale and shift are then the
        # backward's only two reductions over the activation
        scale = gamma * jax.lax.rsqrt(var + self.eps)
        m1_held = jax.lax.stop_gradient(m1)
        out = (xc - m1_held) * scale + (beta - (m1 - m1_held) * scale)
        return out.astype(in_dtype), new_state


@register_layer
@dataclass
class LocalResponseNormalization(BaseLayerConf):
    """Cross-channel LRN: x / (k + alpha*sum_{nearby channels} x^2)^beta
    (ref: nn/layers/normalization/LocalResponseNormalization.java;
    CudnnLocalResponseNormalizationHelper). Composed from XLA reduce-window
    over the channel axis."""
    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75

    def set_n_in(self, in_type: InputType) -> None:
        self.n_in = in_type.flat_size()

    def infer_output_type(self, in_type: InputType) -> InputType:
        return in_type

    def param_order(self) -> List[str]:
        return []

    def apply(self, params, x, *, state, train, rng, mask=None):
        half = int(self.n // 2)
        sq = x * x
        # sum over a window of `n` channels centered at each channel (NHWC)
        summed = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add,
            window_dimensions=(1, 1, 1, int(self.n)),
            window_strides=(1, 1, 1, 1),
            padding=[(0, 0), (0, 0), (0, 0), (half, half)],
        )
        return x / jnp.power(self.k + self.alpha * summed, self.beta), state


@register_layer
@dataclass
class LayerNormalization(BaseLayerConf):
    """Layer normalization over the feature (last) axis — per example,
    batch-independent. The reference snapshot predates LayerNorm (its
    normalization is BatchNormalization.java); this is the modern
    companion of SelfAttentionLayer (pre/post-norm transformer blocks)
    and, being stateless, it composes with every trainer including the
    GPipe pipelines. Statistics compute in >= f32 like BN."""
    eps: float = 1e-5
    # filled by builder:
    n_features: int = 0

    def set_n_in(self, in_type: InputType) -> None:
        # same per-kind feature-axis rule as BatchNormalization above
        self.n_in = in_type.flat_size()
        self.n_features = (in_type.channels if in_type.kind == "cnn"
                           else in_type.flat_size())

    def infer_output_type(self, in_type: InputType) -> InputType:
        return in_type

    def param_order(self) -> List[str]:
        return ["gamma", "beta"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        return {"gamma": jnp.ones((self.n_features,), dtype),
                "beta": jnp.zeros((self.n_features,), dtype)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        in_dtype = x.dtype
        xs = x.astype(jnp.promote_types(in_dtype, jnp.float32))
        mean = jnp.mean(xs, axis=-1, keepdims=True)
        var = jnp.var(xs, axis=-1, keepdims=True)
        xhat = (xs - mean) * jax.lax.rsqrt(var + self.eps)
        out = params["gamma"] * xhat + params["beta"]
        return out.astype(in_dtype), state


@register_layer
@dataclass
class RMSNorm(BaseLayerConf):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the feature axis, with no
    mean taken out and no shift (Zhang and Sennrich, arXiv:1910.07467):
    the norm of today's decoder blocks. Statistics in float32 or wider,
    the output in the input's dtype; rank-agnostic, as
    ``LayerNormalization`` is."""
    eps: float = 1e-6
    n_features: int = 0

    def set_n_in(self, in_type: InputType) -> None:
        self.n_in = in_type.flat_size()
        self.n_features = (in_type.channels if in_type.kind == "cnn"
                           else in_type.flat_size())

    def infer_output_type(self, in_type: InputType) -> InputType:
        return in_type

    def param_order(self) -> List[str]:
        return ["gamma"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        return {"gamma": jnp.ones((self.n_features,), dtype)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        y = rms_normalize(x, self.eps) * params["gamma"].astype(
            jnp.promote_types(x.dtype, jnp.float32))
        return y.astype(x.dtype), state
