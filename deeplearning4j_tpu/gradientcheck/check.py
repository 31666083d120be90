"""Finite-difference gradient checking.

Ref: gradientcheck/GradientCheckUtil.java:75 — centered differences
(f(θ+ε) - f(θ-ε)) / 2ε per parameter vs the analytic gradient, in double
precision, with a smooth-activation whitelist (:47-58) and
maxRelError ≈ 1e-3 / ε ≈ 1e-6 defaults.

In the reference this validates ~10k lines of hand-written backprop; here
autodiff makes the network gradient correct by construction, so the harness's
remaining job is validating **custom gradients** (Pallas kernels with
custom_vjp, hand-coded CD gradients, masking/loss edge semantics) and
guarding against layer-math regressions. TPU f32 is too noisy for ε=1e-6
(SURVEY §7 hard part 4), so checks run on CPU under
``jax.enable_x64`` exactly as the reference runs f64 on CPU.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import numpy as np

enable_x64 = jax.enable_x64  # re-exported: tests scope f64 with it

logger = logging.getLogger("deeplearning4j_tpu")


class GradientCheckUtil:
    SMOOTH_ACTIVATIONS = ("identity", "sigmoid", "tanh", "softmax", "softplus",
                          "softsign", "cube", "elu", "gelu", "rationaltanh")

    @staticmethod
    def check_gradients(net, features, labels, *, epsilon: float = 1e-6,
                        max_rel_error: float = 1e-3,
                        min_abs_error: float = 1e-8,
                        features_mask=None, labels_mask=None,
                        subset: Optional[int] = 128,
                        seed: int = 12345,
                        print_results: bool = False) -> bool:
        """True iff every checked parameter's relative error is within
        tolerance (ref: GradientCheckUtil.checkGradients signature/semantics).

        ``subset``: check at most this many randomly-chosen parameters per
        layer (None = all — the reference checks all; subsetting keeps CI
        fast for bigger nets while still covering every parameter tensor).
        """
        import jax.numpy as jnp
        with enable_x64(True):
            # Rebuild everything in f64
            params64 = [
                {k: jnp.asarray(np.asarray(v), jnp.float64)
                 for k, v in p.items()} for p in net.params]
            states64 = [
                {k: jnp.asarray(np.asarray(v), jnp.float64)
                 for k, v in s.items()} for s in net.states]
            f = jnp.asarray(np.asarray(features), jnp.float64)
            l = jnp.asarray(np.asarray(labels), jnp.float64)
            fm = (None if features_mask is None
                  else jnp.asarray(np.asarray(features_mask), jnp.float64))
            lm = (None if labels_mask is None
                  else jnp.asarray(np.asarray(labels_mask), jnp.float64))

            @jax.jit
            def loss(p):
                # train=True, rng=None => dropout disabled, exactly as the
                # reference disables dropout for gradient checks
                val, _ = net._loss_fn(p, states64, f, l, fm, lm, rng=None,
                                      train=True)
                return val

            analytic = jax.jit(jax.grad(loss))(params64)

            rng = np.random.default_rng(seed)
            total_fail = 0
            total_checked = 0
            max_err_seen = 0.0
            for li, pdict in enumerate(params64):
                for name, arr in pdict.items():
                    flat = np.array(arr).ravel()  # writable copy
                    n = flat.size
                    idxs = (np.arange(n) if subset is None or n <= subset
                            else rng.choice(n, size=subset, replace=False))
                    a_flat = np.asarray(analytic[li][name]).ravel()
                    for i in idxs:
                        orig = flat[i]
                        flat[i] = orig + epsilon
                        p_plus = _with(params64, li, name, flat, arr.shape)
                        s_plus = float(loss(p_plus))
                        flat[i] = orig - epsilon
                        p_minus = _with(params64, li, name, flat, arr.shape)
                        s_minus = float(loss(p_minus))
                        flat[i] = orig
                        numeric = (s_plus - s_minus) / (2.0 * epsilon)
                        a = float(a_flat[i])
                        denom = max(abs(a), abs(numeric))
                        rel = abs(a - numeric) / denom if denom > 0 else 0.0
                        total_checked += 1
                        max_err_seen = max(max_err_seen, rel)
                        if rel > max_rel_error and abs(a - numeric) > min_abs_error:
                            total_fail += 1
                            if print_results or total_fail <= 10:
                                logger.warning(
                                    "Gradient check FAIL layer %d param %s[%d]: "
                                    "analytic=%.8g numeric=%.8g rel=%.4g",
                                    li, name, i, a, numeric, rel)
            if print_results:
                logger.info("Gradient check: %d/%d failed (max rel err %.3g)",
                            total_fail, total_checked, max_err_seen)
            return total_fail == 0


def _with(params, li, name, flat, shape):
    import jax.numpy as jnp
    new = [dict(p) for p in params]
    new[li][name] = jnp.asarray(flat.reshape(shape))
    return new
