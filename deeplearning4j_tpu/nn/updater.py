"""Updaters: gradient post-processing + update rules, built on optax.

The reference's updater pipeline (ref: nn/updater/LayerUpdater.java):
``preApply`` (gradient normalization/clipping, :186-220) → per-param
``GradientUpdater.getGradient`` (Adam/Nesterov/... math in ND4J's
org.nd4j.linalg.learning) → ``postApply`` (L1/L2 into gradient, ÷ batch,
:106-116). Here:

- normalization/clipping = :func:`normalize_gradients` applied to the
  per-layer gradient pytree inside the jitted train step;
- the update rule = an optax ``GradientTransformation`` built by
  :func:`build_optimizer` from the conf's :class:`UpdaterConfig`;
- L1/L2 is added to the loss (so autodiff produces the regularized
  gradient), and batch division is implicit in the mean-loss convention;
- learning-rate policies (ref: nn/conf/LearningRatePolicy.java) become an
  optax schedule from :func:`make_lr_schedule`.

Optimizer state is a pytree mirroring the param pytree — the flattened
``updaterState.bin`` view the reference checkpoints
(nn/updater/MultiLayerUpdater.java) is recovered at the serialization
boundary by util/serializer.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu.nn.conf.builder import TrainingConfig, UpdaterConfig


# ---------------------------------------------------------------------------
# mixed-precision policy (bf16 compute / fp32 master weights)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PrecisionPolicy:
    """First-class matmul/update precision policy with explicit cast
    seams — replaces the previous per-model ad-hoc bf16 handling.

    ``compute_dtype`` is what the forward/backward runs in: params (and
    float batch features) are cast to it at the step boundary, so every
    matmul sees half-precision operands while ``params_dtype`` master
    weights — owned by the updater, never donated away — stay full
    precision. The loss is cast back to ``params_dtype`` before it
    leaves the loss function, gradients are cast to ``params_dtype``
    the moment autodiff returns them, and every post-gradient op
    (normalization/clipping, optax, the divergence sentinel's grad-norm)
    therefore runs in fp32. ``loss_scale`` (static) multiplies the loss
    before differentiation and divides the fp32 gradients after — bf16
    shares fp32's exponent range so it rarely needs one, but the knob is
    the seam fp16 (and graphcheck's precision rule) expects.

    The default policy is pure fp32: every cast is gated out and the
    compiled step is the exact program it was before this policy
    existed — the bitwise-parity guarantees of the weight-update
    sharding modes only apply there.
    """

    compute_dtype: str = "float32"
    params_dtype: str = "float32"
    loss_scale: Optional[float] = None

    #: accepted shorthand -> (compute_dtype, params_dtype)
    PRESETS = {
        "fp32": ("float32", "float32"),
        "float32": ("float32", "float32"),
        "bf16": ("bfloat16", "float32"),
        "bfloat16": ("bfloat16", "float32"),
        "fp16": ("float16", "float32"),
        "float16": ("float16", "float32"),
    }

    def __post_init__(self):
        for field_name in ("compute_dtype", "params_dtype"):
            dt = getattr(self, field_name)
            try:
                ok = jnp.issubdtype(jnp.dtype(dt), jnp.floating)
            except TypeError:
                ok = False
            if not ok:
                raise ValueError(
                    f"precision {field_name} must be a float dtype, "
                    f"got {dt!r}")
        if self.loss_scale is not None and not self.loss_scale > 0:
            raise ValueError(
                f"loss_scale must be positive, got {self.loss_scale!r}")

    @property
    def mixed(self) -> bool:
        """True when the step needs cast seams (compute != master)."""
        return (self.compute_dtype != self.params_dtype
                or self.compute_dtype != "float32")

    @staticmethod
    def parse(value: Union["PrecisionPolicy", str, None],
              loss_scale: Optional[float] = None) -> "PrecisionPolicy":
        """None / "fp32" / "bf16" / a dtype name / an instance — the
        form every trainer constructor (and TrainingConfig.precision)
        takes. ``loss_scale`` applies to the string forms only."""
        if value is None:
            return PrecisionPolicy(loss_scale=loss_scale)
        if isinstance(value, PrecisionPolicy):
            return value
        key = str(value).lower()
        compute, params = PrecisionPolicy.PRESETS.get(key, (key, "float32"))
        return PrecisionPolicy(compute_dtype=compute, params_dtype=params,
                               loss_scale=loss_scale)


def cast_floats(tree, dtype):
    """Cast every inexact (float/complex) array leaf of ``tree`` to
    ``dtype``; integer/bool leaves (labels-as-ids, step counters) and
    None subtrees pass through. Works traced and untraced."""
    dtype = jnp.dtype(dtype)

    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, tree)


def precision_value_and_grad(loss_fn, policy: "PrecisionPolicy"):
    """``jax.value_and_grad(loss_fn, has_aux=True)`` with the policy's
    cast seams folded in. ``loss_fn(params, *args) -> (loss, aux)`` is
    differentiated w.r.t. ``params``; under a mixed policy the params
    are cast to the compute dtype at the boundary, the loss is cast
    back to the master dtype (and optionally loss-scaled around the
    differentiation), and the returned gradients are master-dtype.

    Pure-fp32 policies return the plain ``jax.value_and_grad`` — the
    compiled step stays the exact pre-policy program, which is what the
    weight-update-sharding bitwise parity gates run on.
    """
    if not policy.mixed:
        return jax.value_and_grad(loss_fn, has_aux=True)
    cdt = jnp.dtype(policy.compute_dtype)
    pdt = jnp.dtype(policy.params_dtype)
    scale = policy.loss_scale

    def vag(params, *args):
        with jax.named_scope("train:cast"):
            cparams = cast_floats(params, cdt)

        def seamed(p, *a):
            loss, aux = loss_fn(p, *a)
            # the loss seam: everything downstream (reporting, the
            # sentinel, the backward's seed cotangent) sees fp32
            loss = loss.astype(pdt)
            scaled = loss * scale if scale else loss
            return scaled, (loss, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(
            seamed, has_aux=True)(cparams, *args)
        # the gradient seam: master-dtype the instant autodiff returns,
        # so clip/optax/sentinel math never runs in half precision
        with jax.named_scope("train:cast"):
            grads = cast_floats(grads, pdt)
            if scale:
                grads = jax.tree.map(lambda g: g / scale, grads)
        return (loss, aux), grads

    return vag


def make_lr_schedule(u: UpdaterConfig) -> Callable:
    """iteration -> learning rate (ref: LearningRatePolicy.java semantics,
    applied in BaseOptimizer.applyLearningRateDecayPolicy)."""
    base = u.learning_rate
    policy = (u.lr_policy or "none").lower()
    if policy == "none":
        return lambda step: base
    if policy == "exponential":
        return lambda step: base * jnp.power(u.lr_policy_decay_rate, step)
    if policy == "inverse":
        return lambda step: base / jnp.power(
            1.0 + u.lr_policy_decay_rate * step, u.lr_policy_power)
    if policy == "poly":
        return lambda step: base * jnp.power(
            jnp.maximum(1.0 - step / jnp.maximum(u.lr_policy_steps, 1.0), 0.0),
            u.lr_policy_power)
    if policy == "sigmoid":
        return lambda step: base / (
            1.0 + jnp.exp(-u.lr_policy_decay_rate * (step - u.lr_policy_steps)))
    if policy == "step":
        return lambda step: base * jnp.power(
            u.lr_policy_decay_rate, jnp.floor(step / u.lr_policy_steps))
    if policy == "schedule":
        sched = sorted((u.lr_schedule or {}).items())
        if not sched:
            return lambda step: base
        bounds = jnp.array([k for k, _ in sched])
        values = jnp.array([base] + [v for _, v in sched])
        return lambda step: values[jnp.searchsorted(bounds, step, side="right")]
    raise ValueError(f"Unknown lr policy {policy!r}")


def build_optimizer(training: TrainingConfig) -> optax.GradientTransformation:
    """UpdaterConfig -> optax transform (ref: nn/conf/Updater.java enum +
    UpdaterCreator)."""
    u = training.updater
    lr = make_lr_schedule(u)
    name = u.name.lower()
    if name == "sgd":
        tx = optax.sgd(lr)
    elif name == "nesterovs":
        tx = optax.sgd(lr, momentum=u.momentum, nesterov=True)
    elif name == "adam":
        tx = optax.adam(lr, b1=u.beta1, b2=u.beta2, eps=u.epsilon)
    elif name == "adamax":
        tx = optax.adamax(lr, b1=u.beta1, b2=u.beta2, eps=u.epsilon)
    elif name == "adagrad":
        tx = optax.adagrad(lr, eps=u.epsilon)
    elif name == "adadelta":
        tx = optax.adadelta(learning_rate=1.0, rho=u.rho, eps=u.epsilon)
    elif name == "rmsprop":
        tx = optax.rmsprop(lr, decay=u.rho, eps=u.epsilon)
    elif name == "none":
        tx = optax.sgd(lr)
    else:
        raise ValueError(f"Unknown updater {u.name!r}")
    if not training.minimize:
        # maximize: ascend the objective (ref: conf.minimize flag consumed by
        # the step function, stepfunctions/NegativeGradientStepFunction)
        tx = optax.chain(optax.scale(-1.0), tx)
    return tx


def _global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l * l) for l in leaves) + 1e-12)


def normalize_gradients(grads, training: TrainingConfig):
    """Gradient normalization/clipping applied before the update rule
    (ref: nn/conf/GradientNormalization.java + LayerUpdater.preApply:186-220).

    ``grads`` is the container gradient pytree: list (per layer) of dicts
    (param name -> array), or any nested pytree where the first level is the
    per-layer grouping.
    """
    kind = (training.gradient_normalization or "none").lower()
    t = training.gradient_normalization_threshold
    if kind in ("none", ""):
        return grads

    def per_layer(fn):
        if isinstance(grads, list):
            return [fn(g) for g in grads]
        return fn(grads)

    if kind == "renormalizel2perlayer":
        return per_layer(lambda g: jax.tree.map(lambda x: x / _global_norm(g), g))
    if kind == "renormalizel2perparamtype":
        return jax.tree.map(
            lambda x: x / jnp.sqrt(jnp.sum(x * x) + 1e-12), grads)
    if kind == "clipelementwiseabsolutevalue":
        return jax.tree.map(lambda x: jnp.clip(x, -t, t), grads)
    if kind == "clipl2perlayer":
        def clip_layer(g):
            n = _global_norm(g)
            scale = jnp.where(n > t, t / n, 1.0)
            return jax.tree.map(lambda x: x * scale, g)
        return per_layer(clip_layer)
    if kind == "clipl2perparamtype":
        def clip_param(x):
            n = jnp.sqrt(jnp.sum(x * x) + 1e-12)
            return x * jnp.where(n > t, t / n, 1.0)
        return jax.tree.map(clip_param, grads)
    raise ValueError(f"Unknown gradient normalization {kind!r}")


def l1_l2_penalty(params, layers) -> jax.Array:
    """Score regularization term: sum over layers of 0.5*l2*||W||^2 + l1*|W|
    (ref: BaseLayer.calcL2/calcL1; added to score in computeGradientAndScore).
    ``params``: list of per-layer param dicts aligned with ``layers``."""
    total = jnp.zeros(())
    for layer, p in zip(layers, params):
        if not p:
            continue
        reg = layer.regularization()
        for name, arr in p.items():
            l1, l2 = reg.get(name, (0.0, 0.0))
            if l2:
                total = total + 0.5 * l2 * jnp.sum(arr * arr)
            if l1:
                total = total + l1 * jnp.sum(jnp.abs(arr))
    return total


def _zip_layers(tree, layers):
    """Pair each layer with its per-layer subtree. ``tree`` is a list
    aligned with ``layers`` (MultiLayerNetwork) or a dict keyed by the
    layer's node name (ComputationGraph)."""
    if isinstance(tree, dict):
        by_name = {l.name: l for l in layers}
        return [(by_name[k], k, v) for k, v in tree.items()]
    return [(l, i, v) for i, (l, v) in enumerate(zip(layers, tree))]


def mask_frozen(grads, layers):
    """Zero frozen layers' gradients BEFORE clipping/updating, matching the
    reference's FrozenLayer.backpropGradient returning a zero gradient
    (so frozen params neither skew global-norm clipping nor accumulate
    optimizer moments)."""
    if not any(l.frozen for l in layers):
        return grads
    if isinstance(grads, dict):
        by_name = {l.name: l for l in layers}
        return {k: (jax.tree.map(jnp.zeros_like, v)
                    if by_name[k].frozen else v)
                for k, v in grads.items()}
    return [jax.tree.map(jnp.zeros_like, g) if l.frozen else g
            for l, g in zip(layers, grads)]


def compute_updates(tx, grads, opt_state, params, layers,
                    training: TrainingConfig):
    """The shared post-gradient pipeline every training path uses:
    freeze-mask -> gradient normalization/clipping -> update rule ->
    per-layer LR scaling. Returns (new_params, new_opt_state)."""
    grads = mask_frozen(grads, layers)
    grads = normalize_gradients(grads, training)
    updates, new_opt = tx.update(grads, opt_state, params)
    updates = per_layer_lr_scale(updates, layers,
                                 training.updater.learning_rate)
    new_params = jax.tree.map(lambda p, u: p + u, params, updates)
    return new_params, new_opt


# ---------------------------------------------------------------------------
# ZeRO-1/2 weight-update sharding (parallel trainers, mode="zero1"/"zero2")
# — zero2 shares every helper here; it differs only in the trainer-side
# gradient layout (no replicated anchor: grads arrive already sharded)
# ---------------------------------------------------------------------------

def _is_shardable(x) -> bool:
    """Leaves that carry per-parameter state (arrays with >= 1 dim) are
    sharded; scalars (optax step counters) stay replicated."""
    return getattr(x, "ndim", 0) >= 1


def shard_updater_state(opt_state, mesh_ctx, axis: Optional[str] = None):
    """Re-lay an optax state pytree into the ZeRO-1 layout: every array
    leaf becomes its flattened pad-to-divisible ``(dp, chunk)`` view
    placed with a ``NamedSharding`` over the mesh's data axis, so each
    replica holds 1/dp of Adam's m+v instead of a full copy.

    Returns ``(sharded_state, template)`` — the template records each
    sharded leaf's original shape/dtype (as ``jax.ShapeDtypeStruct``) so
    :func:`gather_updater_state` can restore the replicated layout for
    the zip serializer or a non-zero1 trainer. Accumulated state is
    PRESERVED through the flatten (wrapping a trained net mid-run keeps
    its Adam moments, same as the replicated path).
    """
    from deeplearning4j_tpu.parallel.mesh import zero1_shard_leaf
    dp = mesh_ctx.zero1_shards(axis)
    sharding = mesh_ctx.zero1_sharding(axis)
    rep = mesh_ctx.replicated()

    def place(x):
        if _is_shardable(x):
            return jax.device_put(zero1_shard_leaf(x, dp), sharding)
        return jax.device_put(x, rep) if hasattr(x, "shape") else x

    def describe(x):
        if _is_shardable(x):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return None

    template = jax.tree.map(describe, opt_state,
                            is_leaf=lambda x: x is None)
    return jax.tree.map(place, opt_state), template


def gather_updater_state(opt_state, template):
    """Inverse of :func:`shard_updater_state`: slice away the padding
    and restore every leaf's original shape (replicated values). Leaves
    whose template entry is None were never sharded and pass through."""
    from deeplearning4j_tpu.parallel.mesh import zero1_unshard_leaf

    def restore(x, t):
        if t is None:
            return x
        return zero1_unshard_leaf(x, t.shape)

    return jax.tree.map(restore, opt_state, template,
                        is_leaf=lambda x: x is None)


def reshard_updater_state(opt_state, template, mesh_ctx,
                          axis: Optional[str] = None):
    """Re-lay a zero1-sharded optax state onto a DIFFERENT-width mesh:
    ``(dp_old, chunk)`` flattened views (host or device) are un-padded
    back to their original shapes via ``template`` (the record
    :func:`shard_updater_state` returned when the state was first
    sharded) and re-flattened to ``(dp_new, chunk')`` over ``mesh_ctx``'s
    data axis. Returns ``(sharded_state, new_template)`` like
    :func:`shard_updater_state`.

    The transformation is exact: un-padding recovers bitwise the values
    a replicated :func:`gather_updater_state` would, and the new padding
    is zeros the shard-local update never reads — so a trainer resumed
    at the new width computes the same updates it would have at the old
    one (the elastic resize guarantee).
    """
    return shard_updater_state(gather_updater_state(opt_state, template),
                               mesh_ctx, axis)


def updater_state_template(opt_state):
    """The gather/reshard template for an optax state already in the
    REPLICATED (full-shape) layout — what :func:`shard_updater_state`
    would have recorded. Lets a cross-width restore path that only has
    the gathered state (e.g. a checkpoint un-padded by
    ``restore_sharded_into(reshard_zero1=True)``) build the record the
    reshard helpers need."""
    def describe(x):
        if _is_shardable(x):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return None

    return jax.tree.map(describe, opt_state, is_leaf=lambda x: x is None)


def compute_updates_sharded(tx, fgrads, opt_state, params, layers,
                            training: TrainingConfig, mesh_ctx,
                            axis: Optional[str] = None):
    """ZeRO-1 counterpart of :func:`compute_updates`, traced inside the
    parallel train step. ``fgrads`` is the gradient pytree whose leaves
    are already flattened ``(dp, chunk)`` views sharded over the data
    axis (the reduce-scattered sum); ``opt_state`` leaves live in the
    same layout persistently. The whole optimizer pipeline runs on the
    local shard only — every supported update rule is elementwise, so
    the shard-local math is bit-identical to the replicated layout's —
    and the updated params are restored to full (replicated) shape,
    which XLA realizes as the ZeRO-1 all-gather.

    Per-layer gradient-norm clipping still sees per-layer subtrees (the
    flatten preserves pytree structure; padding contributes zeros to
    every norm), so ``normalize_gradients`` keeps its semantics.
    """
    from deeplearning4j_tpu.parallel.mesh import (zero1_shard_leaf,
                                                  zero1_unshard_leaf)
    dp = mesh_ctx.zero1_shards(axis)
    sharding = mesh_ctx.zero1_sharding(axis)
    rep = mesh_ctx.replicated()

    fgrads = mask_frozen(fgrads, layers)
    fgrads = normalize_gradients(fgrads, training)
    fparams = jax.tree.map(
        lambda p: jax.lax.with_sharding_constraint(
            zero1_shard_leaf(p, dp), sharding), params)
    updates, new_opt = tx.update(fgrads, opt_state, fparams)
    # pin the outgoing state to the 1/dp layout — left to propagation,
    # GSPMD may emit it replicated and the memory win evaporates after
    # the first (donated) step
    new_opt = jax.tree.map(
        lambda x: (jax.lax.with_sharding_constraint(x, sharding)
                   if getattr(x, "ndim", 0) >= 1 else x), new_opt)
    updates = per_layer_lr_scale(updates, layers,
                                 training.updater.learning_rate)
    fnew = jax.tree.map(lambda p, u: p + u, fparams, updates)
    new_params = jax.tree.map(
        lambda y, like: jax.lax.with_sharding_constraint(
            zero1_unshard_leaf(y, tuple(like.shape)), rep), fnew, params)
    return new_params, new_opt


def per_layer_lr_scale(updates, layers, base_lr: float):
    """Per-layer learning-rate override: scale each layer's update by
    layer.learning_rate / base_lr (the reference instead builds a separate
    GradientUpdater per layer with its own lr — equivalent scaling since
    update magnitude is linear in lr for every supported rule)."""
    if not any(l.learning_rate is not None for l in layers):
        return updates
    scaled = {} if isinstance(updates, dict) else [None] * len(layers)
    for layer, key, upd in _zip_layers(updates, layers):
        if layer.learning_rate is not None and base_lr > 0:
            s = layer.learning_rate / base_lr
            upd = jax.tree.map(lambda x: x * s, upd)
        scaled[key] = upd
    return scaled
