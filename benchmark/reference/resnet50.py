"""Plain ResNet-50 v1 (He et al., arXiv:1512.03385, table 1) for the check
of outputs: weights from a seed, forward, loss, gradients and the Nesterov
step in straightforward ``jax.numpy``, float32 at HIGHEST matmul precision.

Imports nothing of the program. Departures from the paper that follow the
program's configuration (``benchmark/configs/resnet50.json``): NHWC, the
stride of a downsampling block on its first 1x1 convolution (v1, as
published), no bias on convolutions, batch norm with eps 1e-5 and the
biased batch variance, a softmax head with bias, the loss a mean over the
batch of the cross entropy.

``precision`` selects what the arithmetic is done in: ``float32`` is the
reference; ``fp8`` is the control of the check, never a reference: the
operands and the result of every convolution and of the
head, and each block's output, rounded to that type (``lowprec.rounders``),
which is where the configuration itself keeps bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.lowprec import rounders, seed_key

STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))
BN_EPS = 1e-5


def conv_specs(cfg: dict) -> list:
    """Every convolution in forward order: (name, k, c_in, c_out, stride)."""
    specs = [("stem", 7, cfg["channels"], 64, 2)]
    c_in = 64
    for si, (width, blocks, first_stride) in enumerate(STAGES):
        for bi in range(blocks):
            stride = first_stride if bi == 0 else 1
            name = f"s{si}b{bi}"
            specs.append((f"{name}_a", 1, c_in, width, stride))
            specs.append((f"{name}_b", 3, width, width, 1))
            specs.append((f"{name}_c", 1, width, 4 * width, 1))
            if bi == 0:
                specs.append((f"{name}_proj", 1, c_in, 4 * width, stride))
            c_in = 4 * width
    return specs


def make_weights(cfg: dict, seed: int) -> dict:
    """Flat ``{"<node>/<param>": array}``: He-normal convolutions, gamma 1
    (``residual_gamma`` on the last batch norm of each block), beta 0, a
    normal head of ``head_std``, in one jitted call on the default device. Values are
    rounded to the configuration's dtype and kept in float32, so that the
    program (in that dtype) and the reference start from the same numbers."""
    specs = conv_specs(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    res_gamma = cfg.get("residual_gamma", 1.0)
    head_std = cfg.get("head_std", (2.0 / 2048) ** 0.5)

    def build(key):
        keys = jax.random.split(key, len(specs) + 1)
        w = {}
        for (name, k, c_in, c_out, _), kk in zip(specs, keys):
            std = (2.0 / (k * k * c_in)) ** 0.5
            w[f"{name}_conv/W"] = std * jax.random.normal(
                kk, (k, k, c_in, c_out), jnp.float32)
            gamma = res_gamma if name.endswith("_c") else 1.0
            w[f"{name}_bn/gamma"] = jnp.full((c_out,), gamma, jnp.float32)
            w[f"{name}_bn/beta"] = jnp.zeros((c_out,), jnp.float32)
        w["out/W"] = head_std * jax.random.normal(
            keys[-1], (2048, cfg["n_classes"]), jnp.float32)
        w["out/b"] = jnp.zeros((cfg["n_classes"],), jnp.float32)
        return {k: v.astype(dtype).astype(jnp.float32) for k, v in w.items()}

    return jax.jit(build)(seed_key(seed))


def loss_fn(w: dict, x, y, precision: str = "float32"):
    """Mean over the batch of the cross entropy of ``y`` (one-hot rows)."""
    q, product = rounders(precision)

    def conv_bn(name, h, stride, relu):
        h = product(lax.conv_general_dilated(
            q(h), q(w[f"{name}_conv/W"]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST))
        mean = jnp.mean(h, axis=(0, 1, 2))
        var = jnp.mean((h - mean) ** 2, axis=(0, 1, 2))
        h = (h - mean) * lax.rsqrt(var + BN_EPS)
        h = h * w[f"{name}_bn/gamma"] + w[f"{name}_bn/beta"]
        return jnp.maximum(h, 0.0) if relu else h

    def block(name, h, stride, project):
        a = conv_bn(f"{name}_a", h, stride, True)
        b = conv_bn(f"{name}_b", a, 1, True)
        c = conv_bn(f"{name}_c", b, 1, False)
        short = conv_bn(f"{name}_proj", h, stride, False) if project else h
        return product(jnp.maximum(c + short, 0.0))

    h = conv_bn("stem", x.astype(jnp.float32), 2, True)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for si, (_, blocks, first_stride) in enumerate(STAGES):
        for bi in range(blocks):
            # one block's activations live at a time (recomputed backward)
            h = jax.checkpoint(block, static_argnums=(0, 2, 3))(
                f"s{si}b{bi}", h, first_stride if bi == 0 else 1, bi == 0)
    h = jnp.mean(h, axis=(1, 2))
    logits = product(jnp.dot(q(h), q(w["out/W"]),
                             precision=lax.Precision.HIGHEST)) + w["out/b"]
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(logits), axis=-1))


def train_steps(cfg: dict, weights: dict, batches, precision="float32",
                fault=None) -> dict:
    """Follow the first ``len(batches)`` steps of training from ``weights``
    (Nesterov momentum as the configuration states). Returns each step's
    loss, every leaf's gradient norm at step 1 and every leaf's norm of
    change after the last step. ``fault="half_batch"`` plants the fault
    the check must catch: half of the rows left out."""
    lr, mu = cfg["learning_rate"], cfg["momentum"]

    @jax.jit
    def step(w, trace, x, y):
        loss, g = jax.value_and_grad(loss_fn)(w, x, y, precision)
        trace = {k: g[k] + mu * trace[k] for k in w}
        new = {k: w[k] - lr * (g[k] + mu * trace[k]) for k in w}
        return new, trace, loss, {k: jnp.sqrt(jnp.sum(g[k] ** 2)) for k in w}

    w = weights
    trace = {k: jnp.zeros_like(v) for k, v in w.items()}
    losses, grad_norm = [], None
    for x, y in batches:
        if fault == "half_batch":
            x, y = x[: len(x) // 2], y[: len(y) // 2]
        w, trace, loss, gn = step(w, trace, jnp.asarray(x, jnp.float32),
                                  jnp.asarray(y, jnp.float32))
        losses.append(float(loss))
        if grad_norm is None:
            grad_norm = {k: float(v) for k, v in gn.items()}
    delta = jax.jit(lambda a, b: {
        k: jnp.sqrt(jnp.sum((a[k] - b[k]) ** 2)) for k in a})(w, weights)
    return {"losses": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(v) for k, v in delta.items()}}


# ---------------------------------------------------------------------------
# operations the algorithm needs, from shapes
# ---------------------------------------------------------------------------

def forward_flops_per_sample(cfg: dict) -> float:
    """Multiply-adds counted as two operations, convolutions and head only.
    With the stride on the first 1x1 convolution (v1) this is 3.86 G
    multiply-adds at 224x224, the paper's "3.8 x 10^9"; the 4.1 G often
    quoted is v1.5, which strides its 3x3."""
    up = lambda n, s: -(-n // s)
    h, w = up(cfg["height"], 2), up(cfg["width"], 2)        # stem
    macs = h * w * 49 * cfg["channels"] * 64
    h, w, c_in = up(h, 2), up(w, 2), 64                     # max pool
    for width, blocks, first_stride in STAGES:
        for bi in range(blocks):
            s = first_stride if bi == 0 else 1
            h, w = up(h, s), up(w, s)
            per_pixel = c_in * width + 9 * width * width + 4 * width * width
            if bi == 0:
                per_pixel += c_in * 4 * width
            macs += h * w * per_pixel
            c_in = 4 * width
    return 2.0 * (macs + 2048 * cfg["n_classes"])


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward once and backward twice (gradients of inputs and of weights)."""
    return 3.0 * forward_flops_per_sample(cfg)
