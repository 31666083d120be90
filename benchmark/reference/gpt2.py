"""Plain GPT-2 (Radford et al. 2019) for the check of outputs: weights from
a seed, the forward pass, the loss, gradients and the Adam step in
straightforward ``jax.numpy``, float32 at HIGHEST matmul precision, no
kernels, no cache, no batching across requests.

Imports nothing of the program. Departures from the published block that
follow the program's ``gpt_decoder`` (``benchmark/configs/gpt2-small.json``
lists them under ``assumed``): no bias on the attention projections, q, k
and v as three matrices, one bias on the embedding's output. The rest is
as published: learned positions, pre-LN, tanh GELU, a head tied to the
token embedding, LayerNorm eps 1e-5. Tokens are ids here; the program is
fed one-hot rows of the same ids.

``precision``: ``float32`` is the reference. ``bfloat16`` is the control of
the check, never a reference: weights, activations and every operation in
bfloat16, the precision below the float32 the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.lowprec import seed_key

LN_EPS = 1e-5


def make_weights(cfg: dict, seed: int) -> dict:
    """Flat ``{"<node>/<param>": array}`` in one jitted call on the default
    device: normal(0, 0.02) matrices as GPT-2 initialises them, the
    projections into the residual stream scaled by 1/sqrt(2 n_layer)."""
    V, T, D = cfg["vocab_size"], cfg["n_positions"], cfg["n_embd"]
    L, F = cfg["n_layer"], 4 * cfg["n_embd"]
    dtype = jnp.dtype(cfg["dtype"])
    shapes = {"embed/W": (V, D), "embed/P": (T, D)}
    for i in range(L):
        shapes.update({f"b{i}_attn/Wq": (D, D), f"b{i}_attn/Wk": (D, D),
                       f"b{i}_attn/Wv": (D, D), f"b{i}_attn/Wo": (D, D),
                       f"b{i}_ff1/W": (D, F), f"b{i}_ff2/W": (F, D)})

    def build(key):
        keys = jax.random.split(key, len(shapes))
        w = {}
        for (name, shape), k in zip(shapes.items(), keys):
            std = 0.02
            if name.endswith(("attn/Wo", "ff2/W")):
                std = 0.02 / (2 * L) ** 0.5
            w[name] = std * jax.random.normal(k, shape, jnp.float32)
        w["embed/b"] = jnp.zeros((D,), jnp.float32)
        for i in range(L):
            w[f"b{i}_ff1/b"] = jnp.zeros((F,), jnp.float32)
            w[f"b{i}_ff2/b"] = jnp.zeros((D,), jnp.float32)
        for ln in [f"b{i}_ln{j}" for i in range(L) for j in (1, 2)] + ["ln_f"]:
            w[f"{ln}/gamma"] = jnp.ones((D,), jnp.float32)
            w[f"{ln}/beta"] = jnp.zeros((D,), jnp.float32)
        return {k: v.astype(dtype).astype(jnp.float32) for k, v in w.items()}

    return jax.jit(build)(seed_key(seed))


def logits_fn(cfg: dict, w: dict, ids, precision: str = "float32"):
    """``ids`` [B, T] -> logits [B, T, V] in float32."""
    prec = lax.Precision.HIGHEST
    if precision == "bfloat16":
        prec = None
        w = {k: v.astype(jnp.bfloat16) for k, v in w.items()}
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    H = cfg["n_head"]
    B, T = ids.shape
    D = cfg["n_embd"]

    def ln(name, h):
        mean = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean((h - mean) ** 2, axis=-1, keepdims=True)
        return ((h - mean) * lax.rsqrt(var + LN_EPS) * w[f"{name}/gamma"]
                + w[f"{name}/beta"])

    def mm(a, b):
        return jnp.matmul(a, b, precision=prec)

    def block(i, h):
        x = ln(f"b{i}_ln1", h)
        heads = lambda m: mm(x, m).reshape(B, T, H, D // H).transpose(
            0, 2, 1, 3)
        qh, kh, vh = (heads(w[f"b{i}_attn/W{c}"]) for c in "qkv")
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                       precision=prec) / (D // H) ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vh,
                       precision=prec)
        a = a.transpose(0, 2, 1, 3).reshape(B, T, D)
        h = h + mm(a, w[f"b{i}_attn/Wo"])
        x = ln(f"b{i}_ln2", h)
        x = jax.nn.gelu(mm(x, w[f"b{i}_ff1/W"]) + w[f"b{i}_ff1/b"],
                        approximate=True)
        return h + mm(x, w[f"b{i}_ff2/W"]) + w[f"b{i}_ff2/b"]

    h = w["embed/W"][ids] + w["embed/b"] + w["embed/P"][None, :T]
    for i in range(cfg["n_layer"]):
        h = jax.checkpoint(block, static_argnums=(0,))(i, h)
    return mm(ln("ln_f", h), w["embed/W"].T).astype(jnp.float32)


def loss_fn(cfg, w, ids, targets, precision="float32"):
    """Cross entropy summed over time and averaged over the batch."""
    logp = jax.nn.log_softmax(logits_fn(cfg, w, ids, precision))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(jnp.sum(picked, axis=1))


def train_steps(cfg: dict, weights: dict, batches, precision="float32",
                fault=None) -> dict:
    """Follow the first ``len(batches)`` steps of Adam from ``weights``.
    ``batches`` are (ids, targets) of whole numbers. Returns what
    ``resnet50.train_steps`` returns."""
    lr, b1, b2, eps = cfg["learning_rate"], 0.9, 0.999, 1e-8

    @jax.jit
    def step(w, mu, nu, t, ids, targets):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, targets, precision))(w)
        mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in w}
        nu = {k: b2 * nu[k] + (1 - b2) * g[k] ** 2 for k in w}
        new = {k: w[k] - lr * (mu[k] / (1 - b1 ** t))
               / (jnp.sqrt(nu[k] / (1 - b2 ** t)) + eps) for k in w}
        return new, mu, nu, loss, {k: jnp.sqrt(jnp.sum(g[k] ** 2)) for k in w}

    w = weights
    mu = {k: jnp.zeros_like(v) for k, v in w.items()}
    nu = {k: jnp.zeros_like(v) for k, v in w.items()}
    losses, grad_norm = [], None
    for t, (ids, targets) in enumerate(batches, start=1):
        if fault == "half_batch":
            ids, targets = ids[: len(ids) // 2], targets[: len(targets) // 2]
        w, mu, nu, loss, gn = step(w, mu, nu, jnp.float32(t),
                                   jnp.asarray(ids), jnp.asarray(targets))
        losses.append(float(loss))
        if grad_norm is None:
            grad_norm = {k: float(v) for k, v in gn.items()}
    delta = jax.jit(lambda a, b: {
        k: jnp.sqrt(jnp.sum((a[k] - b[k]) ** 2)) for k in a})(w, weights)
    return {"losses": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(v) for k, v in delta.items()}}


def served_gaps(cfg: dict, weights: dict, requests, precision="float32"):
    """For each ``(prompt, served)`` pair, the reference's logits over the
    prompt with its served tokens, one sequence at a time, padded to the
    model's positions. Returns per request the gaps by which each served
    token's logit lies below the reference's best and, for a control
    ``precision``, the gaps of the tokens that precision puts first."""
    T = cfg["n_positions"]

    @jax.jit
    def one(w, ids, served, start, n):
        ref = logits_fn(cfg, w, ids[None], "float32")[0]
        pos = start - 1 + jnp.arange(served.shape[0])
        rows = ref[jnp.clip(pos, 0, T - 1)]
        best = jnp.max(rows, axis=-1)
        live = jnp.arange(served.shape[0]) < n
        gap = jnp.where(live, best - jnp.take_along_axis(
            rows, served[:, None], axis=-1)[:, 0], 0.0)
        if precision == "float32":
            return gap, gap
        low = logits_fn(cfg, w, ids[None], precision)[0]
        first = jnp.argmax(low[jnp.clip(pos, 0, T - 1)], axis=-1)
        cgap = jnp.where(live, best - jnp.take_along_axis(
            rows, first[:, None], axis=-1)[:, 0], 0.0)
        return gap, cgap

    out = []
    for prompt, served in requests:
        n = len(served)
        ids = np.zeros((T,), np.int32)
        seq = list(prompt) + list(served)
        ids[: len(seq)] = seq[:T]
        pad = np.zeros((T,), np.int32)
        pad[:n] = served
        gap, cgap = one(weights, jnp.asarray(ids), jnp.asarray(pad),
                        len(prompt), n)
        out.append((np.asarray(gap)[:n], np.asarray(cgap)[:n]))
    return out


# ---------------------------------------------------------------------------
# operations and bytes the algorithm needs, from shapes
# ---------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Weights a token is multiplied by: per layer q, k, v, o and the two
    MLP matrices, and the tied head. The embedding is a lookup."""
    D = cfg["n_embd"]
    return cfg["n_layer"] * (4 * D * D + 2 * D * 4 * D) + cfg["vocab_size"] * D


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """One token attending to ``context`` positions (itself included)."""
    return (2.0 * matmul_params(cfg)
            + cfg["n_layer"] * 2 * 2.0 * context * cfg["n_embd"])


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """A sample is one sequence of ``seq_len`` tokens; causal attention, so
    a token attends to (T + 1) / 2 positions on average; backward twice."""
    T = traffic["seq_len"]
    return 3.0 * T * forward_flops_per_token(cfg, (T + 1) / 2.0)


def decode_step_roofline(cfg: dict, rows: float, context: float,
                         peaks: dict) -> dict:
    """Least time of one decode step of ``rows`` rows at a mean ``context``:
    the weights read once and each row's K and V read once, against the
    step's operations."""
    item = jnp.dtype(cfg["dtype"]).itemsize
    bytes_ = item * (matmul_params(cfg)
                     + rows * context * cfg["n_layer"] * 2 * cfg["n_embd"])
    flops = rows * forward_flops_per_token(cfg, context)
    t_flops, t_bytes = flops / peaks["flops_per_s"], bytes_ / peaks["bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "bandwidth"}
