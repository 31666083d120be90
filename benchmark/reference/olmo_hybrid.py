"""Plain hybrid decoder of the Olmo-Hybrid kind for the check of outputs:
gated delta-rule linear attention (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464) in three layers of four and full softmax attention in
the fourth, in straightforward ``jax.numpy``, float32 at HIGHEST matmul
precision (the entry sets it).

Imports nothing of the program. The recurrence runs token by token, as its
equations read (no chunks, no kernel)::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

and the full attention is ``softmax(Q K^T / sqrt(d) + mask) V``. Only what
memory forces departs from the plainest form: the time loop runs in blocks
of 64 tokens under ``jax.checkpoint`` (the backward keeps one state a block,
283 MB a layer at 8,192 tokens, and not one a token, 18 GB), the attention
takes its queries 512 at a time, each block of the model is recomputed in
the backward, and ``train_steps`` keeps the start weights on the host. What
the source's ``config.json`` does not give is listed in the configuration
file under ``assumed``.

``precision`` selects what the arithmetic is done in: ``float32`` is the
reference; ``fp8`` is the control of the check, never a reference: the
operands and the result of every matrix product, what goes into and comes
out of the recurrence, and each block's output, rounded to that type
(``rounders`` below: ``lowprec.rounders``' rounding, done in float32
arithmetic), which is where the configuration keeps bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.lowprec import seed_key

TIME_BLOCK = 64         # tokens of the recurrence between two kept states
QUERY_BLOCK = 512       # queries of the full attention scored at a time
L2_EPS = 1e-6


# ---------------------------------------------------------------------------
# the control's rounding
# ---------------------------------------------------------------------------

FP8 = {"e4m3": (3, -6, 448.0), "e5m2": (2, -14, 57344.0)}


def round_fp8(a, kind: str):
    """``a`` rounded to fp8 with a scale per tensor, as ``lowprec._fp8``
    rounds it, by float32 arithmetic alone: to nearest even onto the grid of
    a format with ``FP8[kind]``'s explicit mantissa bits, least normal
    exponent and largest finite value. On the CPU the two agree bit for bit
    (``tests/benchmark/test_benchmark_olmo.py``). On the v5e XLA's own
    float32 -> float8 convert gave NaN inside the fused program of one layer
    of this model where every piece alone was finite (my chip runs, PR 28),
    so this reference does not use it."""
    bits, min_exp, top = FP8[kind]
    scale = jnp.max(jnp.abs(a)) / top + 1e-30
    x = a / scale
    _, e = jnp.frexp(x)                         # x = m 2^e, 0.5 <= |m| < 1
    n = jnp.maximum(e - 1, min_exp) - bits      # the grid's step is 2^n
    step = lax.bitcast_convert_type((n + 127) << 23, jnp.float32)
    return jnp.clip(jnp.round(x / step) * step, -top, top) * scale


def rounders(precision: str):
    """``(operand, product)`` as ``lowprec.rounders`` gives them: operands
    rounded to e4m3 with the gradient passing straight through; a result
    rounded to e4m3 and its cotangent to e5m2."""
    same = lambda a: a
    if precision == "float32":
        return same, same
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    low = lambda a: round_fp8(a, "e4m3")

    @jax.custom_vjp
    def product(a):
        return low(a)
    product.defvjp(lambda a: (low(a), None),
                   lambda _, ct: (round_fp8(ct, "e5m2"),))
    return (lambda a: a + lax.stop_gradient(low(a) - a)), product


def layer_kinds(cfg: dict) -> list:
    """The mixers of the layers that are built: the published order, cut to
    ``num_hidden_layers``."""
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def _dims(cfg: dict) -> tuple:
    H = cfg["linear_num_value_heads"]
    if H != cfg["linear_num_key_heads"]:
        raise ValueError("the layer takes one head count for keys and values")
    return (cfg["hidden_size"], H, cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"])


def param_shapes(cfg: dict) -> dict:
    """Flat ``{"<node>/<param>": shape}`` under the program's names."""
    F, H, dk, dv, K = _dims(cfg)
    M, V = cfg["intermediate_size"], cfg["vocab_size"]
    s = {"embed/W": (V, F)}
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"b{i}"
        if kind == "linear_attention":
            s.update({
                f"{b}_mix/Wq": (F, H * dk), f"{b}_mix/Wk": (F, H * dk),
                f"{b}_mix/Wv": (F, H * dv), f"{b}_mix/Wa": (F, H),
                f"{b}_mix/Wb": (F, H), f"{b}_mix/Wg": (F, H * dv),
                f"{b}_mix/conv_q": (K, H * dk), f"{b}_mix/conv_k": (K, H * dk),
                f"{b}_mix/conv_v": (K, H * dv), f"{b}_mix/A_log": (H,),
                f"{b}_mix/dt_bias": (H,), f"{b}_mix/gamma": (dv,),
                f"{b}_mix/Wo": (H * dv, F)})
        else:
            s.update({f"{b}_mix/W{n}": (F, F) for n in "qkvo"})
            s.update({f"{b}_mix/q_gamma": (F,), f"{b}_mix/k_gamma": (F,)})
        s.update({f"{b}_mix_norm/gamma": (F,), f"{b}_ffn/W_gate": (F, M),
                  f"{b}_ffn/W_up": (F, M), f"{b}_ffn/W_down": (M, F),
                  f"{b}_ffn_norm/gamma": (F,)})
    s.update({"norm_f/gamma": (F,), "head/W": (F, V)})
    return s


def make_weights(cfg: dict, seed: int) -> dict:
    """Flat ``{"<node>/<param>": array}``, float32, in one jitted call on
    the default device: matrices normal(0, ``init_std``); the convolutions'
    filters uniform within 1/sqrt(K) (one input channel a filter); a decay
    rate ``exp(A_log)`` uniform in [1, 16) and a step ``softplus(dt_bias)``
    log-uniform in [1e-3, 1e-1]; every norm's gain 1."""
    shapes = param_shapes(cfg)
    std = cfg.get("init_std", 0.02)
    K = cfg["linear_conv_kernel_dim"]

    def build(key):
        w = {}
        for name, kk in zip(shapes, jax.random.split(key, len(shapes))):
            shape, leaf = shapes[name], name.split("/")[1]
            if leaf.endswith("gamma"):
                w[name] = jnp.ones(shape, jnp.float32)
            elif leaf.startswith("conv_"):
                w[name] = jax.random.uniform(kk, shape, jnp.float32,
                                             -K ** -0.5, K ** -0.5)
            elif leaf == "A_log":
                w[name] = jnp.log(jax.random.uniform(kk, shape, jnp.float32,
                                                     1.0, 16.0))
            elif leaf == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    kk, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                w[name] = dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1
            else:
                w[name] = std * jax.random.normal(kk, shape, jnp.float32)
        return w

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def causal_conv(x, w):
    """``y_t = sum_j w[j] x_{t-(K-1)+j}``: ``x [B, T, C]``, ``w [K, C]``."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[j] for j in range(K))


def delta_rule_recurrent(q, k, v, log_alpha, beta):
    """The recurrence, one token after another from ``S_0 = 0``.
    ``q, k [B, T, H, d_k]``, ``v [B, T, H, d_v]``, ``log_alpha, beta
    [B, T, H]``; returns ``o [B, T, H, d_v]``."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def token(S, x):                    # S [B, H, d_v, d_k]
        q_t, k_t, v_t, a_t, b_t = x
        S = jnp.exp(a_t)[..., None, None] * S
        write = b_t[..., None] * (v_t - jnp.einsum("bhvk,bhk->bhv", S, k_t))
        S = S + write[..., :, None] * k_t[..., None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, q_t)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(token, S, xs)

    n = -(-T // TIME_BLOCK)

    def blocked(x):     # [B, T, ...] -> [n, TIME_BLOCK, B, ...]; the padding
        x = jnp.pad(x, ((0, 0), (0, n * TIME_BLOCK - T))    # writes nothing
                    + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((n, TIME_BLOCK) + x.shape[1:])

    S0 = jnp.zeros((B, H, dv, dk), q.dtype)
    _, o = lax.scan(block, S0, tuple(map(
        blocked, (q, k, v, log_alpha, beta))))
    return jnp.moveaxis(o.reshape((n * TIME_BLOCK,) + o.shape[2:]), 0, 1)[:, :T]


def causal_attention(q, k, v, q_, product):
    """``softmax(Q K^T / sqrt(d) + mask) V`` by head, ``[B, H, T, d]``, the
    queries ``QUERY_BLOCK`` at a time."""
    B, H, T, D = q.shape
    bq = min(QUERY_BLOCK, T)
    n = -(-T // bq)
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, n * bq - T), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(B, H, n, bq, D), 2, 0)

    @jax.checkpoint
    def rows(args):
        q_i, start = args
        s = product(jnp.einsum("bhqd,bhkd->bhqk", q_(q_i), q_(k))
                    ) / math.sqrt(D)
        seen = (start + jnp.arange(bq))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return product(jnp.einsum("bhqk,bhkd->bhqd", q_(p), q_(v)))

    out = lax.map(rows, (qb, jnp.arange(n) * bq))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, n * bq, D)[:, :, :T]


def linear_mixer(w, p, x, cfg, q_, product):
    F, H, dk, dv, _ = _dims(cfg)
    B, T, _ = x.shape
    dot = lambda name: product(jnp.dot(q_(x), q_(w[f"{p}/{name}"])))
    short = lambda W, c: jax.nn.silu(causal_conv(dot(W), w[f"{p}/{c}"]))
    l2 = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q = l2(short("Wq", "conv_q").reshape(B, T, H, dk)) / math.sqrt(dk)
    k = l2(short("Wk", "conv_k").reshape(B, T, H, dk))
    v = short("Wv", "conv_v").reshape(B, T, H, dv)
    beta = jax.nn.sigmoid(dot("Wb"))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    log_alpha = -jnp.exp(w[f"{p}/A_log"]) * jax.nn.softplus(
        dot("Wa") + w[f"{p}/dt_bias"])
    o = product(delta_rule_recurrent(q_(q), q_(k), q_(v), log_alpha, beta))
    gate = jax.nn.silu(dot("Wg").reshape(B, T, H, dv))
    o = rms_norm(o, w[f"{p}/gamma"], cfg["rms_norm_eps"]) * gate
    return product(jnp.dot(q_(o.reshape(B, T, H * dv)), q_(w[f"{p}/Wo"])))


def full_mixer(w, p, x, cfg, q_, product):
    B, T, F = x.shape
    H = cfg["num_attention_heads"]
    eps = cfg["rms_norm_eps"]
    dot = lambda a, name: product(jnp.dot(q_(a), q_(w[f"{p}/{name}"])))
    heads = lambda a: a.reshape(B, T, H, F // H).transpose(0, 2, 1, 3)
    q = heads(rms_norm(dot(x, "Wq"), w[f"{p}/q_gamma"], eps))
    k = heads(rms_norm(dot(x, "Wk"), w[f"{p}/k_gamma"], eps))
    o = causal_attention(q, k, heads(dot(x, "Wv")), q_, product)
    return dot(o.transpose(0, 2, 1, 3).reshape(B, T, F), "Wo")


def block(kind, name, w, x, cfg, precision):
    """``h = x + RMSNorm(mixer(x))``, ``out = h + RMSNorm(ffn(h))``."""
    q_, product = rounders(precision)
    eps = cfg["rms_norm_eps"]
    mixer = linear_mixer if kind == "linear_attention" else full_mixer
    h = x + rms_norm(mixer(w, f"{name}_mix", x, cfg, q_, product),
                     w[f"{name}_mix_norm/gamma"], eps)
    dot = lambda a, leaf: product(jnp.dot(q_(a), q_(w[f"{name}_ffn/{leaf}"])))
    ffn = dot(jax.nn.silu(dot(h, "W_gate")) * dot(h, "W_up"), "W_down")
    return product(h + rms_norm(ffn, w[f"{name}_ffn_norm/gamma"], eps))


def loss_fn(w: dict, ids, targets, cfg: dict, precision: str = "float32"):
    """Mean over the sequences of the sum over time of the cross entropy of
    ``targets [B, T]`` (ids) under the logits of ``ids [B, T]``."""
    q_, product = rounders(precision)
    h = w["embed/W"][ids]
    frozen = FrozenCfg(cfg)
    for i, kind in enumerate(layer_kinds(cfg)):
        # one block's activations live at a time (recomputed backward)
        h = jax.checkpoint(block, static_argnums=(0, 1, 4, 5))(
            kind, f"b{i}", w, h, frozen, precision)
    h = rms_norm(h, w["norm_f/gamma"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(product(jnp.dot(q_(h), q_(w["head/W"]))))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(jnp.sum(picked, axis=1))


class FrozenCfg(dict):
    """A configuration that may be a static argument of ``jax.checkpoint``:
    hashed by identity, it is built once a trace."""
    __hash__ = object.__hash__
    __eq__ = object.__eq__


def train_steps(cfg: dict, weights: dict, batches, precision="float32",
                fault=None) -> dict:
    """Follow the first ``len(batches)`` steps of training from ``weights``
    (Nesterov momentum as the configuration states) over ``(ids, targets)``
    pairs. Returns each step's loss, every leaf's gradient norm at step 1
    and every leaf's norm of change after the last step.
    ``fault="half_batch"`` plants the fault the check must catch: at a
    batch of one sequence, the second half of the sequence left out.

    ``weights`` is consumed: its buffers are given to the first step, and a
    copy on the host stands for the start in the parameters' change (929 M
    float32 parameters, their momentum and their gradient are 11 GB of the
    chip's 16 before any activation)."""
    lr, mu = cfg["learning_rate"], cfg["momentum"]
    frozen = FrozenCfg(cfg)

    def step(w, trace, ids, targets):
        loss, g = jax.value_and_grad(loss_fn)(w, ids, targets, frozen,
                                              precision)
        trace = {k: g[k] + mu * trace[k] for k in w}
        new = {k: w[k] - lr * (g[k] + mu * trace[k]) for k in w}
        return new, trace, loss, {k: jnp.sqrt(jnp.sum(g[k] ** 2)) for k in w}

    step = jax.jit(step, donate_argnums=(0, 1))
    start = {k: np.asarray(v) for k, v in weights.items()}
    w = weights
    trace = jax.jit(lambda t: {k: jnp.zeros_like(v) for k, v in t.items()})(w)
    losses, grad_norm = [], None
    for ids, targets in batches:
        if fault == "half_batch":
            half = ids.shape[1] // 2
            ids, targets = ids[:, :half], targets[:, :half]
        w, trace, loss, gn = step(w, trace, jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(targets, jnp.int32))
        losses.append(float(loss))
        if grad_norm is None:
            grad_norm = {k: float(v) for k, v in gn.items()}
    del trace
    change = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)))
    delta = {k: float(change(w[k], start[k])) for k in w}
    return {"losses": losses, "grad_norm": grad_norm, "delta_norm": delta}


# ---------------------------------------------------------------------------
# operations and bytes the algorithm needs, from shapes
# ---------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Weights that meet every token in a matrix product: the projections,
    the feed-forwards and the head; not the embedding, which is gathered."""
    F, H, dk, dv, _ = _dims(cfg)
    linear = F * (2 * H * dk + 2 * H * dv + 2 * H) + H * dv * F
    per = {"linear_attention": linear, "full_attention": 4 * F * F}
    ffn = 3 * F * cfg["intermediate_size"]
    return (sum(per[kind] + ffn for kind in layer_kinds(cfg))
            + F * cfg["vocab_size"])


def attention_forward_flops(cfg: dict, seq_len: int) -> float:
    """One full-attention layer, one sequence: ``T (T + 1) / 2`` scores a
    head, each a product over ``d`` and a weight on ``d`` values."""
    F = cfg["hidden_size"]
    return 4.0 * F * seq_len * (seq_len + 1) / 2


def recurrence_forward_flops(cfg: dict, seq_len: int) -> float:
    """One linear-attention layer, one sequence, token by token: ``S k``,
    the rank-one write and ``S q``, ``d_v d_k`` multiply-adds each a head."""
    _, H, dk, dv, _ = _dims(cfg)
    return 6.0 * dk * dv * H * seq_len


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward once and backward twice, a sequence: the matrix products of
    every token, the full layers' causal scores and the recurrences.
    Recomputation (the program's ``remat``, a kernel's) is not counted."""
    T = traffic["seq_len"]
    kinds = layer_kinds(cfg)
    forward = (2.0 * matmul_params(cfg) * T
               + kinds.count("full_attention") * attention_forward_flops(cfg, T)
               + kinds.count("linear_attention")
               * recurrence_forward_flops(cfg, T))
    return 3.0 * forward


def flash_attention_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """What the causal attention of the full layers needs a train step,
    forward and backward (two products forward, four backward; the scores a
    kernel rebuilds in its backward are not counted): ``flops``, and the
    ``bytes`` of reading q, k, v and writing o forward, reading those four
    and the output's cotangent and writing three gradients backward."""
    T, B = traffic["seq_len"], traffic["batch"]
    layers = layer_kinds(cfg).count("full_attention")
    return {"flops": 3.0 * B * layers * attention_forward_flops(cfg, T),
            "bytes": 12.0 * B * layers * T * cfg["hidden_size"] * itemsize}
