"""Plain decoder-hybrid-decoder of the SambaY kind (Ren et al.,
arXiv:2507.06607) as Phi-4-mini-flash-reasoning is built, for the check of
outputs: Mamba-1 selective scans (Gu and Dao, arXiv:2312.00752),
differential attention (Ye et al., arXiv:2410.05258) with a window or
without, one scan's output read as memory by gated memory units and one
layer's keys and values read by cross attention, in straightforward
``jax.numpy``, float32 at HIGHEST matmul precision (the entry sets it).

Imports nothing of the program. The recurrence runs token by token, as its
equations read (no chunks, no associative scan)::

    h_t = exp(Delta_t (.) A) (.) h_{t-1} + (Delta_t (.) x_t) B_t^T
    s_t = h_t C_t + D (.) x_t

and the attention is two dense softmaxes a pair, ``(A^1 - lambda A^2) V``,
with the mask written out as two inequalities on the positions. Only what
memory forces departs from the plainest form: the time loop runs in blocks
of 64 tokens under ``jax.checkpoint`` (the backward keeps one state a
block, 42 MB a layer at 8,192 tokens, and not one a token, 2.7 GB), the
attention takes its queries 512 at a time, each block of the model is
recomputed in the backward, and ``train_steps`` keeps the start weights on
the host. What the source's ``config.json`` does not give is listed in the
configuration file under ``assumed``.

Weights go by the program's names, ``"<node>/<param>"`` with the node
named by the layer's published index, and one leaf, ``embed/W``, is both
the embedding and the head. ``precision`` is ``reference/olmo_hybrid``'s:
``float32`` is the reference; ``fp8`` the control of the check, with that
module's own rounding of the operands and the result of every matrix
product, of what goes into and comes out of a scan, and of each block's
output.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.lowprec import seed_key
from benchmark.reference.olmo_hybrid import FrozenCfg, causal_conv, rounders

TIME_BLOCK = 64         # tokens of the recurrence between two kept states
QUERY_BLOCK = 512       # queries of an attention layer scored at a time
ATTENTION = ("window", "full", "cross")


def layer_kinds(cfg: dict) -> list:
    """The kinds of the layers that are built, in order: ``layer_types`` is
    the whole model's, ``layers`` the published indices kept."""
    return [cfg["layer_types"][i] for i in cfg["layers"]]


def _dims(cfg: dict) -> tuple:
    F, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (F, H, cfg["num_key_value_heads"], F // H, cfg["mamba_d_inner"],
            cfg["mamba_d_state"], cfg["mamba_dt_rank"])


def param_shapes(cfg: dict) -> dict:
    """Flat ``{"<node>/<param>": shape}`` under the program's names."""
    F, H, G, d, Di, N, R = _dims(cfg)
    M, K = cfg["intermediate_size"], cfg["mamba_d_conv"]
    norm = lambda n: {f"{n}/gamma": (F,), f"{n}/beta": (F,)}
    s = {"embed/W": (cfg["vocab_size"], F)}
    for i, kind in zip(cfg["layers"], layer_kinds(cfg)):
        b = f"b{i}"
        s.update(norm(f"{b}_norm1"))
        if kind == "mamba":
            s.update({
                f"{b}_ssm/W_in": (F, Di), f"{b}_ssm/conv_w": (K, Di),
                f"{b}_ssm/conv_b": (Di,), f"{b}_ssm/W_x": (Di, R + 2 * N),
                f"{b}_ssm/W_dt": (R, Di), f"{b}_ssm/b_dt": (Di,),
                f"{b}_ssm/A_log": (N, Di), f"{b}_ssm/D": (Di,)})
        if kind in ("mamba", "gmu"):
            s.update({f"{b}_mix/W_in": (F, Di), f"{b}_mix/W_out": (Di, F)})
        if kind in ("window", "full"):
            s.update({f"{b}_kv/W": (F, 2 * G * d), f"{b}_kv/b": (2 * G * d,)})
        if kind in ATTENTION:
            s.update({f"{b}_mix/Wq": (F, H * d), f"{b}_mix/bq": (H * d,),
                      f"{b}_mix/gamma": (2 * d,),
                      f"{b}_mix/Wo": (H * d, F), f"{b}_mix/bo": (F,)})
            s.update({f"{b}_mix/{n}": (d,)
                      for n in ("lq1", "lk1", "lq2", "lk2")})
        s.update(norm(f"{b}_norm2"))
        s.update({f"{b}_ffn/W_gate": (F, M), f"{b}_ffn/W_up": (F, M),
                  f"{b}_ffn/W_down": (M, F)})
    s.update(norm("norm_f"))
    return s


def make_weights(cfg: dict, seed: int) -> dict:
    """Flat ``{"<node>/<param>": array}``, float32, in one jitted call on
    the default device: matrices and biases normal(0, ``init_std``); the
    convolutions' filters uniform within 1/sqrt(K) (one input channel a
    filter); state ``n`` of every channel decays at rate ``n + 1``
    (``A_log = log(1..N)``), ``D = 1`` and a step ``softplus(b_dt)``
    log-uniform in [1e-3, 1e-1] (Mamba's defaults); the four ``l_*`` of a
    differential layer normal(0, 0.1); every norm's gain 1 and shift 0."""
    shapes = param_shapes(cfg)
    std = cfg.get("init_std", 0.02)
    K = cfg["mamba_d_conv"]

    def build(key):
        w = {}
        for name, kk in zip(shapes, jax.random.split(key, len(shapes))):
            shape, leaf = shapes[name], name.split("/")[1]
            if leaf == "gamma" or leaf == "D":
                w[name] = jnp.ones(shape, jnp.float32)
            elif leaf == "beta":
                w[name] = jnp.zeros(shape, jnp.float32)
            elif leaf == "conv_w":
                w[name] = jax.random.uniform(kk, shape, jnp.float32,
                                             -K ** -0.5, K ** -0.5)
            elif leaf == "A_log":
                rates = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
                w[name] = jnp.broadcast_to(jnp.log(rates)[:, None], shape)
            elif leaf == "b_dt":
                dt = jnp.exp(jax.random.uniform(
                    kk, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                w[name] = dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1
            elif leaf in ("lq1", "lk1", "lq2", "lk2"):
                w[name] = 0.1 * jax.random.normal(kk, shape, jnp.float32)
            else:
                w[name] = std * jax.random.normal(kk, shape, jnp.float32)
        return w

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def layer_norm(x, w, name, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps) * w[f"{name}/gamma"]
            + w[f"{name}/beta"])


def selective_scan_recurrent(x, delta, a, b, c):
    """The recurrence, one token after another from ``h_0 = 0``. ``x, delta
    [B, T, d_in]``, ``a [N, d_in]``, ``b, c [B, T, N]``; returns ``sum_n
    h_t[n] c_t[n]``, ``[B, T, d_in]``."""
    B, T, Di = x.shape

    def token(h, xs):                   # h [B, N, d_in]
        x_t, delta_t, b_t, c_t = xs
        h = (jnp.exp(delta_t[:, None, :] * a) * h
             + (delta_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    @jax.checkpoint
    def block(h, xs):
        return lax.scan(token, h, xs)

    n = -(-T // TIME_BLOCK)

    def blocked(z):     # [B, T, F] -> [n, TIME_BLOCK, B, F]; the padding has
        z = jnp.pad(z, ((0, 0), (0, n * TIME_BLOCK - T), (0, 0)))  # delta 0
        z = jnp.moveaxis(z, 1, 0)
        return z.reshape((n, TIME_BLOCK) + z.shape[1:])

    h0 = jnp.zeros((B, a.shape[0], Di), x.dtype)
    _, y = lax.scan(block, h0, tuple(map(blocked, (x, delta, b, c))))
    y = y.reshape((n * TIME_BLOCK,) + y.shape[2:])
    return jnp.moveaxis(y, 0, 1)[:, :T]


def scan_output(w, p, u, cfg, q_, product):
    """A Mamba mixer up to its scan: ``s [B, T, d_in]``."""
    _, _, _, _, _, N, R = _dims(cfg)
    dot = lambda a, name: product(jnp.dot(q_(a), q_(w[f"{p}/{name}"])))
    x = jax.nn.silu(causal_conv(dot(u, "W_in"), w[f"{p}/conv_w"])
                    + w[f"{p}/conv_b"])
    proj = dot(x, "W_x")
    delta = jax.nn.softplus(dot(proj[..., :R], "W_dt") + w[f"{p}/b_dt"])
    y = selective_scan_recurrent(
        q_(x), delta, -jnp.exp(w[f"{p}/A_log"]), q_(proj[..., R:R + N]),
        q_(proj[..., R + N:]))
    return product(y + w[f"{p}/D"] * x)


def gated_memory(w, p, u, memory, q_, product):
    """``W_out (m * SiLU(W_in u))``: a Mamba mixer's own gate and output
    projection, and the unit that reads another layer's scan."""
    dot = lambda a, name: product(jnp.dot(q_(a), q_(w[f"{p}/{name}"])))
    return dot(memory * jax.nn.silu(dot(u, "W_in")), "W_out")


def keys_values(w, p, u, q_, product):
    return product(jnp.dot(q_(u), q_(w[f"{p}/W"]))) + w[f"{p}/b"]


def differential_attention(w, p, u, kv, cfg, index, window, q_, product):
    """``W_o concat_p (1 - lambda_init) gamma RMSNorm((A^1 - lambda A^2)
    V) + b_o``; query pair ``p`` = heads ``(2p, 2p+1)``, key/value pair
    ``p // (H / G)`` = key heads ``(2r, 2r+1)`` and the value ``[v_2r ;
    v_2r+1]``. ``window`` None: causal alone."""
    F, H, G, d, _, _, _ = _dims(cfg)
    B, T, _ = u.shape
    pairs, group = H // 2, H // G
    q = (product(jnp.dot(q_(u), q_(w[f"{p}/Wq"]))) + w[f"{p}/bq"]
         ).reshape(B, T, pairs, 2, d)
    k = kv[..., :G * d].reshape(B, T, G // 2, 2, d)
    v = kv[..., G * d:].reshape(B, T, G // 2, 2 * d)
    k = jnp.repeat(k, group, axis=2)        # pair p reads kv pair p // group
    v = jnp.repeat(v, group, axis=2)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(w[f"{p}/lq1"] * w[f"{p}/lk1"]))
           - jnp.exp(jnp.sum(w[f"{p}/lq2"] * w[f"{p}/lk2"])) + lam_init)
    bq = min(QUERY_BLOCK, T)
    n = -(-T // bq)
    qb = jnp.pad(q, ((0, 0), (0, n * bq - T)) + ((0, 0),) * 3)
    qb = jnp.moveaxis(qb.reshape(B, n, bq, pairs, 2, d), 1, 0)

    @jax.checkpoint
    def rows(args):
        q_i, first = args
        t = (first + jnp.arange(bq))[:, None]
        s = jnp.arange(T)[None, :]
        seen = s <= t
        if window is not None:
            seen = seen & (t - s < window)
        scores = product(jnp.einsum("bqpjd,bkpjd->bpjqk", q_(q_i), q_(k))
                         ) / math.sqrt(d)
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out = product(jnp.einsum("bpjqk,bkpe->bqpje", q_(maps), q_(v)))
        return out[..., 0, :] - lam * out[..., 1, :]    # [B, bq, pairs, 2d]

    o = lax.map(rows, (qb, jnp.arange(n) * bq))
    o = jnp.moveaxis(o, 0, 1).reshape(B, n * bq, pairs, 2 * d)[:, :T]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg["layer_norm_eps"])
    o = (1.0 - lam_init) * w[f"{p}/gamma"] * o
    return (product(jnp.dot(q_(o.reshape(B, T, H * d)), q_(w[f"{p}/Wo"])))
            + w[f"{p}/bo"])


def block(kind, index, w, x, shared, cfg, precision):
    """``h = x + mixer(LN(x))``, ``out = h + ffn(LN(h))``. ``shared`` is
    ``(memory, keys and values)`` as the layers below left them; returns the
    output and what this layer leaves."""
    q_, product = rounders(precision)
    memory, kv = shared
    name, eps = f"b{index}", cfg["layer_norm_eps"]
    u = layer_norm(x, w, f"{name}_norm1", eps)
    if kind == "mamba":
        memory = scan_output(w, f"{name}_ssm", u, cfg, q_, product)
    if kind in ("window", "full"):
        own = keys_values(w, f"{name}_kv", u, q_, product)
        if kind == "full":
            kv = own
    if kind in ("mamba", "gmu"):
        mixed = gated_memory(w, f"{name}_mix", u, memory, q_, product)
    else:
        mixed = differential_attention(
            w, f"{name}_mix", u, kv if kind == "cross" else own, cfg, index,
            cfg["sliding_window"] if kind == "window" else None, q_, product)
    h = x + mixed
    dot = lambda a, leaf: product(jnp.dot(q_(a), q_(w[f"{name}_ffn/{leaf}"])))
    g = layer_norm(h, w, f"{name}_norm2", eps)
    ffn = dot(jax.nn.silu(dot(g, "W_gate")) * dot(g, "W_up"), "W_down")
    return product(h + ffn), (memory, kv)


def loss_fn(w: dict, ids, targets, cfg: dict, precision: str = "float32",
            head=None):
    """Mean over the sequences of the sum over time of the cross entropy of
    ``targets [B, T]`` (ids) under the logits of ``ids [B, T]``, the head
    the embedding transposed (``head [V, F]`` in its place lets a test take
    the tied leaf's gradient apart into its two parts)."""
    q_, product = rounders(precision)
    h = w["embed/W"][ids]
    frozen = FrozenCfg(cfg)
    B, T = ids.shape
    _, _, G, d, Di, _, _ = _dims(cfg)
    # nothing is read before a layer has left it
    shared = (jnp.zeros((B, T, Di)), jnp.zeros((B, T, 2 * G * d)))
    for i, kind in zip(cfg["layers"], layer_kinds(cfg)):
        # one block's activations live at a time (recomputed backward)
        h, shared = jax.checkpoint(block, static_argnums=(0, 1, 5, 6))(
            kind, i, w, h, shared, frozen, precision)
    h = layer_norm(h, w, "norm_f", cfg["layer_norm_eps"])
    head = w["embed/W"] if head is None else head
    logp = jax.nn.log_softmax(product(jnp.dot(q_(h), q_(head).T)))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(jnp.sum(picked, axis=1))


def train_steps(cfg: dict, weights: dict, batches, precision="float32",
                fault=None) -> dict:
    """Follow the first ``len(batches)`` steps of training from ``weights``
    (Nesterov momentum as the configuration states) over ``(ids, targets)``
    pairs. Returns each step's loss, every leaf's gradient norm at step 1
    and every leaf's norm of change after the last step.
    ``fault="half_batch"`` plants the fault the check must catch: at a
    batch of one sequence, the second half of the sequence left out.

    ``weights`` is consumed: its buffers are given to the first step, and a
    copy on the host stands for the start in the parameters' change."""
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
    the feed-forwards and the head (the embedding's matrix once more, as a
    product; its gather is none)."""
    F, H, G, d, Di, N, R = _dims(cfg)
    gate = 2 * F * Di
    queries = 2 * F * H * d
    per = {"mamba": F * Di + Di * (R + 2 * N) + R * Di + gate, "gmu": gate,
           "window": queries + F * 2 * G * d, "cross": queries}
    per["full"] = per["window"]
    ffn = 3 * F * cfg["intermediate_size"]
    return (sum(per[kind] + ffn for kind in layer_kinds(cfg))
            + F * cfg["vocab_size"])


def scores_seen(seq_len: int, window=None) -> float:
    """Pairs ``(t, s)`` of one causal score map: ``T (T + 1) / 2``, and
    inside a window ``min(t + 1, window)`` a query."""
    T, W = seq_len, seq_len if window is None else min(window, seq_len)
    return W * (W + 1) / 2 + (T - W) * W


def attention_forward_flops(cfg: dict, seq_len: int, kind: str) -> float:
    """One attention layer, one sequence: two maps a query pair, each score
    a product over ``d`` and a weight on ``2 d`` values."""
    _, H, _, d, _, _, _ = _dims(cfg)
    window = cfg["sliding_window"] if kind == "window" else None
    return H * scores_seen(seq_len, window) * 2.0 * (d + 2 * d)


def scan_forward_flops(cfg: dict, seq_len: int) -> float:
    """One state-space layer, one sequence, token by token: the update and
    the read-out, a multiply-add each a state."""
    _, _, _, _, Di, N, _ = _dims(cfg)
    return 4.0 * seq_len * Di * N


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward once and backward twice, a sequence: the matrix products of
    every token, the attention layers' scores and the scans. Recomputation
    (the program's ``remat``, a kernel's) is not counted."""
    T = traffic["seq_len"]
    kinds = layer_kinds(cfg)
    forward = (2.0 * matmul_params(cfg) * T
               + sum(attention_forward_flops(cfg, T, kind)
                     for kind in kinds if kind in ATTENTION)
               + kinds.count("mamba") * scan_forward_flops(cfg, T))
    return 3.0 * forward


def flash_attention_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """What the attention of the window, full and cross layers needs a train
    step, forward and backward, whatever a kernel pads, skips or rebuilds:
    ``flops`` of the scores inside the mask, and the ``bytes`` of reading q,
    k, v and writing the pairs' outputs forward (``H d``, ``G d``, ``G d``
    and ``H d`` a token), reading those four and the output's cotangent and
    writing three gradients backward."""
    T, B = traffic["seq_len"], traffic["batch"]
    _, H, G, d, _, _, _ = _dims(cfg)
    kinds = [kind for kind in layer_kinds(cfg) if kind in ATTENTION]
    token = (H + 2 * G + H) * d
    return {"flops": 3.0 * B * sum(attention_forward_flops(cfg, T, kind)
                                   for kind in kinds),
            "bytes": float(B * len(kinds) * T * itemsize
                           * (token + (token + H * d) + (H + 2 * G) * d))}


def selective_scan_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """What ONE state-space layer's scan needs a train step, whatever
    implements it: ``flops`` forward and twice that backward; the ``bytes``
    of reading ``x`` (``itemsize``), ``Delta`` (float32), ``B`` and ``C``
    (float32, ``N`` a token) and writing ``s`` (``itemsize``) forward, and
    backward those once more, the output's cotangent in and the four
    inputs' cotangents out (``A``, ``D`` and their gradients are a few
    hundred KB). No state is counted: it need not leave the chip."""
    T, B = traffic["seq_len"], traffic["batch"]
    _, _, _, _, Di, N, _ = _dims(cfg)
    forward = Di * (itemsize + 4 + itemsize) + 2 * N * 4
    return {"flops": 3.0 * B * scan_forward_flops(cfg, T),
            "bytes": float(B * T * (forward + forward + Di * (
                2 * itemsize + 4) + 2 * N * 4))}
