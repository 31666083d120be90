"""Plain sparse-attention mixture-of-experts decoder as the language model
of Keye-VL-2.0-30B-A3B is built, for the check of outputs: grouped-query
attention with a norm of q and k by head and rotary positions, over the
keys a learned indexer selects (DeepSeek-V3.2-Exp's), and top-k routed
experts of which a range is held, in straightforward ``jax.numpy``, float32
at HIGHEST matmul precision (the entry sets it).

Imports nothing of the program. The selection is a mask written out over
``[T, T]``: every query's index scores against every key, ranked by a
stable sort (of equal scores the lower key first), the ``topk`` first of
the keys ``s <= t`` kept; the attention is one dense softmax over the kept
keys. The experts are a loop over the held ones (a ``lax.scan``), each run
on every token and weighted token by token (zero where the token did not
choose it); what the absent experts would add is left out, as the
configuration's deployment says. Only what the chip's memory and its
compiler's time force departs from the plainest form: the queries go 512
at a time, ``loss_fn`` recomputes each block in the backward, and
``train_steps`` takes that gradient block by block, with one block's two
programs compiled for all the layers, and keeps the start weights on the
host. What the source's ``config.json`` does not give is listed in the
configuration file under ``assumed``.

Weights go by the program's names, ``"<node>/<param>"``. The indexer's
leaves (``b<i>_index/*``) are frozen: the language-model loss sends them no
gradient (the selection is discrete), ``train_steps`` does not move them
and hands back no number for them. ``precision`` is
``reference/olmo_hybrid``'s: ``float32`` is the reference; ``fp8`` the
control of the check, with that module's own rounding of the operands and
the result of every matrix product and of each block's output.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.lowprec import seed_key
from benchmark.reference.olmo_hybrid import FrozenCfg, rms_norm, rounders

QUERY_BLOCK = 512       # queries scored at a time


def held_experts(cfg: dict) -> tuple:
    """``(first, count)``: the range of experts whose weights are here."""
    return cfg.get("first_expert", 0), cfg["num_experts"]


def _dims(cfg: dict) -> tuple:
    sa = cfg["sa_config"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            sa["indexer_num_heads"], sa["indexer_head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_routed"])


def trained(name: str) -> bool:
    return "_index/" not in name


def param_shapes(cfg: dict) -> dict:
    """Flat ``{"<node>/<param>": shape}`` under the program's names."""
    F, H, G, d, Hi, di, M, E = _dims(cfg)
    _, count = held_experts(cfg)
    s = {"embed/W": (cfg["vocab_size"], F)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"b{i}"
        s.update({
            f"{b}_norm1/gamma": (F,),
            f"{b}_index/Wq": (F, Hi * di), f"{b}_index/Wk": (F, di),
            f"{b}_index/k_gamma": (di,), f"{b}_index/k_beta": (di,),
            f"{b}_index/Ww": (F, Hi),
            f"{b}_mix/Wq": (F, H * d), f"{b}_mix/Wk": (F, G * d),
            f"{b}_mix/Wv": (F, G * d), f"{b}_mix/q_gamma": (d,),
            f"{b}_mix/k_gamma": (d,), f"{b}_mix/Wo": (H * d, F),
            f"{b}_norm2/gamma": (F,),
            f"{b}_moe/W_r": (F, E), f"{b}_moe/W_gate": (count, F, M),
            f"{b}_moe/W_up": (count, F, M), f"{b}_moe/W_down": (count, M, F)})
    s.update({"norm_f/gamma": (F,), "head/W": (F, cfg["vocab_size"])})
    return s


def make_weights(cfg: dict, seed: int) -> dict:
    """Flat ``{"<node>/<param>": array}``, float32, in one jitted call on
    the default device: matrices normal(0, ``init_std``), the embedding's
    rows normal(0, ``embedding_std``), every norm's gain 1 and the
    indexer's key shift 0. The embedding is the larger so that a token's
    own row stays most of what its layers read: an attention layer of
    random weights averages thousands of values into nearly the same vector
    for every query, and at one size with it the router of every layer
    reads that vector and sends all the tokens to the same experts."""
    shapes = param_shapes(cfg)
    std = cfg.get("init_std", 0.02)
    scale = {"embed/W": cfg.get("embedding_std", std)}

    def build(key):
        w = {}
        for name, kk in zip(shapes, jax.random.split(key, len(shapes))):
            shape, leaf = shapes[name], name.split("/")[1]
            if leaf.endswith("gamma"):
                w[name] = jnp.ones(shape, jnp.float32)
            elif leaf.endswith("beta"):
                w[name] = jnp.zeros(shape, jnp.float32)
            else:
                w[name] = scale.get(name, std) * jax.random.normal(
                    kk, shape, jnp.float32)
        return w

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def rotate(x, theta: float):
    """``x [B, T, ..., D]`` turned by its position ``t`` (axis 1): entry
    ``i < D / 2`` and entry ``i + D / 2`` are one pair, turned by ``t
    theta^(-2 i / D)``."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    angle = (jnp.arange(T, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    angle = angle.reshape((1, T) + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def selection_rows(index, first, topk: int):
    """The mask of one block of queries, written out: ``index [B, Q, T]``
    the index scores of queries ``first .. first + Q`` against every key;
    ``True`` at ``(t, s)`` when ``s <= t`` and fewer than ``topk`` keys
    ``s' <= t`` come before ``s`` in the order of falling score (of equal
    scores the lower key first)."""
    B, Q, T = index.shape
    t = (first + jnp.arange(Q))[:, None]
    seen = jnp.arange(T)[None, :] <= t
    order = jnp.argsort(jnp.where(seen, -index, jnp.inf), axis=-1,
                        stable=True)                    # best key first
    place = jnp.zeros((B, Q, T), jnp.int32).at[
        jnp.arange(B)[:, None, None], jnp.arange(Q)[None, :, None],
        order].set(jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                    (B, Q, T)))
    return seen & (place < topk)


def sparse_attention(w, name, u, cfg, q_, product, fault=None):
    """``W_o concat_h sum_{s in S_t} softmax(q_th . k_s / sqrt(d)) v_s``
    with ``S_t`` the indexer's selection. ``fault="dense"`` plants the
    selection left out (every key ``s <= t``)."""
    F, H, G, d, Hi, di, _, _ = _dims(cfg)
    B, T, _ = u.shape
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    topk = cfg["sa_config"]["topk"]
    dot = lambda a, leaf: product(jnp.dot(q_(a), q_(w[f"{name}/{leaf}"])))
    p, ix = f"{name}_mix", f"{name}_index"
    mix = lambda leaf: product(jnp.dot(q_(u), q_(w[f"{p}/{leaf}"])))
    q = rotate(rms_norm(mix("Wq").reshape(B, T, H, d),
                        w[f"{p}/q_gamma"], eps), theta)
    k = rotate(rms_norm(mix("Wk").reshape(B, T, G, d),
                        w[f"{p}/k_gamma"], eps), theta)
    v = mix("Wv").reshape(B, T, G, d)
    k = jnp.repeat(k, H // G, axis=2)       # head h reads kv head h // (H/G)
    v = jnp.repeat(v, H // G, axis=2)
    # the indexer: nothing of it is differentiated
    ui = lax.stop_gradient(u)
    idx = lambda leaf: product(jnp.dot(q_(ui), q_(w[f"{ix}/{leaf}"])))
    qi = rotate(idx("Wq").reshape(B, T, Hi, di), theta)
    ki = idx("Wk")
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean((ki - mean) ** 2, axis=-1, keepdims=True)
    ki = rotate((ki - mean) * lax.rsqrt(var + cfg["indexer_norm_eps"])
                * w[f"{ix}/k_gamma"] + w[f"{ix}/k_beta"], theta)
    wi = idx("Ww")                                          # [B, T, Hi]
    qi, ki, wi = map(lax.stop_gradient, (qi, ki, wi))

    bq = min(QUERY_BLOCK, T)
    n = -(-T // bq)
    blocks = lambda a: jnp.moveaxis(jnp.pad(
        a, ((0, 0), (0, n * bq - T)) + ((0, 0),) * (a.ndim - 2)
    ).reshape((B, n, bq) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def rows(args):
        q_i, qi_i, wi_i, first = args
        if fault == "dense":
            t = (first + jnp.arange(bq))[:, None]
            kept = jnp.broadcast_to(jnp.arange(T)[None, :] <= t, (B, bq, T))
        else:
            hits = jax.nn.relu(product(jnp.einsum(
                "bqjd,bkd->bjqk", q_(qi_i), q_(ki))))
            index = jnp.einsum("bjqk,bqj->bqk", hits, wi_i)
            kept = selection_rows(index, first, topk)
        scores = product(jnp.einsum("bqhd,bkhd->bhqk", q_(q_i), q_(k))
                         ) / math.sqrt(d)
        maps = jax.nn.softmax(jnp.where(kept[:, None], scores, -jnp.inf),
                              axis=-1)
        return product(jnp.einsum("bhqk,bkhd->bqhd", q_(maps), q_(v)))

    o = lax.map(rows, (blocks(q), blocks(qi), blocks(wi),
                       jnp.arange(n) * bq))
    o = jnp.moveaxis(o, 0, 1).reshape(B, n * bq, H * d)[:, :T]
    return product(jnp.dot(q_(o), q_(w[f"{p}/Wo"])))


def routing(w, name, u, cfg):
    """``[B, T, E]`` float32: a token's weight on each of ALL the routed
    experts, nought on those it did not choose; the chosen are the
    ``num_experts_per_tok`` of largest ``softmax(W_r u)`` (of equal ones the
    lower expert), and with ``norm_topk_prob`` their weights are divided by
    their sum, over all the chosen wherever they live. ``fault=`` handled
    by the caller."""
    p = jax.nn.softmax(jnp.dot(u, w[f"{name}/W_r"]), axis=-1)
    order = jnp.argsort(-p, axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1)        # an expert's rank, by token
    chosen = place < cfg["num_experts_per_tok"]
    return jnp.where(chosen, p, 0.0)


def routed_experts(w, name, u, cfg, q_, product, fault=None):
    """``sum_{e chosen and held} g_e W_down,e (SiLU(W_gate,e u) * W_up,e
    u)``, one held expert after another over every token.
    ``fault="raw_weights"`` plants the chosen weights not renormalised."""
    first, count = held_experts(cfg)
    g = routing(w, name, u, cfg)
    if cfg["norm_topk_prob"] and fault != "raw_weights":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    dot = lambda a, m: product(jnp.dot(q_(a), q_(m)))

    def share(gate, up, down, weight):
        out = dot(jax.nn.silu(dot(u, gate)) * dot(u, up), down)
        return weight[..., None] * out

    return lax.scan(lambda y, held: (y + share(*held), None),
                    jnp.zeros_like(u), (
        w[f"{name}/W_gate"], w[f"{name}/W_up"], w[f"{name}/W_down"],
        jnp.moveaxis(g[..., first:first + count], -1, 0)))[0]


def block(name, w, x, cfg, precision, fault):
    """``h = x + Attn(RMSNorm(x))``, ``out = h + Experts(RMSNorm(h))``."""
    q_, product = rounders(precision)
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, w[f"{name}_norm1/gamma"], eps)
    h = x + sparse_attention(w, name, u, cfg, q_, product, fault)
    g = rms_norm(h, w[f"{name}_norm2/gamma"], eps)
    return product(h + routed_experts(w, f"{name}_moe", g, cfg, q_, product,
                                      fault))


def sequence_loss(w: dict, h, targets, cfg: dict, precision: str):
    """The final norm, the logits and the loss of ``h [B, T, F]``: the mean
    over the sequences of the sum over time of the cross entropy of
    ``targets [B, T]``."""
    q_, product = rounders(precision)
    h = rms_norm(h, w["norm_f/gamma"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(product(jnp.dot(q_(h), q_(w["head/W"]))))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(jnp.sum(picked, axis=1))


def loss_fn(w: dict, ids, targets, cfg: dict, precision: str = "float32",
            fault=None):
    """The model whole: ``sequence_loss`` of ``targets [B, T]`` (ids) after
    every block over the rows of ``ids [B, T]``."""
    h = w["embed/W"][ids]
    frozen = FrozenCfg(cfg)
    for i in range(cfg["num_hidden_layers"]):
        # one block's activations live at a time (recomputed backward)
        h = jax.checkpoint(block, static_argnums=(0, 3, 4, 5))(
            f"b{i}", w, h, frozen, precision, fault)
    return sequence_loss(w, h, targets, frozen, precision)


@functools.lru_cache(maxsize=1)
def _step_parts(cfg_json: str, precision: str, planted):
    """``loss_fn``'s gradient and the update, jitted a part at a time: the
    embedding's rows, ONE block forward, ONE block's transpose with its
    leaves' update, the loss's end. Every layer runs the same two programs,
    so a block is compiled once and not once a layer (the step as one
    program took the chip's compiler 3 minutes in float32 and 9 in fp8, and
    left 0.1 GB of the chip free). The parts last asked for are kept, so
    that a further seed in the same process finds them compiled."""
    cfg = FrozenCfg(json.loads(cfg_json))
    lr, mu = cfg["learning_rate"], cfg["momentum"]

    def updated(w, g, trace):
        trace = {k: g[k] + mu * trace[k] for k in g}
        return ({k: w[k] - lr * (g[k] + mu * trace[k]) for k in g}, trace,
                {k: jnp.sqrt(jnp.sum(g[k] ** 2)) for k in g})

    def one_block(w, still, x):
        return block("", {**w, **still}, x, cfg, precision, planted)

    def block_back(w, still, trace, x, ct):
        g, ct = jax.vjp(lambda w, x: one_block(w, still, x), w, x)[1](ct)
        return updated(w, g, trace) + (ct,)

    def end_back(w, trace, h, targets):
        loss, (g, ct) = jax.value_and_grad(sequence_loss, argnums=(0, 1))(
            w, h, targets, cfg, precision)
        return updated(w, g, trace) + (ct, loss)

    def rows_back(w, trace, ids, ct):
        g = jax.vjp(lambda w: w["embed/W"][ids], w)[1](ct)[0]
        return updated(w, g, trace)

    return (jax.jit(lambda w, ids: w["embed/W"][ids]), jax.jit(one_block),
            jax.jit(block_back, donate_argnums=(0, 2)),
            jax.jit(end_back, donate_argnums=(0, 1)),
            jax.jit(rows_back, donate_argnums=(0, 1)))


def train_steps(cfg: dict, weights: dict, batches, precision="float32",
                fault=None) -> dict:
    """Follow the first ``len(batches)`` steps of training from ``weights``
    (Nesterov momentum as the configuration states, the indexer frozen)
    over ``(ids, targets)`` pairs. Returns each step's loss, every TRAINED
    leaf's gradient norm at step 1 and norm of change after the last step;
    the indexer's leaves appear in neither. ``fault="half_batch"`` plants
    the fault the check must catch: at a batch of one sequence, the second
    half of the sequence left out; ``"dense"`` and ``"raw_weights"`` are
    the mechanisms' own (``sparse_attention``, ``routed_experts``).

    A step is ``loss_fn``'s gradient taken block by block
    (``_step_parts``): forward with each block's input kept, then from the
    loss's end back, each part's leaves updated as its gradient is made. A
    block's leaves go under the names of no layer (``_mix/Wq``).

    ``weights`` is consumed: its buffers are given to the first step, and a
    copy on the host stands for the start in the parameters' change."""
    rows, forward, block_back, end_back, rows_back = _step_parts(
        json.dumps(cfg, sort_keys=True), precision,
        fault if fault in ("dense", "raw_weights") else None)
    blocks = [f"b{i}" for i in range(cfg["num_hidden_layers"])]
    ends = {"rows": ("embed/",), "end": ("norm_f/", "head/")}

    def part(tree, head):
        if head in ends:
            return {k: v for k, v in tree.items() if k.startswith(ends[head])}
        return {k[len(head):]: v for k, v in tree.items()
                if k.startswith(head + "_")}

    still = {k: v for k, v in weights.items() if not trained(k)}
    w = {k: v for k, v in weights.items() if trained(k)}
    start = {k: np.asarray(v) for k, v in w.items()}
    trace = jax.jit(lambda t: {k: jnp.zeros_like(v) for k, v in t.items()})(w)
    losses, grad_norm = [], None
    for ids, targets in batches:
        if fault == "half_batch":
            half = ids.shape[1] // 2
            ids, targets = ids[:, :half], targets[:, :half]
        ids = jnp.asarray(ids, jnp.int32)
        norms = {}

        def keep(head, new, moved, norm):
            for tree, got in ((w, new), (trace, moved), (norms, norm)):
                tree.update({("" if head in ends else head) + k: v
                             for k, v in got.items()})

        xs = [rows(part(w, "rows"), ids)]
        for b in blocks:
            xs.append(forward(part(w, b), part(still, b), xs[-1]))
        *news, ct, loss = end_back(part(w, "end"), part(trace, "end"),
                                   xs.pop(), jnp.asarray(targets, jnp.int32))
        keep("end", *news)
        for b in reversed(blocks):
            *news, ct = block_back(part(w, b), part(still, b),
                                   part(trace, b), xs.pop(), ct)
            keep(b, *news)
        keep("rows", *rows_back(part(w, "rows"), part(trace, "rows"), ids,
                                ct))
        losses.append(float(loss))
        if grad_norm is None:
            grad_norm = {k: float(v) for k, v in norms.items()}
    del trace
    change = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)))
    delta = {k: float(change(w[k], start[k])) for k in w}
    return {"losses": losses, "grad_norm": grad_norm, "delta_norm": delta}


# ---------------------------------------------------------------------------
# operations and bytes the algorithm needs, from shapes
# ---------------------------------------------------------------------------

def scores_seen(seq_len: int, topk=None) -> float:
    """Pairs ``(t, s)`` a head scores: ``min(t + 1, topk)`` a query, and
    ``T (T + 1) / 2`` with no selection."""
    T, K = seq_len, seq_len if topk is None else min(topk, seq_len)
    return K * (K + 1) / 2 + (T - K) * K


def held_assignments(cfg: dict, seq_len: int) -> float:
    """Assignments ``(token, expert)`` to held experts a sequence and a
    layer under a uniform router: the expectation the work is counted at."""
    _, count = held_experts(cfg)
    return (seq_len * cfg["num_experts_per_tok"] * count
            / cfg["num_experts_routed"])


def trained_forward_flops(cfg: dict, seq_len: int) -> float:
    """One sequence forward, without the indexer: the projections, the
    router, the held experts at their expected load, the selected scores
    and the head."""
    F, H, G, d, _, _, M, E = _dims(cfg)
    T = seq_len
    layer = (2.0 * T * (2 * F * H * d + 2 * F * G * d + F * E)
             + 2.0 * held_assignments(cfg, T) * 3 * F * M
             + H * scores_seen(T, cfg["sa_config"]["topk"]) * 2.0 * 2 * d)
    return (cfg["num_hidden_layers"] * layer
            + 2.0 * T * F * cfg["vocab_size"])


def indexer_forward_flops(cfg: dict, seq_len: int) -> float:
    """One sequence through every layer's indexer: its three projections
    and an index score a head for every pair ``s <= t`` (a product over the
    head's width, and the head's weight)."""
    F, _, _, _, Hi, di, _, _ = _dims(cfg)
    T = seq_len
    return cfg["num_hidden_layers"] * (
        2.0 * T * F * (Hi * di + di + Hi)
        + Hi * scores_seen(T) * (2.0 * di + 2.0))


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward once and backward twice for what is trained, the frozen
    indexer forward alone. Recomputation (the program's ``remat``, a
    kernel's) is not counted, nor the work of an absent expert."""
    T = traffic["seq_len"]
    return (3.0 * trained_forward_flops(cfg, T)
            + indexer_forward_flops(cfg, T))


def flash_attention_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """What the attention over the selected keys needs a train step, all
    layers, forward and backward, whatever a kernel pads, skips or rebuilds:
    ``flops`` of the selected scores (``min(t + 1, topk)`` a query and a
    head), and the ``bytes`` of reading q, k, v and writing the output
    forward (``H d``, ``G d``, ``G d``, ``H d`` a token), reading those four
    and the output's cotangent and writing three gradients backward. The
    selection's own bytes are not counted (a kernel may hold it packed, or
    as indices)."""
    T, B = traffic["seq_len"], traffic["batch"]
    _, H, G, d, _, _, _, _ = _dims(cfg)
    L = cfg["num_hidden_layers"]
    token = (H + 2 * G + H) * d
    seen = scores_seen(T, cfg["sa_config"]["topk"])
    return {"flops": 3.0 * B * L * H * seen * 2.0 * 2 * d,
            "bytes": float(B * L * T * itemsize
                           * (token + (token + H * d) + (H + 2 * G) * d))}


def moe_expert_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """What the grouped products of ONE layer's held experts need a train
    step at the expected load, whatever implements them: ``flops`` of three
    products an assignment forward and twice that backward; the ``bytes``
    of reading the held experts' three matrices forward and again backward
    and writing their gradients, and an assignment's row in and out
    (``F``) with its two intermediates (``M``) forward, and backward the
    rows and their cotangents once more."""
    T, B = traffic["seq_len"], traffic["batch"]
    F, _, _, _, _, _, M, _ = _dims(cfg)
    _, count = held_experts(cfg)
    rows = B * held_assignments(cfg, T)
    weights = count * 3 * F * M
    return {"flops": 3.0 * rows * 3 * 2.0 * F * M,
            "bytes": float(itemsize * (3 * weights
                                       + rows * (2 * F + 2 * M)
                                       + rows * (4 * F + 4 * M)))}
