"""Plain mixture-of-experts decoder as SmallThinker-21BA3B is built, for the
check of outputs: grouped-query attention, full and position-free in some
layers and over a window of keys with rotary positions in the others, and
top-k routed ReLU-gated experts whose router reads the block's input ahead
of attention, of which a range is held; in straightforward ``jax.numpy``,
float32 at HIGHEST matmul precision (the entry sets it).

Imports nothing of the program. The attention of a layer is one dense
softmax over a mask written out over ``[T, T]`` (``s <= t``, and ``t - s <
window`` in a window layer), 512 queries at a time. The experts are a loop
over the held ones (a ``lax.scan``), each run on every token and weighted
token by token (zero where the token did not choose it); what the absent
experts would add is left out, as the configuration's deployment says.
Only what the chip's memory and its compiler's time force departs from the
plainest form: the queries go 512 at a time, ``loss_fn`` recomputes each
block in the backward, and ``train_steps`` takes that gradient block by
block, one block's two programs compiled for each kind of layer (full, or
window), and keeps the start weights on the host. Where the description
is silent the configuration file's ``assumed`` gives the choice: the
router reads the block's normed input ``u`` (the attention's own), q and k
are not normed, an expert is ``W_down (ReLU(W_gate v) * W_up v)``, and key
``s`` is in query ``t``'s window when ``0 <= t - s < window``.

Weights go by the program's names, ``"<node>/<param>"``. ``precision`` is
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

from benchmark.reference.keye_vl2 import rotate, scores_seen
from benchmark.reference.lowprec import seed_key
from benchmark.reference.olmo_hybrid import FrozenCfg, rms_norm, rounders

QUERY_BLOCK = 512       # queries scored at a time
#: the mechanisms' own planted faults (``train_steps``)
FAULTS = ("no_window", "rope_everywhere", "route_after")


def held_experts(cfg: dict) -> tuple:
    """``(first, count)``: the range of experts whose weights are here."""
    return cfg.get("first_expert", 0), cfg["moe_num_primary_experts"]


def _dims(cfg: dict) -> tuple:
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"], cfg["num_experts_routed"])


def layer_kind(cfg: dict, i: int, fault=None) -> tuple:
    """``(window, rotated)`` of layer ``i``: the window's length or None,
    and whether q and k turn."""
    windowed = cfg["sliding_window_layout"][i] and fault != "no_window"
    rotated = cfg["rope_layout"][i] or fault == "rope_everywhere"
    return (cfg["sliding_window_size"] if windowed else None, bool(rotated))


def param_shapes(cfg: dict) -> dict:
    """Flat ``{"<node>/<param>": shape}`` under the program's names."""
    F, H, G, d, M, E = _dims(cfg)
    _, count = held_experts(cfg)
    s = {"embed/W": (cfg["vocab_size"], F)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"b{i}"
        s.update({
            f"{b}_norm1/gamma": (F,),
            f"{b}_mix/Wq": (F, H * d), f"{b}_mix/Wk": (F, G * d),
            f"{b}_mix/Wv": (F, G * d), f"{b}_mix/Wo": (H * d, F),
            f"{b}_norm2/gamma": (F,),
            f"{b}_moe/W_r": (F, E), f"{b}_moe/W_gate": (count, F, M),
            f"{b}_moe/W_up": (count, F, M), f"{b}_moe/W_down": (count, M, F)})
    s.update({"norm_f/gamma": (F,), "head/W": (F, cfg["vocab_size"])})
    return s


def make_weights(cfg: dict, seed: int) -> dict:
    """Flat ``{"<node>/<param>": array}``, float32, in one jitted call on
    the default device: matrices normal(0, ``init_std``), the embedding's
    rows normal(0, ``embedding_std``), every norm's gain 1 (the
    configuration's ``assumed.weights`` says why the embedding is the
    larger)."""
    shapes = param_shapes(cfg)
    std = cfg.get("init_std", 0.02)
    scale = {"embed/W": cfg.get("embedding_std", std)}

    def build(key):
        w = {}
        for name, kk in zip(shapes, jax.random.split(key, len(shapes))):
            if name.endswith("gamma"):
                w[name] = jnp.ones(shapes[name], jnp.float32)
            else:
                w[name] = scale.get(name, std) * jax.random.normal(
                    kk, shapes[name], jnp.float32)
        return w

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def attention(w, name, u, cfg, kind, q_, product):
    """``W_o concat_h sum_s softmax_s(q_th . k_s / sqrt(d)) v_s`` over the
    keys ``s <= t`` (and ``t - s < window``), head ``h`` reading key/value
    head ``h // (H / G)``, q and k turned where the layer is rotated."""
    F, H, G, d, _, _ = _dims(cfg)
    B, T, _ = u.shape
    window, rotated = kind
    mix = lambda leaf: product(jnp.dot(q_(u), q_(w[f"{name}_mix/{leaf}"])))
    q = mix("Wq").reshape(B, T, H, d)
    k = mix("Wk").reshape(B, T, G, d)
    v = mix("Wv").reshape(B, T, G, d)
    if rotated:
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    k = jnp.repeat(k, H // G, axis=2)
    v = jnp.repeat(v, H // G, axis=2)

    bq = min(QUERY_BLOCK, T)
    n = -(-T // bq)
    q = jnp.moveaxis(jnp.pad(q, ((0, 0), (0, n * bq - T), (0, 0), (0, 0))
                             ).reshape(B, n, bq, H, d), 1, 0)

    @jax.checkpoint
    def rows(args):
        q_i, first = args
        t = (first + jnp.arange(bq))[:, None]
        s = jnp.arange(T)[None, :]
        seen = s <= t
        if window is not None:
            seen = seen & (t - s < window)
        scores = product(jnp.einsum("bqhd,bkhd->bhqk", q_(q_i), q_(k))
                         ) / math.sqrt(d)
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return product(jnp.einsum("bhqk,bkhd->bqhd", q_(maps), q_(v)))

    o = lax.map(rows, (q, jnp.arange(n) * bq))
    o = jnp.moveaxis(o, 0, 1).reshape(B, n * bq, H * d)[:, :T]
    return product(jnp.dot(q_(o), q_(w[f"{name}_mix/Wo"])))


def routing(w, name, r, cfg):
    """``[B, T, E]`` float32: a token's weight on each of ALL the routed
    experts, nought on those it did not choose; the chosen are the
    ``moe_num_active_primary_experts`` of largest ``softmax(W_r r)`` (of
    equal ones the lower expert), and with ``norm_topk_prob`` their weights
    are divided by their sum, over all the chosen wherever they live."""
    p = jax.nn.softmax(jnp.dot(r, w[f"{name}/W_r"]), axis=-1)
    order = jnp.argsort(-p, axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1)        # an expert's rank, by token
    g = jnp.where(place < cfg["moe_num_active_primary_experts"], p, 0.0)
    if cfg["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g


def routed_experts(w, name, v, r, cfg, q_, product):
    """``sum_{e chosen by r and held} g_e W_down,e (ReLU(W_gate,e v) *
    W_up,e v)``, one held expert after another over every token."""
    first, count = held_experts(cfg)
    g = routing(w, name, r, cfg)
    dot = lambda a, m: product(jnp.dot(q_(a), q_(m)))

    def share(gate, up, down, weight):
        out = dot(jax.nn.relu(dot(v, gate)) * dot(v, up), down)
        return weight[..., None] * out

    return lax.scan(lambda y, held: (y + share(*held), None),
                    jnp.zeros_like(v), (
        w[f"{name}/W_gate"], w[f"{name}/W_up"], w[f"{name}/W_down"],
        jnp.moveaxis(g[..., first:first + count], -1, 0)))[0]


def block(name, w, x, cfg, kind, precision, fault):
    """``u = RMSNorm(x)``, ``h = x + Attn(u)``, ``out = h +
    Experts(RMSNorm(h))`` routed by ``u``. ``fault="route_after"`` plants
    the router reading ``RMSNorm(h)`` instead."""
    q_, product = rounders(precision)
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, w[f"{name}_norm1/gamma"], eps)
    h = x + attention(w, name, u, cfg, kind, q_, product)
    v = rms_norm(h, w[f"{name}_norm2/gamma"], eps)
    r = v if fault == "route_after" else u
    return product(h + routed_experts(w, f"{name}_moe", v, r, cfg, q_,
                                      product))


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
        h = jax.checkpoint(block, static_argnums=(0, 3, 4, 5, 6))(
            f"b{i}", w, h, frozen, layer_kind(cfg, i, fault), precision,
            fault)
    return sequence_loss(w, h, targets, frozen, precision)


@functools.lru_cache(maxsize=1)
def _step_parts(cfg_json: str, precision: str, planted):
    """``loss_fn``'s gradient and the update, jitted a part at a time: the
    embedding's rows, ONE block forward, ONE block's transpose with its
    leaves' update (each compiled once for each kind of layer), the loss's
    end. The parts last asked for are kept, so that a further seed in the
    same process finds them compiled."""
    cfg = FrozenCfg(json.loads(cfg_json))
    lr, mu = cfg["learning_rate"], cfg["momentum"]

    def updated(w, g, trace):
        trace = {k: g[k] + mu * trace[k] for k in g}
        return ({k: w[k] - lr * (g[k] + mu * trace[k]) for k in g}, trace,
                {k: jnp.sqrt(jnp.sum(g[k] ** 2)) for k in g})

    def one_block(w, x, kind):
        return block("", w, x, cfg, kind, precision, planted)

    def block_back(w, trace, x, ct, kind):
        g, ct = jax.vjp(lambda w, x: one_block(w, x, kind), w, x)[1](ct)
        return updated(w, g, trace) + (ct,)

    def end_back(w, trace, h, targets):
        loss, (g, ct) = jax.value_and_grad(sequence_loss, argnums=(0, 1))(
            w, h, targets, cfg, precision)
        return updated(w, g, trace) + (ct, loss)

    def rows_back(w, trace, ids, ct):
        g = jax.vjp(lambda w: w["embed/W"][ids], w)[1](ct)[0]
        return updated(w, g, trace)

    return (jax.jit(lambda w, ids: w["embed/W"][ids]),
            jax.jit(one_block, static_argnums=2),
            jax.jit(block_back, donate_argnums=(0, 1), static_argnums=4),
            jax.jit(end_back, donate_argnums=(0, 1)),
            jax.jit(rows_back, donate_argnums=(0, 1)))


def train_steps(cfg: dict, weights: dict, batches, precision="float32",
                fault=None) -> dict:
    """Follow the first ``len(batches)`` steps of training from ``weights``
    (Nesterov momentum as the configuration states) over ``(ids, targets)``
    pairs. Returns each step's loss, every leaf's gradient norm at step 1
    and norm of change after the last step. ``fault="half_batch"`` plants
    the fault the check must catch: at a batch of one sequence, the second
    half of the sequence left out; ``FAULTS`` are the mechanisms' own: every
    layer full (``no_window``), the full layer rotated too
    (``rope_everywhere``), the router reading the normed stream after
    attention (``route_after``).

    A step is ``loss_fn``'s gradient taken block by block
    (``_step_parts``): forward with each block's input kept, then from the
    loss's end back, each part's leaves updated as its gradient is made.

    ``weights`` is consumed: its buffers are given to the first step, and a
    copy on the host stands for the start in the parameters' change."""
    planted = fault if fault in FAULTS else None
    rows, forward, block_back, end_back, rows_back = _step_parts(
        json.dumps(cfg, sort_keys=True), precision, planted)
    blocks = [(f"b{i}", layer_kind(cfg, i, planted))
              for i in range(cfg["num_hidden_layers"])]
    ends = {"rows": ("embed/",), "end": ("norm_f/", "head/")}

    def part(tree, head):
        if head in ends:
            return {k: v for k, v in tree.items() if k.startswith(ends[head])}
        return {k[len(head):]: v for k, v in tree.items()
                if k.startswith(head + "_")}

    w = dict(weights)
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
        for b, kind in blocks:
            xs.append(forward(part(w, b), xs[-1], kind))
        *news, ct, loss = end_back(part(w, "end"), part(trace, "end"),
                                   xs.pop(), jnp.asarray(targets, jnp.int32))
        keep("end", *news)
        for b, kind in reversed(blocks):
            *news, ct = block_back(part(w, b), part(trace, b), xs.pop(), ct,
                                   kind)
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

def held_assignments(cfg: dict, seq_len: int) -> float:
    """Assignments ``(token, expert)`` to held experts a sequence and a
    layer under a uniform router: the expectation the work is counted at."""
    _, count = held_experts(cfg)
    return (seq_len * cfg["moe_num_active_primary_experts"] * count
            / cfg["num_experts_routed"])


def _layers(cfg: dict, windowed: bool) -> list:
    """The windows of the layers that have one (``windowed``) or of those
    that have none."""
    return [layer_kind(cfg, i)[0] for i in range(cfg["num_hidden_layers"])
            if (layer_kind(cfg, i)[0] is not None) == windowed]


def trained_forward_flops(cfg: dict, seq_len: int) -> float:
    """One sequence forward: the projections, the router, the held experts
    at their expected load, the scores inside each layer's mask and the
    head."""
    F, H, G, d, M, E = _dims(cfg)
    T = seq_len
    layers = cfg["num_hidden_layers"]
    per_layer = (2.0 * T * (2 * F * H * d + 2 * F * G * d + F * E)
                 + 2.0 * held_assignments(cfg, T) * 3 * F * M)
    scores = sum(H * scores_seen(T, layer_kind(cfg, i)[0]) * 2.0 * 2 * d
                 for i in range(layers))
    return layers * per_layer + scores + 2.0 * T * F * cfg["vocab_size"]


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward once and backward twice. Recomputation (the program's
    ``remat``) is not counted, nor the work of an absent expert."""
    return 3.0 * trained_forward_flops(cfg, traffic["seq_len"])


def _attention_cost(cfg: dict, traffic: dict, windows: list,
                    itemsize: int) -> dict:
    """Scores inside the masks of the layers with ``windows`` (None for a
    full layer), forward and backward, and the bytes of q, k, v and the
    output forward (``H d``, ``G d``, ``G d``, ``H d`` a token) and of
    those four, the output's cotangent and three gradients backward."""
    T, B = traffic["seq_len"], traffic["batch"]
    _, H, G, d, _, _ = _dims(cfg)
    token = (H + 2 * G + H) * d
    seen = sum(scores_seen(T, win) for win in windows)
    return {"flops": 3.0 * B * H * seen * 2.0 * 2 * d,
            "bytes": float(B * len(windows) * T * itemsize
                           * (token + (token + H * d) + (H + 2 * G) * d))}


def flash_attention_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """What the attention of a train step needs, all layers, forward and
    backward, whatever a kernel pads, skips or rebuilds: ``flops`` of the
    scores inside each layer's mask (``min(t + 1, window)`` a query and a
    head in a window layer, ``t + 1`` in a full one) and the bytes of
    ``_attention_cost``."""
    windows = _layers(cfg, True) + _layers(cfg, False)
    return _attention_cost(cfg, traffic, windows, itemsize)


def window_attention_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """``flash_attention_cost`` of the window layers alone."""
    return _attention_cost(cfg, traffic, _layers(cfg, True), itemsize)


def moe_expert_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """What the grouped products of ONE layer's held experts need a train
    step at the expected load, whatever implements them: ``flops`` of three
    products an assignment forward and twice that backward; the ``bytes``
    of reading the held experts' three matrices forward and again backward
    and writing their gradients, and an assignment's row in and out
    (``F``) with its two intermediates (``M``) forward, and backward the
    rows and their cotangents once more."""
    T, B = traffic["seq_len"], traffic["batch"]
    F, _, _, _, M, _ = _dims(cfg)
    _, count = held_experts(cfg)
    rows = B * held_assignments(cfg, T)
    weights = count * 3 * F * M
    return {"flops": 3.0 * rows * 3 * 2.0 * F * M,
            "bytes": float(itemsize * (3 * weights
                                       + rows * (2 * F + 2 * M)
                                       + rows * (4 * F + 4 * M)))}
