"""Routed experts held as one chip's share.

A mixture-of-experts feed-forward whose router scores ALL ``n_experts`` and
whose parameters are the ``count`` experts from ``first`` on, the share of
one chip among several that hold the layer together. Tokens routed to an
expert that lives elsewhere add nothing here: on one chip there is no
exchange and no code that stands in for the absent chips, and the partial
sum is the layer's output. ``parallel/expert.py`` is the older layer (top-1,
a capacity, dense one-hot dispatch); this one drops no token and builds no
``[N, E, C]`` tensor.

Tokens reach their experts and come back by GATHERS alone, in the backward
pass too (``take_rows``, ``weigh_back``: each the other's transpose, written
out): a gather's own transpose is a scatter-add, which the v5e runs some
ten times slower a row (4.7 ms for 16,384 rows of 2,048 where the gather
took 0.42; PERF.md PR 35), and a step whose time depends on how many rows
it scatters depends on where the router leans.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    BaseLayerConf, Params, register_layer,
)
from deeplearning4j_tpu.ops.activations import get_activation


def route_top_k(logits, top_k: int, renormalize: bool = True):
    """``(weights [N, k], experts [N, k])``: the ``top_k`` largest of
    ``softmax(logits)`` a token, in float32, and with ``renormalize`` each
    token's weights divided by their sum over ALL ``top_k`` chosen, held
    here or not."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(p, top_k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


def grouped_dot(x, w, sizes):
    """Rows of ``x [M, K]`` in groups of ``sizes [G]`` (in order), each
    group against its own ``w[g] [K, N]``. What the rows past the groups'
    sum hold afterwards is NOT defined, in the product and in both its
    transposes: the CPU writes zeros, the TPU's grouped-product kernel
    skips those rows and leaves what the buffer held. Whoever calls this
    keeps them out of what it reads (``RoutedExpertsLayer`` sets them to
    nought at both ends of its three products). Float32 operands follow the
    ambient matmul precision; narrower ones name the default, because the
    TPU's kernel refuses a bfloat16 product under an ambient "highest" (as
    ``ops/pallas_attention._dot`` found)."""
    precision = None if x.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.ragged_dot(x, w, sizes, precision=precision)


@jax.custom_vjp
def take_rows(u, order, place, held):
    """``rows[p] = u[order[p] // K]``: the token of each assignment, in the
    sorted order. ``order [N K]`` lists the assignments ``t K + k`` by
    expert, ``place [N, K]`` is its inverse (where assignment ``(t, k)``
    stands) and ``held [N, K]`` says which live here. Backward each token
    gathers the cotangents of its own held assignments and adds them."""
    return u[order // place.shape[1]]


def _take_rows_fwd(u, order, place, held):
    return take_rows(u, order, place, held), (place, held)


def _take_rows_bwd(res, d_rows):
    place, held = res
    mine = jnp.where(held[..., None], d_rows[place], 0)       # [N, K, F]
    du = jnp.sum(mine.astype(jnp.float32), axis=1).astype(d_rows.dtype)
    return du, None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def weigh_back(out, weights, order, place):
    """``y[t] = sum_k weights[t, k] out[place[t, k]]`` in float32: each token
    gathers the outputs of its assignments (``weights`` is nought for those
    held elsewhere) and adds them by weight. Backward an assignment's row
    takes its token's cotangent times its weight, and a weight the product
    of its token's cotangent and its row: gathers both."""
    mine = out[place].astype(jnp.float32)                      # [N, K, F]
    return jnp.sum(mine * weights[..., None], axis=1)


def _weigh_back_fwd(out, weights, order, place):
    return weigh_back(out, weights, order, place), (out, weights, order,
                                                    place)


def _weigh_back_bwd(res, dy):
    out, weights, order, place = res
    K = place.shape[1]
    d_out = (dy[order // K] * weights.reshape(-1)[order][:, None]
             ).astype(out.dtype)
    d_weights = jnp.sum(out[place].astype(jnp.float32) * dy[:, None, :],
                        axis=-1).astype(weights.dtype)
    return d_out, d_weights, None, None


weigh_back.defvjp(_weigh_back_fwd, _weigh_back_bwd)


@register_layer
@dataclass
class RoutedExpertsLayer(BaseLayerConf):
    """``y_t = sum_{e in E_t, first <= e < first + count} g_te W_down,e
    (act(W_gate,e u_t) * W_up,e u_t)`` over ``[B, T, F]`` or ``[N, F]``,
    with ``E_t`` the ``top_k`` experts of largest ``softmax(W_r u_t)`` over
    all ``n_experts`` and ``g`` their probabilities, renormalised over the
    ``top_k`` chosen (``norm_topk_prob``). No bias, no capacity, no dropped
    token, no auxiliary loss. With ``route_from_side`` the node takes two
    inputs ``(u, r)`` of one width: the experts read ``u`` and the router
    reads ``r`` (a router placed ahead of attention reads the block's input
    to attention, while the experts read the stream after it); under remat
    the node keeps both, so the rebuild routes from what the forward
    routed from. With one input the router reads ``u``.

    The ``N top_k`` assignments ``(token, expert)`` are sorted by expert (a
    stable sort; those to absent experts sort last), the tokens gathered in
    that order, multiplied in groups (``jax.lax.ragged_dot``: one product
    whose rows meet their own expert's matrix, and which passes over the
    rows of no group), and each token gathers its own assignments' outputs
    back and adds them by weight. All ``N top_k`` rows are laid out
    whatever the router does, so a step takes the same operations when
    every token leans on one held expert as when none does; the rows past
    the held assignments are multiplied by nothing and weigh nothing.

    Params: ``W_r [F, n_experts]`` and, stacked by held expert, ``W_gate,
    W_up [count, F, M]``, ``W_down [count, M, F]``. State: ``assigned``
    int32 ``[count]``, the last step's count of assignments by held
    expert."""
    n_experts: int = 8
    top_k: int = 2
    n_hidden: int = 0           # an expert's width; default 4 * F
    first: int = 0              # the held range of experts
    count: int = 0              # default: all of them
    norm_topk_prob: bool = True
    route_from_side: bool = False

    @property
    def N_INPUTS(self) -> int:
        return 2 if self.route_from_side else 1

    def set_n_in(self, in_type: InputType) -> None:
        self.n_in = (in_type.size if in_type.kind == "rnn"
                     else in_type.flat_size())
        if not self.n_hidden:
            self.n_hidden = 4 * self.n_in
        if not self.count:
            self.count = self.n_experts - self.first
        if not (0 <= self.first and self.count >= 1
                and self.first + self.count <= self.n_experts
                and 1 <= self.top_k <= self.n_experts):
            raise ValueError(
                f"RoutedExpertsLayer({self.name!r}): experts {self.first} "
                f"to {self.first + self.count} of {self.n_experts}, "
                f"{self.top_k} a token")

    def set_side_inputs(self, in_types) -> None:
        (r,) = in_types
        width = r.size if r.kind == "rnn" else r.flat_size()
        if width != self.n_in:
            raise ValueError(
                f"RoutedExpertsLayer({self.name!r}): the router's input "
                f"must be {self.n_in} wide, as the experts' is; got {r}")

    def infer_output_type(self, in_type: InputType) -> InputType:
        return in_type

    def param_order(self) -> List[str]:
        return ["W_r", "W_gate", "W_up", "W_down"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, M, E, C = self.n_in, self.n_hidden, self.n_experts, self.count
        ks = jax.random.split(rng, 4)
        return {"W_r": self._init_w(ks[0], (F, E), F, E, dtype),
                "W_gate": self._init_w(ks[1], (C, F, M), F, M, dtype),
                "W_up": self._init_w(ks[2], (C, F, M), F, M, dtype),
                "W_down": self._init_w(ks[3], (C, M, F), M, F, dtype)}

    def init_state(self):
        return {"assigned": jnp.zeros((self.count or 1,), jnp.int32)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        from deeplearning4j_tpu.profiling.metrics import get_registry
        x, side = x if self.route_from_side else (x, None)
        x = self._dropout_input(x, train, rng)
        shape = x.shape
        u = x.reshape(-1, shape[-1])
        r = u if side is None else side.reshape(-1, shape[-1])
        N, K, C = u.shape[0], self.top_k, self.count
        act = get_activation(self.activation or "silu")
        get_registry().labeled_counter(
            "moe_grouped_traces_total",
            "routed-expert layers traced, by the path of their grouped "
            "products (per trace)").labels(path="ragged_dot").inc()
        with jax.named_scope("moe:route"):
            # the router's product and softmax in float32
            logits = jnp.dot(r.astype(jnp.float32),
                             params["W_r"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            weights, experts = route_top_k(logits, K, self.norm_topk_prob)
        with jax.named_scope("moe:dispatch"):
            local = experts - self.first                      # [N, K]
            held = (local >= 0) & (local < C)
            key = jnp.where(held, local, C).reshape(-1)  # absent sort last
            order = jnp.argsort(key, stable=True)
            place = jnp.argsort(order).reshape(N, K)
            sizes = jnp.sum(jax.nn.one_hot(key, C, dtype=jnp.int32), axis=0)
            # the rows past the held assignments belong to no group and the
            # grouped products leave them undefined: they are set to nought
            # where they come in and where they go out, so nothing of them
            # is read forward and no cotangent of theirs reaches a token
            live = (jnp.arange(N * K) < jnp.sum(sizes))[:, None]
            rows = jnp.where(live, take_rows(u, order, place, held), 0)
        with jax.named_scope("moe:experts"):
            hidden = (act(grouped_dot(rows, params["W_gate"], sizes))
                      * grouped_dot(rows, params["W_up"], sizes))
            out = jnp.where(live, grouped_dot(
                hidden.astype(u.dtype), params["W_down"], sizes), 0)
        with jax.named_scope("moe:combine"):
            y = weigh_back(out, jnp.where(held, weights, 0.0), order, place)
        y = y.astype(x.dtype).reshape(shape)
        if mask is not None:
            y = y * mask[..., None]
        return y, {"assigned": sizes}
