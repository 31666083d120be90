"""Routed experts held as one chip's share.

A mixture-of-experts feed-forward whose router scores ALL ``n_experts`` and
whose parameters are the ``count`` experts from ``first`` on, the share of
one chip among several that hold the layer together. Tokens routed to an
expert that lives elsewhere add nothing here: on one chip there is no
exchange and no code that stands in for the absent chips, and the partial
sum is the layer's output. ``parallel/expert.py`` is the older layer (top-1,
a capacity, dense one-hot dispatch); this one drops no token and builds no
``[N, E, C]`` tensor.

Only the held assignments are laid out, multiplied and brought back. They
go through in fixed chunks of ``chunk_rows`` sorted rows, twice a uniform
router's held share, by one loop whose body the compiled step holds once a
pass (``held_experts``): a step whose router spreads as a uniform one runs
one chunk, a router that leans on the held experts runs more chunks of the
same body, and no step holds a second, full-size layout. A share of every
expert (``count == n_experts``) runs all its rows as one chunk.

Tokens reach their experts and come back by GATHERS alone, in the backward
pass too (written out in ``held_experts``' backward): a gather's own
transpose is a scatter-add, which the v5e runs some ten times slower a row
(4.7 ms for 16,384 rows of 2,048 where the gather took 0.42; PERF.md PR
35).
"""

from __future__ import annotations

import functools
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


def chunk_rows(assignments: int, count: int, n_experts: int) -> int:
    """The rows of one chunk of the held assignments: twice a uniform
    router's held share of ``assignments`` (``count`` of ``n_experts``
    experts held), rounded up to 8, and no more than all of them rounded up
    to 8 (a share of every expert runs one chunk)."""
    up8 = lambda n: -(-n // 8) * 8
    return min(up8(-(-2 * assignments * count // n_experts)),
               up8(assignments))


def gather_back(rows, place, held, weights=None):
    """``y[t] = sum_k weights[t, k] rows[place[t, k]]`` over the held
    assignments of each token, in float32, by a gather: the row an
    assignment that is not held points at adds nothing, whatever it holds.
    Without ``weights`` each held row counts once."""
    mine = jnp.where(held[..., None], rows[place].astype(jnp.float32), 0)
    if weights is not None:
        mine = mine * weights[..., None]
    return jnp.sum(mine, axis=1)


def _chunk(j, rows, order, ends, sizes):
    """Chunk ``j`` of the sorted assignments: its first row, the
    assignments it covers, which of its rows are held (``[rows, 1]``) and
    its rows by held expert (each expert's rows clipped to the chunk)."""
    lo = j * rows
    covered = jax.lax.dynamic_slice_in_dim(order, lo, rows)
    live = (lo + jnp.arange(rows) < ends[-1])[:, None]
    starts = ends - sizes
    return lo, covered, live, (jnp.clip(ends - lo, 0, rows)
                               - jnp.clip(starts - lo, 0, rows))


def _over_chunks(run, body, init):
    """``body(j, carry)`` for the chunks ``j < run`` alone, in one loop
    whose body is traced once."""
    return jax.lax.while_loop(lambda c: c[0] < run,
                              lambda c: (c[0] + 1, body(*c)),
                              (jnp.int32(0), init))[1]


def _held_rows(rows, act, u, K, order, sizes, w_gate, w_up, w_down, keep):
    """The chunks' forward: one loop of fixed chunks of ``rows`` sorted
    assignments over the held ones alone. Gives the experts' outputs in the
    sorted order, ``[J rows, F]``, and with ``keep`` the two products the
    backward reads. A row that no chunk that ran wrote is never read: the
    readers select the held rows."""
    F, M = u.shape[1], w_gate.shape[2]
    ends = jnp.cumsum(sizes)

    def body(j, bufs):
        lo, covered, live, sz = _chunk(j, rows, order, ends, sizes)
        # a chunk's rows past the held assignments belong to no group and
        # the grouped products leave them undefined: they are set to nought
        # where they come in and where they go out (and so backward)
        with jax.named_scope("moe:dispatch"):
            x = jnp.where(live, u[covered // K], 0)
        with jax.named_scope("moe:experts"):
            a = grouped_dot(x, w_gate, sz)
            b = grouped_dot(x, w_up, sz)
            out = jnp.where(live, grouped_dot(
                (act(a) * b).astype(u.dtype), w_down, sz), 0)
        new = (out, a, b) if keep else (out,)
        return tuple(jax.lax.dynamic_update_slice_in_dim(buf, v, lo, 0)
                     for buf, v in zip(bufs, new))

    dots = jax.eval_shape(lambda x, w: grouped_dot(x, w, sizes),
                          jax.ShapeDtypeStruct((rows, F), u.dtype), w_gate)
    total = order.shape[0]
    bufs = (jnp.zeros((total, F), u.dtype),)
    if keep:
        bufs += (jnp.zeros((total, M), dots.dtype),) * 2
    return _over_chunks(-(-ends[-1] // rows), body, bufs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def held_experts(rows, act, u, weights, order, place, held, sizes, w_gate,
                 w_up, w_down):
    """``y [N, F]`` float32: each token's held assignments multiplied by
    their experts (``act(x W_gate) * x W_up) W_down``) and added by
    ``weights [N, K]``. ``order`` lists the ``N K`` assignments by held
    expert (padded with zeros to whole chunks of ``rows``), ``place [N, K]``
    is where each stands in it, ``held [N, K]`` which live here and
    ``sizes [count]`` how many each held expert has. The rows go through
    in chunks of ``rows``: only the chunks that hold a held assignment run,
    and the backward runs the same chunks."""
    out, = _held_rows(rows, act, u, place.shape[1], order, sizes, w_gate,
                      w_up, w_down, keep=False)
    with jax.named_scope("moe:combine"):
        return gather_back(out, place, held, weights)


def _held_experts_fwd(rows, act, u, weights, order, place, held, sizes,
                      w_gate, w_up, w_down):
    out, a, b = _held_rows(rows, act, u, place.shape[1], order, sizes,
                           w_gate, w_up, w_down, keep=True)
    with jax.named_scope("moe:combine"):
        y = gather_back(out, place, held, weights)
    return y, (u, weights, order, place, held, sizes, w_gate, w_up, w_down,
               out, a, b)


def _transpose(fn, primal, ct):
    """The cotangent of ``fn``'s one argument, ``fn`` linear in it: the
    transposed product alone, no forward product."""
    return jax.linear_transpose(fn, primal)(ct)[0]


def _held_experts_bwd(rows, act, res, dy):
    """The same chunks backward. Each chunk gathers its assignments' token
    cotangents (times their weights: the outputs' cotangent; and dotted
    with the outputs: the weights' cotangent, read back by a gather),
    transposes the three products, and adds the weights' gradients in
    float32 to a carry that a chunk that does not run leaves as it is; each
    token then gathers its held rows' cotangents back."""
    (u, weights, order, place, held, sizes, w_gate, w_up, w_down,
     out_all, a_all, b_all) = res
    K = place.shape[1]
    ends = jnp.cumsum(sizes)
    flat_weights = weights.reshape(-1)

    def body(j, carry):
        d_rows, d_dots, dws = carry
        lo, covered, live, sz = _chunk(j, rows, order, ends, sizes)
        take = lambda buf: jax.lax.dynamic_slice_in_dim(buf, lo, rows)
        out, a, b = take(out_all), take(a_all), take(b_all)
        with jax.named_scope("moe:combine"):
            g = dy[covered // K]
            d_out = jnp.where(live, g * flat_weights[covered][:, None], 0
                              ).astype(out.dtype)
            d_w = jnp.where(live[:, 0], jnp.sum(
                out.astype(jnp.float32) * g, axis=-1), 0)
        with jax.named_scope("moe:dispatch"):
            x = jnp.where(live, u[covered // K], 0)
        with jax.named_scope("moe:experts"):
            hidden = lambda a, b: (act(a) * b).astype(u.dtype)
            h, pull = jax.vjp(hidden, a, b)
            dh = _transpose(lambda h: grouped_dot(h, w_down, sz), h, d_out)
            d_down = _transpose(lambda w: grouped_dot(h, w, sz), w_down,
                                d_out)
            da, db = pull(dh)
            dx = (_transpose(lambda x: grouped_dot(x, w_gate, sz), x, da)
                  + _transpose(lambda x: grouped_dot(x, w_up, sz), x, db))
            d_gate = _transpose(lambda w: grouped_dot(x, w, sz), w_gate, da)
            d_up = _transpose(lambda w: grouped_dot(x, w, sz), w_up, db)
        d_dots = tuple(acc + d.astype(jnp.float32) for acc, d in
                       zip(d_dots, (d_gate, d_up, d_down)))
        put = jax.lax.dynamic_update_slice_in_dim
        return (put(d_rows, jnp.where(live, dx, 0), lo, 0), d_dots,
                put(dws, d_w, lo, 0))

    zeros = lambda w: jnp.zeros(w.shape, jnp.float32)
    d_rows, d_dots, dws = _over_chunks(-(-ends[-1] // rows), body, (
        jnp.zeros(out_all.shape, u.dtype),
        (zeros(w_gate), zeros(w_up), zeros(w_down)),
        jnp.zeros(order.shape, jnp.float32)))
    with jax.named_scope("moe:dispatch"):
        du = gather_back(d_rows, place, held).astype(u.dtype)
    with jax.named_scope("moe:combine"):
        d_weights = jnp.where(held, dws[place], 0).astype(weights.dtype)
    d_gate, d_up, d_down = (d.astype(w.dtype) for d, w in
                            zip(d_dots, (w_gate, w_up, w_down)))
    return du, d_weights, None, None, None, None, d_gate, d_up, d_down


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


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
    stable sort; those to absent experts sort last). The held ones, the
    first ``H`` of that order, go through in chunks of ``R =
    chunk_rows(N top_k, count, n_experts)`` rows: chunk ``j`` gathers the
    tokens of sorted rows ``[jR, (j+1)R)`` and multiplies them in groups
    (``jax.lax.ragged_dot``: one product whose rows meet their own expert's
    matrix, and which passes over the rows of no group), each expert's
    rows clipped to the chunk; only the ``ceil(H / R)`` chunks that hold a
    held row run, forward and backward. Each token then gathers its held
    assignments' outputs back and adds them by weight in float32. A router
    that spreads as a uniform one fills one chunk; one that leans on the
    held experts fills more and costs a chunk more each, never a layout of
    all ``N top_k`` rows. No token is dropped: a chunk's rows past the held
    ones are multiplied by nothing and weigh nothing. Beside one pass
    through all the rows, only the order in which the weights' gradients
    are summed differs, where an expert's rows straddle a chunk's edge.

    Params: ``W_r [F, n_experts]`` and, stacked by held expert, ``W_gate,
    W_up [count, F, M]``, ``W_down [count, M, F]``. State: ``assigned``
    int32 ``[count]``, the last step's count of assignments by held
    expert, and ``chunks`` int32, the chunks of held rows it ran."""
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
        return {"assigned": jnp.zeros((self.count or 1,), jnp.int32),
                "chunks": jnp.zeros((), jnp.int32)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        from deeplearning4j_tpu.profiling.metrics import get_registry
        x, side = x if self.route_from_side else (x, None)
        x = self._dropout_input(x, train, rng)
        shape = x.shape
        u = jnp.asarray(x).reshape(-1, shape[-1])
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
        R = chunk_rows(N * K, C, self.n_experts)
        with jax.named_scope("moe:dispatch"):
            local = experts - self.first                      # [N, K]
            held = (local >= 0) & (local < C)
            key = jnp.where(held, local, C).reshape(-1)  # absent sort last
            order = jnp.argsort(key, stable=True)
            place = jnp.argsort(order).reshape(N, K)
            sizes = jnp.sum(jax.nn.one_hot(key, C, dtype=jnp.int32), axis=0)
            # whole chunks: the rows past the assignments belong to no
            # chunk's held rows and are never read
            order = jnp.pad(order, (0, -(N * K) % R))
        y = held_experts(R, act, u, jnp.where(held, weights, 0.0), order,
                         place, held, sizes, params["W_gate"],
                         params["W_up"], params["W_down"])
        y = y.astype(x.dtype).reshape(shape)
        if mask is not None:
            y = y * mask[..., None]
        return y, {"assigned": sizes,
                   "chunks": -(-jnp.sum(sizes) // R).astype(jnp.int32)}
