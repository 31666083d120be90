"""Selective state-space layers (Mamba: Gu and Dao, arXiv:2312.00752) and
the gated memory unit that reads one such layer's scan from the layers
above it (SambaY: Ren et al., arXiv:2507.06607).

Every channel ``c`` of ``d_in`` keeps ``N`` numbers of state and moves them
by a decay that the token sets, differently for every channel and state::

    h_t[c, n] = exp(Delta_t[c] A[c, n]) h_{t-1}[c, n]
                + Delta_t[c] x_t[c] B_t[n]
    s_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]

There is no matrix form of it (the decay is no product of a factor a
channel and a factor a state), so the work is elementwise: the VPU's, and
the EUP's for the exponentials. Token by token it is ``T`` dependent steps
on ``d_in N`` numbers, and all ``T`` states at once are ``T d_in N`` float32
(2.7 GB at 8,192 tokens of 5,120 channels and 16 states).

``selective_scan`` is the seam, and one algorithm with two implementations
behind it. **On a TPU, in float32, for whole lanes of channels** (``d_in``
a multiple of 128, ``N`` of 8, blocks inside the kernels' VMEM gate) the
recurrence runs token by token in the two Pallas kernels of
``ops/pallas_selective_scan.py``, forward and backward, which hold a tile
of channels' state in vector registers, so that what made token-by-token
slow in XLA (the state's trip through HBM every step) is gone; they add no
``while`` to a step. **Everything else takes** ``selective_scan_chunked``:
float64 (the gradient checks), ``DL4J_TPU_PALLAS=off``, a CPU (unless the
variable says ``interpret``), and every shape the gate refuses, the tests'
tiny models among them (a refusal with the kernels on counts under
``pallas_gate_fallbacks_total{kernel="selective_scan"}``). It stays for
them, and as the kernels' reference in the tests beside
``selective_scan_recurrent``. ``ssm_scan_traces_total{path=}`` says which
of the two a trace took.

``selective_scan_chunked`` neither steps token by token nor keeps all
states. A ``lax.scan`` walks the sequence
in blocks of ``steps * lanes`` tokens and carries ``h [B, N, d_in]``. A
block is ``lanes`` runs of ``steps`` consecutive tokens. All runs take their
``steps`` steps side by side from a zero state (the loop is unrolled:
``steps`` fused passes over ``[lanes, N, d_in]``); the runs' ends are then
chained (``lax.associative_scan`` over ``lanes`` pairs of a run's whole
decay and its end state), which gives every run the state it starts from;
and what that start adds to each token's output, ``sum_n C_t[n]
exp(A[c, n] cumsum(Delta)_t[c]) h_start[c, n]``, is one more fused pass. No
step divides by a decay: a running product of decays underflows to zero and
harms nothing, where its inverse would overflow at ``Delta A`` near ``-16``
a token. The block's function is under ``jax.checkpoint``, so the backward
pass keeps the carries at the blocks' edges (``T / (steps lanes)`` of
``[B, N, d_in]``) and rebuilds one block's states at a time.

``Delta``, ``A``, the decays, the state and the sum over ``N`` are float32
(float64 under a float64 gradient check) whatever the layer computes in.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    Array, BaseLayerConf, Params, register_layer,
)
from deeplearning4j_tpu.nn.layers.linear_attention import (
    causal_depthwise_conv,
)
from deeplearning4j_tpu.nn.remat import backward_after_cotangent

#: consecutive tokens a run takes one after another (unrolled)
STEPS = 16
#: runs of a block that take their steps side by side
LANES = 16
#: a fresh layer's step is log-uniform between these (Mamba's defaults)
DT_MIN, DT_MAX = 1e-3, 1e-1


def _count_trace(path: str) -> None:
    """``ssm_scan_traces_total{path=}``: which path a scan took, once a
    trace. ``"xla"`` is the chunked scan below, which counts itself
    (whoever calls it); ``"kernel"`` the Pallas kernels, counted at the
    seam: exactly one of the two a scan."""
    from deeplearning4j_tpu.profiling.metrics import get_registry
    get_registry().labeled_counter(
        "ssm_scan_traces_total",
        "selective-scan traces by the path they took (per trace)",
    ).labels(path=path).inc()


def selective_scan(x: Array, delta: Array, a: Array, b: Array, c: Array, *,
                   layer=None) -> Array:
    """``y_t[c] = sum_n h_t[c, n] C_t[n]`` of the module's recurrence from
    ``h_0 = 0``, by the Pallas kernels where their gate allows and by
    :func:`selective_scan_chunked` (whose arguments these are) otherwise; a
    refusal counts under ``layer``'s name, where the caller is a layer."""
    from deeplearning4j_tpu.ops import pallas_selective_scan as pss
    from deeplearning4j_tpu.ops.pallas_attention import attention_mode
    from deeplearning4j_tpu.ops.pallas_kernels import count_gate_fallback

    mode = attention_mode()
    if mode != "off":
        if pss.selective_scan_ok(x.shape[1], x.shape[2], a.shape[0],
                                 delta.dtype, x.dtype):
            _count_trace("kernel")
            # the kernels' own rule: inputs and block starts kept
            return backward_after_cotangent(functools.partial(
                pss.selective_scan, interpret=mode == "interpret"))(
                    x, delta, a, b, c)
        if layer is not None:
            count_gate_fallback(layer, "selective_scan")
    return selective_scan_chunked(x, delta, a, b, c)


def selective_scan_chunked(x: Array, delta: Array, a: Array, b: Array,
                           c: Array, *, steps: int = STEPS,
                           lanes: int = LANES) -> Array:
    """``y_t[c] = sum_n h_t[c, n] C_t[n]`` of the module's recurrence from
    ``h_0 = 0``. ``x [B, T, d_in]`` (any float dtype), ``delta [B, T,
    d_in]``, ``a [N, d_in]`` (negative), ``b, c [B, T, N]``, all three in
    the accumulation dtype, which is the result's. ``T`` need not be a
    multiple of the block: the padding has ``Delta = 0``, which neither
    decays nor writes."""
    _count_trace("xla")
    B, T, D = x.shape
    N = a.shape[0]
    acc = delta.dtype
    n_blocks = -(-T // (steps * lanes))
    pad = n_blocks * steps * lanes - T

    def blocks(z):      # [B, T, F] -> [n_blocks, steps, B, lanes, F]
        z = jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
        z = z.reshape(B, n_blocks, lanes, steps, z.shape[-1])
        return z.transpose(1, 3, 0, 2, 4)

    def chain(left, right):     # two stretches of the sequence as one
        (decay_l, end_l), (decay_r, end_r) = left, right
        return decay_r * decay_l, decay_r * end_l + end_r

    @jax.checkpoint
    def block(h_in, xs):        # h_in [B, N, D]
        x_b, delta_b, b_b, c_b = xs
        write = delta_b * x_b.astype(acc)
        h = jnp.zeros((B, lanes, N, D), acc)
        y = []
        # unrolled on purpose: a loop within the loop would hide the scan's
        # time from the trace's reader, which sums the step's `while`s
        for j in range(steps):  # jaxlint: disable=JL004 -- 16, static
            h = (jnp.exp(delta_b[j][:, :, None, :] * a) * h
                 + write[j][:, :, None, :] * b_b[j][..., None])
            y.append(jnp.sum(h * c_b[j][..., None], axis=2))
        seen = jnp.cumsum(delta_b, axis=0)              # [steps, B, lanes, D]
        decay, end = lax.associative_scan(
            chain, (jnp.exp(seen[-1][:, :, None, :] * a), h), axis=1)
        after = decay * h_in[:, None] + end             # [B, lanes, N, D]
        start = jnp.concatenate([h_in[:, None], after[:, :-1]], axis=1)
        carried = jnp.sum(
            c_b[..., None] * jnp.exp(seen[:, :, :, None, :] * a) * start,
            axis=3)
        return after[:, -1], jnp.stack(y) + carried

    h0 = jnp.zeros((B, N, D), acc)
    _, y = lax.scan(block, h0, (blocks(x), blocks(delta), blocks(b),
                                blocks(c)))
    # [n_blocks, steps, B, lanes, D] -> [B, T, D]
    y = y.transpose(2, 0, 3, 1, 4).reshape(B, n_blocks * lanes * steps, D)
    return y[:, :T]


def selective_scan_recurrent(x, delta, a, b, c):
    """The same ``y``, one token after another: what the chunked form is
    tested against. Keeps every state for its backward pass, so for small
    shapes only."""
    def token(h, xs):
        x_t, delta_t, b_t, c_t = xs
        h = (jnp.exp(delta_t[:, None, :] * a) * h
             + (delta_t * x_t)[:, None, :] * b_t[..., None])
        return h, jnp.sum(h * c_t[..., None], axis=1)

    time_first = lambda z: jnp.moveaxis(z, 1, 0)
    h0 = jnp.zeros((x.shape[0], a.shape[0], x.shape[2]), delta.dtype)
    _, y = lax.scan(token, h0, tuple(map(
        time_first, (x.astype(delta.dtype), delta, b, c))))
    return jnp.moveaxis(y, 0, 1)


@register_layer
@dataclass
class SelectiveScanLayer(BaseLayerConf):
    """A Mamba mixer up to its scan, ``[B, T, F] -> [B, T, d_in]``:

    ``x = SiLU(conv(W_in u) + b_conv)`` (a causal depthwise convolution
    over time, one filter a channel); ``[r, B, C] = W_x x`` (``dt_rank``,
    ``N`` and ``N`` wide); ``Delta = softplus(W_dt r + b_dt)``; ``A =
    -exp(A_log)``; the recurrence of the module's docstring; the output is
    ``s``, ungated. The mixer's gate ``SiLU(W_z u)`` and its output
    projection are the node after it (a ``GatedMemoryUnitLayer`` of ``(u,
    s)``), so that ``s`` is a node's own output: the layers above may read
    it as their memory through units of their own, and under ``remat`` none
    of them rebuilds the scan.

    Params: ``W_in [F, d_in]``, ``conv_w [K, d_in]``, ``conv_b [d_in]``,
    ``W_x [d_in, dt_rank + 2 N]``, ``W_dt [dt_rank, d_in]``, ``b_dt
    [d_in]``, ``A_log [N, d_in]`` (a row a state, so that the channels lie
    along the lanes), ``D [d_in]``."""
    n_inner: int = 0            # d_in; default 2 * F
    n_state: int = 16
    dt_rank: int = 0            # default ceil(F / 16)
    conv_kernel: int = 4

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(
                f"SelectiveScanLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size
        if not self.n_inner:
            self.n_inner = 2 * self.n_in
        if not self.dt_rank:
            self.dt_rank = -(-self.n_in // 16)

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_inner, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W_in", "conv_w", "conv_b", "W_x", "W_dt", "b_dt", "A_log",
                "D"]

    def regularization(self):
        reg = super().regularization()
        for p in ("conv_w", "conv_b", "b_dt", "A_log", "D"):
            reg[p] = (self.l1_bias or 0.0, self.l2_bias or 0.0)
        return reg

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, D, N, R = self.n_in, self.n_inner, self.n_state, self.dt_rank
        k_in, k_conv, k_x, k_dt, k_step = jax.random.split(rng, 5)
        bound = self.conv_kernel ** -0.5      # one input channel a filter
        # a step log-uniform in [DT_MIN, DT_MAX], stored through the inverse
        # of softplus; state n decays at rate n + 1 (Mamba's defaults)
        step = jnp.exp(jax.random.uniform(
            k_step, (D,), jnp.float32, jnp.log(DT_MIN), jnp.log(DT_MAX)))
        rates = jnp.arange(1, N + 1, dtype=jnp.float32)[:, None]
        return {
            "W_in": self._init_w(k_in, (F, D), F, D, dtype),
            "conv_w": jax.random.uniform(
                k_conv, (self.conv_kernel, D), dtype, -bound, bound),
            "conv_b": jnp.zeros((D,), dtype),
            "W_x": self._init_w(k_x, (D, R + 2 * N), D, R + 2 * N, dtype),
            "W_dt": self._init_w(k_dt, (R, D), R, D, dtype),
            "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "A_log": jnp.broadcast_to(jnp.log(rates), (N, D)).astype(dtype),
            "D": jnp.ones((D,), dtype),
        }

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        if mask is not None:
            x = x * mask[..., None]
        N, R = self.n_state, self.dt_rank
        acc = jnp.promote_types(x.dtype, jnp.float32)
        wide = lambda name: params[name].astype(acc)
        with jax.named_scope("ssm:in_conv"):
            inner = jax.nn.silu(causal_depthwise_conv(
                (x @ params["W_in"]).astype(acc), wide("conv_w"))
                + wide("conv_b"))
            inner_c = inner.astype(x.dtype)
        with jax.named_scope("ssm:dt_bc"):
            proj = inner_c @ params["W_x"]
            delta = jax.nn.softplus(
                (proj[..., :R] @ params["W_dt"]).astype(acc) + wide("b_dt"))
            if mask is not None:    # a masked step decays and writes nothing
                delta = delta * mask[..., None]
            b_t = proj[..., R:R + N].astype(acc)
            c_t = proj[..., R + N:].astype(acc)
        with jax.named_scope("ssm:scan"):
            y = selective_scan(inner_c, delta, -jnp.exp(wide("A_log")),
                               b_t, c_t, layer=self)
            out = (y + wide("D") * inner).astype(x.dtype)
        return out, state


@register_layer
@dataclass
class GatedMemoryUnitLayer(BaseLayerConf):
    """``y = W_out (m * SiLU(W_in u))`` of two inputs, the stream's ``u [B,
    T, F]`` and a memory ``m [B, T, d_m]`` that another node made: the
    scan output of one state-space layer, gated anew by every layer that
    reads it, in place of a mixer of that layer's own. The second half of
    a Mamba mixer is the same function of its own scan's output (``W_in``
    its ``W_z``), and the same class: a profile tells the two by the node's
    name. No bias. Params: ``W_in [F, d_m]``, ``W_out [d_m, F]``."""
    n_memory: int = 0           # d_m; filled from the second input

    N_INPUTS = 2

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(f"{type(self).__name__} expects RNN input, "
                             f"got {in_type}")
        self.n_in = in_type.size

    def set_side_inputs(self, in_types: Sequence[InputType]) -> None:
        (memory,) = in_types
        if memory.kind != "rnn":
            raise ValueError(f"{type(self).__name__}({self.name!r}): the "
                             f"memory must be a sequence, got {memory}")
        self.n_memory = memory.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W_in", "W_out"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, M = self.n_in, self.n_memory
        k_in, k_out = jax.random.split(rng)
        return {"W_in": self._init_w(k_in, (F, M), F, M, dtype),
                "W_out": self._init_w(k_out, (M, F), M, F, dtype)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        u, memory = x
        u = self._dropout_input(u, train, rng)
        with jax.named_scope("gmu:gate"):
            out = (memory * jax.nn.silu(u @ params["W_in"])
                   ) @ params["W_out"]
        if mask is not None:
            out = out * mask[..., None]
        return out, state
