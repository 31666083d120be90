"""Recurrent layers: LSTM / GravesLSTM (peepholes) / bidirectional / RNN out.

The reference implements LSTM with a hand-written per-timestep Java loop and
cached gate activations (ref: nn/layers/recurrent/LSTMHelpers.java:57-420 —
forward loop at :161, backward loop at :333, FwdPassReturn caching). Here the
time loop is ``jax.lax.scan`` — XLA compiles it into a single fused while-op,
and autodiff through scan replaces the hand-written backward loop; the
activation caching the reference does by hand is what jax does automatically
(and can be tuned with ``jax.checkpoint``).

Param layout (our ordering contract, cf. nn/params/GravesLSTMParamInitializer
W/RW/b): W [n_in, 4H], RW [n_out, 4H], b [4H]; Graves peepholes pW [3H]
(input/forget/output gates see c). **Gate block order is (i, f, g, o)** —
documented here because checkpoints and Keras import depend on it.

Masking: per-timestep mask [B, T]; masked steps pass previous state through
unchanged and output zeros (matches the reference's mask-propagation through
feedForwardMaskArray + zeroed epsilons).

Stateful streaming inference (``rnnTimeStep``,
ref: MultiLayerNetwork.java:2234) is supported via ``step()`` — the container
stores the carried (h, c) per layer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    Array, BaseLayerConf, Params, register_layer,
)
from deeplearning4j_tpu.nn.layers.core import OutputLayer
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.losses import (
    get_loss, is_class_ids, promote_loss_dtype,
)


def _lstm_cell(params: Params, x_t: Array, h: Array, c: Array,
               gate_act, out_act, forget_bias: float,
               peephole: bool) -> Tuple[Array, Array]:
    """One LSTM step. Gate order (i, f, g, o)."""
    z = x_t @ params["W"] + h @ params["RW"] + params["b"]
    H = h.shape[-1]
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    if peephole:
        pi, pf, po = jnp.split(params["pW"], 3, axis=-1)
        zi = zi + c * pi
        zf = zf + c * pf
    i = gate_act(zi)
    f = gate_act(zf + forget_bias)
    g = out_act(zg)
    c_new = f * c + i * g
    if peephole:
        zo = zo + c_new * po
    o = gate_act(zo)
    h_new = o * out_act(c_new)
    return h_new, c_new


def _carry_like(carry, x):
    """Make the initial carry inherit ``x``'s varying mesh axes. Inside
    ``shard_map`` (the pipeline trainers) a plain-zeros init is unvaried
    while the scan body's outputs derive from the sharded batch, and
    ``lax.scan`` rejects the type mismatch; adding a zero-weighted slice
    of x is a numerical no-op that fixes the types, and folds away
    entirely outside shard_map."""
    z = (x[:, 0, :1] * 0)
    return jax.tree.map(lambda c: c + z.astype(c.dtype)
                        if getattr(c, "ndim", 0) == 2
                        and c.shape[0] == x.shape[0] else c, carry)


@register_layer
@dataclass
class LSTM(BaseLayerConf):
    """Standard LSTM (no peepholes)."""
    n_out: int = 0
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    _peephole = False
    # Containers thread (h, c) carries through layers with this set — the
    # tBPTT / rnnTimeStep dispatch flag. Bidirectional layers cannot stream
    # (the backward pass needs the full sequence) so they leave it False.
    supports_carry = True

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(f"{type(self).__name__} expects RNN input, got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W", "RW", "b"] + (["pW"] if self._peephole else [])

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        H = self.n_out
        k1, k2, _ = jax.random.split(rng, 3)
        fan_in, fan_out = self.n_in + H, 4 * H
        p = {
            "W": self._init_w(k1, (self.n_in, 4 * H), fan_in, fan_out, dtype),
            "RW": self._init_w(k2, (H, 4 * H), fan_in, fan_out, dtype),
            "b": jnp.zeros((4 * H,), dtype),
        }
        if self._peephole:
            p["pW"] = jnp.zeros((3 * H,), dtype)
        return p

    def initial_carry(self, batch: int, dtype=jnp.float32):
        H = self.n_out
        return (jnp.zeros((batch, H), dtype), jnp.zeros((batch, H), dtype))

    def step(self, params: Params, x_t: Array, carry):
        """Single timestep for stateful inference (rnnTimeStep)."""
        h, c = carry
        gate_act = get_activation(self.gate_activation)
        out_act = get_activation(self.activation or "tanh")
        h2, c2 = _lstm_cell(params, x_t, h, c, gate_act, out_act,
                            self.forget_gate_bias_init, self._peephole)
        return h2, (h2, c2)

    def _fused_kernel_ok(self, mask, batch=None) -> bool:
        """Helper-discovery decision (the reference's cuDNN-helper seam,
        ref: ConvolutionLayer.java:55-77): use the Pallas fused kernel when
        the configuration matches what the kernel hardcodes.

        Non-tile-aligned H/B do not fall back to scan: ``fused_lstm``
        pads to the (8, 128) tile grid and slices outputs (exact — see its
        docstring). What remains is the VMEM-residency bound
        (``lstm_vmem_bytes``, which counts the double-buffered blocks on
        padded sizes); a shape past it trains on the scan path and is
        counted in ``pallas_gate_fallbacks_total``."""
        from deeplearning4j_tpu.ops import pallas_kernels
        mode = pallas_kernels.lstm_mode()
        if (mode == "off" or mask is not None
                or self.gate_activation != "sigmoid"
                or (self.activation or "tanh") != "tanh"):
            return False
        need = pallas_kernels.lstm_vmem_bytes(batch or 8, self.n_out or 128)
        if mode == "compiled" and need > pallas_kernels.VMEM_GATE_BYTES:
            pallas_kernels.count_gate_fallback(self, "fused_lstm")
            return False
        return True

    def scan(self, params: Params, x: Array, carry, mask: Optional[Array],
             reverse: bool = False):
        """Run the full sequence [B, T, F] -> ([B, T, H], final_carry)."""
        carry = _carry_like(carry, x)
        if self._fused_kernel_ok(mask, batch=x.shape[0]):
            from deeplearning4j_tpu.ops.pallas_kernels import (
                fused_lstm, lstm_mode)
            h0, c0 = carry
            xin = jnp.flip(x, axis=1) if reverse else x
            ys, hT, cT = fused_lstm(
                xin, params["W"], params["RW"], params["b"],
                params.get("pW") if self._peephole else None, h0, c0,
                forget_bias=self.forget_gate_bias_init,
                interpret=lstm_mode() == "interpret")
            if reverse:
                ys = jnp.flip(ys, axis=1)
            return ys, (hT, cT)
        gate_act = get_activation(self.gate_activation)
        out_act = get_activation(self.activation or "tanh")

        def body(carry, inp):
            h, c = carry
            if mask is None:
                x_t = inp
                h2, c2 = _lstm_cell(params, x_t, h, c, gate_act, out_act,
                                    self.forget_gate_bias_init, self._peephole)
                return (h2, c2), h2
            x_t, m_t = inp
            h2, c2 = _lstm_cell(params, x_t, h, c, gate_act, out_act,
                                self.forget_gate_bias_init, self._peephole)
            m = m_t[:, None]
            h2 = m * h2 + (1 - m) * h
            c2 = m * c2 + (1 - m) * c
            return (h2, c2), m * h2

        xs = jnp.swapaxes(x, 0, 1)  # [T, B, F] time-major for scan
        inputs = xs if mask is None else (xs, jnp.swapaxes(mask, 0, 1))
        final, ys = jax.lax.scan(body, carry, inputs, reverse=reverse)
        return jnp.swapaxes(ys, 0, 1), final

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        carry = self.initial_carry(x.shape[0], x.dtype)
        ys, _ = self.scan(params, x, carry, mask)
        return ys, state


@register_layer
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections, as in Graves (2013)
    (ref: nn/layers/recurrent/GravesLSTM.java + LSTMHelpers.java)."""
    _peephole = True


@register_layer
@dataclass
class GravesBidirectionalLSTM(LSTM):
    """Bidirectional Graves LSTM; forward and backward outputs are **added**
    (ref: nn/layers/recurrent/GravesBidirectionalLSTM.java:206
    `fwdOutput.addi(backOutput)`)."""
    _peephole = True
    supports_carry = False  # backward direction needs the full sequence

    def param_order(self) -> List[str]:
        return ["W", "RW", "b", "pW", "W_bwd", "RW_bwd", "b_bwd", "pW_bwd"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        k_f, k_b = jax.random.split(rng)
        fwd = super().init_params(k_f, dtype)
        bwd = super().init_params(k_b, dtype)
        fwd.update({f"{k}_bwd": v for k, v in bwd.items()})
        return fwd

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        carry = self.initial_carry(x.shape[0], x.dtype)
        fwd_p = {k: params[k] for k in ("W", "RW", "b", "pW")}
        bwd_p = {k: params[f"{k}_bwd"] for k in ("W", "RW", "b", "pW")}
        ys_f, _ = self.scan(fwd_p, x, carry, mask)
        ys_b, _ = self.scan(bwd_p, x, carry, mask, reverse=True)
        return ys_f + ys_b, state


@register_layer
@dataclass
class SimpleRnn(BaseLayerConf):
    """Vanilla RNN: h_t = act(x_t W + h_{t-1} RW + b)."""
    n_out: int = 0

    supports_carry = True

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(f"SimpleRnn expects RNN input, got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W", "RW", "b"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        H = self.n_out
        k1, k2 = jax.random.split(rng)
        return {
            "W": self._init_w(k1, (self.n_in, H), self.n_in, H, dtype),
            "RW": self._init_w(k2, (H, H), H, H, dtype),
            "b": self._init_b((H,), dtype),
        }

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.n_out), dtype)

    def step(self, params, x_t, carry):
        act = get_activation(self.activation or "tanh")
        h = act(x_t @ params["W"] + carry @ params["RW"] + params["b"])
        return h, h

    def scan(self, params, x, carry, mask: Optional[Array] = None,
             reverse: bool = False):
        act = get_activation(self.activation or "tanh")
        carry = _carry_like(carry, x)

        def body(h, inp):
            if mask is None:
                x_t = inp
                h2 = act(x_t @ params["W"] + h @ params["RW"] + params["b"])
                return h2, h2
            x_t, m_t = inp
            h2 = act(x_t @ params["W"] + h @ params["RW"] + params["b"])
            m = m_t[:, None]
            h2 = m * h2 + (1 - m) * h
            return h2, m * h2

        xs = jnp.swapaxes(x, 0, 1)
        inputs = xs if mask is None else (xs, jnp.swapaxes(mask, 0, 1))
        final, ys = jax.lax.scan(body, carry, inputs, reverse=reverse)
        return jnp.swapaxes(ys, 0, 1), final

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        ys, _ = self.scan(params, x, self.initial_carry(x.shape[0], x.dtype), mask)
        return ys, state


@register_layer
@dataclass
class GRU(BaseLayerConf):
    """Gated recurrent unit, Keras-compatible gate layout (z, r, h blocks
    in ``W``/``RW``/``b``).

    ``reset_after=True`` (Keras >= 2.1 default, what CuDNN implements)
    applies the reset gate AFTER the recurrent matmul and keeps a second
    recurrent bias ``b2``; ``False`` is the classic formulation. The
    reference imports Keras GRUs through KerasLayer.java's recurrent
    mapping (ref: deeplearning4j-modelimport/.../KerasLayer.java).
    """
    n_out: int = 0
    gate_activation: str = "sigmoid"
    reset_after: bool = True

    supports_carry = True

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(f"GRU expects RNN input, got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W", "RW", "b"] + (["b2"] if self.reset_after else [])

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        H = self.n_out
        k1, k2 = jax.random.split(rng)
        fan_in, fan_out = self.n_in + H, 3 * H
        p = {
            "W": self._init_w(k1, (self.n_in, 3 * H), fan_in, fan_out, dtype),
            "RW": self._init_w(k2, (H, 3 * H), fan_in, fan_out, dtype),
            "b": jnp.zeros((3 * H,), dtype),
        }
        if self.reset_after:
            p["b2"] = jnp.zeros((3 * H,), dtype)
        return p

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.n_out), dtype)

    def _cell(self, params, x_t, h):
        H = self.n_out
        gate = get_activation(self.gate_activation)
        act = get_activation(self.activation or "tanh")
        xz = x_t @ params["W"] + params["b"]
        if self.reset_after:
            hz = h @ params["RW"] + params["b2"]
            z = gate(xz[:, :H] + hz[:, :H])
            r = gate(xz[:, H:2 * H] + hz[:, H:2 * H])
            hh = act(xz[:, 2 * H:] + r * hz[:, 2 * H:])
        else:
            hz = h @ params["RW"][:, :2 * H]
            z = gate(xz[:, :H] + hz[:, :H])
            r = gate(xz[:, H:2 * H] + hz[:, H:])
            hh = act(xz[:, 2 * H:] + (r * h) @ params["RW"][:, 2 * H:])
        return z * h + (1.0 - z) * hh  # Keras update convention

    def step(self, params, x_t, carry):
        h = self._cell(params, x_t, carry)
        return h, h

    def scan(self, params, x, carry, mask: Optional[Array] = None,
             reverse: bool = False):
        carry = _carry_like(carry, x)

        def body(h, inp):
            if mask is None:
                h2 = self._cell(params, inp, h)
                return h2, h2
            x_t, m_t = inp
            h2 = self._cell(params, x_t, h)
            m = m_t[:, None]
            h2 = m * h2 + (1 - m) * h
            return h2, m * h2

        xs = jnp.swapaxes(x, 0, 1)
        inputs = xs if mask is None else (xs, jnp.swapaxes(mask, 0, 1))
        final, ys = jax.lax.scan(body, carry, inputs, reverse=reverse)
        return jnp.swapaxes(ys, 0, 1), final

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        ys, _ = self.scan(params, x,
                          self.initial_carry(x.shape[0], x.dtype), mask)
        return ys, state


@register_layer
@dataclass
class RnnOutputLayer(BaseLayerConf):
    """Per-timestep dense + loss over [B, T, F]
    (ref: nn/layers/recurrent/RnnOutputLayer.java — 2D reshape + OutputLayer;
    here just a batched matmul over the time axis)."""
    n_out: int = 0
    loss: str = "mcxent"
    has_bias: bool = True       # False: an untied language-model head

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(f"RnnOutputLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W", "b"] if self.has_bias else ["W"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        k_w, _ = jax.random.split(rng)
        p = {"W": self._init_w(k_w, (self.n_in, self.n_out), self.n_in,
                               self.n_out, dtype)}
        if self.has_bias:
            p["b"] = self._init_b((self.n_out,), dtype)
        return p

    def _logits(self, params, x):
        out = x @ params["W"]
        return out + params["b"] if self.has_bias else out

    def apply(self, params, x, *, state, train, rng, mask=None):
        out = get_activation(self.activation)(self._logits(params, x))
        if mask is not None:
            out = out * mask[..., None]
        return out, state

    def compute_loss(self, params, x, labels, *, mask=None, average: bool = True):
        """Loss summed over timesteps; score = total / minibatch size, with
        masked timesteps excluded from the total (matches the reference's
        score semantics for time series). ``labels``: one-hot rows
        ``[B, T, F]`` or class ids ``[B, T]`` (``ops.losses.is_class_ids``)."""
        preout, labels = promote_loss_dtype(self._logits(params, x), labels)
        per = flat_time_loss(self.loss, labels, preout, self.activation, mask)
        return jnp.mean(per.sum(axis=1)) if average else per


def flat_time_loss(loss: str, labels, preout, activation, mask):
    """The per-timestep loss ``[B, T]`` of logits ``[B, T, F]`` by the flat
    ``[B*T, F]`` route, for one-hot rows and class ids alike."""
    B, T, F = preout.shape
    flat_lab = (labels.reshape(B * T) if is_class_ids(labels)
                else labels.reshape(B * T, F))
    flat_mask = mask.reshape(B * T) if mask is not None else None
    per = get_loss(loss)(flat_lab, preout.reshape(B * T, F), activation,
                         flat_mask)
    return per.reshape(B, T)


@register_layer
@dataclass
class LastTimeStepLayer(BaseLayerConf):
    """[B, T, F] -> [B, F]: the last time step, or with a mask the last
    UNMASKED step per example (ref: the reference's graph-side
    nn/conf/graph/rnn/LastTimeStepVertex.java; later DL4J added the
    equivalent feed-forward wrapper layer nn/conf/layers/recurrent/
    LastTimeStep for Keras return_sequences=False import parity)."""

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(f"LastTimeStepLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.feed_forward(in_type.size)

    def param_order(self) -> List[str]:
        return []

    def propagate_mask(self, mask):
        return None  # output is [B, F]; the time mask is consumed here

    def apply(self, params, x, *, state, train, rng, mask=None):
        if mask is None:
            return x[:, -1, :], state
        # index of the LAST step where mask == 1 (works for pre- and
        # post-padding: scan the reversed mask for its first 1)
        T = mask.shape[1]
        idx = T - 1 - jnp.argmax(jnp.flip(mask, axis=1) > 0, axis=1)
        out = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :]
        return out, state
