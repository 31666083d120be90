"""The gated feed-forward of today's decoder blocks, as one layer."""

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


@register_layer
@dataclass
class GatedFeedForwardLayer(BaseLayerConf):
    """``W_down(act(W_gate x) * W_up x)`` over the feature axis of
    ``[B, T, F]`` or ``[B, F]``, no bias (Shazeer, arXiv:2002.05202;
    ``activation="silu"`` is SwiGLU). One node and not three, so that under
    ``remat`` the backward keeps the layer's input (63 MB at 8,192 tokens
    of 3,840 in bfloat16) and rebuilds the two intermediates of width
    ``n_hidden`` (180 MB each at 11,008) instead of keeping them."""
    n_hidden: int = 0           # default 4 * F

    def set_n_in(self, in_type: InputType) -> None:
        self.n_in = (in_type.size if in_type.kind == "rnn"
                     else in_type.flat_size())
        if not self.n_hidden:
            self.n_hidden = 4 * self.n_in

    def infer_output_type(self, in_type: InputType) -> InputType:
        return in_type

    def param_order(self) -> List[str]:
        return ["W_gate", "W_up", "W_down"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, M = self.n_in, self.n_hidden
        k_gate, k_up, k_down = jax.random.split(rng, 3)
        return {"W_gate": self._init_w(k_gate, (F, M), F, M, dtype),
                "W_up": self._init_w(k_up, (F, M), F, M, dtype),
                "W_down": self._init_w(k_down, (M, F), M, F, dtype)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        act = get_activation(self.activation or "silu")
        out = (act(x @ params["W_gate"]) * (x @ params["W_up"])
               ) @ params["W_down"]
        if mask is not None:
            out = out * mask[..., None]
        return out, state
