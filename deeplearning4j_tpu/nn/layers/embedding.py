"""Transformer-LM embedding layers: token+position embedding and the
weight-tied LM head.

The reference snapshot predates transformer LMs entirely (SURVEY §5.7 —
"the RNN era"); these two layers close the gap between the existing
attention/normalization vocabulary and a GPT-style decoder:

- :class:`PositionalEmbeddingLayer` — token embedding (one-hot or dense
  [B, T, V] features times ``W``) plus LEARNED positions ``P[:T]``, the
  GPT-2 input block. Keeping the input rnn-typed end to end means the
  sp mesh axis can shard T (ring attention) and the pipeline trainers
  get static boundary shapes.
- :class:`TiedRnnOutputLayer` — a per-timestep softmax/mcxent head whose
  projection is the TRANSPOSE of another layer's token-embedding matrix
  (``tied_to`` names the embedding node). The layer owns only its bias;
  the container injects the tied matrix under ``params["W_tok"]`` at
  apply/loss time (see ``ComputationGraph._layer_params``), so autodiff
  sends the head's gradient into the embedding — true weight tying, one
  V x D matrix for both ends of the model.

Weight tying is resolved by the CONTAINER (graph node name -> params
entry), which is why ``tied_to`` is a node name: the head itself stays a
pure function of the params dict it is handed.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    BaseLayerConf, Params, register_layer,
)
from deeplearning4j_tpu.nn.layers.recurrent import (
    RnnOutputLayer, flat_time_loss,
)
from deeplearning4j_tpu.ops.activations import get_activation

#: GPT-2's positional-embedding init scale
POSITION_INIT_SCALE = 0.02


@register_layer
@dataclass
class PositionalEmbeddingLayer(BaseLayerConf):
    """[B, T, V] -> [B, T, D]: ``x @ W + b + P[:T]`` — token embedding as
    a (one-hot) matmul, exactly like :class:`EmbeddingLayer`'s
    one-hot-times-W contract but time-distributed, plus learned absolute
    positions. ``max_timesteps`` (the P table's length) is filled from
    the input type at build time; shorter tBPTT windows index a prefix."""
    n_out: int = 0
    max_timesteps: int = 0

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(
                f"PositionalEmbeddingLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size
        if not self.max_timesteps:
            if in_type.timesteps is None:
                raise ValueError(
                    "PositionalEmbeddingLayer needs fixed timesteps (set "
                    "max_timesteps= or declare them in the InputType) — "
                    "the learned position table must have a static length")
            self.max_timesteps = int(in_type.timesteps)

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W", "P", "b"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        k_w, k_p = jax.random.split(rng)
        return {
            "W": self._init_w(k_w, (self.n_in, self.n_out), self.n_in,
                              self.n_out, dtype),
            "P": (POSITION_INIT_SCALE
                  * jax.random.normal(k_p, (self.max_timesteps, self.n_out))
                  ).astype(dtype),
            "b": self._init_b((self.n_out,), dtype),
        }

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        T = x.shape[1]
        if T > self.max_timesteps:
            raise ValueError(
                f"sequence length {T} exceeds the learned position table "
                f"({self.max_timesteps}); rebuild with max_timesteps>={T}")
        out = x @ params["W"] + params["b"] + params["P"][None, :T, :]
        out = get_activation(self.activation or "identity")(out)
        if mask is not None:
            out = out * mask[..., None]
        return out, state

    def decode_step(self, params, x, positions):
        """Incremental-decode embedding of ONE token per row: ``x``
        [B, 1, V] one-hot, ``positions`` [B] the per-row sequence
        position — each row indexes its own learned position, so rows
        at different depths of their generations share one compiled
        step. Returns [B, 1, D]."""
        out = x @ params["W"] + params["b"] \
            + params["P"][positions][:, None, :]
        return get_activation(self.activation or "identity")(out)


@register_layer
@dataclass
class TiedRnnOutputLayer(RnnOutputLayer):
    """Per-timestep loss head projecting through the TRANSPOSED token
    embedding of the layer/node named ``tied_to`` (weight tying, GPT-2
    style: no output bias — faithful to the architecture AND
    load-bearing for parity: a head-bias gradient is a pure reduction
    over the (data, sp)-sharded batch, the exact leaf pattern GSPMD
    mis-shards under zero1/zero2 on an sp mesh — see the sp_mesh note
    in ``parallel/trainer.py`` and graphcheck GC017). Owns NO params;
    ``params["W_tok"]`` ([V, D]) is injected by the container from the
    tied node's ``W`` — never serialized, never counted twice."""
    tied_to: Optional[str] = None

    def param_order(self) -> List[str]:
        return []

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        return {}

    def _logits(self, params, x):
        if "W_tok" not in params:
            raise ValueError(
                f"TiedRnnOutputLayer({self.name!r}): no tied weights were "
                f"injected — tied_to={self.tied_to!r} must name a layer "
                "node with a 'W' param, and the container must thread it "
                "(ComputationGraph does; MultiLayerNetwork does not "
                "support tied heads)")
        return x @ params["W_tok"].T

    def compute_loss(self, params, x, labels, *, mask=None,
                     average: bool = True):
        """Same loss semantics as RnnOutputLayer (per-timestep loss summed
        over time, averaged over batch) but WITHOUT the ``[B, T, F] ->
        [B*T, F]`` flatten: under a dp x sp mesh that reshape folds two
        SHARDED axes into one, and with a zero1/zero2 sharding constraint
        downstream GSPMD miscompiles it — the bias gradient comes back
        multiplied by the sp axis size (measured on CPU dp=2 x sp=2,
        jax 0.4.37: exactly 2x). The loss ops reduce every non-batch axis
        natively, so the rank-3 path needs no reshape at all — which is
        also one less all-gather of the logits. ``average=False`` (the
        eval path, never sharded) keeps the per-timestep matrix via the
        flat route. Class ids ``[B, T]`` go either way as one-hot rows do."""
        from deeplearning4j_tpu.ops.losses import get_loss, promote_loss_dtype
        preout = self._logits(params, x)
        preout, labels = promote_loss_dtype(preout, labels)
        if not average:
            return flat_time_loss(self.loss, labels, preout, self.activation,
                                  mask)
        per_ex = get_loss(self.loss)(labels, preout, self.activation, mask)
        return jnp.mean(per_ex)


@register_layer
@dataclass
class TokenEmbeddingLayer(BaseLayerConf):
    """``[B, T]`` int32 token ids -> ``[B, T, D]``: the rows of ``W [V, D]``
    gathered by id, no positions and no bias. 64 KB a sequence of 8,192
    cross the host link where one-hot rows would be 411 MB at a vocabulary
    of 12,544, and the lookup is a gather, not a ``[T, V] x [V, D]``
    product; the gradient scatters into the rows that were read. Declared
    by ``InputType.token_ids(vocab_size, timesteps)``."""
    n_out: int = 0

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "ids":
            raise ValueError(
                f"TokenEmbeddingLayer expects token ids "
                f"(InputType.token_ids), got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["W"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        return {"W": self._init_w(rng, (self.n_in, self.n_out), self.n_in,
                                  self.n_out, dtype)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        if not jnp.issubdtype(x.dtype, jnp.integer):
            raise ValueError(
                f"TokenEmbeddingLayer({self.name!r}) takes integer ids "
                f"[B, T], got {x.dtype} {x.shape}")
        out = jnp.take(params["W"], x, axis=0)
        if mask is not None:
            out = out * mask[..., None].astype(out.dtype)
        return out, state
