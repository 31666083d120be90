"""Base layer contract + registry.

The reference's contracts live in nn/api/Layer.java:37-310 (activate /
backpropGradient / feedForwardMaskArray) and nn/conf/layers/Layer.java
(hyperparameter inheritance from the global builder). Here a layer is a
dataclass with:

- ``infer_output_type(in_type)``  — shape inference (ref: InputType system)
- ``init_params(rng, dtype)``     — returns a dict of named arrays; the
  ordering contract the reference keeps in nn/params/*ParamInitializer is
  preserved by ``param_order()`` for flat-buffer checkpoints.
- ``apply(params, x, state, train, rng, mask)`` — pure forward; autodiff
  replaces the reference's hand-written backpropGradient.
- ``init_state()``                — mutable-in-spirit state (BN running stats),
  threaded functionally through the container.

Inherited hyperparameters (activation, weight_init, l1/l2, dropout, ...)
are materialized onto each layer dataclass at build time by
``NeuralNetConfiguration`` (ref: nn/conf/NeuralNetConfiguration.Builder
global-then-per-layer override semantics).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.weights import Distribution, init_weight

Array = jax.Array
Params = Dict[str, Array]
State = Dict[str, Array]

LAYER_REGISTRY: Dict[str, Type["BaseLayerConf"]] = {}

# Sentinel meaning "inherit from the global NeuralNetConfiguration builder".
INHERIT = None


def register_layer(cls):
    """Class decorator: registers the layer under its type tag for JSON serde."""
    LAYER_REGISTRY[cls.type_tag()] = cls
    return cls


@dataclass
class BaseLayerConf:
    """Common hyperparameters every layer inherits from the global builder
    unless overridden per-layer (ref: nn/conf/layers/Layer.java fields +
    NeuralNetConfiguration.Builder.layer(...) inheritance)."""

    name: Optional[str] = None
    activation: Optional[str] = None          # INHERIT -> global
    weight_init: Optional[str] = None
    dist: Optional[Distribution] = None
    bias_init: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    dropout: Optional[float] = None           # DL4J semantics: *retain* prob
    learning_rate: Optional[float] = None     # per-layer LR multiplier source
    updater: Optional[str] = None             # per-layer updater override
    # frozen layers take no updates (ref: nn/layers/FrozenLayer.java wrapper;
    # here a flag consumed by the train step's update mask)
    frozen: bool = False
    # filled by the builder:
    n_in: Optional[int] = None

    #: inputs a graph node of this layer takes. A layer of more than one is
    #: handed them as a tuple in ``apply``'s ``x``, the first being the
    #: stream (the one a preprocessor, the mask and ``set_n_in`` go by) and
    #: the rest other nodes' outputs, described by ``set_side_inputs``
    N_INPUTS = 1

    # ------------------------------------------------------------------ serde
    @classmethod
    def type_tag(cls) -> str:
        return cls.__name__

    def to_dict(self) -> dict:
        d = {"@type": self.type_tag()}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if isinstance(v, Distribution):
                v = v.to_dict()
            elif isinstance(v, tuple):
                v = list(v)
            d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "BaseLayerConf":
        d = dict(d)
        d.pop("@type", None)
        if "dist" in d and isinstance(d["dist"], dict):
            d["dist"] = Distribution.from_dict(d["dist"])
        # tuples serialized as lists
        for f in dataclasses.fields(cls):
            if f.name in d and isinstance(d[f.name], list):
                hint = str(f.type)
                if "Tuple" in hint or "tuple" in hint:
                    d[f.name] = tuple(d[f.name])
        return cls(**d)

    # ------------------------------------------------------- builder plumbing
    def apply_global_defaults(self, g: "GlobalConf") -> None:
        """Fill INHERIT fields from the global conf (ref: Builder.layer())."""
        if self.activation is None:
            self.activation = g.activation
        if self.weight_init is None:
            self.weight_init = g.weight_init
        if self.dist is None:
            self.dist = g.dist
        if self.bias_init is None:
            self.bias_init = g.bias_init
        if self.l1 is None:
            self.l1 = g.l1
        if self.l2 is None:
            self.l2 = g.l2
        if self.l1_bias is None:
            self.l1_bias = g.l1_bias
        if self.l2_bias is None:
            self.l2_bias = g.l2_bias
        if self.dropout is None:
            self.dropout = g.dropout

    # ------------------------------------------------------------- shape plan
    def propagate_mask(self, mask):
        """The time mask downstream layers should see after this layer:
        passthrough by default; layers that consume or rearrange the time
        axis (pooling over time, last-step, reshape/permute) override to
        return None so a stale [B, T] mask is never zipped against a
        differently-shaped activation."""
        return mask

    def set_n_in(self, in_type: InputType) -> None:
        self.n_in = in_type.flat_size()

    def set_side_inputs(self, in_types) -> None:
        """The types of the inputs after the first, for a layer of
        ``N_INPUTS > 1`` to check and to size its parameters by."""
        raise NotImplementedError

    def infer_output_type(self, in_type: InputType) -> InputType:
        raise NotImplementedError

    # ------------------------------------------------------------------ state
    def init_params(self, rng: Array, dtype=jnp.float32) -> Params:
        return {}

    def init_state(self) -> State:
        return {}

    def param_order(self) -> List[str]:
        """Flat-buffer ordering contract (ref: nn/params/*ParamInitializer)."""
        return ["W", "b"]

    def regularization(self) -> Dict[str, Tuple[float, float]]:
        """param name -> (l1, l2). Weights get l1/l2, biases l1_bias/l2_bias
        (ref: BaseLayer.calcL2/calcL1 applying conf.getL2ByParam)."""
        out = {}
        for p in self.param_order():
            if p in ("b", "beta", "gamma", "mean", "var"):
                out[p] = (self.l1_bias or 0.0, self.l2_bias or 0.0)
            else:
                out[p] = (self.l1 or 0.0, self.l2 or 0.0)
        return out

    # ---------------------------------------------------------------- forward
    def apply(self, params: Params, x: Array, *, state: State, train: bool,
              rng: Optional[Array], mask: Optional[Array] = None
              ) -> Tuple[Array, State]:
        raise NotImplementedError

    # ----------------------------------------------------------------- helpers
    def _dropout_input(self, x: Array, train: bool, rng: Optional[Array]) -> Array:
        """Inverted dropout on the layer *input* during training
        (ref: nn/layers/BaseLayer.applyDropOutIfNecessary + util/Dropout.java).
        DL4J's conf stores the *retain* probability."""
        retain = self.dropout
        if not train or retain is None or retain <= 0.0 or retain >= 1.0 or rng is None:
            return x
        keep = jax.random.bernoulli(rng, p=retain, shape=x.shape)
        return jnp.where(keep, x / retain, 0.0)

    def _init_w(self, rng, shape, fan_in, fan_out, dtype):
        return init_weight(rng, shape, fan_in, fan_out,
                           scheme=self.weight_init or "xavier",
                           distribution=self.dist, dtype=dtype)

    def _init_b(self, shape, dtype):
        return jnp.full(shape, self.bias_init or 0.0, dtype)

    def has_params(self) -> bool:
        return bool(self.param_order())


@dataclass
class GlobalConf:
    """Global hyperparameters from NeuralNetConfiguration.Builder that layers
    inherit (ref: nn/conf/NeuralNetConfiguration.java Builder fields)."""
    activation: str = "sigmoid"
    weight_init: str = "xavier"
    dist: Optional[Distribution] = None
    bias_init: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    l1_bias: float = 0.0
    l2_bias: float = 0.0
    dropout: float = 0.0


def layer_from_dict(d: dict) -> BaseLayerConf:
    tag = d.get("@type")
    if tag not in LAYER_REGISTRY:
        raise ValueError(f"Unknown layer type tag {tag!r}")
    return LAYER_REGISTRY[tag].from_dict(d)
