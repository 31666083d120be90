"""InputType: shape metadata flowing through the config DSL.

Mirrors the reference's ``InputType`` sealed hierarchy
(ref: nn/conf/inputs/InputType.java:47 — FF / RNN / CNN / CNNFlat) which
drives nIn inference and automatic preprocessor insertion between layer
representation families.

Convention difference from the reference: CNN activations are **NHWC**
(TPU/XLA-native layout) rather than DL4J's NCHW. Shapes recorded here are
per-example (no batch dim).
"""

from __future__ import annotations

from dataclasses import dataclass, asdict
from typing import Optional, Tuple


@dataclass(frozen=True)
class InputType:
    kind: str  # "ff" | "rnn" | "cnn" | "cnnflat" | "ids"
    size: Optional[int] = None            # ff / rnn feature size; ids: vocabulary
    timesteps: Optional[int] = None       # rnn (None = variable)
    height: Optional[int] = None          # cnn
    width: Optional[int] = None
    channels: Optional[int] = None

    # ---- factories (mirror InputType.feedForward/recurrent/convolutional) ----
    @staticmethod
    def feed_forward(size: int) -> "InputType":
        return InputType(kind="ff", size=size)

    @staticmethod
    def recurrent(size: int, timesteps: Optional[int] = None) -> "InputType":
        return InputType(kind="rnn", size=size, timesteps=timesteps)

    @staticmethod
    def token_ids(vocab_size: int, timesteps: Optional[int] = None
                  ) -> "InputType":
        """Integer token ids ``[B, T]`` below ``vocab_size``: the one input
        that is no float array. Only ``TokenEmbeddingLayer`` takes it."""
        return InputType(kind="ids", size=vocab_size, timesteps=timesteps)

    @staticmethod
    def convolutional(height: int, width: int, channels: int) -> "InputType":
        return InputType(kind="cnn", height=height, width=width, channels=channels)

    @staticmethod
    def convolutional_flat(height: int, width: int, channels: int) -> "InputType":
        return InputType(kind="cnnflat", height=height, width=width, channels=channels,
                         size=height * width * channels)

    # ---- derived ----
    def flat_size(self) -> int:
        if self.kind in ("ff", "cnnflat", "rnn", "ids"):
            return int(self.size)
        if self.kind == "cnn":
            return int(self.height * self.width * self.channels)
        raise ValueError(self.kind)

    def example_shape(self) -> Tuple[int, ...]:
        """Per-example array shape (batch dim excluded)."""
        if self.kind in ("ff", "cnnflat"):
            return (self.flat_size(),)
        if self.kind == "rnn":
            ts = self.timesteps or 1
            return (ts, self.size)  # [T, F] per example (batch-major [B,T,F])
        if self.kind == "ids":
            return (self.timesteps or 1,)
        if self.kind == "cnn":
            return (self.height, self.width, self.channels)  # NHWC
        raise ValueError(self.kind)

    def to_dict(self) -> dict:
        return {k: v for k, v in asdict(self).items() if v is not None}

    @staticmethod
    def from_dict(d: dict) -> "InputType":
        return InputType(**d)
