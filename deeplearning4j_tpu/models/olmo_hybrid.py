"""A hybrid decoder language model of the Olmo family's kind: gated
delta-rule linear attention in most layers, full softmax attention in
every n-th, on the ComputationGraph DSL as ``models/gpt.py`` is.

The block is the family's reordered norm, the branch normalised after the
mixer and not before it::

    h   = x + RMSNorm(mixer(x))
    out = h + RMSNorm(W_down(SiLU(W_gate h) * W_up h))

``mixer`` is ``GatedDeltaNetLayer`` for a ``"linear_attention"`` layer and
``QKNormAttentionLayer`` (QK-norm, no rotary or other positional term: the
recurrent layers carry the order) for a ``"full_attention"`` one. Token ids
go in as int32 ``[B, T]`` through ``TokenEmbeddingLayer``'s gather and come
back as targets of the same shape to an untied ``RnnOutputLayer`` head; the
loss is the mean over sequences of the sum over time of the cross entropy.
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    GatedDeltaNetLayer, GatedFeedForwardLayer, QKNormAttentionLayer, RMSNorm,
    RnnOutputLayer, TokenEmbeddingLayer,
)
from deeplearning4j_tpu.nn.weights import Distribution

#: the published period: three recurrent layers, then one of full attention
PERIOD = ("linear_attention",) * 3 + ("full_attention",)


def olmo_hybrid(vocab_size: int, seq_len: Optional[int] = None,
                hidden_size: int = 3840, n_layers: int = 4,
                layer_types: Sequence[str] = PERIOD,
                n_heads: int = 30, intermediate_size: int = 11008,
                linear_n_heads: int = 30, linear_key_dim: int = 96,
                linear_value_dim: int = 192, linear_conv_kernel: int = 4,
                linear_allow_neg_eigval: bool = True,
                rms_norm_eps: float = 1e-6,
                learning_rate: float = 1e-4, updater: str = "nesterovs",
                precision: Optional[str] = None, remat: bool = False,
                seed: int = 12345, dtype: str = "float32"
                ) -> ComputationGraphConfiguration:
    """Build the decoder's configuration. ``layer_types`` gives each
    layer's mixer, in order; the first ``n_layers`` of it are built (a
    published list of 32 cut to one period keeps its order). Input: int32
    token ids ``[B, T]``; labels: the ids shifted by one, ``[B, T]``."""
    kinds = list(layer_types)[:n_layers]
    if len(kinds) < n_layers:
        raise ValueError(f"layer_types names {len(kinds)} layers, "
                         f"n_layers asks for {n_layers}")
    if hidden_size % n_heads:
        raise ValueError(f"hidden_size={hidden_size} not divisible by "
                         f"n_heads={n_heads}")
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater, learning_rate=learning_rate)
         .weight_init("distribution")
         .dist(Distribution.normal(0.0, 0.02))
         .activation("identity"))
    if precision is not None:
        b = b.precision(precision)
    if remat:
        b = b.gradient_checkpointing()
    g = b.dtype(dtype).graph_builder().add_inputs("tokens")
    g.add_layer("embed", TokenEmbeddingLayer(n_out=hidden_size), "tokens")
    cur = "embed"
    for i, kind in enumerate(kinds):
        blk = f"b{i}"
        if kind == "linear_attention":
            mixer = GatedDeltaNetLayer(
                n_heads=linear_n_heads, key_dim=linear_key_dim,
                value_dim=linear_value_dim, conv_kernel=linear_conv_kernel,
                allow_neg_eigval=linear_allow_neg_eigval,
                norm_eps=rms_norm_eps)
        elif kind == "full_attention":
            mixer = QKNormAttentionLayer(n_heads=n_heads,
                                         norm_eps=rms_norm_eps)
        else:
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
        g.add_layer(f"{blk}_mix", mixer, cur)
        g.add_layer(f"{blk}_mix_norm", RMSNorm(eps=rms_norm_eps),
                    f"{blk}_mix")
        g.add_vertex(f"{blk}_res1", ElementWiseVertex(op="add"),
                     cur, f"{blk}_mix_norm")
        g.add_layer(f"{blk}_ffn", GatedFeedForwardLayer(
            n_hidden=intermediate_size, activation="silu"), f"{blk}_res1")
        g.add_layer(f"{blk}_ffn_norm", RMSNorm(eps=rms_norm_eps),
                    f"{blk}_ffn")
        g.add_vertex(f"{blk}_res2", ElementWiseVertex(op="add"),
                     f"{blk}_res1", f"{blk}_ffn_norm")
        cur = f"{blk}_res2"
    g.add_layer("norm_f", RMSNorm(eps=rms_norm_eps), cur)
    g.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="mcxent",
        has_bias=False), "norm_f")
    return (g.set_outputs("head")
            .set_input_types(InputType.token_ids(vocab_size, seq_len))
            .build())


def olmo_hybrid_tiny(vocab_size: int = 64, seq_len: Optional[int] = None,
                     **kw) -> ComputationGraphConfiguration:
    """The CPU-testable size: one period of four layers at hidden 64."""
    kw.setdefault("hidden_size", 64)
    kw.setdefault("n_heads", 2)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("linear_n_heads", 2)
    kw.setdefault("linear_key_dim", 8)
    kw.setdefault("linear_value_dim", 16)
    return olmo_hybrid(vocab_size, seq_len, **kw)
