"""A mixture-of-experts decoder as SmallThinker-21BA3B is built: grouped-query
attention that is full and position-free (NoPE) in some layers and a window
of keys with rotary positions in the others, and a feed-forward of routed
ReLU-gated (ReGLU) experts whose router sits AHEAD of attention, of which
this chip holds a range. On the ComputationGraph DSL as
``models/keye_vl2.py`` is.

The block is pre-norm with RMSNorm; the router reads the block's normed
input ``u``, the experts the normed stream after attention::

    u   = RMSNorm(x)
    h   = x + Attn_l(u)
    out = h + Experts(RMSNorm(h), routed by u)

``Attn_l`` is causal over every key in a full layer and over the
``window`` keys ``0 <= t - s < window`` in a window layer, rotated in a
rotary layer and not at all in the others, with no norm of q or k. Which
layer is which follows ``window_layout`` and ``rope_layout`` (the source's
``sliding_window_layout`` and ``rope_layout``, a 0 or 1 a layer).

Its nodes: ``b<i>_norm1`` (``RMSNorm``), ``b<i>_mix``
(``GroupedQueryAttentionLayer`` of one input), ``b<i>_res1``,
``b<i>_norm2``, ``b<i>_moe`` (``RoutedExpertsLayer`` of ``(v, u)``: under
remat it keeps both and routes from the kept ``u``), ``b<i>_res2``. Token
ids go in as int32 ``[B, T]`` through ``TokenEmbeddingLayer``'s gather; the
head is an untied matrix without bias after a final RMSNorm, the targets
ids too.
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
    GroupedQueryAttentionLayer, RMSNorm, RnnOutputLayer, RoutedExpertsLayer,
    TokenEmbeddingLayer,
)
from deeplearning4j_tpu.nn.weights import Distribution

#: the published period: layer 4j full and position-free, 4j+1..4j+3 a
#: rotary window
LAYOUT = (0, 1, 1, 1)


def smallthinker(vocab_size: int, seq_len: Optional[int] = None,
                 hidden_size: int = 2560, n_layers: int = 52,
                 n_heads: int = 28, n_kv_heads: int = 4, head_dim: int = 128,
                 n_experts: int = 64, experts_per_token: int = 6,
                 expert_size: int = 768, first_expert: int = 0,
                 held_experts: int = 0, norm_topk_prob: bool = True,
                 window: int = 4096,
                 window_layout: Optional[Sequence[int]] = None,
                 rope_layout: Optional[Sequence[int]] = None,
                 rope_theta: float = 1.5e6, rms_norm_eps: float = 1e-6,
                 learning_rate: float = 1e-4, updater: str = "nesterovs",
                 precision: Optional[str] = None, remat: bool = False,
                 seed: int = 12345, dtype: str = "float32"
                 ) -> ComputationGraphConfiguration:
    """Build the decoder's configuration. Layer ``i`` attends over a
    ``window`` where ``window_layout[i]`` is 1 and is rotated where
    ``rope_layout[i]`` is 1 (``LAYOUT`` repeated by default; a layout
    longer than ``n_layers`` is read from its start). The router of every
    layer scores all ``n_experts``; ``first_expert`` and ``held_experts``
    give the range this program holds (all of them by default). Input:
    int32 token ids ``[B, T]``; labels: the ids shifted by one, ``[B,
    T]``."""
    period = LAYOUT * (-(-n_layers // len(LAYOUT)))
    windowed = list(period if window_layout is None else window_layout)
    rotated = list(period if rope_layout is None else rope_layout)
    if min(len(windowed), len(rotated)) < n_layers:
        raise ValueError(f"layouts of {len(windowed)} and {len(rotated)} "
                         f"entries for {n_layers} layers")
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
    for i in range(n_layers):
        blk = f"b{i}"
        u = f"{blk}_norm1"
        g.add_layer(u, RMSNorm(eps=rms_norm_eps), cur)
        g.add_layer(f"{blk}_mix", GroupedQueryAttentionLayer(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            rope_theta=rope_theta, selected=False, qk_norm=False,
            rotate=bool(rotated[i]),
            window=window if windowed[i] else None), u)
        g.add_vertex(f"{blk}_res1", ElementWiseVertex(op="add"),
                     cur, f"{blk}_mix")
        g.add_layer(f"{blk}_norm2", RMSNorm(eps=rms_norm_eps),
                    f"{blk}_res1")
        g.add_layer(f"{blk}_moe", RoutedExpertsLayer(
            n_experts=n_experts, top_k=experts_per_token,
            n_hidden=expert_size, first=first_expert, count=held_experts,
            norm_topk_prob=norm_topk_prob, activation="relu",
            route_from_side=True), f"{blk}_norm2", u)
        g.add_vertex(f"{blk}_res2", ElementWiseVertex(op="add"),
                     f"{blk}_res1", f"{blk}_moe")
        cur = f"{blk}_res2"
    g.add_layer("norm_f", RMSNorm(eps=rms_norm_eps), cur)
    g.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="mcxent",
        has_bias=False), "norm_f")
    return (g.set_outputs("head")
            .set_input_types(InputType.token_ids(vocab_size, seq_len))
            .build())


def smallthinker_tiny(vocab_size: int = 64, seq_len: Optional[int] = None,
                      **kw) -> ComputationGraphConfiguration:
    """The CPU-testable size: one period of four layers at hidden 64, 4 / 2
    heads of 16, a window of 8, 4 of 8 experts of 32 held, 2 a token."""
    kw.setdefault("hidden_size", 64)
    kw.setdefault("n_layers", 4)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("n_experts", 8)
    kw.setdefault("experts_per_token", 2)
    kw.setdefault("expert_size", 32)
    kw.setdefault("first_expert", 2)
    kw.setdefault("held_experts", 4)
    kw.setdefault("window", 8)
    return smallthinker(vocab_size, seq_len, **kw)
