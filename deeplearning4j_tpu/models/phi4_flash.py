"""A decoder-hybrid-decoder language model of the SambaY kind (Ren et al.,
arXiv:2507.06607), as Phi-4-mini-flash-reasoning is built: Mamba
state-space layers and differential attention with a sliding window take
turns in the lower half; the layer at the boundary runs one more scan,
whose output is THE memory, and the layer after it full attention, whose
keys and values are THE keys and values; every layer above reads one or
the other and owns neither a scan nor ``W_k, W_v``. On the ComputationGraph
DSL as ``models/olmo_hybrid.py`` is.

The block is pre-norm with LayerNorm (gain and bias)::

    h   = x + mixer(LN(x))
    out = h + W_down(SiLU(W_gate LN(h)) * W_up LN(h))

and ``mixer``, by the layer's kind (``layer_kinds`` gives the published
rule), is made of these nodes, each shared array a node's own output:

* ``"mamba"``: ``SelectiveScanLayer`` (``b<i>_ssm``) then its gate and
  output projection, a ``GatedMemoryUnitLayer`` of ``(u, s)``;
* ``"window"``, ``"full"``: ``KeyValueProjectionLayer`` (``b<i>_kv``) then
  ``DifferentialAttentionLayer`` of ``(u, kv)``, the first with a window;
* ``"gmu"``: ``GatedMemoryUnitLayer`` of ``(u, s of the boundary's scan)``;
* ``"cross"``: ``DifferentialAttentionLayer`` of ``(u, kv of the full
  layer)``.

A node is named by its layer's PUBLISHED index ``i``, which also sets the
differential attention's ``lambda_init``, so a model cut to some of its
layers keeps both. Token ids go in as int32 ``[B, T]`` through
``TokenEmbeddingLayer``'s gather; the head is that matrix transposed
(``TiedRnnOutputLayer``) after a final LayerNorm, the targets ids too. No
positional term anywhere: the scans carry the order.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    DifferentialAttentionLayer, GatedFeedForwardLayer, GatedMemoryUnitLayer,
    KeyValueProjectionLayer, LayerNormalization, SelectiveScanLayer,
    TiedRnnOutputLayer, TokenEmbeddingLayer,
)
from deeplearning4j_tpu.nn.weights import Distribution


def layer_kinds(n_layers: int = 32, mb_per_layer: int = 2) -> List[str]:
    """The kind of each of a whole model's layers, by index: every
    ``mb_per_layer``-th from 0 a state-space layer and the others attention
    with a window, up to the middle; there one more state-space layer (the
    memory) and one of full attention (the keys and values); above them
    memory units and cross attention take the same turns."""
    half = n_layers // 2
    kinds = []
    for i in range(n_layers):
        scans = i % mb_per_layer == 0
        if i < half:
            kinds.append("mamba" if scans else "window")
        elif i == half:
            kinds.append("mamba")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if scans else "cross")
    return kinds


def phi4_flash(vocab_size: int, seq_len: Optional[int] = None,
               hidden_size: int = 2560,
               layer_types: Sequence[str] = tuple(layer_kinds()),
               layers: Optional[Sequence[int]] = None,
               n_heads: int = 40, n_kv_heads: int = 20,
               intermediate_size: int = 10240, sliding_window: int = 512,
               ssm_inner: int = 5120, ssm_state: int = 16,
               ssm_dt_rank: int = 160, ssm_conv_kernel: int = 4,
               layer_norm_eps: float = 1e-5,
               learning_rate: float = 1e-4, updater: str = "nesterovs",
               precision: Optional[str] = None, remat: bool = False,
               seed: int = 12345, dtype: str = "float32"
               ) -> ComputationGraphConfiguration:
    """Build the decoder's configuration. ``layer_types`` names the kind of
    every layer of the whole model; ``layers`` lists the published indices
    that are built, in order (all of them by default). A cut must keep the
    scan and the full layer that the memory units and the cross layers it
    keeps read. Input: int32 token ids ``[B, T]``; labels: the ids shifted
    by one, ``[B, T]``."""
    kinds = list(layer_types)
    built = list(range(len(kinds))) if layers is None else list(layers)
    if hidden_size % n_heads:
        raise ValueError(f"hidden_size={hidden_size} not divisible by "
                         f"n_heads={n_heads}")
    head_dim = hidden_size // n_heads
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
    memory = keys_values = None     # the nodes the upper layers read
    for i in built:
        kind, blk = kinds[i], f"b{i}"
        u = f"{blk}_norm1"
        g.add_layer(u, LayerNormalization(eps=layer_norm_eps), cur)
        attention = lambda window=None: DifferentialAttentionLayer(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            window=window, depth=i, norm_eps=layer_norm_eps)
        if kind == "mamba":
            memory = f"{blk}_ssm"
            g.add_layer(memory, SelectiveScanLayer(
                n_inner=ssm_inner, n_state=ssm_state, dt_rank=ssm_dt_rank,
                conv_kernel=ssm_conv_kernel), u)
            g.add_layer(f"{blk}_mix", GatedMemoryUnitLayer(), u, memory)
        elif kind in ("window", "full"):
            kv = f"{blk}_kv"
            g.add_layer(kv, KeyValueProjectionLayer(
                n_kv_heads=n_kv_heads, head_dim=head_dim), u)
            g.add_layer(f"{blk}_mix", attention(
                sliding_window if kind == "window" else None), u, kv)
            if kind == "full":
                keys_values = kv
        elif kind == "gmu":
            if memory is None:
                raise ValueError(f"layer {i}: a memory unit with no "
                                 "state-space layer below it")
            g.add_layer(f"{blk}_mix", GatedMemoryUnitLayer(), u, memory)
        elif kind == "cross":
            if keys_values is None:
                raise ValueError(f"layer {i}: cross attention with no "
                                 "full-attention layer below it")
            g.add_layer(f"{blk}_mix", attention(), u, keys_values)
        else:
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
        g.add_vertex(f"{blk}_res1", ElementWiseVertex(op="add"),
                     cur, f"{blk}_mix")
        g.add_layer(f"{blk}_norm2", LayerNormalization(eps=layer_norm_eps),
                    f"{blk}_res1")
        g.add_layer(f"{blk}_ffn", GatedFeedForwardLayer(
            n_hidden=intermediate_size, activation="silu"), f"{blk}_norm2")
        g.add_vertex(f"{blk}_res2", ElementWiseVertex(op="add"),
                     f"{blk}_res1", f"{blk}_ffn")
        cur = f"{blk}_res2"
    g.add_layer("norm_f", LayerNormalization(eps=layer_norm_eps), cur)
    g.add_layer("head", TiedRnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="mcxent",
        tied_to="embed"), "norm_f")
    return (g.set_outputs("head")
            .set_input_types(InputType.token_ids(vocab_size, seq_len))
            .build())


#: the CPU-testable cut: both halves, the boundary, and three readers each
#: of the memory and of the keys and values
TINY_LAYERS = (0, 1, 2, 3, 16, 17, 18, 19, 20, 21, 22, 23)


def phi4_flash_tiny(vocab_size: int = 64, seq_len: Optional[int] = None,
                    **kw) -> ComputationGraphConfiguration:
    """The CPU-testable size: twelve layers at hidden 64."""
    kw.setdefault("hidden_size", 64)
    kw.setdefault("layers", TINY_LAYERS)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("sliding_window", 24)
    kw.setdefault("ssm_inner", 128)
    kw.setdefault("ssm_state", 4)
    kw.setdefault("ssm_dt_rank", 4)
    return phi4_flash(vocab_size, seq_len, **kw)
