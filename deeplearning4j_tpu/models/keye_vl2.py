"""A sparse-attention mixture-of-experts decoder, as the language model of
Keye-VL-2.0-30B-A3B is built: every layer grouped-query attention with
rotary positions and a norm of q and k by head, over the keys a learned
indexer selects for each query, then a feed-forward of routed experts of
which this chip holds a range. On the ComputationGraph DSL as
``models/olmo_hybrid.py`` is.

The block is pre-norm with RMSNorm::

    h   = x + Attn(RMSNorm(x), S)       S = Indexer(RMSNorm(x))
    out = h + Experts(RMSNorm(h))

Its nodes, each shared array a node's own output: ``b<i>_norm1``
(``RMSNorm``), ``b<i>_index`` (``SparseIndexerLayer``, frozen: the
selection ``[B, T, T]``), ``b<i>_mix`` (``GroupedQueryAttentionLayer`` of
``(u, selection)``, so that under remat the backward keeps the selection and
does not select again), ``b<i>_norm2``, ``b<i>_moe``
(``RoutedExpertsLayer``). Token ids go in as int32 ``[B, T]`` through
``TokenEmbeddingLayer``'s gather; the head is an untied matrix without
bias after a final RMSNorm, the targets ids too.
"""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    GroupedQueryAttentionLayer, RMSNorm, RnnOutputLayer, RoutedExpertsLayer,
    SparseIndexerLayer, TokenEmbeddingLayer,
)
from deeplearning4j_tpu.nn.weights import Distribution


#: the published indexer (``sa_config`` of the source's ``config.json``)
SA_CONFIG = {"indexer_num_heads": 16, "indexer_head_dim": 64, "topk": 2048,
             "q_chunk_size": 512}
#: the CPU-testable one: 2 heads of 8 keeping 12 keys, 16 queries a turn
TINY_SA_CONFIG = {"indexer_num_heads": 2, "indexer_head_dim": 8, "topk": 12,
                  "q_chunk_size": 16}


def keye_vl2(vocab_size: int, seq_len: Optional[int] = None,
             hidden_size: int = 2048, n_layers: int = 48,
             n_heads: int = 32, n_kv_heads: int = 4, head_dim: int = 128,
             n_experts: int = 128, experts_per_token: int = 8,
             expert_size: int = 768, first_expert: int = 0,
             held_experts: int = 0, norm_topk_prob: bool = True,
             sa_config: Optional[dict] = None,
             indexer_norm_eps: float = 1e-6,
             rope_theta: float = 1e7, rms_norm_eps: float = 1e-6,
             learning_rate: float = 1e-4, updater: str = "nesterovs",
             precision: Optional[str] = None, remat: bool = False,
             seed: int = 12345, dtype: str = "float32"
             ) -> ComputationGraphConfiguration:
    """Build the decoder's configuration. The router of every layer scores
    all ``n_experts``; ``first_expert`` and ``held_experts`` give the range
    this program holds (all of them by default). ``sa_config`` is the
    indexer under the source's own keys (``SA_CONFIG``, the published
    group, by default): ``indexer_num_heads`` heads of
    ``indexer_head_dim``, ``topk`` keys kept a query, index scores made
    ``q_chunk_size`` queries at a time. The indexer is frozen: its
    selection is discrete, so no loss here reaches it. Input: int32 token
    ids ``[B, T]``; labels: the ids shifted by one, ``[B, T]``."""
    sa = SA_CONFIG if sa_config is None else sa_config
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
        g.add_layer(f"{blk}_index", SparseIndexerLayer(
            n_heads=sa["indexer_num_heads"], head_dim=sa["indexer_head_dim"],
            topk=sa["topk"], rope_theta=rope_theta,
            norm_eps=indexer_norm_eps, query_chunk=sa["q_chunk_size"],
            frozen=True), u)
        g.add_layer(f"{blk}_mix", GroupedQueryAttentionLayer(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            rope_theta=rope_theta, norm_eps=rms_norm_eps),
            u, f"{blk}_index")
        g.add_vertex(f"{blk}_res1", ElementWiseVertex(op="add"),
                     cur, f"{blk}_mix")
        g.add_layer(f"{blk}_norm2", RMSNorm(eps=rms_norm_eps),
                    f"{blk}_res1")
        g.add_layer(f"{blk}_moe", RoutedExpertsLayer(
            n_experts=n_experts, top_k=experts_per_token,
            n_hidden=expert_size, first=first_expert, count=held_experts,
            norm_topk_prob=norm_topk_prob, activation="silu"),
            f"{blk}_norm2")
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


def keye_vl2_tiny(vocab_size: int = 64, seq_len: Optional[int] = None,
                  **kw) -> ComputationGraphConfiguration:
    """The CPU-testable size: two layers at hidden 64, 4 / 2 heads of 16,
    an indexer of 2 heads of 8 keeping 12 keys, 4 of 8 experts of 32 held,
    2 a token."""
    kw.setdefault("hidden_size", 64)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("n_experts", 8)
    kw.setdefault("experts_per_token", 2)
    kw.setdefault("expert_size", 32)
    kw.setdefault("first_expert", 2)
    kw.setdefault("held_experts", 4)
    kw.setdefault("sa_config", TINY_SA_CONFIG)
    return keye_vl2(vocab_size, seq_len, **kw)
