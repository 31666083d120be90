"""GPT-style decoder-only language model (ROADMAP item 1: the
composition workload).

A pre-LN transformer decoder assembled entirely from the existing layer
vocabulary on the ComputationGraph container: token embedding + learned
positions (``PositionalEmbeddingLayer``), N blocks of causal
self-attention (``SelfAttentionLayer`` — Pallas-flash-backed on TPU,
ring-attention-sharded over an 'sp' mesh axis under ``ParallelTrainer``)
and a time-distributed MLP, each wrapped in residual adds
(``ElementWiseVertex``) with ``LayerNormalization`` in front, and a
weight-tied LM head (``TiedRnnOutputLayer`` projecting through the
transposed embedding).

Why this model exists in the zoo: it is the one workload that exercises
EVERY expensive subsystem at once — dp x tp x sp (ring attention) under
``ParallelTrainer`` with ``weight_update_sharding=zero1/zero2`` and the
bf16 ``PrecisionPolicy``, and dp x pp under ``GraphPipelineTrainer``
(the residual stream between blocks is the single-tensor cut point GPipe
needs; inside a block the residual skip makes a cut illegal, which is
exactly what graphcheck's GC017 verifies). ``tools/lm_smoke.py`` gates
the composed configs bitwise against their replicated twins.

The character data path is ``models/char_rnn``'s: one-hot char windows,
next-char targets — here shaped for the streaming pipeline
(``char_lm_sources`` feeds ``datasets/pipeline.StreamingInputPipeline``).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    DenseLayer, LayerNormalization, PositionalEmbeddingLayer,
    RnnOutputLayer, SelfAttentionLayer, TiedRnnOutputLayer,
    TimeDistributedLayer,
)

#: default charset of the synthetic char-LM workloads (the smokes) —
#: small enough that tiny models learn it, matching char_rnn's usage
DEFAULT_CHARSET = "abcdefghijklmnopqrstuvwxyz .,;\n"


def gpt_decoder(vocab_size: int, seq_len: int, d_model: int = 128,
                n_heads: int = 4, n_layers: int = 4,
                d_ff: Optional[int] = None, seed: int = 12345,
                learning_rate: float = 3e-4, updater: str = "adam",
                dropout: Optional[float] = None,
                precision: Optional[str] = None,
                loss_scale: Optional[float] = None,
                block_size: int = 512,
                tie_weights: bool = True,
                dtype: str = "float32") -> ComputationGraphConfiguration:
    """Build the decoder LM config.

    Input: one-hot char/token windows ``[B, T=seq_len, V=vocab_size]``
    (rnn-typed, so the batch shards over 'data' AND — when T divides the
    axis — 'sp'). Output: per-timestep next-token distribution
    ``[B, T, V]`` under MCXENT, the exact char_rnn head semantics.
    """
    if d_ff is None:
        d_ff = 4 * d_model
    if d_model % n_heads:
        raise ValueError(f"d_model={d_model} not divisible by "
                         f"n_heads={n_heads}")
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater, learning_rate=learning_rate)
         .weight_init("xavier"))
    if dropout is not None:
        b = b.dropout(dropout)
    if precision is not None:
        b = b.precision(precision, loss_scale=loss_scale)
    g = b.dtype(dtype).graph_builder().add_inputs("tokens")
    g.add_layer("embed", PositionalEmbeddingLayer(
        n_out=d_model, activation="identity"), "tokens")
    cur = "embed"
    for i in range(n_layers):
        blk = f"b{i}"
        # pre-LN attention sublayer + residual. The residual stream
        # (`cur`) crosses each sublayer, so no single-tensor pipeline
        # cut exists INSIDE a block — blocks are the GPipe stage atoms.
        g.add_layer(f"{blk}_ln1", LayerNormalization(), cur)
        g.add_layer(f"{blk}_attn", SelfAttentionLayer(
            n_heads=n_heads, causal=True, block_size=block_size,
            activation="identity"), f"{blk}_ln1")
        g.add_vertex(f"{blk}_res1", ElementWiseVertex(op="add"),
                     cur, f"{blk}_attn")
        # pre-LN MLP sublayer + residual (time-distributed dense pair)
        g.add_layer(f"{blk}_ln2", LayerNormalization(), f"{blk}_res1")
        g.add_layer(f"{blk}_ff1", TimeDistributedLayer(
            inner=DenseLayer(n_out=d_ff, activation="gelu")),
            f"{blk}_ln2")
        g.add_layer(f"{blk}_ff2", TimeDistributedLayer(
            inner=DenseLayer(n_out=d_model, activation="identity")),
            f"{blk}_ff1")
        g.add_vertex(f"{blk}_res2", ElementWiseVertex(op="add"),
                     f"{blk}_res1", f"{blk}_ff2")
        cur = f"{blk}_res2"
    g.add_layer("ln_f", LayerNormalization(), cur)
    head = (TiedRnnOutputLayer(n_out=vocab_size, tied_to="embed",
                               activation="softmax", loss="mcxent")
            if tie_weights else
            RnnOutputLayer(n_out=vocab_size, activation="softmax",
                           loss="mcxent"))
    g.add_layer("head", head, "ln_f")
    return (g.set_outputs("head")
            .set_input_types(InputType.recurrent(vocab_size, seq_len))
            .build())


def gpt_tiny(vocab_size: int = 16, seq_len: int = 8, **kw
             ) -> ComputationGraphConfiguration:
    """Small CPU-testable decoder (the smoke/tier-1 shape)."""
    kw.setdefault("d_model", 16)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_layers", 2)
    kw.setdefault("d_ff", 32)
    kw.setdefault("block_size", 4)
    return gpt_decoder(vocab_size, seq_len, **kw)


# ---------------------------------------------------------------------------
# decode entry point (ISSUE 15: the singleton reference path)
# ---------------------------------------------------------------------------

def greedy_generate(net, prompt: Sequence[int], max_new_tokens: int,
                    ) -> List[int]:
    """SINGLETON greedy decode through the SAME prefill/decode kernels
    the serving engine batches (``net.decode_fns()``): prompt prefilled
    at its pow2 length bucket, then one token per decode step in the
    1-row bucket. This is the reference side of the batched ==
    singleton bitwise gate — the serving engine must reproduce these
    tokens exactly for every request, whatever its batchmates do.
    ``net`` is an initialized ComputationGraph (e.g. ``gpt_decoder``).
    """
    import jax
    from deeplearning4j_tpu.util.math_utils import next_pow_of_2

    prompt = list(prompt)
    V, max_len = net.decode_vocab(), net.decode_max_len()
    if not 0 < len(prompt) < max_len:
        raise ValueError(f"prompt length must be in (0, {max_len})")
    max_new = min(int(max_new_tokens), max_len - len(prompt))
    # cache the jitted pair on the net: jax.jit caches per WRAPPER
    # object, so rebuilding the wrappers per call would retrace and
    # recompile identical shapes every generation
    jits = getattr(net, "_greedy_jits", None)
    if jits is None:
        prefill, decode = net.decode_fns()
        jits = net._greedy_jits = (jax.jit(prefill),
                                   jax.jit(decode, donate_argnums=(2,)))
    prefill_jit, decode_jit = jits
    eye = np.eye(V, dtype=np.float32)
    bucket = min(next_pow_of_2(len(prompt)), max_len)
    x = np.zeros((1, bucket, V), np.float32)
    x[0, :len(prompt)] = eye[np.asarray(prompt)]
    caches = net.init_decode_cache(1)
    probs, caches = prefill_jit(
        net.params, net.states, caches, x,
        np.asarray([len(prompt)], np.int32))
    out = [int(np.asarray(probs)[0].argmax())]
    pos = len(prompt)
    while len(out) < max_new:
        xt = eye[np.asarray([out[-1]])][:, None, :]
        probs, caches = decode_jit(net.params, net.states, caches, xt,
                                   np.asarray([pos], np.int32))
        out.append(int(np.asarray(probs)[0].argmax()))
        pos += 1
    return out


def sample_generate(net, prompt: Sequence[int], max_new_tokens: int,
                    temperature: float, seed: int) -> List[int]:
    """SINGLETON seeded-sampling decode (sampling v0) — the reference
    side of the batched == singleton bitwise gate for temperature
    sampling: the same kernels as ``greedy_generate``, with next-token
    selection through the engine's own ``sample_token`` at draw index
    = tokens generated so far. A fixed seed pins the exact token
    stream the serving engine must reproduce under batching, churn,
    page eviction, and replay."""
    import jax
    from deeplearning4j_tpu.keras.generation import sample_token
    from deeplearning4j_tpu.util.math_utils import next_pow_of_2

    prompt = list(prompt)
    V, max_len = net.decode_vocab(), net.decode_max_len()
    if not 0 < len(prompt) < max_len:
        raise ValueError(f"prompt length must be in (0, {max_len})")
    max_new = min(int(max_new_tokens), max_len - len(prompt))
    jits = getattr(net, "_greedy_jits", None)
    if jits is None:
        prefill, decode = net.decode_fns()
        jits = net._greedy_jits = (jax.jit(prefill),
                                   jax.jit(decode, donate_argnums=(2,)))
    prefill_jit, decode_jit = jits
    eye = np.eye(V, dtype=np.float32)
    bucket = min(next_pow_of_2(len(prompt)), max_len)
    x = np.zeros((1, bucket, V), np.float32)
    x[0, :len(prompt)] = eye[np.asarray(prompt)]
    caches = net.init_decode_cache(1)
    probs, caches = prefill_jit(
        net.params, net.states, caches, x,
        np.asarray([len(prompt)], np.int32))
    out = [sample_token(np.asarray(probs)[0], temperature, seed, 0)]
    pos = len(prompt)
    while len(out) < max_new:
        xt = eye[np.asarray([out[-1]])][:, None, :]
        probs, caches = decode_jit(net.params, net.states, caches, xt,
                                   np.asarray([pos], np.int32))
        out.append(sample_token(np.asarray(probs)[0], temperature,
                                seed, len(out)))
        pos += 1
    return out


# ---------------------------------------------------------------------------
# character data path (char_rnn's, shaped for the LM + streaming pipeline)
# ---------------------------------------------------------------------------

def char_vocab(text: str) -> str:
    """Sorted unique charset of ``text`` — index IS the token id."""
    return "".join(sorted(set(text)))


def char_lm_batches(text: str, seq_len: int, batch_size: int,
                    charset: Optional[str] = None,
                    max_batches: Optional[int] = None) -> List:
    """One-hot next-char DataSets from raw text — the char_rnn data
    path: features ``[B, T, V]`` are windows of ``text``, labels the
    same windows shifted one char (per-timestep MCXENT targets).
    Deterministic (sequential windows), so two consumers of the same
    text see the same batches — the property every bitwise gate needs.
    """
    from deeplearning4j_tpu.datasets.dataset import DataSet
    cs = charset if charset is not None else char_vocab(text)
    idx = {c: i for i, c in enumerate(cs)}
    V = len(cs)
    ids = np.asarray([idx[c] for c in text if c in idx], np.int32)
    window = seq_len + 1
    n_win = (len(ids) - 1) // window
    eye = np.eye(V, dtype=np.float32)
    out, buf = [], []
    for w in range(n_win):
        chunk = ids[w * window:w * window + window]
        buf.append(chunk)
        if len(buf) == batch_size:
            arr = np.stack(buf)
            out.append(DataSet(eye[arr[:, :-1]], eye[arr[:, 1:]]))
            buf = []
            if max_batches is not None and len(out) >= max_batches:
                break
    return out


def synthetic_char_text(n_chars: int, seed: int = 0,
                        charset: str = DEFAULT_CHARSET) -> str:
    """Deterministic synthetic 'prose' with local structure (repeated
    trigram draws) so a tiny LM has something learnable — the smokes'
    corpus when no file is given."""
    rng = np.random.default_rng(seed)
    grams = ["the ", "and ", "ing ", "ion ", "ent ", "was ", "are ",
             "of ", "to ", "in ", "he ", "she ", "it ", ". "]
    parts, n = [], 0
    while n < n_chars:
        gram = grams[int(rng.integers(0, len(grams)))]
        parts.append(gram)
        n += len(gram)
    return "".join(parts)[:n_chars]


def char_lm_sources(text: str, seq_len: int, batch_size: int,
                    n_sources: int,
                    charset: Optional[str] = None
                    ) -> Tuple[Sequence[Callable], str]:
    """Shard ``text``'s batch stream into ``n_sources`` zero-arg
    callables for ``datasets/pipeline.StreamingInputPipeline`` (its
    callable-source payload kind) — the char_rnn data path behind the
    sharded streaming front. Returns (sources, charset). Strided
    round-robin over the deterministic batch list, so the pipeline's
    source-order emission reproduces the plain in-order stream."""
    cs = charset if charset is not None else char_vocab(text)
    batches = char_lm_batches(text, seq_len, batch_size, charset=cs)

    def make(shard: int) -> Callable:
        def load():
            return batches[shard::n_sources]
        return load

    return [make(s) for s in range(n_sources)], cs
