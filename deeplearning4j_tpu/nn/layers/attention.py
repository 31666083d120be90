"""Attention layers.

The reference snapshot predates attention entirely (SURVEY §5.7: "there is
no attention at all in this snapshot; the RNN era") — long sequences are
handled by truncated BPTT. This module is the modern long-context path the
TPU build treats as first-class: standard multi-head attention for
single-device use, and a blockwise (flash-style) kernel that
parallel/sequence.py distributes as ring attention over a mesh axis.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    Array, BaseLayerConf, Params, register_layer,
)
from deeplearning4j_tpu.nn.layers.normalization import rms_normalize
from deeplearning4j_tpu.ops.topk_threshold import topk_threshold

NEG_INF = -1e30


def attention_reference(q: Array, k: Array, v: Array,
                        causal: bool = False,
                        mask: Optional[Array] = None,
                        window: Optional[int] = None) -> Array:
    """Plain softmax(QK^T/sqrt(d))V. q,k: [B, H, T, D], v: [B, H, T, Dv].
    ``window`` (self-attention, with ``causal``): key ``s`` is seen from
    ``t`` when ``0 <= t - s < window``."""
    d = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            cm = cm & ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        logits = jnp.where(cm, logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :] > 0, logits, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v)


def blockwise_attention(q: Array, k: Array, v: Array, *,
                        block_size: int = 512, causal: bool = False,
                        q_offset: int = 0,
                        kv_mask: Optional[Array] = None
                        ) -> Tuple[Array, Array, Array]:
    """Flash-style blockwise attention over the KV axis with running
    log-sum-exp, returning (unnormalized_out, running_max, running_lse) so
    partial results compose across ring steps.

    q,k,v: [B, H, T, D]. ``q_offset``: global position of q block 0 —
    needed for causal masking when q is a sequence shard (ring attention).
    ``kv_mask``: [B, TK] validity of key positions (sequence padding).
    Scanning KV blocks keeps the T x T score matrix out of HBM, which is
    what lets sequence length scale past VMEM on TPU.
    """
    B, H, TQ, D = q.shape
    TK = k.shape[2]
    bs = min(block_size, TK)
    n_blocks = (TK + bs - 1) // bs
    pad = n_blocks * bs - TK
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, n_blocks, bs, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, n_blocks, bs, D).transpose(2, 0, 1, 3, 4)
    if kv_mask is not None:
        mb = jnp.pad(kv_mask.astype(bool), ((0, 0), (0, pad)))
        mb = mb.reshape(B, n_blocks, bs).transpose(1, 0, 2)  # [n, B, bs]
    else:
        mb = jnp.ones((n_blocks, B, bs), bool)
    scale = 1.0 / math.sqrt(D)
    q_pos = q_offset + jnp.arange(TQ)

    def body(carry, blk):
        out, m, lse = carry
        kblk, vblk, mblk, bidx = blk
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, kblk) * scale
        k_pos = bidx * bs + jnp.arange(bs)
        valid = (k_pos < TK)[None, :] & mblk          # [B, bs]
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        if causal:
            cm = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(cm[None, None], logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # rescale previous accumulators
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        out = out * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vblk)
        lse = lse * corr + jnp.sum(p, axis=-1)
        return (out, m_new, lse), None

    # derive initial carries from q so their varying-manual-axes match the
    # body outputs under shard_map (constants are unvarying; q is varying)
    out0 = q * 0.0
    m0 = q[..., 0] * 0.0 + NEG_INF
    lse0 = q[..., 0] * 0.0
    (out, m, lse), _ = jax.lax.scan(
        body, (out0, m0, lse0),
        (kb, vb, mb, jnp.arange(n_blocks)))
    return out, m, lse


def finalize_attention(out: Array, lse: Array) -> Array:
    return out / jnp.maximum(lse[..., None], 1e-30)


# ---------------------------------------------------------------------------
# block-paged KV caches (ISSUE 20): the page-table indirection seam
# ---------------------------------------------------------------------------

def gather_kv_pages(pages: Array, page_table: Array) -> Array:
    """Materialize per-row dense KV state from a block-paged pool.

    ``pages``: the pool, ``[n_pages, H, page_len, D]``. ``page_table``:
    ``[rows, pages_per_row]`` int32 physical page ids per row. Returns
    the dense ``[rows, H, pages_per_row * page_len, D]`` cache view the
    unmodified attention ``decode_step`` expects — when ``page_len``
    divides ``max_len`` this is shape- and VALUE-identical to the
    whole-row cache, so the paged decode step stays bitwise equal to
    the dense one (garbage in unmapped/stale pages is finite and sits
    only at masked positions, where softmax contributes exact zeros).
    """
    rows, ppr = page_table.shape
    _, H, page_len, D = pages.shape
    g = pages[page_table]                       # [rows, ppr, H, pl, D]
    g = g.transpose(0, 2, 1, 3, 4)              # [rows, H, ppr, pl, D]
    return g.reshape(rows, H, ppr * page_len, D)


def scatter_kv_token(pages: Array, new_kv: Array, page_table: Array,
                     positions: Array) -> Array:
    """Write one decode step's K (or V) back into the paged pool.

    ``new_kv``: ``[rows, H, D]`` — each row's K/V at its current write
    position. The write lands in page ``page_table[row, pos // pl]`` at
    offset ``pos % pl``. Write pages are EXCLUSIVE per row by
    construction (the engine only shares fully-prefilled prompt pages),
    so the scatter indices of live rows never collide — which is what
    keeps shared pages read-only through the compiled step."""
    page_len = pages.shape[2]
    rows = jnp.arange(page_table.shape[0])
    phys = page_table[rows, positions // page_len]
    return pages.at[phys, :, positions % page_len, :].set(new_kv)


@register_layer
@dataclass
class SelfAttentionLayer(BaseLayerConf):
    """Multi-head self attention over [B, T, F] with optional causal mask
    and the blockwise kernel. Params: Wq/Wk/Wv [F, H*D], Wo [H*D, F]."""
    n_heads: int = 8
    head_dim: int = 0          # default F // n_heads
    causal: bool = False
    block_size: int = 512
    use_blockwise: bool = True
    # route through ring attention over the 'sp' mesh axis when trained
    # inside a sequence_parallel_scope (ParallelTrainer with n_seq > 1);
    # False pins the layer to local attention regardless of mesh
    sequence_parallel: bool = True

    supports_carry = False

    @property
    def supports_kv_cache(self) -> bool:
        """Incremental (token-at-a-time) decode is only meaningful for
        CAUSAL attention: position p's output depends on positions
        <= p alone, so a per-request KV cache makes each decode step
        O(p) instead of re-running the O(T^2) window."""
        return self.causal

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(f"SelfAttentionLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size
        if not self.head_dim:
            self.head_dim = max(1, self.n_in // self.n_heads)

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in, in_type.timesteps)

    def param_order(self) -> List[str]:
        return ["Wq", "Wk", "Wv", "Wo"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F = self.n_in
        HD = self.n_heads * self.head_dim
        ks = jax.random.split(rng, 4)
        return {
            "Wq": self._init_w(ks[0], (F, HD), F, HD, dtype),
            "Wk": self._init_w(ks[1], (F, HD), F, HD, dtype),
            "Wv": self._init_w(ks[2], (F, HD), F, HD, dtype),
            "Wo": self._init_w(ks[3], (HD, F), HD, F, dtype),
        }

    def _split_heads(self, x):
        B, T, _ = x.shape
        return x.reshape(B, T, self.n_heads, self.head_dim).transpose(0, 2, 1, 3)

    def _ring_context(self, x, mask):
        """The active MeshContext when this apply should run as ring
        attention: inside a sequence_parallel_scope, allowed by config,
        T divides the sp axis, and B divides the data axis (the
        shard_map shards both). Sequence-padding masks ride the ring
        (their KV shard rotates with the KVs)."""
        if not self.sequence_parallel:
            return None
        from deeplearning4j_tpu.parallel.mesh import active_sequence_context
        ctx = active_sequence_context()
        if ctx is None:
            return None
        if (x.shape[1] % ctx.mesh.shape[ctx.seq_axis] != 0
                or x.shape[0] % ctx.mesh.shape[ctx.data_axis] != 0):
            return None
        return ctx

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        ring = self._ring_context(x, mask)
        if ring is not None:
            # sequence parallelism (VERDICT r3 #5): T sharded over 'sp',
            # B over 'data', blockwise attention against ring-rotated KV
            from deeplearning4j_tpu.parallel.sequence import (
                ring_self_attention)
            out = ring_self_attention(
                x, params, ring.mesh, n_heads=self.n_heads,
                head_dim=self.head_dim, seq_axis=ring.seq_axis,
                batch_axis=ring.data_axis, causal=self.causal,
                block_size=self.block_size, mask=mask)
            return out, state
        out = self._attend(x @ params["Wq"], x @ params["Wk"],
                           x @ params["Wv"], mask) @ params["Wo"]
        if mask is not None:
            out = out * mask[..., None]
        return out, state

    def _attend(self, q, k, v, mask):
        """Softmax attention of projected ``q, k, v [B, T, H*D]`` by head,
        heads merged again."""
        out = self._attend_heads(*map(self._split_heads, (q, k, v)), mask)
        B, H, T, D = out.shape
        return out.transpose(0, 2, 1, 3).reshape(B, T, H * D)

    def _attend_heads(self, q, k, v, mask, window: Optional[int] = None,
                      select: Optional[Array] = None):
        """Softmax attention of ``q, k [B, H, T, D]`` and ``v [B, H, T,
        Dv]``: the Pallas flash kernel where its shape gate allows, else the
        blockwise or the plain XLA path (the plain one alone knows a window
        and a value wider than its key). ``select [B, T, T]``: the keys
        each query reads (causal; no key mask on its XLA path,
        ``attention_selected``); a refusal of the gate is then counted
        under ``kernel="flash_select"``."""
        # helper seam (the cuDNN-discovery analog, like the fused LSTM):
        # MXU-native flash attention when the Pallas kernel applies
        from deeplearning4j_tpu.ops.pallas_attention import (
            attention_mode, flash_attention, flash_ok)
        from deeplearning4j_tpu.ops.pallas_kernels import count_gate_fallback
        amode = attention_mode()
        plain = window is None and v.shape[-1] == q.shape[-1]
        use_flash = amode != "off" and flash_ok(
            q.shape[2], max(q.shape[-1], v.shape[-1]), q.dtype.itemsize,
            selected=select is not None)
        if amode != "off" and not use_flash:
            count_gate_fallback(self, "flash_attention" if select is None
                                else "flash_select")
        if use_flash:
            return flash_attention(q, k, v, causal=self.causal,
                                   kv_mask=mask,
                                   interpret=amode == "interpret",
                                   window=window, select=select)
        if select is not None:
            return attention_selected(q, k, v, select)
        if self.use_blockwise and plain:
            out, _, lse = blockwise_attention(q, k, v, block_size=self.block_size,
                                              causal=self.causal, kv_mask=mask)
            return finalize_attention(out, lse)
        return attention_reference(q, k, v, causal=self.causal, mask=mask,
                                   window=window)

    # ------------------------------------------------- incremental decode
    def cache_shape(self, rows: int, max_len: int) -> Tuple[int, ...]:
        """Static per-bucket KV cache shape: [rows, H, max_len, D]."""
        return (rows, self.n_heads, max_len, self.head_dim)

    def prefill(self, params, x, k_cache, v_cache, lengths):
        """Prompt-window forward that FILLS the KV cache: ``x`` is the
        padded prompt block [B, T, F], ``lengths`` [B] the per-row
        valid prompt lengths, caches [B, H, Tmax, D] (T <= Tmax). The
        full window's K/V land in cache[:, :, :T]; padded positions
        write garbage-but-finite values that incremental decode later
        OVERWRITES (the first generated token decodes at position
        ``length``) or masks (positions > pos are invalid), so they
        are never attended. Returns (out [B, T, F], k_cache, v_cache).
        """
        if not self.causal:
            raise ValueError("prefill/decode need causal attention")
        q = self._split_heads(x @ params["Wq"])
        k = self._split_heads(x @ params["Wk"])
        v = self._split_heads(x @ params["Wv"])
        kv_mask = (jnp.arange(x.shape[1])[None, :]
                   < lengths[:, None]).astype(x.dtype)
        out = attention_reference(q, k, v, causal=True, mask=kv_mask)
        T = x.shape[1]
        k_cache = k_cache.at[:, :, :T, :].set(k)
        v_cache = v_cache.at[:, :, :T, :].set(v)
        B, H, _, D = q.shape
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        return out @ params["Wo"], k_cache, v_cache

    def decode_step(self, params, x, k_cache, v_cache, positions):
        """ONE token per row: ``x`` [B, 1, F] is the current token's
        activation, ``positions`` [B] its sequence position per row.
        Writes this position's K/V into the cache and attends the
        query over cache positions <= position (each row masks its own
        prefix — rows are fully independent, which is what makes
        batched decode bitwise equal to singleton decode). Returns
        (out [B, 1, F], new_k_cache, new_v_cache)."""
        if not self.causal:
            raise ValueError("prefill/decode need causal attention")
        q = self._split_heads(x @ params["Wq"])          # [B, H, 1, D]
        k_new = self._split_heads(x @ params["Wk"])[:, :, 0, :]
        v_new = self._split_heads(x @ params["Wv"])[:, :, 0, :]
        B = x.shape[0]
        rows = jnp.arange(B)
        k_cache = k_cache.at[rows, :, positions, :].set(k_new)
        v_cache = v_cache.at[rows, :, positions, :].set(v_new)
        scale = 1.0 / math.sqrt(self.head_dim)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache) * scale
        valid = (jnp.arange(k_cache.shape[2])[None, :]
                 <= positions[:, None])                  # [B, Tmax]
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        out = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(logits, axis=-1), v_cache)
        H, D = self.n_heads, self.head_dim
        out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * D)
        return out @ params["Wo"], k_cache, v_cache


@register_layer
@dataclass
class QKNormAttentionLayer(SelfAttentionLayer):
    """Causal multi-head self attention with QK-norm and no positional
    term: ``q = RMSNorm(Wq x)``, ``k = RMSNorm(Wk x)`` over the whole
    projection (all heads together, one gain a channel), then softmax
    attention by head through ``SelfAttentionLayer``'s kernel seam. The
    full-attention layer of a hybrid decoder whose other layers carry the
    order of the tokens in their recurrent state. Trains; it has no
    incremental decode and does not ride the sequence-parallel ring."""
    causal: bool = True
    norm_eps: float = 1e-6
    sequence_parallel: bool = False

    supports_kv_cache = False

    def param_order(self) -> List[str]:
        return ["Wq", "Wk", "Wv", "q_gamma", "k_gamma", "Wo"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        p = super().init_params(rng, dtype)
        hd = self.n_heads * self.head_dim
        p["q_gamma"] = jnp.ones((hd,), dtype)
        p["k_gamma"] = jnp.ones((hd,), dtype)
        return p

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        norm = lambda a, g: (rms_normalize(a, self.norm_eps)
                             * params[g]).astype(x.dtype)
        out = self._attend(norm(x @ params["Wq"], "q_gamma"),
                           norm(x @ params["Wk"], "k_gamma"),
                           x @ params["Wv"], mask) @ params["Wo"]
        if mask is not None:
            out = out * mask[..., None]
        return out, state


@register_layer
@dataclass
class KeyValueProjectionLayer(BaseLayerConf):
    """The keys and values of one attention layer as a node's own output:
    ``[B, T, F] -> [B, T, 2 G D]``, ``[W_k u + b_k ; W_v u + b_v]`` for ``G``
    key/value heads of ``D``, in one product. The attention layer after it
    reads them as its second input, and so may any layer above that owns
    queries alone (``DifferentialAttentionLayer``): one K, V read across
    layers. Params: ``W [F, 2 G D]`` (keys first), ``b [2 G D]``."""
    n_kv_heads: int = 8
    head_dim: int = 64

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(
                f"KeyValueProjectionLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(2 * self.n_kv_heads * self.head_dim,
                                   in_type.timesteps)

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, W = self.n_in, 2 * self.n_kv_heads * self.head_dim
        return {"W": self._init_w(rng, (F, W), F, W, dtype),
                "b": self._init_b((W,), dtype)}

    def apply(self, params, x, *, state, train, rng, mask=None):
        x = self._dropout_input(x, train, rng)
        return x @ params["W"] + params["b"], state


@register_layer
@dataclass
class DifferentialAttentionLayer(SelfAttentionLayer):
    """Causal differential attention (Ye et al., arXiv:2410.05258) over two
    inputs: the stream's ``u [B, T, F]``, which it projects to queries, and
    the keys and values ``[B, T, 2 G D]`` of a ``KeyValueProjectionLayer``,
    its own block's or a layer's further down (a cross layer: it owns no
    ``W_k``, ``W_v``). No positional term of any kind.

    ``n_heads`` query heads and ``G = n_kv_heads`` key/value heads of ``D =
    head_dim`` make ``n_heads / 2`` query pairs and ``G / 2`` key/value
    pairs: query pair ``p`` is heads ``(2p, 2p+1)`` and reads key/value pair
    ``p // (n_heads / G)``, whose two keys score one map each over ONE value
    ``V = [v_a ; v_b]`` of ``2 D``::

        A^j  = softmax(q^j k^jT / sqrt(D) + mask),  j = 1, 2
        lam  = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init
        o_p  = (1 - lambda_init) gamma * RMSNorm_2D((A^1 - lam A^2) V)
        y    = W_o concat_p o_p + b_o

    ``lambda_init = 0.8 - 0.6 exp(-0.3 depth)`` with ``depth`` the layer's
    index in the whole model. With ``window`` key ``s`` is seen from ``t``
    when ``0 <= t - s < window``. A map is one head of the flash kernels:
    ``q^j, k^j`` of ``D`` beside a value of ``2 D`` (the kernels' scale is
    ``1 / sqrt(D)`` of the key's width), the keys and values repeated to
    the query heads' count. Trains; no incremental decode, no sequence-parallel ring.

    Params: ``Wq [F, H D]``, ``bq``, ``lq1, lk1, lq2, lk2 [D]``, ``gamma
    [2 D]``, ``Wo [H D, F]``, ``bo``."""
    n_kv_heads: int = 0         # default n_heads
    window: Optional[int] = None
    depth: int = 0
    norm_eps: float = 1e-5
    causal: bool = True
    sequence_parallel: bool = False

    N_INPUTS = 2
    supports_kv_cache = False

    @property
    def lambda_init(self) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * self.depth)

    def set_n_in(self, in_type: InputType) -> None:
        super().set_n_in(in_type)
        if not self.n_kv_heads:
            self.n_kv_heads = self.n_heads
        if (self.n_heads % 2 or self.n_kv_heads % 2
                or self.n_heads % self.n_kv_heads):
            raise ValueError(
                f"DifferentialAttentionLayer({self.name!r}): pairs need "
                f"even head counts and n_heads a multiple of n_kv_heads, "
                f"got {self.n_heads} and {self.n_kv_heads}")

    def set_side_inputs(self, in_types) -> None:
        (kv,) = in_types
        want = 2 * self.n_kv_heads * self.head_dim
        if kv.kind != "rnn" or kv.size != want:
            raise ValueError(
                f"DifferentialAttentionLayer({self.name!r}): keys and "
                f"values of width {want} expected as second input, got {kv}")

    def param_order(self) -> List[str]:
        return ["Wq", "bq", "lq1", "lk1", "lq2", "lk2", "gamma", "Wo", "bo"]

    def regularization(self):
        reg = super().regularization()
        for p in ("bq", "bo", "lq1", "lk1", "lq2", "lk2"):
            reg[p] = (self.l1_bias or 0.0, self.l2_bias or 0.0)
        return reg

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, D = self.n_in, self.head_dim
        HD = self.n_heads * D
        ks = jax.random.split(rng, 6)
        p = {"Wq": self._init_w(ks[0], (F, HD), F, HD, dtype),
             "bq": self._init_b((HD,), dtype),
             "gamma": jnp.ones((2 * D,), dtype),
             "Wo": self._init_w(ks[1], (HD, F), HD, F, dtype),
             "bo": self._init_b((F,), dtype)}
        for name, k in zip(("lq1", "lk1", "lq2", "lk2"), ks[2:]):
            # each l_* normal(0, 0.1), as the differential transformer's
            p[name] = (0.1 * jax.random.normal(k, (D,))).astype(dtype)
        return p

    def apply(self, params, x, *, state, train, rng, mask=None):
        u, kv = x
        u = self._dropout_input(u, train, rng)
        B, T, _ = u.shape
        H, G, D = self.n_heads, self.n_kv_heads, self.head_dim
        pairs, kv_pairs = H // 2, G // 2
        acc = jnp.promote_types(u.dtype, jnp.float32)
        q = self._split_heads(u @ params["Wq"] + params["bq"])

        def to_query_heads(a, width):
            # [B, T, kv_pairs, m, width] -> [B, H, T, width]: kernel head
            # 2p + j takes entry j % m of key/value pair p // (pairs /
            # kv_pairs)
            m = a.shape[3]
            a = a.transpose(0, 2, 3, 1, 4)[:, :, None, None]
            a = jnp.broadcast_to(a, (B, kv_pairs, pairs // kv_pairs, 2 // m,
                                     m, T, width))
            return a.reshape(B, H, T, width)

        k = to_query_heads(kv[..., :G * D].reshape(B, T, kv_pairs, 2, D), D)
        v = to_query_heads(
            kv[..., G * D:].reshape(B, T, kv_pairs, 1, 2 * D), 2 * D)
        out = self._attend_heads(q, k, v, mask, window=self.window)
        with jax.named_scope("attn:diff_norm"):
            wide = lambda name: params[name].astype(acc)
            lam = (jnp.exp(jnp.sum(wide("lq1") * wide("lk1")))
                   - jnp.exp(jnp.sum(wide("lq2") * wide("lk2")))
                   + self.lambda_init)
            out = out.reshape(B, pairs, 2, T, 2 * D).astype(acc)
            out = rms_normalize(out[:, :, 0] - lam * out[:, :, 1],
                                self.norm_eps)
            out = out * (wide("gamma") * (1.0 - self.lambda_init))
            out = out.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        out = out.astype(u.dtype) @ params["Wo"] + params["bo"]
        if mask is not None:
            out = out * mask[..., None]
        return out, state


# ---------------------------------------------------------------------------
# rotary positions, a learned selection of keys, grouped-query attention
# ---------------------------------------------------------------------------

def rotary(x: Array, positions: Array, theta: float) -> Array:
    """``x [..., T, D]`` rotated by position (Su et al., arXiv:2104.09864),
    the half-split form: entry ``i < D / 2`` pairs with entry ``i + D / 2``
    and the pair turns by ``positions[t] * theta ** (-2 i / D)``. Angles,
    sines and the rotation in float32, the result in ``x``'s dtype.
    ``positions [T]``: ``arange(T)`` in training; an argument, so that a
    cache can hand the positions it is at."""
    half = x.shape[-1] // 2
    wide = jnp.promote_types(x.dtype, jnp.float32)
    freq = theta ** (-jnp.arange(half, dtype=wide) / half)
    angle = positions.astype(wide)[:, None] * freq          # [T, D / 2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half].astype(wide), x[..., half:].astype(wide)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def query_chunks(a: Array, axis: int, size: int) -> Array:
    """``a`` cut along its query ``axis`` into chunks of ``size`` (the last
    padded with zeros), the chunks in front: ``[n, ..., size, ...]``, for a
    ``lax.map`` over them."""
    pad = -a.shape[axis] % size
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    a = jnp.pad(a, widths)
    a = a.reshape(a.shape[:axis] + (-1, size) + a.shape[axis + 1:])
    return jnp.moveaxis(a, axis, 0)


def seen_keys(first: Array, Q: int, T: int) -> Array:
    """``[Q, T]`` bool: key ``s`` has come by query ``first + i``."""
    return jnp.arange(T)[None, :] <= first + jnp.arange(Q)[:, None]


def keeps_all(first: Array, Q: int, topk: int) -> Array:
    """Whether none of the queries ``first .. first + Q - 1`` has seen more
    keys than ``topk``: their rows of :func:`top_keys` are then
    :func:`seen_keys` whatever the scores, which need not be made."""
    return first + Q <= topk


def top_keys(scores: Array, first: Array, topk: int) -> Array:
    """For each query row of ``scores [..., Q, T]`` (query ``first + i`` in
    row ``i``) the ``topk`` keys ``s <= t`` of largest score, all of them
    while ``t < topk``, as int8 ``[..., Q, T]``; of equal scores the lower
    ``s`` first. The ``topk``-th largest score of a row is its threshold,
    found by counting and not by sorting (``ops/topk_threshold.py``: the
    value ``lax.top_k`` would put last, which on a v5e cost a sort of the
    row with its indices, 1.85 ms for 512 rows of 8,192 where the search
    takes 0.13; PERF.md PR 36): what lies above is in, and of what equals
    it the first as many as are still wanted (a running count along the
    row)."""
    Q, T = scores.shape[-2:]
    seen = seen_keys(first, Q, T)
    scores = jnp.where(seen, scores, -jnp.inf)
    if topk >= T:
        return jnp.broadcast_to(seen, scores.shape).astype(jnp.int8)
    edge = topk_threshold(scores, topk)
    above = scores > edge
    level = scores == edge
    wanted = topk - jnp.sum(above, axis=-1, keepdims=True)
    among = jnp.cumsum(level.astype(jnp.int32), axis=-1)
    return (seen & (above | (level & (among <= wanted)))).astype(jnp.int8)


@register_layer
@dataclass
class SparseIndexerLayer(BaseLayerConf):
    """The learned indexer of a sparse attention layer (DeepSeek-V3.2-Exp's
    "lightning indexer") as a node of its own: from the block's normed input
    ``u [B, T, F]`` to the selection ``[B, T, T]`` int8, nonzero where query
    ``t`` reads key ``s``::

        qI_t = rotary(W_q u_t)  as n_heads heads of head_dim
        kI_s = rotary(LayerNorm(W_k u_s))  one head
        I_ts = sum_j (W_w u_t)_j ReLU(qI_tj . kI_s)
        S_t  = the topk keys s <= t of largest I_ts (all while t < topk)

    The index scores are float32 and are made ``query_chunk`` queries at a
    time (``[n_heads, query_chunk, T]`` a turn; the whole ``[n_heads, T,
    T]`` is in no buffer). The selection is discrete: no gradient passes
    it, so the layer is ``frozen`` by default and a language-model loss
    leaves its leaves where they were. The attention layer reads the
    selection as its second input, and under remat keeps it (``T^2`` bytes)
    instead of selecting again in the backward.

    Params: ``Wq [F, n_heads head_dim]``, ``Wk [F, head_dim]``, ``k_gamma,
    k_beta [head_dim]``, ``Ww [F, n_heads]``."""
    n_heads: int = 16
    head_dim: int = 64
    topk: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    query_chunk: int = 512
    frozen: bool = True

    def set_n_in(self, in_type: InputType) -> None:
        if in_type.kind != "rnn":
            raise ValueError(
                f"SparseIndexerLayer expects RNN input, got {in_type}")
        self.n_in = in_type.size

    def infer_output_type(self, in_type: InputType) -> InputType:
        # a row of the selection a query: as many entries as there are keys
        return InputType.recurrent(in_type.timesteps or 0, in_type.timesteps)

    def propagate_mask(self, mask):
        return None

    def param_order(self) -> List[str]:
        return ["Wq", "Wk", "k_gamma", "k_beta", "Ww"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, H, D = self.n_in, self.n_heads, self.head_dim
        ks = jax.random.split(rng, 3)
        return {"Wq": self._init_w(ks[0], (F, H * D), F, H * D, dtype),
                "Wk": self._init_w(ks[1], (F, D), F, D, dtype),
                "k_gamma": jnp.ones((D,), dtype),
                "k_beta": jnp.zeros((D,), dtype),
                "Ww": self._init_w(ks[2], (F, H), F, H, dtype)}

    def index_parts(self, params, u, positions):
        """``(qI [B, H, T, D], kI [B, T, D], w [B, T, H])``, float32."""
        B, T, _ = u.shape
        H, D = self.n_heads, self.head_dim
        f32 = jnp.promote_types(u.dtype, jnp.float32)
        with jax.named_scope("attn:rope"):
            q = (u @ params["Wq"]).reshape(B, T, H, D).transpose(0, 2, 1, 3)
            q = rotary(q.astype(f32), positions, self.rope_theta)
            k = (u @ params["Wk"]).astype(f32)
            mean = jnp.mean(k, axis=-1, keepdims=True)
            var = jnp.mean((k - mean) ** 2, axis=-1, keepdims=True)
            k = ((k - mean) * jax.lax.rsqrt(var + self.norm_eps)
                 * params["k_gamma"].astype(f32)
                 + params["k_beta"].astype(f32))
            k = rotary(k, positions, self.rope_theta)
        return q, k, (u @ params["Ww"]).astype(f32)

    def apply(self, params, x, *, state, train, rng, mask=None):
        from deeplearning4j_tpu.profiling.metrics import get_registry
        u = jax.lax.stop_gradient(x)
        params = jax.lax.stop_gradient(params)
        B, T, _ = u.shape
        q, k, w = self.index_parts(params, u, jnp.arange(T))
        C = min(self.query_chunk, T)
        qc, wc = query_chunks(q, 2, C), query_chunks(w, 1, C)
        get_registry().labeled_counter(
            "sparse_select_traces_total",
            "selections of a sparse attention layer's keys by the path "
            "that makes them (per trace)",
        ).labels(path="all" if self.topk >= T else "threshold").inc()

        def select(q_i, w_i, first):
            with jax.named_scope("dsa:index"):
                hits = jax.nn.relu(jnp.einsum(
                    "bhqd,bkd->bhqk", q_i, k,
                    preferred_element_type=jnp.float32))
                # sixteen weighted maps summed where they are made, in
                # float32 (a product over 16 would round them)
                scores = jnp.sum(
                    hits * w_i.transpose(0, 2, 1)[..., None], axis=1)
            with jax.named_scope("dsa:topk"):
                return top_keys(scores, first, self.topk)

        def every(q_i, w_i, first):
            return jnp.broadcast_to(
                seen_keys(first, C, T).astype(jnp.int8), (B, C, T))

        def chunk(args):
            # q_i [B, H, C, D], w_i [B, C, H]. No score is made for a chunk
            # that keeps every key it has seen: the first ``topk // C``
            return jax.lax.cond(keeps_all(args[2], C, self.topk), every,
                                select, *args)

        sel = jax.lax.map(chunk, (qc, wc, jnp.arange(len(qc)) * C))
        sel = jnp.moveaxis(sel, 0, 1).reshape(B, -1, T)[:, :T]  # [n,B,C,T]
        return sel, state


def attention_selected(q: Array, k: Array, v: Array, select: Array,
                       chunk: int = 512) -> Array:
    """Causal softmax attention of ``q, k, v [B, H, T, D]`` over the keys
    ``select [B, T, T]`` names, in plain XLA, ``chunk`` queries at a time
    (``[B, H, chunk, T]`` of scores a turn). A query with no key gives
    zeros. The path of "off", of float64 and of shapes the flash kernels'
    gate refuses."""
    B, H, T, D = q.shape
    C = min(chunk, T)
    qc, sc = query_chunks(q, 2, C), query_chunks(select, 1, C)
    scale = 1.0 / math.sqrt(D)

    def rows(args):
        q_i, s_i, first = args
        t = first + jnp.arange(C)[:, None]
        seen = (s_i != 0) & (jnp.arange(T)[None, :] <= t)     # [B, C, T]
        logits = jnp.einsum("bhqd,bhkd->bhqk", q_i, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(seen[:, None], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        p = jnp.where(jnp.any(seen, axis=-1)[:, None, :, None], p, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)

    out = jax.lax.map(jax.checkpoint(rows),
                      (qc, sc, jnp.arange(len(qc)) * C))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, -1, D)[:, :, :T]


@register_layer
@dataclass
class GroupedQueryAttentionLayer(SelfAttentionLayer):
    """Causal grouped-query attention over the stream's ``u [B, T, F]``,
    with rotary positions and a norm by head, each of which can be off::

        q = rotary(RMSNorm_D(W_q u) g_q)   n_heads heads of head_dim
        k = rotary(RMSNorm_D(W_k u) g_k)   n_kv_heads heads
        o_th = sum_{s in S_t} softmax_{s in S_t}(q_th . k_s / sqrt(D)) v_s
        y = W_o concat_h o_h

    with query head ``h`` reading key/value head ``h // (n_heads /
    n_kv_heads)`` and one gain of ``head_dim`` each for q and k (none
    without ``qk_norm``; no turn without ``rotate``). No bias. ``S_t`` is
    every key ``s <= t``; with ``window`` those with ``0 <= t - s <
    window``; with ``selected`` (the default) the node takes a second input,
    the selection ``[B, T, T]`` of a ``SparseIndexerLayer``, and ``S_t`` is
    the keys its row names and no others.

    The selection and the window are operands of the flash kernels
    (``flash_attention(..., select=, window=)``) behind
    ``SelfAttentionLayer``'s seam; where their gate refuses, or the mode is
    off, a selected layer runs in XLA in query chunks
    (``attention_selected``), a refusal counted under
    ``kernel="flash_select"``. A windowed layer's attention runs under
    ``jax.named_scope("attn:window")``, so its kernels are told from a full
    layer's in a profile. Trains; no incremental decode, no
    sequence-parallel ring.

    Params: ``Wq [F, H D]``, ``Wk, Wv [F, G D]``, ``q_gamma, k_gamma [D]``
    (with ``qk_norm``), ``Wo [H D, F]``."""
    n_kv_heads: int = 0         # default n_heads
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    causal: bool = True
    sequence_parallel: bool = False
    selected: bool = True
    window: Optional[int] = None
    rotate: bool = True
    qk_norm: bool = True

    supports_kv_cache = False

    @property
    def N_INPUTS(self) -> int:
        return 2 if self.selected else 1

    def set_n_in(self, in_type: InputType) -> None:
        super().set_n_in(in_type)
        if not self.n_kv_heads:
            self.n_kv_heads = self.n_heads
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"GroupedQueryAttentionLayer({self.name!r}): n_heads a "
                f"multiple of n_kv_heads and an even head_dim, got "
                f"{self.n_heads}, {self.n_kv_heads}, {self.head_dim}")
        if self.selected and self.window is not None:
            raise ValueError(
                f"GroupedQueryAttentionLayer({self.name!r}): a selection "
                f"and a window together (attention_selected, the selected "
                f"path's XLA side, knows no window)")

    def set_side_inputs(self, in_types) -> None:
        (sel,) = in_types
        if sel.kind != "rnn":
            raise ValueError(
                f"GroupedQueryAttentionLayer({self.name!r}): a selection "
                f"[B, T, T] expected as second input, got {sel}")

    def param_order(self) -> List[str]:
        gains = ["q_gamma", "k_gamma"] if self.qk_norm else []
        return ["Wq", "Wk", "Wv", *gains, "Wo"]

    def init_params(self, rng, dtype=jnp.float32) -> Params:
        F, D = self.n_in, self.head_dim
        HD, GD = self.n_heads * D, self.n_kv_heads * D
        ks = jax.random.split(rng, 4)
        p = {"Wq": self._init_w(ks[0], (F, HD), F, HD, dtype),
             "Wk": self._init_w(ks[1], (F, GD), F, GD, dtype),
             "Wv": self._init_w(ks[2], (F, GD), F, GD, dtype),
             "Wo": self._init_w(ks[3], (HD, F), HD, F, dtype)}
        if self.qk_norm:
            p.update(q_gamma=jnp.ones((D,), dtype),
                     k_gamma=jnp.ones((D,), dtype))
        return p

    def apply(self, params, x, *, state, train, rng, mask=None):
        u, select = x if self.selected else (x, None)
        u = self._dropout_input(u, train, rng)
        B, T, _ = u.shape
        H, G, D = self.n_heads, self.n_kv_heads, self.head_dim
        heads = lambda a, n: a.reshape(B, T, n, D).transpose(0, 2, 1, 3)

        def turned(a, g):
            if self.qk_norm:
                a = (rms_normalize(a, self.norm_eps)
                     * params[g].astype(jnp.promote_types(a.dtype,
                                                          jnp.float32))
                     ).astype(u.dtype)
            if self.rotate:
                a = rotary(a, jnp.arange(T), self.rope_theta)
            return a

        with (jax.named_scope("attn:rope") if self.rotate or self.qk_norm
              else contextlib.nullcontext()):
            q = turned(heads(u @ params["Wq"], H), "q_gamma")
            k = turned(heads(u @ params["Wk"], G), "k_gamma")
        v = heads(u @ params["Wv"], G)
        # query head h reads key/value head h // (H / G)
        k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
        with (jax.named_scope("attn:window") if self.window is not None
              else contextlib.nullcontext()):
            out = self._attend_heads(q, k, v, mask, window=self.window,
                                     select=select)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * D) @ params["Wo"]
        if mask is not None:
            out = out * mask[..., None]
        return out, state
