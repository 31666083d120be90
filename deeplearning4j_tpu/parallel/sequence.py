"""Sequence/context parallelism: ring attention over a mesh axis.

The reference has no sequence parallelism (SURVEY §5.7); this is the
first-class long-context path of the TPU build. Ring attention
(Liu et al.): shard the sequence over mesh axis ``sp``; each device holds
Q/K/V shards, iterates n_sp steps, computing blockwise attention of its Q
shard against the KV shard currently resident, then passes KV to the next
ring neighbor with ``jax.lax.ppermute`` over ICI. Compute overlaps
communication (the permute is issued alongside the attention block), and
the flash-style log-sum-exp accumulators make the per-step partial results
exactly composable.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.nn.layers.attention import (
    NEG_INF, blockwise_attention, finalize_attention,
)


def ring_attention_sharded(q, k, v, axis_name: str, *, causal: bool = False,
                           block_size: int = 512, kv_mask=None):
    """Runs INSIDE shard_map. q,k,v: local shards [B, H, T_local, D];
    the global sequence is axis_size * T_local. ``kv_mask``: the local
    [B, T_local] key-validity shard (sequence padding) — it rotates
    around the ring alongside its KV shard. Returns the local output
    shard [B, H, T_local, D]."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    T_local = q.shape[2]
    q_offset = my_idx * T_local
    # when unmasked, keep the 5-element carry: an all-ones mask would
    # still be ppermuted every ring step (a dead ICI collective per layer)
    has_mask = kv_mask is not None

    def step(carry, i):
        if has_mask:
            out, m, lse, k_cur, v_cur, mask_cur = carry
        else:
            out, m, lse, k_cur, v_cur = carry
            mask_cur = None
        # which device's KV shard are we holding at ring step i?
        src = (my_idx - i) % axis_size
        o_blk, m_blk, lse_blk = blockwise_attention(
            q, k_cur, v_cur, block_size=block_size, causal=False,
            kv_mask=mask_cur)  # None when unmasked
        if causal:
            # causal across shards: KV shard `src` is fully visible if
            # src < my_idx, invisible if src > my_idx, diagonal if equal.
            kv_offset = src * T_local
            q_pos = q_offset + jnp.arange(T_local)
            # recompute the diagonal block with exact causal mask
            o_diag, m_diag, lse_diag = blockwise_attention(
                q, k_cur, v_cur, block_size=block_size, causal=True,
                q_offset=q_offset - kv_offset, kv_mask=mask_cur)
            fully_visible = src < my_idx
            o_blk = jnp.where(fully_visible, o_blk, o_diag)
            m_blk = jnp.where(fully_visible, m_blk, m_diag)
            lse_blk = jnp.where(fully_visible, lse_blk, lse_diag)
            invisible = src > my_idx
            o_blk = jnp.where(invisible, 0.0, o_blk)
            m_blk = jnp.where(invisible, NEG_INF, m_blk)
            lse_blk = jnp.where(invisible, 0.0, lse_blk)
        # combine running accumulators (same algebra as blockwise inner loop)
        m_new = jnp.maximum(m, m_blk)
        corr_old = jnp.exp(m - m_new)
        corr_blk = jnp.exp(m_blk - m_new)
        out = out * corr_old[..., None] + o_blk * corr_blk[..., None]
        lse = lse * corr_old + lse_blk * corr_blk
        # rotate KV (and its validity mask) around the ring (ICI hop)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        if has_mask:
            mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
            return (out, m_new, lse, k_nxt, v_nxt, mask_nxt), None
        return (out, m_new, lse, k_nxt, v_nxt), None

    # q-derived initial carries: correct varying-manual-axes under shard_map
    out0 = q * 0.0
    m0 = q[..., 0] * 0.0 + NEG_INF
    lse0 = q[..., 0] * 0.0
    carry0 = ((out0, m0, lse0, k, v, kv_mask) if has_mask
              else (out0, m0, lse0, k, v))
    final_carry, _ = jax.lax.scan(step, carry0, jnp.arange(axis_size))
    out, m, lse = final_carry[:3]
    return finalize_attention(out, lse)


def ring_self_attention(x, params, mesh: Mesh, *, n_heads: int,
                        head_dim: int, seq_axis: str = "data",
                        batch_axis: Optional[str] = None,
                        causal: bool = False, block_size: int = 512,
                        mask=None):
    """Full sequence-parallel self attention: x [B, T, F] sharded over
    ``seq_axis`` on its T dimension (and over ``batch_axis`` on B when
    composing with data parallelism — without it every dp device would
    redundantly attend over the whole batch); QKV projections are local,
    attention runs as a ring. ``mask``: [B, T] sequence-padding validity
    — its key shard rotates with the KVs and the output is zeroed at
    masked query positions, matching the local layer path. Entry point
    used by SelfAttentionLayer when a mesh context is active, and
    directly by transformer blocks."""
    from jax import shard_map

    def local_fn(x_l, Wq, Wk, Wv, Wo, *mask_rest):
        mask_l = mask_rest[0] if mask_rest else None
        B, T_l, F = x_l.shape

        def split(h):
            return h.reshape(B, T_l, n_heads, head_dim).transpose(0, 2, 1, 3)

        q, k, v = split(x_l @ Wq), split(x_l @ Wk), split(x_l @ Wv)
        out = ring_attention_sharded(
            q, k, v, seq_axis, causal=causal, block_size=block_size,
            kv_mask=None if mask is None else mask_l)
        out = out.transpose(0, 2, 1, 3).reshape(B, T_l, n_heads * head_dim)
        out = out @ Wo
        if mask is not None:
            out = out * mask_l[..., None]
        return out

    spec_x = P(batch_axis, seq_axis, None)
    spec_w = P()
    in_specs = [spec_x, spec_w, spec_w, spec_w, spec_w]
    args = [x, params["Wq"], params["Wk"], params["Wv"], params["Wo"]]
    if mask is not None:
        in_specs.append(P(batch_axis, seq_axis))
        args.append(jnp.asarray(mask, x.dtype))
    fn = shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=spec_x)
    return fn(*args)
