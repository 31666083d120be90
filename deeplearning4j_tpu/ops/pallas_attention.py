"""Pallas TPU flash-attention kernel (forward + FA2-style backward).

The single-device attention path in ``nn/layers/attention.py`` composes
XLA einsums (reference impl) or a ``lax.scan`` over KV blocks (blockwise
impl). This module is the MXU-native version of the same math: one
kernel invocation per (batch*head, q-block) computes online-softmax
attention with the score tile, running max and normalizer all resident
in VMEM — no [T, T] score matrix ever reaches HBM, and the K/V panels
stream through the MXU at 128-wide tiles. Backward is the standard
FlashAttention-2 recomputation: per-row ``D = rowsum(dO * O)`` plus the
saved logsumexp lets dq and dk/dv kernels rebuild the probability tiles
block-by-block instead of storing them.

Same dispatch seam as the fused LSTM (the reference's cuDNN-helper
discovery pattern, ConvolutionLayer.java:55-77): ``attention_mode()``
reads ``DL4J_TPU_PALLAS`` — compiled on TPU by default, interpret for
CPU CI, off to force the XLA paths. Parity between the kernel and
``attention_reference`` is enforced by tests/test_pallas_attention.py.

Shapes: q, k, v are [B, H, T, D] (self-attention: same T). The kernel
pads T to the 128-lane block and D to 128 internally; padded KV columns
are masked with the same additive bias that carries ``kv_mask``.

Future work: the ring-attention path (parallel/sequence.py) still uses
the lax.scan blockwise kernel for its per-shard step — composing ring
steps needs the (unnormalized acc, running max, lse) carry, so routing
it through this kernel means exposing a partial-softmax variant and
threading the FA2 residuals through the ppermute schedule.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas_kernels import (
    VMEM_GATE_BYTES, _round_up, lstm_mode, vmem_limit,
)

NEG_INF = -1e30
# q/k block = MXU tile width. Per-row vectors (lse, rowsum(dO*O)) travel
# as [G, Tp, _BLK] arrays too, the value repeated along the lane axis: a
# [_BLK, _BLK] block satisfies the (8, 128) tiling and has the shape of
# the score tile, so the backward subtracts it from the scores
# elementwise with no column-to-lane relayout inside the kernel.
_BLK = 128


def attention_mode() -> str:
    """'compiled' | 'interpret' | 'off' — shared helper-discovery rule
    (same env knob as the LSTM kernel)."""
    return lstm_mode()


def flash_vmem_bytes(T: int, D: int = 128, itemsize: int = 4) -> int:
    """VMEM the largest of the three kernels (dk/dv) asks for, counting
    what Pallas allocates: every BlockSpec operand is double-buffered,
    the whole-sequence operands are [Tp, Dp] panels (K and V in the
    forward and dq kernels, Q and dO in dk/dv) and [Tp, _BLK] f32 row
    vectors (lse, rowsum(dO*O)), and the loop body holds a handful of
    [_BLK, _BLK] / [_BLK, Dp] f32 tiles."""
    Tp = _round_up(T, _BLK)
    Dp = _round_up(D, _BLK)
    panels = 2 * Tp * Dp * itemsize            # two whole-sequence panels
    rows = 2 * Tp * _BLK * 4                   # lse + dvec, whole sequence
    blocks = 4 * _BLK * Dp * itemsize + 8 * _BLK  # k, v in; dk, dv out; bias
    tiles = 8 * _BLK * max(_BLK, Dp) * 4       # s, p, dp, ds, q, do, dk, dv
    return 2 * (panels + rows + blocks) + tiles


def flash_ok(T: int, D: int = 128, itemsize: int = 4) -> bool:
    """Shape gate: the kernels keep whole-sequence panels on-chip, so a
    long T (or a very wide head) must go to the XLA path, not die in
    Mosaic. Counts :func:`flash_vmem_bytes` against ``VMEM_GATE_BYTES``."""
    return flash_vmem_bytes(T, D, itemsize) <= VMEM_GATE_BYTES


def _params(T: int, D: int, itemsize: int):
    # no carry between grid steps in any of the three kernels
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=vmem_limit(flash_vmem_bytes(T, D, itemsize)))


def _blk_slice(j):
    return pl.dslice(pl.multiple_of(j * _BLK, _BLK), _BLK)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                causal: bool, n_kv: int, scale: float):
    q = q_ref[0].astype(jnp.float32) * scale          # [Bq, Dp]
    Bq = q.shape[0]
    qi = pl.program_id(1)
    q_pos = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, _BLK), 0)

    def body(j, carry):
        acc, m, l = carry                              # m, l: [Bq, 1]
        kblk = k_ref[0, _blk_slice(j), :].astype(jnp.float32)
        vblk = v_ref[0, _blk_slice(j), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bq, BLK]
        s = s + bias_ref[0, :, _blk_slice(j)]           # [1, BLK] over rows
        if causal:
            k_pos = j * _BLK + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, _BLK), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    Dp = q_ref.shape[-1]
    acc0 = jnp.zeros((Bq, Dp), jnp.float32)
    m0 = jnp.full((Bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, 1), jnp.float32)
    # causal: KV blocks past the q block's diagonal are wholly masked —
    # skip them instead of feeding NEG_INF tiles to the MXU (Bq == BLK,
    # so block j is live iff j <= qi)
    hi = jnp.minimum(qi + 1, n_kv) if causal else n_kv
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    # a fully-masked row (zero valid keys) never raises m off NEG_INF —
    # float absorption keeps l > 0 there (exp(s - m) == exp(0)), so the
    # validity test must be on m, not l: masked rows emit a zero output
    # and an EXACT NEG_INF lse, which is what the backward kernels gate
    # their recomputed probabilities on (ADVICE r5)
    valid = m > NEG_INF / 2
    o_ref[0] = jnp.where(valid, acc / l_safe, 0.0).astype(o_ref.dtype)
    lse = jnp.where(valid, m + jnp.log(l_safe), NEG_INF)
    lse_ref[0] = jnp.broadcast_to(lse, (Bq, _BLK))


def _run_fwd(q, k, v, bias, causal, interpret):
    """q,k,v: [G, Tp, Dp]; bias: [G, 1, Tp] additive (0 / NEG_INF).
    Returns (out [G, Tp, Dp], lse [G, Tp, _BLK] lane-replicated)."""
    G, Tp, Dp = q.shape
    n_q = Tp // _BLK
    return pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, n_kv=Tp // _BLK,
                          scale=1.0 / math.sqrt(Dp)),
        grid=(G, n_q),
        in_specs=[
            pl.BlockSpec((1, _BLK, Dp), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, 1, Tp), lambda g, i: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _BLK, Dp), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, _BLK, _BLK), lambda g, i: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, Tp, Dp), q.dtype),
            jax.ShapeDtypeStruct((G, Tp, _BLK), jnp.float32),
        ],
        compiler_params=_params(Tp, Dp, q.dtype.itemsize),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v, bias)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 recomputation)
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dvec_ref,
               dq_ref, *, causal: bool, n_kv: int, scale: float):
    q = q_ref[0].astype(jnp.float32)                  # [Bq, Dp]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                  # [Bq, BLK] replicated
    dvec = dvec_ref[0]                                # [Bq, BLK] replicated
    Bq = q.shape[0]
    qi = pl.program_id(1)
    q_pos = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, _BLK), 0)

    def body(j, dq):
        kblk = k_ref[0, _blk_slice(j), :].astype(jnp.float32)
        vblk = v_ref[0, _blk_slice(j), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0, :, _blk_slice(j)]
        if causal:
            k_pos = j * _BLK + jax.lax.broadcasted_iota(
                jnp.int32, (Bq, _BLK), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        # fully-masked query rows (zero valid keys) carry lse == NEG_INF
        # from the forward; exp(s - lse) there is garbage (float
        # absorption, not inf) — gate them to zero probability so the
        # row's gradients are exactly zero (ADVICE r5)
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dvec)
        return dq + jax.lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    dq0 = jnp.zeros(q.shape, jnp.float32)
    hi = jnp.minimum(qi + 1, n_kv) if causal else n_kv
    dq_ref[0] = jax.lax.fori_loop(0, hi, body, dq0).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dvec_ref,
                dk_ref, dv_ref, *, causal: bool, n_q: int, scale: float):
    kblk = k_ref[0].astype(jnp.float32)               # [Bk, Dp]
    vblk = v_ref[0].astype(jnp.float32)
    bias = bias_ref[0]                                # [1, Bk]
    Bk = kblk.shape[0]
    ki = pl.program_id(1)
    k_pos = ki * Bk + jax.lax.broadcasted_iota(jnp.int32, (_BLK, Bk), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, _blk_slice(i), :].astype(jnp.float32)
        do = do_ref[0, _blk_slice(i), :].astype(jnp.float32)
        lse = lse_ref[0, _blk_slice(i), :]             # [Bq, Bk] replicated
        dvec = dvec_ref[0, _blk_slice(i), :]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = s + bias
        if causal:
            q_pos = i * _BLK + jax.lax.broadcasted_iota(
                jnp.int32, (_BLK, Bk), 0)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        # same masked-row gate as _dq_kernel: rows with lse == NEG_INF
        # (no valid key) must contribute zero to dk/dv
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dvec)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        return dk, dv

    z = jnp.zeros(kblk.shape, jnp.float32)
    # causal: q blocks above the diagonal never attend to this KV block
    lo = ki if causal else 0
    dk, dv = jax.lax.fori_loop(lo, n_q, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _run_bwd(q, k, v, bias, do, out, lse, causal, interpret):
    G, Tp, Dp = q.shape
    scale = 1.0 / math.sqrt(Dp)
    dvec = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)             # [G, Tp, 1]
    dvec = jnp.broadcast_to(dvec, (G, Tp, _BLK))
    qspec = pl.BlockSpec((1, _BLK, Dp), lambda g, i: (g, i, 0))
    fullspec = pl.BlockSpec((1, Tp, Dp), lambda g, i: (g, 0, 0))
    rowspec = pl.BlockSpec((1, _BLK, _BLK), lambda g, i: (g, i, 0))
    fullrow = pl.BlockSpec((1, Tp, _BLK), lambda g, i: (g, 0, 0))
    biasfull = pl.BlockSpec((1, 1, Tp), lambda g, i: (g, 0, 0))
    biasblk = pl.BlockSpec((1, 1, _BLK), lambda g, i: (g, 0, i))
    params = _params(Tp, Dp, q.dtype.itemsize)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, n_kv=Tp // _BLK,
                          scale=scale),
        grid=(G, Tp // _BLK),
        in_specs=[qspec, fullspec, fullspec, biasfull, qspec, rowspec,
                  rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((G, Tp, Dp), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, bias, do, lse, dvec)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, n_q=Tp // _BLK,
                          scale=scale),
        grid=(G, Tp // _BLK),
        in_specs=[fullspec, qspec, qspec, biasblk, fullspec, fullrow,
                  fullrow],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((G, Tp, Dp), k.dtype),
                   jax.ShapeDtypeStruct((G, Tp, Dp), v.dtype)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, bias, do, lse, dvec)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable core + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_core(q, k, v, bias, causal, interpret):
    out, _ = _run_fwd(q, k, v, bias, causal, interpret)
    return out


def _flash_core_fwd(q, k, v, bias, causal, interpret):
    out, lse = _run_fwd(q, k, v, bias, causal, interpret)
    return out, (q, k, v, bias, out, lse)


def _flash_core_bwd(causal, interpret, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv = _run_bwd(q, k, v, bias, g, out, lse, causal, interpret)
    return dq, dk, dv, jnp.zeros_like(bias)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    kv_mask: Optional[jnp.ndarray] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """softmax(QK^T/sqrt(D))V via the Pallas kernels. q,k,v: [B,H,T,D]
    (self-attention: shared T). ``kv_mask``: [B, T] key validity.

    NOTE the softmax scale uses the PADDED head dim when D is not a
    multiple of 128 — callers pre-scale q so the math matches the
    unpadded reference exactly (this function does that internally)."""
    B, H, T, D = q.shape
    Tp, Dp = _round_up(T, _BLK), _round_up(D, _BLK)
    # the kernel divides by sqrt(Dp); fold the correction into q
    q = q * (math.sqrt(Dp) / math.sqrt(D))

    def prep(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Tp - T), (0, Dp - D)))
        return x.reshape(B * H, Tp, Dp)

    qf, kf, vf = prep(q), prep(k), prep(v)
    valid = jnp.ones((B, T), jnp.float32) if kv_mask is None \
        else kv_mask.astype(jnp.float32)
    valid = jnp.pad(valid, ((0, 0), (0, Tp - T)))
    bias = jnp.where(valid > 0, 0.0, NEG_INF).astype(jnp.float32)
    bias = jnp.repeat(bias, H, axis=0)[:, None, :]     # [B*H, 1, Tp]
    out = _flash_core(qf, kf, vf, bias, causal, interpret)
    return out.reshape(B, H, Tp, Dp)[:, :, :T, :D]
