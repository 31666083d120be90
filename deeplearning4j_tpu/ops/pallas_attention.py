"""Pallas TPU flash-attention kernel (forward + FA2-style backward).

The single-device attention path in ``nn/layers/attention.py`` composes
XLA einsums (reference impl) or a ``lax.scan`` over KV blocks (blockwise
impl). This module is the MXU-native version of the same math: one
kernel invocation per (batch*head, q-block) computes online-softmax
attention with the score tile, running max and normalizer all resident
in VMEM — no [T, T] score matrix ever reaches HBM, and the K/V panels
stream through the MXU in blocks of up to 512 rows. Backward is the standard
FlashAttention-2 recomputation: per-row ``D = rowsum(dO * O)`` plus the
saved logsumexp lets dq and dk/dv kernels rebuild the probability tiles
block-by-block instead of storing them.

Same dispatch seam as the fused LSTM (the reference's cuDNN-helper
discovery pattern, ConvolutionLayer.java:55-77): ``attention_mode()``
reads ``DL4J_TPU_PALLAS`` — compiled on TPU by default, interpret for
CPU CI, off to force the XLA paths. Parity between the kernel and
``attention_reference`` is enforced by tests/test_pallas_attention.py.

Precision: the MXU's operands have the dtype of the kernel's inputs and
every product accumulates in float32 (``preferred_element_type``).
bfloat16 q, k, v and dO go to the MXU as they are; the probabilities
``P`` and ``dS = P * (dP - D)`` are formed in float32 and cast to the
operand dtype where they enter ``P V``, ``P^T dO``, ``dS K`` and
``dS^T Q``; with float32 inputs each cast is the identity and every
product has float32 operands (how many MXU passes those take is the
ambient matmul precision's affair: at the default Mosaic makes one
bfloat16 pass of them on a v5e, PERF.md PR 30). Float32 whatever the
inputs are: the scores (the softmax scale multiplies them, never a
bfloat16 q), the additive bias and the causal select, the running max
and normaliser, ``exp``, the logsumexp, ``D = rowsum(dO * O)``, the
accumulators and the masked-row gates on ``NEG_INF``; the outputs are
cast to the inputs' dtype at the end.
``pallas_flash_traces_total`` counts the traces by that dtype.

A causal ``window`` (key ``s`` seen from ``t`` when ``0 <= t - s <
window``) is a second select beside the causal one AND a bound of each
kernel's loop: the forward and dq start at the first block of keys the
window reaches, dk/dv stops after the last block of queries that see its
keys, so at a block of 512 and a window of 512 a grid step makes two tiles
and not up to seventeen. With ``window=None`` the three kernels lower to
what they were before the option (PERF.md, PR 33).
``pallas_flash_traces_total{operands=..., window=..., select=...}`` counts
the traces.

A ``select`` operand (``[B, T, T]``, nonzero where query ``t`` of a batch
row reads key ``s``: the keys a learned indexer chose, shared by the row's
heads) is one more select on the float32 scores of all three kernels. It
reaches the forward and dq as int8 rows by query block (``[B, Tp]`` a grid
step, each turn a ``[B, B]`` tile of it) and dk/dv as the same rows of its
transpose, made once outside the kernels, because dk/dv's tiles are
transposed. A query none of whose keys is selected is a fully masked row
(zero output, zero gradients). No tile is skipped: the loops keep the
causal bounds. With ``select=None`` the three kernels lower to what they
were before the operand (PERF.md, PR 35).

Under remat (``nn/remat.checkpoint_after_cotangent``, every node of a net
trained with ``conf.training.remat``) the forward kernel runs once: the
node keeps ``out`` and ``lse`` as the kernel wrote them beside its inputs,
and in the node's rebuild the call site takes ``_flash_kept``, whose primal
is the kept ``out`` and whose backward hands the kept pair and the rebuilt
q, k, v to the same two backward kernels. The same bits either way: the
rebuilt forward would have written that pair from those operands. ``lse``
is kept lane-replicated, 134 MB at ``[32, 8192, 128]`` where one lane is
1 MB: cut to a lane and broadcast again before dq it cost 0.7 ms a layer
and, on the sparse decoder's step, moved the compiler's placement of other
layers' operands for 20 ms more (PERF.md, PR 38). Without a remat node
round it (inference, a net trained with remat off, a ``jax.checkpoint`` of
a scan) a call is ``_flash_core``.

Shapes: q, k are [B, H, T, D] and v [B, H, T, Dv] (self-attention: same
T; ``Dv`` may differ, all three are padded to one lane width). The kernel
pads D to 128 and T to its block internally (``_padded_len``: 512, 256 or
128 rows, the largest that costs no more than an eighth of padding);
padded KV columns are masked with the same additive bias that carries
``kv_mask``. One turn of a kernel's inner loop makes a square [B, B]
score tile. The forward and dq hold B queries a grid step and walk the
keys; dk/dv holds B keys and walks the queries on TRANSPOSED tiles
(``S^T = K Q^T``), so that neither ``P^T dO`` nor ``dS^T Q`` transposes a
tile on its way to the MXU.

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
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.remat import kept
from deeplearning4j_tpu.ops.pallas_kernels import (
    VMEM_GATE_BYTES, _round_up, lstm_mode, vmem_limit,
)

NEG_INF = -1e30
# MXU tile width, and the least block. The forward writes its per-row lse
# as a [G, Tp, _BLK] array, the value repeated along the lane axis: a
# [B, _BLK] block satisfies the (8, 128) tiling, and dq repeats it to the
# width of its score tile and subtracts it elementwise, with no
# column-to-lane relayout inside the kernel (dk/dv, whose tiles are
# transposed, reads the same vectors as [1, Tp] rows).
_BLK = 128


def attention_mode() -> str:
    """'compiled' | 'interpret' | 'off' — shared helper-discovery rule
    (same env knob as the LSTM kernel)."""
    return lstm_mode()


def _padded_len(T: int) -> int:
    """The length the kernels run at: ``T`` padded to the largest block of
    512, 256, 128 that costs no more than an eighth over the 128-padding
    (T = 8,320 runs at 8,704 in blocks of 512, not at 8,320 in blocks of
    128; T = 300 runs at 384)."""
    base = _round_up(T, _BLK)
    return next(Tp for Tp in (_round_up(T, b) for b in (512, 256, _BLK))
                if 8 * Tp <= 9 * base)


def _block(Tp: int) -> int:
    """Side of the square score tile one turn of a kernel's inner loop
    makes, and the rows a grid step holds (queries in the forward and dq,
    keys in dk/dv): the most of 512, 256, 128 that divides the padded
    length. A turn costs some 260 ns whatever it holds and the per-row
    vectors (running max, normaliser, the rescaled accumulator) a pass
    over the rows whatever the tile's width, so the kernels are at 1.3 to
    1.8 times the products' own time on a v5e at 512 and at 5 to 9 times
    at 128; past 512 nothing more is gained (PERF.md, PR 30)."""
    return next(b for b in (512, 256, _BLK) if Tp % b == 0)


def _vmem_bytes(Tp: int, Dp: int, itemsize: int,
                selected: bool = False) -> int:
    B = _block(Tp)
    panels = 2 * Tp * Dp * itemsize
    blocks = 4 * B * Dp * itemsize
    vectors = (2 * B * _BLK + 2 * 8 * Tp) * 4
    tiles = (8 * B * B + 4 * B * Dp) * 4
    # a selection's int8 rows, [B, Tp] a grid step, and one more tile
    rows = B * Tp + 2 * B * B if selected else 0
    return 2 * (panels + blocks + vectors + rows) + tiles


def flash_vmem_bytes(T: int, D: int = 128, itemsize: int = 4,
                     selected: bool = False) -> int:
    """VMEM the largest of the three kernels asks for, counting what
    Pallas allocates, with B the block of the padded length. Every
    BlockSpec operand is double-buffered and has the inputs' ``itemsize``:
    two whole-sequence [Tp, Dp] panels (K and V in the forward and dq
    kernels, Q and dO in dk/dv) and four [B, Dp] blocks (k, v in and dk,
    dv out). The per-row vectors are float32 whatever the inputs are: two
    lane-replicated [B, _BLK] blocks (lse and rowsum(dO*O) in dq; the
    keys' bias column in dk/dv pads to one) and two [1, Tp] rows padded
    to 8 sublanes (dk/dv's lse and rowsum(dO*O); the bias row elsewhere).
    The loop body's tiles are float32 too, and single: eight of [B, B]
    (s, p, dp, ds, the positions, the broadcast bias and vectors) and
    four of [B, Dp] (the accumulators and their updates). ``selected``:
    the int8 ``[B, Tp]`` rows of a selection, double-buffered too, and
    the tile of them a turn widens."""
    return _vmem_bytes(_padded_len(T), _round_up(D, _BLK), itemsize,
                       selected)


def flash_ok(T: int, D: int = 128, itemsize: int = 4,
             selected: bool = False) -> bool:
    """Shape gate: the kernels keep whole-sequence panels on-chip, so a
    long T (or a very wide head) must go to the XLA path, not die in
    Mosaic. Counts :func:`flash_vmem_bytes` against ``VMEM_GATE_BYTES``."""
    return flash_vmem_bytes(T, D, itemsize, selected) <= VMEM_GATE_BYTES


def _params(Tp: int, Dp: int, itemsize: int, selected: bool = False):
    # no carry between grid steps in any of the three kernels
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=vmem_limit(_vmem_bytes(Tp, Dp, itemsize,
                                                selected)))


def _dot(a, b, contract_b: int = 0):
    """``a @ b`` on the MXU with a float32 accumulator (``a @ b.T`` for
    ``contract_b=1``). Float32 operands follow the ambient matmul
    precision, as every product of the program does; narrower ones are
    exact products whatever it says, and Mosaic refuses them under
    "highest", so they name the default."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, (((1,), (contract_b,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return _dot(a, b, 1)


def _blk_slice(j, B: int):
    return pl.dslice(pl.multiple_of(j * B, B), B)


def _lanes(x, B: int):
    """A lane-replicated [B, _BLK] per-row vector at the width of a [B, B]
    score tile."""
    return jnp.concatenate([x] * (B // _BLK), axis=1)


def _chosen(sel_ref, j, B: int):
    """The ``[B, B]`` tile of a selection's rows at block ``j`` of the lane
    axis, as a predicate."""
    return sel_ref[0, :, _blk_slice(j, B)].astype(jnp.int32) != 0


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _first_key_block(qi, B: int, window: int):
    """The first block of keys that a block of queries sees through a
    window: its first query ``qi B`` sees key ``s`` when ``qi B - s <
    window``."""
    return jnp.maximum(qi * B - (window - 1), 0) // B


def _last_query_block(ki, B: int, window: int, n_blocks: int):
    """One past the last block of queries that see a block of keys through
    a window: its last key ``(ki + 1) B - 1`` is seen from ``t`` while ``t -
    (ki + 1) B + 1 < window``."""
    return jnp.minimum(((ki + 1) * B + window - 2) // B + 1, n_blocks)


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, *rest,
                causal: bool, scale: float, window: Optional[int] = None):
    # with a selection its rows come before the outputs
    *sel_ref, o_ref, lse_ref = rest
    # MXU operands (q, k, v, and p below) keep the input dtype; the
    # scale multiplies the float32 scores, never a bfloat16 q
    q = q_ref[0]                                      # [B, Dp]
    B, Dp = q.shape
    qi = pl.program_id(1)
    q_pos = qi * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)

    def body(j, carry):
        acc, m, l = carry                              # m, l: [B, 1]
        kblk = k_ref[0, _blk_slice(j, B), :]
        vblk = v_ref[0, _blk_slice(j, B), :]
        s = _dot_nt(q, kblk) * scale                   # [B, B]
        s = s + bias_ref[0, :, _blk_slice(j, B)]        # [1, B] over rows
        if causal:
            k_pos = j * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if sel_ref:
            s = jnp.where(_chosen(sel_ref[0], j, B), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(vblk.dtype), vblk)
        return acc, m_new, l

    acc0 = jnp.zeros((B, Dp), jnp.float32)
    m0 = jnp.full((B, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, 1), jnp.float32)
    # causal: KV blocks past the q block's diagonal are wholly masked —
    # skip them instead of feeding NEG_INF tiles to the MXU (square
    # blocks, so block j is live iff j <= qi)
    hi = (qi + 1) if causal else k_ref.shape[1] // B
    # a window: blocks wholly before it are skipped too. A row whose window
    # starts past the first block visited sees that block all masked, and
    # what it adds there is wiped when the diagonal block raises m
    lo = 0 if window is None else _first_key_block(qi, B, window)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    # a fully-masked row (zero valid keys) never raises m off NEG_INF —
    # float absorption keeps l > 0 there (exp(s - m) == exp(0)), so the
    # validity test must be on m, not l: masked rows emit a zero output
    # and an EXACT NEG_INF lse, which is what the backward kernels gate
    # their recomputed probabilities on (ADVICE r5)
    valid = m > NEG_INF / 2
    o_ref[0] = jnp.where(valid, acc / l_safe, 0.0).astype(o_ref.dtype)
    lse = jnp.where(valid, m + jnp.log(l_safe), NEG_INF)
    lse_ref[0] = jnp.broadcast_to(lse, (B, _BLK))


def _select_spec(select, G: int, B: int, Tp: int):
    """The ``[B, Tp]`` rows of a ``[batch, Tp, Tp]`` selection at a grid
    step: kernel head ``g`` belongs to batch row ``g // heads``."""
    heads = G // select.shape[0]
    return pl.BlockSpec((1, B, Tp), lambda g, i: (g // heads, i, 0))


def _run_fwd(q, k, v, bias, causal, interpret, scale, window=None,
             select=None):
    """q,k,v: [G, Tp, Dp]; bias: [G, 1, Tp] additive (0 / NEG_INF);
    ``select`` None or ``(rows, transposed rows)``, int8 ``[batch, Tp,
    Tp]`` each. Returns (out [G, Tp, Dp], lse [G, Tp, _BLK]
    lane-replicated)."""
    G, Tp, Dp = q.shape
    B = _block(Tp)
    chosen = () if select is None else (select[0],)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          window=window),
        grid=(G, Tp // B),
        in_specs=[
            pl.BlockSpec((1, B, Dp), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, Tp, Dp), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, 1, Tp), lambda g, i: (g, 0, 0)),
        ] + [_select_spec(x, G, B, Tp) for x in chosen],
        out_specs=[
            pl.BlockSpec((1, B, Dp), lambda g, i: (g, i, 0)),
            pl.BlockSpec((1, B, _BLK), lambda g, i: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, Tp, Dp), q.dtype),
            jax.ShapeDtypeStruct((G, Tp, _BLK), jnp.float32),
        ],
        compiler_params=_params(Tp, Dp, q.dtype.itemsize, bool(chosen)),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v, bias, *chosen)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 recomputation)
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dvec_ref,
               *rest, causal: bool, scale: float,
               window: Optional[int] = None):
    *sel_ref, dq_ref = rest
    q = q_ref[0]                                      # [B, Dp]
    do = do_ref[0]
    B = q.shape[0]
    lse = _lanes(lse_ref[0], B)                       # [B, B] replicated
    dvec = _lanes(dvec_ref[0], B)
    qi = pl.program_id(1)
    q_pos = qi * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)

    def body(j, dq):
        kblk = k_ref[0, _blk_slice(j, B), :]
        vblk = v_ref[0, _blk_slice(j, B), :]
        s = _dot_nt(q, kblk) * scale
        s = s + bias_ref[0, :, _blk_slice(j, B)]
        if causal:
            k_pos = j * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if sel_ref:
            s = jnp.where(_chosen(sel_ref[0], j, B), s, NEG_INF)
        # fully-masked query rows (zero valid keys) carry lse == NEG_INF
        # from the forward; exp(s - lse) there is garbage (float
        # absorption, not inf) — gate them to zero probability so the
        # row's gradients are exactly zero (ADVICE r5)
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)
        dp = _dot_nt(do, vblk)
        ds = (p * (dp - dvec)).astype(kblk.dtype)
        return dq + _dot(ds, kblk) * scale

    dq0 = jnp.zeros(q.shape, jnp.float32)
    hi = (qi + 1) if causal else k_ref.shape[1] // B
    lo = 0 if window is None else _first_key_block(qi, B, window)
    dq_ref[0] = jax.lax.fori_loop(lo, hi, body, dq0).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dvec_ref,
                *rest, causal: bool, scale: float,
                window: Optional[int] = None):
    """One grid step holds B keys and walks the queries in blocks of B, on
    TRANSPOSED score tiles ``S^T = K Q^T``: keys along the sublanes,
    queries along the lanes. ``P^T dO`` and ``dS^T Q`` are then plain
    products (no tile is transposed on its way to the MXU), and the
    per-query lse and rowsum(dO*O) are [1, B] rows that broadcast over
    the sublanes. A selection comes transposed, keys by queries, as the
    tiles are."""
    *sel_ref, dk_ref, dv_ref = rest
    kblk = k_ref[0]                                   # [B, Dp]
    vblk = v_ref[0]
    B = kblk.shape[0]
    ki = pl.program_id(1)
    bias = jnp.broadcast_to(bias_ref[0], (B, B))      # [B, 1] over lanes
    k_pos = ki * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, _blk_slice(i, B), :]
        do = do_ref[0, _blk_slice(i, B), :]
        lse = lse_ref[0, :, _blk_slice(i, B)]          # [1, B]
        dvec = dvec_ref[0, :, _blk_slice(i, B)]
        st = _dot_nt(kblk, q) * scale                  # [keys, queries]
        st = st + bias
        if causal:
            q_pos = i * B + jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
            st = jnp.where(k_pos <= q_pos, st, NEG_INF)
            if window is not None:
                st = jnp.where(q_pos - k_pos < window, st, NEG_INF)
        if sel_ref:
            st = jnp.where(_chosen(sel_ref[0], i, B), st, NEG_INF)
        # same masked-row gate as _dq_kernel: queries with lse == NEG_INF
        # (no valid key) must contribute zero to dk/dv
        pt = jnp.where(lse > NEG_INF / 2, jnp.exp(st - lse), 0.0)
        dv = dv + _dot(pt.astype(do.dtype), do)
        dpt = _dot_nt(vblk, do)
        dst = (pt * (dpt - dvec)).astype(q.dtype)
        dk = dk + _dot(dst, q) * scale
        return dk, dv

    z = jnp.zeros(kblk.shape, jnp.float32)
    # causal: q blocks above the diagonal never attend to this KV block
    lo = ki if causal else 0
    n_blocks = q_ref.shape[1] // B
    # a window: nor do the q blocks wholly past it
    hi = n_blocks if window is None else _last_query_block(
        ki, B, window, n_blocks)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _run_bwd(q, k, v, bias, do, out, lse, causal, interpret, scale,
             window=None, select=None):
    G, Tp, Dp = q.shape
    B = _block(Tp)
    rows, columns = ((), ()) if select is None else (
        (select[0],), (select[1],))
    dvec = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)             # [G, Tp, 1]
    blkspec = pl.BlockSpec((1, B, Dp), lambda g, i: (g, i, 0))
    fullspec = pl.BlockSpec((1, Tp, Dp), lambda g, i: (g, 0, 0))
    vecspec = pl.BlockSpec((1, B, _BLK), lambda g, i: (g, i, 0))
    colspec = pl.BlockSpec((1, B, 1), lambda g, i: (g, i, 0))
    fullrow = pl.BlockSpec((1, 1, Tp), lambda g, i: (g, 0, 0))
    params = _params(Tp, Dp, q.dtype.itemsize, select is not None)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          window=window),
        grid=(G, Tp // B),
        in_specs=[blkspec, fullspec, fullspec, fullrow, blkspec, vecspec,
                  vecspec] + [_select_spec(x, G, B, Tp) for x in rows],
        out_specs=blkspec,
        out_shape=jax.ShapeDtypeStruct((G, Tp, Dp), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, bias, do, lse, jnp.broadcast_to(dvec, (G, Tp, _BLK)), *rows)
    # dk/dv read the per-query vectors as [1, Tp] rows and the keys' bias
    # as a [B, 1] column
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          window=window),
        grid=(G, Tp // B),
        in_specs=[fullspec, blkspec, blkspec, colspec, fullspec, fullrow,
                  fullrow] + [_select_spec(x, G, B, Tp) for x in columns],
        out_specs=[blkspec, blkspec],
        out_shape=[jax.ShapeDtypeStruct((G, Tp, Dp), k.dtype),
                   jax.ShapeDtypeStruct((G, Tp, Dp), v.dtype)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, bias.reshape(G, Tp, 1), do, lse[:, :, 0].reshape(G, 1, Tp),
      dvec.reshape(G, 1, Tp), *columns)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable core + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(q, k, v, bias, select, causal, interpret, scale, window):
    out, _ = _run_fwd(q, k, v, bias, causal, interpret, scale, window,
                      select)
    return out


def _flash_core_fwd(q, k, v, bias, select, causal, interpret, scale, window):
    out, lse = _run_fwd(q, k, v, bias, causal, interpret, scale, window,
                        select)
    return out, (q, k, v, bias, select, out, lse)


def _flash_core_bwd(causal, interpret, scale, window, res, g):
    q, k, v, bias, select, out, lse = res
    dq, dk, dv = _run_bwd(q, k, v, bias, g, out, lse, causal, interpret,
                          scale, window, select)
    # whole numbers take the placeholder cotangent; None has no leaf
    nothing = jax.tree.map(
        lambda x: np.zeros(x.shape, jax.dtypes.float0), select)
    return dq, dk, dv, jnp.zeros_like(bias), nothing


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flash_kept(q, k, v, bias, select, out, lse, causal, interpret, scale,
                window):
    """``_flash_core`` of a call site whose ``out`` and ``lse`` a remat node
    holds (``nn/remat.kept``): no forward kernel, and the backward kernels
    read the held pair beside the rebuilt operands."""
    return out


def _flash_kept_fwd(q, k, v, bias, select, out, lse, causal, interpret,
                    scale, window):
    return out, (q, k, v, bias, select, out, lse)


def _flash_kept_bwd(causal, interpret, scale, window, res, g):
    # the pair is the node's residual, as select is an operand: no gradient
    *grads, nothing = _flash_core_bwd(causal, interpret, scale, window, res,
                                      g)
    out, lse = res[-2:]
    return (*grads, nothing, jnp.zeros_like(out), jnp.zeros_like(lse))


_flash_kept.defvjp(_flash_kept_fwd, _flash_kept_bwd)


def _count_trace(dtype, window: Optional[int], selected: bool) -> None:
    """Which products a run's kernels make, counted once per trace (not
    per step) under the operands' dtype, the window's width and whether a
    selection came."""
    from deeplearning4j_tpu.profiling.metrics import get_registry
    get_registry().labeled_counter(
        "pallas_flash_traces_total",
        "flash-attention traces by the dtype of the MXU operands, the "
        "window and the selection (per trace)",
    ).labels(operands=jnp.dtype(dtype).name,
             window="none" if window is None else window,
             select="rows" if selected else "none").inc()


def flash_attention(q, k, v, *, causal: bool = False,
                    kv_mask: Optional[jnp.ndarray] = None,
                    interpret: bool = False,
                    window: Optional[int] = None,
                    select: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """softmax(QK^T/sqrt(D))V via the Pallas kernels. q, k: [B,H,T,D], v:
    [B,H,T,Dv] (self-attention: shared T; ``Dv`` may differ from ``D``, as
    where two score maps of head 64 share a value of 128). ``kv_mask``:
    [B, T] key validity. ``window`` (with ``causal``): key ``s`` is seen
    from ``t`` when ``0 <= t - s < window``; the kernels' loops leave out
    the blocks of keys wholly outside it. ``select``: ``[B, T, T]``,
    nonzero where query ``t`` reads key ``s``, the same for every head of a
    batch row; a select beside the others, so with ``causal`` a selected
    key past the query stays unseen.

    The products' operands have ``q.dtype`` (k and v are brought to it)
    and their accumulators are float32: bfloat16 inputs reach the MXU as
    they are, float32 inputs are not narrowed. The softmax scale is that
    of the UNPADDED head dim ``D`` and multiplies the float32 scores."""
    B, H, T, D = q.shape
    Dv = v.shape[-1]
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is causal and at least 1 wide, got "
                         f"causal={causal}, window={window}")
    Tp, Dp = _padded_len(T), _round_up(max(D, Dv), _BLK)
    _count_trace(q.dtype, window, select is not None)

    def prep(x):
        x = jnp.pad(x.astype(q.dtype), ((0, 0), (0, 0), (0, Tp - T),
                                        (0, Dp - x.shape[-1])))
        return x.reshape(B * H, Tp, Dp)

    qf, kf, vf = prep(q), prep(k), prep(v)
    valid = jnp.ones((B, T), jnp.float32) if kv_mask is None \
        else kv_mask.astype(jnp.float32)
    valid = jnp.pad(valid, ((0, 0), (0, Tp - T)))
    bias = jnp.where(valid > 0, 0.0, NEG_INF).astype(jnp.float32)
    bias = jnp.repeat(bias, H, axis=0)[:, None, :]     # [B*H, 1, Tp]
    if select is not None:
        rows = jnp.pad((select != 0).astype(jnp.int8),
                       ((0, 0), (0, Tp - T), (0, Tp - T)))
        select = (rows, rows.transpose(0, 2, 1))
    static = (causal, interpret, 1.0 / math.sqrt(D), window)

    # a remat node keeps the forward's pair as the kernel writes it
    held = kept("flash_attention", lambda: _run_fwd(qf, kf, vf, bias,
                                                    *static, select))
    if held is None:
        out = _flash_core(qf, kf, vf, bias, select, *static)
    else:
        out = _flash_kept(qf, kf, vf, bias, select, *held, *static)
    return out.reshape(B, H, Tp, Dp)[:, :, :T, :Dv]
