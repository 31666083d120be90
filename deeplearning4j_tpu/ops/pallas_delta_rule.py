"""Pallas TPU kernels for the gated delta rule's chunk-local work
(``gdn:chunk_local`` of ``nn/layers/linear_attention.py``), forward and
backward.

For every chunk of ``C = 64`` tokens of every head the chunked form needs,
before its scan across chunks can start, the masked decay matrix, ``K K^T``,
the inverse of a unit lower-triangular 64 x 64 system, ``W``, ``U_0``, the
decayed ``Q K^T`` and the rescaled ``q`` and ``k`` (the module's docstring
there gives the algebra). As XLA operations each of those is a pass over a
tensor of 63 to 189 MB that goes out to HBM and comes back for the next;
here a grid step holds a block of chunks of one head in VMEM, makes all of
it there, and writes each result once, in the order the scan reads them
(``[N, B, H, C, .]``). The backward kernel is handed the same inputs and
the five outputs' cotangents, rebuilds the decay, ``K K^T`` and the inverse
in VMEM, and returns the cotangents of ``q, k, v, g, beta``: nothing but
the inputs is kept between the two.

The arithmetic is ``chunk_local_xla``'s (same file as the layer), which
stays as the path of float64 and of every shape the gate refuses, and as
the reference of ``tests/test_pallas_delta_rule.py``: the decays, the
inverse and ``u0`` are float32; the four products take their operands in
the compute dtype and accumulate in float32, narrowed where the XLA path
narrows them (``inv``, ``k * beta * gamma`` and ``v * beta`` at the
product); the inverse is put together from unit blocks by doubling,
``[[X1, 0], [-X2 a21 X1, X2]]``, with float32 products at HIGHEST, so every
intermediate value is an entry of the inverse itself (no Neumann series);
its derivative is the inverse's own, ``d_a = -inv^T d_inv inv^T``, and not
a walk back through the doubling.

What sets the kernels' time on a v5e (PERF.md, PR 32): a float32 product
of 64 x 64 at HIGHEST takes 142 ns when the next waits for it and 70 when
it does not, and ``[64, 128] @ [128, 128]`` costs what ``[64, 64] @ [64,
64]`` does. So two chunks share a tile, side by side along the lanes (one
turn of the MXU multiplies both against a block-diagonal operand, and the
elementwise work fills its lanes), and the pairs of a block go through
each level of the doubling together.

Same dispatch seam as the flash kernels: ``attention_mode()`` reads
``DL4J_TPU_PALLAS`` (compiled on a TPU, ``interpret`` for the CPU's tests,
``off`` for the XLA path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas_kernels import (
    VMEM_GATE_BYTES, _round_up, vmem_limit,
)

#: tokens a chunk: the kernel is written for the layer's chunk and no other
CHUNK = 64
#: the most chunks of one head a grid step holds, two to a tile
BLOCK_CHUNKS = 16
_LANES = 2 * CHUNK


def padded_chunks(N: int) -> int:
    """The number of chunks the kernels run at for a sequence of ``N``: an
    even number (the kernels work on pairs of chunks) for a short sequence,
    which is one block; else whole blocks of the most of 16, 8, 4, 2 that
    costs no more than an eighth of padding (``N = 128`` runs as it is,
    ``N = 130`` at 144, ``N = 18`` at 20, ``N = 33`` at 36)."""
    even = _round_up(N, 2)
    if even <= BLOCK_CHUNKS:
        return even
    return next(padded for padded in (_round_up(N, nb)
                                      for nb in (BLOCK_CHUNKS, 8, 4, 2))
                if 8 * padded <= 9 * even)


def block_chunks(padded: int) -> int:
    """Chunks a grid step holds, of a count :func:`padded_chunks` gave: all
    of a short sequence's; else the most of 16, 8, 4, 2 that divides it
    (144 runs in blocks of 16, 20 and 36 in blocks of 4, 72 in blocks of
    8). Read from the padded count alone, so that the gate, the padding and
    the two kernels cannot disagree."""
    if padded % 2:
        raise ValueError(f"{padded} chunks: not a count padded_chunks gives")
    if padded <= BLOCK_CHUNKS:
        return padded
    return next(nb for nb in (BLOCK_CHUNKS, 8, 4, 2) if padded % nb == 0)


def gdn_vmem_bytes(nb: int, dk: int, dv: int, itemsize: int = 2) -> int:
    """VMEM the backward kernel (the larger of the two) asks for a block of
    ``nb`` chunks, counting what Pallas allocates: every BlockSpec operand
    double-buffered at its lane-padded width (the forward's inputs ``q, k``
    in float32 and ``v`` in the compute dtype; the cotangents of its
    outputs ``w, q_in, k_out, attn`` in the compute dtype and ``u0`` in
    float32; the cotangents of its inputs; two blocks of gates), and the
    float32 tiles the pairs of the block keep while their inverses are put
    together."""
    pk, pv = _round_up(dk, _LANES), _round_up(dv, _LANES)
    inputs = 2 * pk * 4 + pv * itemsize
    outputs = 3 * pk * itemsize + pv * 4 + _LANES * itemsize
    blocks = nb * CHUNK * (2 * inputs + outputs) \
        + 2 * _round_up(nb, 8) * _LANES * 4
    tiles = (nb // 2) * 16 * CHUNK * _LANES * 4 \
        + 2 * _LANES * (8 * _LANES + 6 * pk + 4 * pv) * 4
    return 2 * blocks + tiles


def gdn_chunk_ok(N: int, dk: int, dv: int, chunk: int, gate_dtype,
                 compute_dtype) -> bool:
    """Shape gate for a sequence of ``N`` chunks: float32 gates, the chunk
    the kernel is written for, and a block of chunks that fits
    ``VMEM_GATE_BYTES``."""
    return (jnp.dtype(gate_dtype) == jnp.float32 and chunk == CHUNK
            and jnp.dtype(compute_dtype).itemsize <= 4
            and gdn_vmem_bytes(block_chunks(padded_chunks(N)), dk, dv,
                               jnp.dtype(compute_dtype).itemsize)
            <= VMEM_GATE_BYTES)


def _dot(a, b, contract, precision):
    """``a @ b`` with a float32 accumulator (``contract=(1, 1)``: ``a @
    b.T``; ``(0, 0)``: ``a.T @ b``)."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _mm(a, b, contract=(1, 0)):
    """A product of operands in the compute dtype. Narrower than float32
    they are exact products and name the default precision (Mosaic refuses
    them under an ambient "highest"); float32 ones follow the ambient
    precision, as the XLA path's do."""
    return _dot(a, b, contract,
                None if a.dtype == jnp.float32 else lax.Precision.DEFAULT)


def _mm_wide(a, b, contract=(1, 0)):
    """A float32 product that keeps float32: the inverse's own."""
    return _dot(a, b, contract, lax.Precision.HIGHEST)


class _Pair:
    """Index tiles for two chunks side by side: a ``[C, 2 C]`` tile holds
    chunk 0's ``[C, C]`` matrix in its left lanes and chunk 1's in its
    right ones, so that elementwise work fills the lanes and one turn of
    the MXU multiplies both (``side_by_side @ block_diagonal``)."""

    def __init__(self):
        C, L = CHUNK, _LANES
        iota = lambda shape, d: lax.broadcasted_iota(jnp.int32, shape, d)
        self.row = iota((C, L), 0)
        lane = iota((C, L), 1)
        self.right = lane >= C
        self.col = jnp.where(self.right, lane - C, lane)
        self.same_half = (iota((L, L), 0) >= C) == (iota((L, L), 1) >= C)
        self.lower_half = iota((L, 1), 0) >= C

    def side_by_side(self, m):
        """``[2 C, 2 C]`` -> its two diagonal blocks as ``[C, 2 C]``."""
        return jnp.where(self.right, m[CHUNK:], m[:CHUNK])

    def block_diagonal(self, m):
        """``[C, 2 C]`` -> ``[2 C, 2 C]`` with the two on the diagonal."""
        return jnp.where(self.same_half, jnp.concatenate([m, m], axis=0), 0.0)

    def columns(self, c):
        """A pair's per-token ``[2 C, 1]`` column -> ``[C, 2 C]``, each
        chunk's over its own lanes."""
        return jnp.where(self.right, c[CHUNK:], c[:CHUNK])


def _gate_columns(gb):
    """The block's gates ``[rows, 2 C]`` (a pair's 128 tokens a row) as
    columns: one transpose of a ``[128, 128]`` tile."""
    pad = _LANES - gb.shape[0]
    if pad:
        gb = jnp.concatenate([gb, jnp.zeros((pad, _LANES), gb.dtype)], axis=0)
    return gb.T


def _pair_rows(ref, p):
    """Chunks ``2 p`` and ``2 p + 1`` of an input's block as ``[2 C, d]``."""
    return ref[0, 0, 2 * p:2 * p + 2].reshape(2 * CHUNK, -1)


def _to_end(ix, g_col):
    """``gamma_C / gamma_t`` for a pair's ``[2 C, 1]`` column of ``g``: each
    chunk's last ``g`` less the token's."""
    last = jnp.where(ix.lower_half, g_col[2 * CHUNK - 1:],
                     g_col[CHUNK - 1:CHUNK])
    return jnp.exp(last - g_col)


def _decay_and_system(ix, k, g_row, g_col, b_col, cd):
    """For a pair: the masked decay ``[C, 2 C]``, ``K K^T`` and the
    strictly lower ``a`` of the triangular system, all side by side."""
    diff = ix.columns(g_col) - g_row                        # [t, i]
    decay = jnp.exp(jnp.where(ix.row >= ix.col, diff, -jnp.inf))
    kc = k.astype(cd)
    kk = ix.side_by_side(_mm(kc, kc, (1, 1)))
    a = jnp.where(ix.row > ix.col, ix.columns(b_col) * kk * decay, 0.0)
    return decay, kk, a


def _unit_lower_inverses(ix, systems):
    """``(I + a)^-1`` for each pair's strictly lower-triangular ``a [C, 2
    C]`` (side by side) in float32. Unit diagonal blocks of 1 are their own
    inverse; pairs of blocks of ``b`` are put together, ``[[X1, 0], [-X2 a21
    X1, X2]]``, until one is left: with ``X`` the block-diagonal of the
    inverses so far and ``a21`` the part of ``a`` below the diagonal blocks
    of ``b`` and inside those of ``2 b``, ``X a21 X`` holds every ``X2 a21
    X1`` at once. Every intermediate value is an entry of the inverse. The
    pairs go through each level together: their products do not depend on
    one another, and a chain of dependent float32 products leaves the MXU
    idle half the time."""
    eye = jnp.where(ix.row == ix.col, 1.0, 0.0)
    xs = [eye] * len(systems)
    b = 1
    while b < CHUNK:
        # row and col in one block of 2 b, row in its lower half, col in
        # its upper
        merge = ((ix.row // (2 * b)) == (ix.col // (2 * b))) \
            & ((ix.row // b) % 2 == 1) & ((ix.col // b) % 2 == 0)
        a21 = [jnp.where(merge, a, 0.0) for a in systems]
        if b == 1:
            xs = [x - a for x, a in zip(xs, a21)]   # X is I: X a21 X is a21
        else:
            ys = [_mm_wide(x, ix.block_diagonal(a)) for x, a in zip(xs, a21)]
            xs = [x - _mm_wide(y, ix.block_diagonal(x))
                  for x, y in zip(xs, ys)]
        b *= 2
    return xs


def _rebuild(ix, k_ref, gb, cd, P):
    """What both kernels start from, for each of the block's ``P`` pairs:
    ``k [2 C, dk]``, the gates as columns ``[2 C, 1]``, the decay and ``K
    K^T`` side by side, and the inverse."""
    cols = _gate_columns(gb)
    ks = [_pair_rows(k_ref, p) for p in range(P)]
    g_cols = [cols[:, p:p + 1] for p in range(P)]
    b_cols = [cols[:, P + p:P + p + 1] for p in range(P)]
    local = [_decay_and_system(ix, ks[p], gb[p:p + 1], g_cols[p], b_cols[p],
                               cd) for p in range(P)]
    invs = _unit_lower_inverses(ix, [a for _, _, a in local])
    return ks, g_cols, b_cols, local, invs


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, w_ref, u0_ref, attn_ref,
                qin_ref, kout_ref, *, nb: int):
    f32 = jnp.float32
    cd = w_ref.dtype
    C, P = CHUNK, nb // 2
    ix = _Pair()
    gb = gb_ref[0, 0, 0]            # rows: g of the P pairs, then beta
    ks, g_cols, b_cols, local, invs = _rebuild(ix, k_ref, gb, cd, P)

    def store(ref, p, x):           # [2 C, d] -> the pair's two chunks
        ref[2 * p, 0, 0] = x[:C].astype(ref.dtype)
        ref[2 * p + 1, 0, 0] = x[C:].astype(ref.dtype)

    for p in range(P):
        k, g_col, b_col = ks[p], g_cols[p], b_cols[p]
        q, v = _pair_rows(q_ref, p), _pair_rows(v_ref, p)
        decay = local[p][0]
        inv = ix.block_diagonal(invs[p]).astype(cd)         # [2 C, 2 C]
        gamma = jnp.exp(g_col)
        store(w_ref, p, _mm(inv, (k * (b_col * gamma)).astype(cd)))
        store(u0_ref, p, _mm(inv, (v.astype(f32) * b_col).astype(cd)))
        attn = ix.side_by_side(_mm(q.astype(cd), k.astype(cd), (1, 1))) \
            * decay
        attn_ref[2 * p, 0, 0] = attn[:, :C].astype(cd)
        attn_ref[2 * p + 1, 0, 0] = attn[:, C:].astype(cd)
        store(qin_ref, p, q * gamma)
        store(kout_ref, p, k * _to_end(ix, g_col))


def _pack_gates(g, beta, nb):
    """``g, beta [B, H, N, C]`` -> ``[B, H, N / nb, rows, 2 C]``: a block's
    ``g`` a pair of chunks a row, then its ``beta`` likewise, then zero
    rows up to a multiple of 8."""
    B, H, N, C = g.shape
    rows = lambda x: x.reshape(B, H, N // nb, nb // 2, 2 * C)
    gb = jnp.concatenate([rows(g), rows(beta)], axis=3)
    return jnp.pad(gb, ((0, 0),) * 3 + ((0, -nb % 8), (0, 0)))


def _specs(nb):
    """Block specs by the last dimension: of an input ``[B, H, N, C, d]``,
    of the packed gates, and of an output ``[N, B, H, C, d]``."""
    inp = lambda d: pl.BlockSpec((1, 1, nb, CHUNK, d),
                                 lambda b, h, n: (b, h, n, 0, 0))
    gates = pl.BlockSpec((1, 1, 1, _round_up(nb, 8), _LANES),
                         lambda b, h, n: (b, h, n, 0, 0))
    out = lambda d: pl.BlockSpec((nb, 1, 1, CHUNK, d),
                                 lambda b, h, n: (n, b, h, 0, 0))
    return inp, gates, out


def _compiler_params(nb, dk, dv, itemsize):
    # no carry between grid steps in either kernel
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=vmem_limit(gdn_vmem_bytes(nb, dk, dv, itemsize)))


# Jitted, so that the kernels of every layer (and a forward's second run
# under remat) are traced and lowered once a step program: unrolled over
# the pairs of a block, a kernel's body is some thousand operations, and
# nine of them added 10 s to a warm set-up.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _run_fwd(q, k, v, g, beta, cd, interpret):
    """``q, k [B, H, N, C, dk]`` float32, ``v [B, H, N, C, dv]``, ``g, beta
    [B, H, N, C]`` float32, ``N`` a multiple of the block. Returns ``w,
    u0, attn, q_in, k_out`` as ``[N, B, H, C, .]``."""
    B, H, N, C, dk = q.shape
    dv = v.shape[-1]
    nb = block_chunks(N)
    cd = jnp.dtype(cd)
    inp, gates, out = _specs(nb)
    shape = lambda d, dt: jax.ShapeDtypeStruct((N, B, H, C, d), dt)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nb=nb),
        grid=(B, H, N // nb),
        in_specs=[inp(dk), inp(dk), inp(dv), gates],
        out_specs=[out(dk), out(dv), out(C), out(dk), out(dk)],
        out_shape=[shape(dk, cd), shape(dv, jnp.float32), shape(C, cd),
                   shape(dk, cd), shape(dk, cd)],
        compiler_params=_compiler_params(nb, dk, dv, cd.itemsize),
        interpret=interpret,
        name="gdn_chunk_local_fwd",
    )(q, k, v, _pack_gates(g, beta, nb))


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, dw_ref, du0_ref, dattn_ref,
                dqin_ref, dkout_ref, dq_ref, dk_ref, dv_ref, dgb_ref, *,
                nb: int):
    """The cotangents of ``q, k, v, g, beta`` for a block of chunks, with
    the chunk's ``decay``, ``K K^T`` and inverse rebuilt in VMEM. The
    ``[C, C]`` cotangents are kept TRANSPOSED (keys along the sublanes,
    queries along the lanes): ``d_inv^T`` and ``(Q K^T)^T`` are plain
    products of what is at hand, the inverse's own derivative is ``d_a^T =
    -strict_upper(inv d_inv^T inv)``, and sums over a chunk's keys, which
    the gates' cotangents need token by token, run down the sublanes and
    come out as lane-dense rows."""
    f32 = jnp.float32
    cd = dw_ref.dtype
    C, P, L = CHUNK, nb // 2, _LANES
    ix = _Pair()
    upper, strict_upper = ix.row <= ix.col, ix.row < ix.col
    eye2 = lax.broadcasted_iota(jnp.int32, (L, L), 0) \
        == lax.broadcasted_iota(jnp.int32, (L, L), 1)
    token = lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    gb = gb_ref[0, 0, 0]            # rows: g of the P pairs, then beta
    # a cotangent's block is laid out as the forward's outputs are
    ct = lambda ref, p: jnp.concatenate(
        [ref[2 * p, 0, 0], ref[2 * p + 1, 0, 0]], axis=0)   # [2 C, d]
    rows_of = lambda c: jnp.sum(jnp.where(eye2, c, 0.0), axis=0,
                                keepdims=True)              # [2 C, 1] -> row

    def transposed(x):      # [2 C, C] -> [C, 2 C]: each chunk's, transposed
        one = jnp.where(eye2, 1.0, 0.0).astype(x.dtype)
        if x.dtype == f32:
            return _mm_wide(x, one, (0, 0))
        return _mm(x, one, (0, 0))

    ks, g_cols, b_cols, local, invs = _rebuild(ix, k_ref, gb, cd, P)

    # d_inv^T = kb dW^T + vb dU0^T, then d_a^T = -strict_upper(inv d_inv^T
    # inv): two float32 products a pair, the pairs side by side in time
    gammas = [jnp.exp(g_col) for g_col in g_cols]
    vs = [_pair_rows(v_ref, p).astype(f32) for p in range(P)]
    dws = [ct(dw_ref, p) for p in range(P)]
    du0s = [ct(du0_ref, p).astype(cd) for p in range(P)]
    d_invt = [ix.side_by_side(
        _mm((ks[p] * (b_cols[p] * gammas[p])).astype(cd), dws[p], (1, 1))
        + _mm((vs[p] * b_cols[p]).astype(cd), du0s[p], (1, 1)))
        for p in range(P)]
    ys = [_mm_wide(invs[p], ix.block_diagonal(d_invt[p])) for p in range(P)]
    d_at = [-jnp.where(strict_upper,
                       _mm_wide(ys[p], ix.block_diagonal(invs[p])), 0.0)
            for p in range(P)]

    dgb_ref[...] = jnp.zeros(dgb_ref.shape, f32)
    for p in range(P):
        k, g_col, b_col, gamma = ks[p], g_cols[p], b_cols[p], gammas[p]
        g_row, b_row = gb[p:p + 1], gb[P + p:P + p + 1]     # [1, 2 C]
        q, v = _pair_rows(q_ref, p), vs[p]
        kc, qc = k.astype(cd), q.astype(cd)
        _, kk, _ = local[p]
        decay_t = jnp.exp(jnp.where(upper, g_row - ix.columns(g_col),
                                    -jnp.inf))              # [i, t]
        qk_t = ix.side_by_side(_mm(kc, qc, (1, 1)))         # k_i . q_t
        d_attn_t = transposed(ct(dattn_ref, p))
        # a = beta kk decay below the diagonal; attn = qk decay on and below
        d_beta_row = jnp.sum(d_at[p] * kk * decay_t, axis=0, keepdims=True)
        d_kk_t = ix.block_diagonal(d_at[p] * b_row * decay_t).astype(cd)
        d_qk_t = ix.block_diagonal(d_attn_t * decay_t).astype(cd)
        d_diff_t = (d_at[p] * b_row * kk + d_attn_t * qk_t) * decay_t
        # decay[t, i] = exp(g_t - g_i): + over the keys, - over the queries
        d_g_row = jnp.sum(d_diff_t, axis=0, keepdims=True)
        d_g_col = -jnp.concatenate(
            [jnp.sum(jnp.where(ix.right, 0.0, d_diff_t), axis=1,
                     keepdims=True),
             jnp.sum(jnp.where(ix.right, d_diff_t, 0.0), axis=1,
                     keepdims=True)], axis=0)               # [2 C, 1]
        # W = inv kb and U0 = inv vb
        inv = ix.block_diagonal(invs[p]).astype(cd)
        d_kb = _mm(inv, dws[p], (0, 0))                     # [2 C, dk]
        d_vb = _mm(inv, du0s[p], (0, 0))
        to_end = _to_end(ix, g_col)
        d_qin, d_kout = ct(dqin_ref, p).astype(f32), \
            ct(dkout_ref, p).astype(f32)
        feat = lambda x: jnp.sum(x, axis=1, keepdims=True)  # over features
        d_bg = feat(d_kb * k)                               # kb = k beta gamma
        d_gamma = d_bg * b_col + feat(d_qin * q)
        d_end = feat(d_kout * k) * to_end                   # k_out = k to_end
        d_g_col = d_g_col + d_gamma * gamma - d_end
        for c in range(2):          # to_end reads the chunk's last g
            d_g_col = d_g_col + jnp.where(
                token == (c + 1) * C - 1,
                jnp.sum(d_end[c * C:(c + 1) * C], axis=0, keepdims=True), 0.0)
        d_beta_col = d_bg * gamma + feat(d_vb * v)
        dgb_ref[0, 0, 0, p:p + 1, :] = d_g_row + rows_of(d_g_col)
        dgb_ref[0, 0, 0, P + p:P + p + 1, :] = d_beta_row \
            + rows_of(d_beta_col)
        d_q = d_qin * gamma + _mm(d_qk_t, kc, (0, 0))
        d_k = d_kb * (b_col * gamma) + d_kout * to_end + _mm(d_qk_t, qc) \
            + _mm(d_kk_t, kc) + _mm(d_kk_t, kc, (0, 0))
        for c in range(2):
            half = slice(c * C, (c + 1) * C)
            dq_ref[0, 0, 2 * p + c] = d_q[half]
            dk_ref[0, 0, 2 * p + c] = d_k[half]
            dv_ref[0, 0, 2 * p + c] = (d_vb * b_col)[half].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6,))
def _run_bwd(q, k, v, g, beta, cts, interpret):
    """The cotangents of ``q, k, v, g, beta`` from those of ``w, u0, attn,
    q_in, k_out`` (``[N, B, H, C, .]``)."""
    B, H, N, C, dk = q.shape
    dv = v.shape[-1]
    nb = block_chunks(N)
    cd = cts[0].dtype
    inp, gates, out = _specs(nb)
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    gb = _pack_gates(g, beta, nb)
    dq, dk_, dv_, dgb = pl.pallas_call(
        functools.partial(_bwd_kernel, nb=nb),
        grid=(B, H, N // nb),
        in_specs=[inp(dk), inp(dk), inp(dv), gates,
                  out(dk), out(dv), out(C), out(dk), out(dk)],
        out_specs=[inp(dk), inp(dk), inp(dv), gates],
        out_shape=[shape(q), shape(k), shape(v), shape(gb)],
        compiler_params=_compiler_params(nb, dk, dv, cd.itemsize),
        interpret=interpret,
        name="gdn_chunk_local_bwd",
    )(q, k, v, gb, *cts)
    rows = lambda x: x.reshape(B, H, N, C)
    return (dq, dk_, dv_, rows(dgb[..., :nb // 2, :]),
            rows(dgb[..., nb // 2:nb, :]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunk_local(q, k, v, g, beta, cd, interpret):
    return tuple(_run_fwd(q, k, v, g, beta, cd, interpret))


def _chunk_local_fwd(q, k, v, g, beta, cd, interpret):
    # the inputs alone are kept, as the XLA path's checkpoint keeps them
    return (tuple(_run_fwd(q, k, v, g, beta, cd, interpret)),
            (q, k, v, g, beta))


def _chunk_local_bwd(cd, interpret, res, ct):
    return _run_bwd(*res, ct, interpret)


_chunk_local.defvjp(_chunk_local_fwd, _chunk_local_bwd)


def count_trace(path: str) -> None:
    """Which path the chunk-local work of a run took, forward and backward
    (``"kernel"`` or ``"xla"``), counted once per trace (not per step)."""
    from deeplearning4j_tpu.profiling.metrics import get_registry
    get_registry().labeled_counter(
        "pallas_gdn_chunk_traces_total",
        "traces of the delta rule's chunk-local work by the path they "
        "took (per trace)",
    ).labels(path=path).inc()


def gdn_chunk_local(q, k, v, g, beta, *, compute_dtype,
                    interpret: bool = False):
    """The chunk-local work by the kernels, forward and backward: ``q, k
    [B, H, N, C, d_k]``, ``v [B, H, N, C, d_v]``, ``g`` (the running sum of
    ``log alpha`` inside the chunk) and ``beta [B, H, N, C]`` in float32,
    ``N`` as :func:`padded_chunks` gives it. Returns what
    ``nn/layers/linear_attention.chunk_local_xla`` returns: ``w, u0, attn,
    q_in, k_out`` as ``[N, B, H, C, .]``, ``u0`` in float32 and the others
    in ``compute_dtype``. Only the inputs are kept for the backward
    kernel; the caller holds that rule between the barriers its remat
    policy needs (``nn/remat.backward_after_cotangent``)."""
    return _chunk_local(q.astype(jnp.float32), k.astype(jnp.float32), v, g,
                        beta, jnp.dtype(compute_dtype), interpret)
