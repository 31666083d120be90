"""Pallas TPU kernels for the selective scan of a state-space layer
(``ssm:scan`` of ``nn/layers/state_space.py``), forward and backward.

The recurrence is the layer's own, one token after another in float32::

    h_t[n, c] = exp(Delta_t[c] A[n, c]) h_{t-1}[n, c]
                + Delta_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n]

As XLA operations the state of a block of tokens is too large to stay on
the chip between two token steps, so every step sends it through HBM
(``selective_scan_chunked``). Here a grid step holds a tile of ``BLOCK_D``
channels over ``BLOCK_T`` tokens: the tile's state is ``[N, BLOCK_D]``, the
states along the sublanes and the channels along the lanes, and lives in
vector registers while a ``fori_loop`` walks the tokens; between grid steps
it waits in VMEM scratch. Nothing of it goes to HBM but the state at each
time block's start (``[T / BLOCK_T, N, d_in]``), which is what the forward
keeps for the backward besides its inputs.

The layout decides the kernels. ``x``, ``Delta``, ``y`` and their cotangents
are read and written as they lie, ``[T, d_in]`` with the tokens along the
sublanes: a token's row is broadcast over the ``N`` sublanes of the state as
it is loaded, and there is no relayout outside the kernels. ``B_t`` and
``C_t`` must meet the state as columns (one number a sublane, the same in
every lane), so the caller hands them repeated along 128 lanes (``[T, N,
128]``, 67 MB each at the SambaY cell's shape); the grid walks the channel
tiles innermost, so a time block's copy is fetched once for all of them.
The sum over ``N`` for ``y_t`` is a sum down the sublanes, one a token and
128 channels. Backward, ``dB_t[n]`` and ``dC_t[n]`` are sums over the
channels: with the states on the sublanes they are sums across lanes and
across the tile's vregs, and the kernel adds the vregs (plain VPU adds),
accumulates the 128 lane-partials over the channel tiles in the output's
block and leaves the last sum over lanes to XLA (``[T, N, 128] -> [T,
N]``).

The backward kernel walks the time blocks last to first. In each it
rebuilds the block's states from the kept start into VMEM scratch
(``BLOCK_T`` x ``N`` x ``BLOCK_D`` float32: 8 MB), then walks the tokens
back carrying ``dh``, with ``dA`` accumulated in scratch across the whole
sequence. No step divides by a decay: ``exp(Delta A) h_{t-1}`` is read as
``dh_{t-1} h_{t-1}`` from the stored state before.

The loops take eight tokens a turn, a float32 tile's sublanes: the tokens'
rows are loaded and stored as whole ``[8, 128]`` tiles and a token's row
is taken from its tile at an index the program text knows (Mosaic loads no
single row, broadcast over sublanes, at an index it learns at run time),
and eight tokens by eight tiles of 128 channels unrolled give the four VPU
slots independent work to fill. On a v5e at the SambaY cell's shape (8,192
tokens, 5,120 channels, 16 states) the forward kernel takes 1.50 ms and
the backward one 3.85, where the XLA path's loops took 13.7 and 25.4
(PERF.md, PR 34); blocks of 64 to 256 tokens and tiles of 512 channels
read within 6 % of that.

``Delta``, ``A``, the decays, the state, the sums over ``N`` and every
accumulated cotangent are float32; ``x`` arrives in the layer's compute
dtype and is widened here, and its cotangent goes back in that dtype.
Padding in time has ``Delta = 0``, which neither decays nor writes.

Same dispatch seam as the other kernels: ``attention_mode()`` reads
``DL4J_TPU_PALLAS``. ``selective_scan_chunked`` stays as the path of
float64, of "off" and of every shape the gate refuses.
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

_LANES = 128
#: tokens a grid step walks
BLOCK_T = 128
#: the most channels a grid step holds (its state is ``N x BLOCK_D / 1024``
#: vregs)
BLOCK_D = 1024
#: tokens a turn of the kernels' loops takes: a float32 tile's sublanes
_TOKENS = 8


def time_block(T: int) -> int:
    """Tokens a grid step walks for a sequence of ``T``: ``BLOCK_T``, or
    all of a shorter sequence in one block (a multiple of 16, the sublanes
    of a bfloat16 tile)."""
    return min(BLOCK_T, _round_up(T, 16))


def channel_block(D: int) -> int:
    """Channels a grid step holds: the most of ``BLOCK_D``, its half, ...,
    128 that divides ``D``."""
    bd = BLOCK_D
    while bd > _LANES and D % bd:
        bd //= 2
    return bd


def selective_scan_vmem_bytes(T: int, D: int, N: int, itemsize: int = 2
                              ) -> int:
    """VMEM the backward kernel (the larger of the two) asks for, counting
    what Pallas allocates: every BlockSpec operand double-buffered (``x``
    and ``dx`` in the compute dtype; ``Delta``, ``dy`` and ``dDelta``; four
    blocks of lane-repeated ``[bt, N, 128]``; ``A``, ``dA`` and the start),
    the block's rebuilt states and the two carries over all channels."""
    bt, bd = time_block(T), channel_block(D)
    rows = bt * bd
    blocks = 2 * rows * itemsize + 3 * rows * 4 \
        + 4 * bt * N * _LANES * 4 + 3 * N * bd * 4
    scratch = (bt + 1) * N * bd * 4 + 2 * N * D * 4
    return 2 * blocks + scratch


def selective_scan_ok(T: int, D: int, N: int, acc_dtype, x_dtype) -> bool:
    """Shape gate: float32 accumulation, ``x`` no wider, whole lanes of
    channels, whole sublanes of states, blocks that fit
    ``VMEM_GATE_BYTES``."""
    return (jnp.dtype(acc_dtype) == jnp.float32
            and jnp.dtype(x_dtype).itemsize <= 4
            and D % _LANES == 0 and N % 8 == 0
            and selective_scan_vmem_bytes(
                T, D, N, jnp.dtype(x_dtype).itemsize) <= VMEM_GATE_BYTES)


def _chunks(bd):
    return [slice(j * _LANES, (j + 1) * _LANES) for j in range(bd // _LANES)]


def _row(tile, k):
    """Token ``k`` of a ``[8, 128]`` tile of tokens by channels, as the
    ``[1, 128]`` row that broadcasts over the states' sublanes."""
    return tile[k:k + 1, :]


def _set_row(tile, k, row):
    """``tile`` with the ``[1, 128]`` ``row`` as its token ``k``."""
    sublane = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.where(sublane == k, row, tile)


def _tiles(x_ref, dl_ref, rows, s):
    """Eight tokens by 128 channels of ``Delta`` and of what the tokens
    write, ``u = Delta x``."""
    dl = dl_ref[0, rows, s]
    return dl, dl * x_ref[0, rows, s].astype(jnp.float32)


def _step(h, a, dl, u, b, k):
    """``h_t`` from ``h_{t-1} [N, 128]`` for token ``k`` of the tiles."""
    return jnp.exp(_row(dl, k) * a) * h + _row(u, k) * b


def _fwd_kernel(x_ref, dl_ref, a_ref, bx_ref, cx_ref, y_ref, start_ref,
                h_scr, *, bt: int, bd: int):
    """One tile of channels over one block of tokens. ``h_scr [D / bd, N,
    bd]`` carries every tile's state from a time block to the next."""
    f32 = jnp.float32
    ti, di = pl.program_id(1), pl.program_id(2)
    chunks = _chunks(bd)

    @pl.when(ti == 0)
    def _():
        h_scr[di] = jnp.zeros(h_scr.shape[1:], f32)

    start_ref[0, 0] = h_scr[di]

    def tokens(i, h):
        first = pl.multiple_of(i * _TOKENS, _TOKENS)
        rows = pl.ds(first, _TOKENS)
        h = list(h)
        for j, s in enumerate(chunks):  # jaxlint: disable=JL004 -- 8
            a, (dl, u) = a_ref[:, s], _tiles(x_ref, dl_ref, rows, s)
            y = jnp.zeros((_TOKENS, _LANES), f32)
            for k in range(_TOKENS):  # jaxlint: disable=JL004 -- 8
                h[j] = _step(h[j], a, dl, u, bx_ref[0, first + k], k)
                y = _set_row(y, k, jnp.sum(h[j] * cx_ref[0, first + k],
                                           axis=0, keepdims=True))
            y_ref[0, rows, s] = y
        return tuple(h)

    h = lax.fori_loop(0, bt // _TOKENS, tokens,
                      tuple(h_scr[di, :, s] for s in chunks))
    for s, h_j in zip(chunks, h):
        h_scr[di, :, s] = h_j


def _bwd_kernel(x_ref, dl_ref, a_ref, bx_ref, cx_ref, start_ref, dy_ref,
                dx_ref, ddl_ref, da_ref, dbx_ref, dcx_ref,
                dh_scr, da_scr, h_scr, *, bt: int, bd: int):
    """The cotangents of one tile over one block of tokens; the grid's
    time index counts from the sequence's end. ``dh_scr`` and ``da_scr [D
    / bd, N, bd]`` carry ``dh`` and the running ``dA`` of every tile;
    ``h_scr [bt + 1, N, bd]`` holds the block's states, the start first."""
    f32 = jnp.float32
    ti, di = pl.program_id(1), pl.program_id(2)
    chunks = _chunks(bd)

    @pl.when(ti == 0)
    def _():
        dh_scr[di] = jnp.zeros(dh_scr.shape[1:], f32)
        da_scr[di] = jnp.zeros(da_scr.shape[1:], f32)

    @pl.when(di == 0)       # summed over the channel tiles, in the block
    def _():
        dbx_ref[...] = jnp.zeros(dbx_ref.shape, f32)
        dcx_ref[...] = jnp.zeros(dcx_ref.shape, f32)

    h_scr[0] = start_ref[0, 0]

    def rebuild(i, h):
        first = pl.multiple_of(i * _TOKENS, _TOKENS)
        rows = pl.ds(first, _TOKENS)
        h = list(h)
        for j, s in enumerate(chunks):
            a, (dl, u) = a_ref[:, s], _tiles(x_ref, dl_ref, rows, s)
            for k in range(_TOKENS):
                h[j] = _step(h[j], a, dl, u, bx_ref[0, first + k], k)
                h_scr[first + k + 1, :, s] = h[j]
        return tuple(h)

    lax.fori_loop(0, bt // _TOKENS, rebuild,
                  tuple(h_scr[0, :, s] for s in chunks))

    def back(i, dh):
        first = pl.multiple_of(bt - _TOKENS * (i + 1), _TOKENS)
        rows = pl.ds(first, _TOKENS)
        dh = list(dh)
        for j, s in enumerate(chunks):  # jaxlint: disable=JL004 -- 8
            a, dy = a_ref[:, s], dy_ref[0, rows, s]
            x = x_ref[0, rows, s].astype(f32)
            dl = dl_ref[0, rows, s]
            u = dl * x
            ddl = du = jnp.zeros((_TOKENS, _LANES), f32)
            da = da_scr[di, :, s]
            for k in reversed(range(_TOKENS)):  # jaxlint: disable=JL004 -- 8
                t = first + k
                b, c = bx_ref[0, t], cx_ref[0, t]
                g = dh[j] + _row(dy, k) * c             # d h_t, all of it
                dcx_ref[0, t] += h_scr[t + 1, :, s] * _row(dy, k)
                dbx_ref[0, t] += g * _row(u, k)
                du = _set_row(du, k, jnp.sum(g * b, axis=0, keepdims=True))
                dh[j] = g * jnp.exp(_row(dl, k) * a)    # d h_{t-1}
                e = dh[j] * h_scr[t, :, s]              # d (Delta_t A)
                ddl = _set_row(ddl, k,
                               jnp.sum(e * a, axis=0, keepdims=True))
                da = da + e * _row(dl, k)
            da_scr[di, :, s] = da
            # u = Delta x: its cotangent goes to both
            dx_ref[0, rows, s] = (du * dl).astype(dx_ref.dtype)
            ddl_ref[0, rows, s] = ddl + du * x
        return tuple(dh)

    dh = lax.fori_loop(0, bt // _TOKENS, back,
                       tuple(dh_scr[di, :, s] for s in chunks))
    for s, dh_j in zip(chunks, dh):
        dh_scr[di, :, s] = dh_j
    da_ref[0] = da_scr[di]      # the last time block's is the whole sum


def _specs(bt, bd, N, n_t, reverse):
    """Block specs of a grid ``(B, T / bt, D / bd)``: a ``[B, T, D]``
    operand, ``A [N, D]``, a lane-repeated ``[B, T, N, 128]`` and the
    starts ``[B, T / bt, N, D]``. ``reverse`` walks the time blocks last
    to first."""
    when = (lambda t: n_t - 1 - t) if reverse else (lambda t: t)
    rows = pl.BlockSpec((1, bt, bd), lambda b, t, d: (b, when(t), d))
    a = pl.BlockSpec((N, bd), lambda b, t, d: (0, d))
    cols = pl.BlockSpec((1, bt, N, _LANES),
                        lambda b, t, d: (b, when(t), 0, 0))
    start = pl.BlockSpec((1, 1, N, bd), lambda b, t, d: (b, when(t), 0, d))
    return rows, a, cols, start


def _compiler_params(T, D, N, itemsize):
    # the state waits in VMEM scratch from a time block to the next: the
    # time blocks, and the channel tiles inside one, run in order
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=vmem_limit(
            selective_scan_vmem_bytes(T, D, N, itemsize)))


def _repeated(z):
    """``[B, T, N] -> [B, T, N, 128]``: a token's ``N`` numbers as columns,
    one a sublane, the same in every lane."""
    return jnp.broadcast_to(z[..., None], z.shape + (_LANES,))


# Jitted, so that the kernels of every layer (and a forward's second run
# under remat) are traced and lowered once a step program.
@functools.partial(jax.jit, static_argnums=(5,))
def _run_fwd(x, delta, a, b, c, interpret):
    """``x, delta [B, T, D]``, ``a [N, D]``, ``b, c [B, T, N]``, ``T`` whole
    time blocks. Returns ``y [B, T, D]`` and the state each time block
    starts from, ``[B, T / bt, N, D]``."""
    B, T, D = x.shape
    N = a.shape[0]
    bt, bd = time_block(T), channel_block(D)
    rows, a_spec, cols, start = _specs(bt, bd, N, T // bt, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bt=bt, bd=bd),
        grid=(B, T // bt, D // bd),
        in_specs=[rows, rows, a_spec, cols, cols],
        out_specs=[rows, start],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, T // bt, N, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((D // bd, N, bd), jnp.float32)],
        compiler_params=_compiler_params(T, D, N, x.dtype.itemsize),
        interpret=interpret,
        name="selective_scan_fwd",
    )(x, delta, a, _repeated(b), _repeated(c))


@functools.partial(jax.jit, static_argnums=(7,))
def _run_bwd(x, delta, a, b, c, starts, dy, interpret):
    """The cotangents of ``x, delta, a, b, c`` from ``y``'s."""
    B, T, D = x.shape
    N = a.shape[0]
    bt, bd = time_block(T), channel_block(D)
    rows, a_spec, cols, start = _specs(bt, bd, N, T // bt, reverse=True)
    da_spec = pl.BlockSpec((1, N, bd), lambda b, t, d: (b, 0, d))
    f32 = jnp.float32
    tiles = lambda *shape: pltpu.VMEM(shape, f32)
    dx, ddelta, da, dbx, dcx = pl.pallas_call(
        functools.partial(_bwd_kernel, bt=bt, bd=bd),
        grid=(B, T // bt, D // bd),
        in_specs=[rows, rows, a_spec, cols, cols, start, rows],
        out_specs=[rows, rows, da_spec, cols, cols],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), x.dtype),
                   jax.ShapeDtypeStruct((B, T, D), f32),
                   jax.ShapeDtypeStruct((B, N, D), f32),
                   jax.ShapeDtypeStruct((B, T, N, _LANES), f32),
                   jax.ShapeDtypeStruct((B, T, N, _LANES), f32)],
        scratch_shapes=[tiles(D // bd, N, bd), tiles(D // bd, N, bd),
                        tiles(bt + 1, N, bd)],
        compiler_params=_compiler_params(T, D, N, x.dtype.itemsize),
        interpret=interpret,
        name="selective_scan_bwd",
    )(x, delta, a, _repeated(b), _repeated(c), starts, dy)
    return (dx, ddelta, jnp.sum(da, axis=0), jnp.sum(dbx, axis=-1),
            jnp.sum(dcx, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, delta, a, b, c, interpret):
    return _run_fwd(x, delta, a, b, c, interpret)[0]


def _scan_fwd(x, delta, a, b, c, interpret):
    y, starts = _run_fwd(x, delta, a, b, c, interpret)
    return y, (x, delta, a, b, c, starts)


def _scan_bwd(interpret, res, dy):
    return _run_bwd(*res, dy, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, delta, a, b, c, *, interpret: bool = False):
    """``y_t[c] = sum_n h_t[c, n] C_t[n]`` of the module's recurrence from
    ``h_0 = 0`` by the kernels, forward and backward: the arguments and the
    result of ``nn/layers/state_space.selective_scan_chunked`` (``x [B, T,
    d_in]`` in any float dtype; ``delta [B, T, d_in]``, ``a [N, d_in]``,
    ``b, c [B, T, N]`` and ``y`` in float32), for shapes
    :func:`selective_scan_ok` allows. The inputs and the state at each time
    block's start are kept for the backward kernel; the caller holds that
    rule between the barriers its remat policy needs
    (``nn/remat.backward_after_cotangent``)."""
    T = x.shape[1]
    pad = -T % time_block(T)
    in_time = lambda z: jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
    y = _scan(in_time(x), in_time(delta), a, in_time(b), in_time(c),
              interpret)
    return y[:, :T]
