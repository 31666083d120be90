"""A row's ``topk``-th largest score by counting, not sorting: the threshold
of a sparse attention layer's selection (``dsa:topk`` of
``nn/layers/attention.py`` ``top_keys``).

``lax.top_k`` of a ``[512, 8192]`` float32 chunk is, on a v5e, a sort of
every row with its indices (1.85 ms a chunk, 128 chunks a step of the
sparse cell: PERF.md, PR 35), to learn one number a row. That number can be
found exactly without moving anything: map each score to the integer of its
width whose signed order is the floats' order (for float32)::

    b = bitcast(x, int32);  key = b ^ ((b >> 31) & 0x7fffffff)

and build, from the top bit down, the largest ``c`` with ``count(key >= c)
>= topk`` in the row: one pass of compare and count for each bit, keeping
or dropping it. That ``c`` is the ``topk``-th largest key itself, and the
map is its own inverse, so the threshold is a score of the row, bit for
bit. The keys' order refines the floats' (``-0.0 < +0.0``, ``-inf`` the
lowest of the numbers), so as a float the threshold EQUALS what
``lax.top_k(x, topk)[0][..., -1]`` returns; whoever compares scores with it
as floats sees no difference. NaNs are ordered by their bits (positive ones
above ``+inf``, negative ones below ``-inf``), which no caller relies on.

The passes are plain XLA operations, a ``fori_loop`` of fused
compare-and-count: on a v5e the compiler keeps a chunk's keys in VMEM
across the loop's turns, 0.13 ms a ``[512, 8192]`` chunk. A Pallas kernel
of the same search (a tile of 128 rows resident, lane-partial counts) read
0.084 ms there and 0.65 % of the sparse cell's step, inside that cell's
spread from seed to seed, and was not kept (PERF.md, PR 36).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _flip(b):
    """Integers with the magnitude bits of the negative ones flipped: the
    map between a float's bits and its key, either way."""
    return b ^ ((b >> (8 * b.dtype.itemsize - 1)) & jnp.iinfo(b.dtype).max)


def topk_threshold(scores, topk: int):
    """``scores [..., T]`` float32 or float64 -> each row's ``topk``-th
    largest, ``[..., 1]``, by a search over as many bits as a score has."""
    bits = 8 * scores.dtype.itemsize
    ints = jnp.dtype(f"int{bits}")
    key = _flip(lax.bitcast_convert_type(scores, ints))

    def step(i, c):
        # the sign's bit first: added to the least integer it wraps to 0,
        # and a later one sets its bit
        cand = c + lax.shift_left(jnp.ones((), ints),
                                  (bits - 1 - i).astype(ints))
        hits = jnp.sum(key >= cand, axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(hits >= topk, cand, c)

    c = jnp.full(scores.shape[:-1] + (1,), jnp.iinfo(ints).min, ints)
    return lax.bitcast_convert_type(
        _flip(lax.fori_loop(0, bits, step, c)), scores.dtype)
