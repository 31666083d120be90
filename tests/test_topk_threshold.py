"""The selection's threshold by counting (``ops/topk_threshold.py``): the
search gives ``lax.top_k``'s last value EXACTLY, and ``top_keys`` gives the
int8 selection it gave when its threshold came from that sort (the form kept
here as the oracle), on plain scores, on ties across the edge, on zeros of
both signs, on rows that have seen fewer keys than they keep, on denormals,
at ``topk`` of 1 and of ``T - 1``, on lengths that are no whole number of
lanes and in float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.attention import (
    SparseIndexerLayer, keeps_all, seen_keys, top_keys)
from deeplearning4j_tpu.ops.topk_threshold import topk_threshold

Q = 24


def selection_by_sort(scores, first, topk):
    """``top_keys`` as PR 35 left it: the threshold from ``lax.top_k``."""
    rows, T = scores.shape[-2:]
    seen = jnp.arange(T)[None, :] <= first + jnp.arange(rows)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    if topk >= T:
        return jnp.broadcast_to(seen, scores.shape).astype(jnp.int8)
    edge = jax.lax.top_k(scores, topk)[0][..., -1:]
    above, level = scores > edge, scores == edge
    wanted = topk - jnp.sum(above, axis=-1, keepdims=True)
    among = jnp.cumsum(level.astype(jnp.int32), axis=-1)
    return (seen & (above | (level & (among <= wanted)))).astype(jnp.int8)


def scores_of_kind(kind, T):
    """``[2, Q, T]`` float32."""
    rng = np.random.default_rng(len(kind) + T)
    x = rng.normal(size=(2, Q, T)).astype(np.float32)
    if kind == "halves":                     # many ties across the edge
        x = np.round(x * 2) / 2
    elif kind == "zeros":                    # +0.0 and -0.0 mixed, all equal
        x = np.where(rng.random(x.shape) < 0.5, 0.0, -0.0).astype(np.float32)
    elif kind == "half_zero":                # the edge among the zeros
        x = np.where(rng.random(x.shape) < 0.5, np.copysign(
            0.0, x), x).astype(np.float32)
    elif kind == "denormal":                 # bit patterns under 2 ** 23
        x = np.copysign(rng.integers(0, 900, x.shape).astype(np.int32)
                        .view(np.float32), x)
    elif kind == "mostly_inf":               # -inf inside the seen keys too
        x = np.where(rng.random(x.shape) < 0.8, -np.inf, x).astype(np.float32)
    return jnp.asarray(x)


KINDS = ["normal", "halves", "zeros", "half_zero", "denormal", "mostly_inf"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("T,topk", [(256, 64), (1024, 700), (300, 17),
                                    (256, 1), (256, 255)],
                         ids=["T256", "T1024", "T300", "top1", "all_but_1"])
def test_the_threshold_is_top_ks_last_value_exactly(kind, T, topk):
    scores = scores_of_kind(kind, T)
    want = jax.lax.top_k(scores, topk)[0][..., -1:]
    got = jax.jit(topk_threshold, static_argnums=1)(scores, topk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # and it is a score of its row, not only equal to one as a float
    bits = lambda a: np.asarray(a).view(np.int32)
    assert (bits(got) == bits(scores)).any(axis=-1).all()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("first,topk", [(0, 8), (0, 64), (232, 64), (232, 1),
                                        (232, 255), (232, 256), (100, 300)])
def test_top_keys_selects_as_it_did_by_the_sort(kind, first, topk):
    """Rows with fewer than ``topk`` keys seen (``first`` of 0: ``-inf``
    beyond ``t`` fills the row's tail, and the threshold is ``-inf``), a
    later chunk whose rows have seen most keys, ``topk`` of 1, of ``T - 1``
    and of ``T`` and more (every seen key, no threshold)."""
    scores = scores_of_kind(kind, 256)
    got = np.asarray(top_keys(scores, first, topk))
    want = np.asarray(selection_by_sort(scores, first, topk))
    assert got.dtype == np.int8 and np.array_equal(got, want)
    kept = np.minimum(first + np.arange(Q) + 1, topk)
    assert (got.sum(-1) == kept).all()


@pytest.mark.parametrize("ties", [False, True], ids=["plain", "halves"])
def test_float64_scores_search_their_64_bits(ties):
    """The gradient checks' dtype: the same search over 64 bits, exact."""
    with jax.enable_x64():
        x = jnp.asarray(np.random.default_rng(5).normal(size=(6, 200)))
        assert x.dtype == jnp.float64
        if ties:
            x = jnp.round(x * 2) / 2
        got = topk_threshold(x, 31)
        assert got.dtype == x.dtype
        assert np.array_equal(np.asarray(got), np.asarray(
            jax.lax.top_k(x, 31)[0][..., -1:]))


@pytest.mark.parametrize("first,topk,all_kept", [
    (0, 24, True), (0, 23, False), (40, 64, True), (41, 64, False),
    (0, 300, True), (232, 255, False)])
def test_keeps_all_says_when_the_scores_do_not_matter(first, topk, all_kept):
    """Where no query of a chunk has seen more keys than it keeps, the
    selection is the seen keys whatever the scores; one query more and a
    key is dropped."""
    assert bool(keeps_all(first, Q, topk)) == all_kept
    got = np.asarray(top_keys(scores_of_kind("normal", 256), first, topk))
    seen = np.asarray(seen_keys(first, Q, 256))
    assert np.array_equal(got[0] != 0, seen) == all_kept


@pytest.mark.parametrize("chunk,topk", [(8, 24), (8, 30), (32, 24), (16, 100),
                                        (16, 96)])
def test_chunks_that_keep_every_seen_key_make_no_scores(chunk, topk):
    """The first ``topk // query_chunk`` chunks of queries are the causal
    mask outright; the selection is what one chunk over all queries (which
    skips nothing while ``topk < T``) gives, whatever the chunk."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    T = 100
    layers = [SparseIndexerLayer(n_heads=2, head_dim=8, topk=topk,
                                 query_chunk=c) for c in (chunk, 128)]
    for layer in layers:
        layer.set_n_in(InputType.recurrent(16, T))
    params = layers[0].init_params(jax.random.PRNGKey(1))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, T, 16))
    got, want = (np.asarray(layer.apply(
        params, u, state={}, train=True, rng=None)[0]) for layer in layers)
    assert got.shape == (2, T, T) and np.array_equal(got, want)
    assert (got.sum(-1) == np.minimum(np.arange(T) + 1, topk)).all()
