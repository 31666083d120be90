"""Routed experts held as one chip's share (``nn/layers/experts.py``)
against the benchmark's plain reference, a loop over experts: the shares of
eight chips add up to the whole layer, no token is dropped however the
router leans, the weights are renormalised over every chosen expert."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as reference
from benchmark.reference.olmo_hybrid import rounders
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import RoutedExpertsLayer
from deeplearning4j_tpu.nn.layers.experts import route_top_k

F, M, E, K = 32, 16, 128, 8
CFG = {"num_experts_per_tok": K, "norm_topk_prob": True}


def layer_of(first, count, **kw):
    layer = RoutedExpertsLayer(n_experts=E, top_k=K, n_hidden=M, first=first,
                               count=count, activation="silu", **kw)
    layer.set_n_in(InputType.recurrent(F, None))
    return layer


def whole_weights(seed=0):
    """All 128 experts' weights under the reference's names."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = {"moe/W_r": jax.random.normal(ks[0], (F, E)) * 0.5,
         "moe/W_gate": jax.random.normal(ks[1], (E, F, M)) * 0.3,
         "moe/W_up": jax.random.normal(ks[2], (E, F, M)) * 0.3,
         "moe/W_down": jax.random.normal(ks[3], (E, M, F)) * 0.3}
    return w


def share(w, first, count):
    cut = lambda a: a[first:first + count]
    return {"W_r": w["moe/W_r"], "W_gate": cut(w["moe/W_gate"]),
            "W_up": cut(w["moe/W_up"]), "W_down": cut(w["moe/W_down"])}


def reference_layer(w, u, first, count, fault=None):
    cfg = dict(CFG, first_expert=first, num_experts=count)
    held = {**w, **{f"moe/{k}": v for k, v in share(w, first, count).items()
                    if k != "W_r"}}
    return reference.routed_experts(held, "moe", u, cfg, *rounders("float32"),
                                    fault=fault)


def run(layer, params, u):
    return layer.apply(params, u, state=layer.init_state(), train=True,
                       rng=None)


def test_the_shares_of_eight_chips_add_up_to_the_whole_layer():
    """Eight held ranges of 16 over the same tokens sum to the uncut
    reference's layer over all 128 experts, and each share is the
    reference's own cut."""
    w = whole_weights()
    u = jax.random.normal(jax.random.PRNGKey(9), (2, 50, F))
    whole = reference_layer(w, u, 0, E)
    total, assigned = 0.0, 0
    for chip in range(8):
        layer = layer_of(16 * chip, 16)
        y, state = run(layer, share(w, 16 * chip, 16), u)
        want = reference_layer(w, u, 16 * chip, 16)
        assert float(jnp.abs(y - want).max()) < 2e-5, chip
        total = total + y
        assigned += int(state["assigned"].sum())
    assert float(jnp.abs(total - whole).max()) < 5e-5
    assert assigned == 2 * 50 * K       # every assignment lives somewhere


def reference_and_grads(w, u, first, count, dtype=jnp.float32):
    """The reference's output and its gradients by ``w`` and ``u``, computed
    in ``dtype`` from the same float32 numbers, as numpy arrays."""
    with jax.enable_x64(dtype == jnp.float64):
        w, u = jax.tree.map(lambda a: jnp.asarray(a, dtype), (w, u))
        layer = lambda ww, uu: reference_layer(ww, uu, first, count)
        y, (gw, gu) = jax.jit(lambda ww, uu: (layer(ww, uu), jax.grad(
            lambda ww, uu: layer(ww, uu).sum(), argnums=(0, 1))(ww, uu)))(
                w, u)
        return jax.tree.map(np.asarray, (y, gw, gu))


def assert_exact(got, plain, exact, tol, what):
    """``got`` lies within ``tol`` of the exact answer (the reference in
    float64), or, where the float32 reference ``plain`` itself lies further
    than half of that from it, within twice the reference's own distance:
    a float32 sum of 64 rows that come to some 900 is good to a few of
    float32's steps there (6.1e-5 each), whatever its order."""
    plain_err = float(np.abs(np.asarray(plain, np.float64) - exact).max())
    err = float(np.abs(np.asarray(got, np.float64) - exact).max())
    assert err < max(tol, 2 * plain_err), (what, err, plain_err)


@pytest.mark.parametrize("count, leaning, assigned, chunks", [
    pytest.param(16, [3], [64], 1, id="16"),
    pytest.param(8, [3], [64], 2, id="8"),
    pytest.param(4, [3], [64], 3, id="4"),
    pytest.param(4, [0, 1, 2, 3], [64] * 4, 8, id="4-all"),
])
def test_no_token_is_dropped_under_a_router_that_leans_on_one_expert(
        count, leaning, assigned, chunks):
    """Every token chooses the experts ``leaning`` (their logits lean on an
    input every token carries): all 64 assignments to each are multiplied,
    many times a uniform router's share, and the output and every gradient
    are the reference's. The layer runs its held rows in chunks of twice a
    uniform router's share (32 rows for 4 held of 128 experts, 64 for 8,
    128 for 16), as many as the held rows fill: one, two, three, and eight
    of the sixteen a share of 4 can take when every token chooses all four
    held experts (the most). Held against the exact answer: the weight
    gradients' sums go by chunks, in another order than the float32
    reference's, and neither order is the exact one."""
    w = whole_weights(1)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 64, F))
    u = u.at[..., 0].set(4.0)
    w["moe/W_r"] = w["moe/W_r"].at[:, leaning].set(0.0).at[0, leaning].set(
        5.0)
    layer = layer_of(0, count)
    y, state = run(layer, share(w, 0, count), u)
    assert [int(state["assigned"][e]) for e in leaning] == assigned
    assert int(state["chunks"]) == chunks
    plain = reference_and_grads(w, u, 0, count)
    exact = reference_and_grads(w, u, 0, count, jnp.float64)
    assert_exact(y, plain[0], exact[0], 2e-5, "y")
    g, gu = jax.jit(jax.grad(lambda p, u: run(layer, p, u)[0].sum(),
                             argnums=(0, 1)))(share(w, 0, count), u)
    for leaf in ("W_gate", "W_up", "W_down", "W_r"):
        cut = slice(None) if leaf == "W_r" else slice(count)
        assert_exact(g[leaf], plain[1][f"moe/{leaf}"][cut],
                     exact[1][f"moe/{leaf}"][cut], 1e-4, leaf)
    assert_exact(gu, plain[2], exact[2], 1e-4, "u")


def test_the_written_out_transposes_are_the_gathers_own():
    """``held_experts`` (its backward written out: gathers and the
    products' transposes, chunk by chunk) against the same layout left to
    autodiff over every row at once (whose gathers' transposes are
    scatter-adds), on a random routing that fills three chunks."""
    from deeplearning4j_tpu.nn.layers.experts import (
        grouped_dot, held_experts)
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    N, k, C, R = 30, 4, 3, 16
    u = jax.random.normal(ks[0], (N, F))
    held = jax.random.bernoulli(ks[1], 0.4, (N, k))
    key = jnp.where(held, jax.random.randint(ks[2], (N, k), 0, C), C
                    ).reshape(-1)
    order = jnp.argsort(key, stable=True)
    place = jnp.argsort(order).reshape(N, k)
    sizes = jnp.sum(jax.nn.one_hot(key, C, dtype=jnp.int32), axis=0)
    weights = jnp.where(held, jax.random.uniform(ks[3], (N, k)), 0.0)
    ws = [jax.random.normal(kk, shape) * 0.3 for kk, shape in zip(
        jax.random.split(ks[4], 3), ((C, F, M), (C, F, M), (C, M, F)))]
    cot = jax.random.normal(ks[5], (N, F))
    assert -(-int(sizes.sum()) // R) == 3

    def mine(u, weights, *ws):
        return held_experts(R, jax.nn.silu, u, weights,
                            jnp.pad(order, (0, -(N * k) % R)), place, held,
                            sizes, *ws)

    def plain(u, weights, w_gate, w_up, w_down):
        live = (jnp.arange(N * k) < sizes.sum())[:, None]
        rows = jnp.where(live, u[order // k], 0)
        hidden = (jax.nn.silu(grouped_dot(rows, w_gate, sizes))
                  * grouped_dot(rows, w_up, sizes))
        out = jnp.where(live, grouped_dot(hidden, w_down, sizes), 0)
        return jnp.sum(out[place] * weights[..., None], axis=1)

    assert float(jnp.abs(mine(u, weights, *ws) - plain(u, weights, *ws)
                         ).max()) < 1e-5
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * cot)
    grads = lambda fn: jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2, 3, 4)))
    got = grads(mine)(u, weights, *ws)
    want = grads(plain)(u, weights, *ws)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-5


def test_the_step_holds_one_body_of_grouped_products():
    """No second full-size path: the layer's ``value_and_grad`` holds the
    parent layer's nine grouped products (three forward, each one's two
    transposes backward), counted over every nested jaxpr, the chunks'
    loops included, at a share that runs up to sixteen chunks."""
    from jax.extend import core as jcore
    layer = layer_of(0, 4)
    w = whole_weights(4)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 64, F))
    loss = lambda p, u: run(layer, p, u)[0].sum()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        share(w, 0, 4), u).jaxpr

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "ragged_dot_general"
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    if isinstance(sub, jcore.ClosedJaxpr):
                        n += count(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        n += count(sub)
        return n

    assert count(jaxpr) <= 9


@pytest.mark.parametrize("assignments, count, n_experts, rows", [
    (8192 * 8, 16, 128, 16384),     # keye_vl2_train_seq8k_1chip: 4 chunks
    (16384 * 6, 8, 64, 24576),      # smallthinker_train_seq16k_1chip: 4
    (64 * 8, 4, 128, 32), (64 * 8, 16, 128, 128),   # this file's layers
    (64 * 8, 128, 128, 512), (50 * 3, 8, 8, 152),   # every expert: 1 chunk
])
def test_a_chunk_is_twice_a_uniform_routers_held_share(
        assignments, count, n_experts, rows):
    from deeplearning4j_tpu.nn.layers.experts import chunk_rows
    assert chunk_rows(assignments, count, n_experts) == rows


def test_the_weights_are_renormalised_over_every_chosen_expert():
    logits = jax.random.normal(jax.random.PRNGKey(4), (10, E))
    weights, experts = route_top_k(logits, K)
    assert np.allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    p = jax.nn.softmax(logits, -1)
    raw, _ = route_top_k(logits, K, renormalize=False)
    assert np.allclose(np.asarray(raw), np.asarray(
        jnp.take_along_axis(p, experts, -1)), atol=1e-7)
    # a share holding some of a token's chosen experts weighs them by the
    # sum over ALL eight, so the layer's output is not the fault's
    w = whole_weights(3)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 20, F))
    layer = layer_of(32, 16)
    y, _ = run(layer, share(w, 32, 16), u)
    fault = reference_layer(w, u, 32, 16, fault="raw_weights")
    assert float(jnp.abs(y - reference_layer(w, u, 32, 16)).max()) < 2e-5
    assert float(jnp.abs(y - fault).max()) > 1e-2


def test_a_flat_input_and_a_mask():
    w = whole_weights(6)
    layer = layer_of(0, 16)
    u = jax.random.normal(jax.random.PRNGKey(7), (12, F))
    y, _ = run(layer, share(w, 0, 16), u)
    assert y.shape == (12, F)
    u3 = u.reshape(2, 6, F)
    mask = jnp.ones((2, 6)).at[1, 4:].set(0.0)
    ym, _ = layer.apply(share(w, 0, 16), u3, state=layer.init_state(),
                        train=True, rng=None, mask=mask)
    assert not np.asarray(ym[1, 4:]).any()
    assert np.allclose(np.asarray(ym[0]), np.asarray(y[:6]), atol=1e-6)


def test_a_range_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="experts 120 to 136"):
        layer_of(120, 16)
