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


@pytest.mark.parametrize("count", [4, 16])
def test_no_token_is_dropped_under_a_router_that_leans_on_one_expert(count):
    """Every token chooses expert 3 (its logit leans on an input every
    token carries): all 64 assignments to it are multiplied, many times a
    uniform router's share, and the output and every gradient are the
    reference's. The layer lays out all ``N K`` rows whatever the router
    does, so this is the same program as any other routing."""
    w = whole_weights(1)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 64, F))
    u = u.at[..., 0].set(4.0)
    w["moe/W_r"] = w["moe/W_r"].at[:, 3].set(0.0).at[0, 3].set(5.0)
    layer = layer_of(0, count)
    y, state = run(layer, share(w, 0, count), u)
    assert int(state["assigned"][3]) == 64      # a uniform share: 4
    want = reference_layer(w, u, 0, count)
    assert float(jnp.abs(y - want).max()) < 2e-5
    g, gu = jax.grad(lambda p, u: run(layer, p, u)[0].sum(),
                     argnums=(0, 1))(share(w, 0, count), u)
    gw, gwu = jax.grad(lambda ww, u: reference_layer(ww, u, 0, count).sum(),
                       argnums=(0, 1))(w, u)
    for leaf in ("W_gate", "W_up", "W_down"):
        assert float(jnp.abs(g[leaf] - gw[f"moe/{leaf}"][:count]).max()
                     ) < 1e-4, leaf
    assert float(jnp.abs(g["W_r"] - gw["moe/W_r"]).max()) < 1e-4
    assert float(jnp.abs(gu - gwu).max()) < 1e-4


def test_the_written_out_transposes_are_the_gathers_own():
    """``take_rows`` and ``weigh_back`` against the same gathers left to
    autodiff (whose transposes are scatter-adds), on a random routing."""
    from deeplearning4j_tpu.nn.layers.experts import take_rows, weigh_back
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    N, k = 30, 4
    u = jax.random.normal(ks[0], (N, F))
    held = jax.random.bernoulli(ks[1], 0.4, (N, k))
    order = jnp.argsort(jnp.where(held, 0, 1).reshape(-1), stable=True)
    place = jnp.argsort(order).reshape(N, k)
    cot = jax.random.normal(ks[2], (N * k, F))
    live = (jnp.arange(N * k) < held.sum())[:, None]
    mine = jax.grad(lambda u: jnp.sum(jnp.where(
        live, take_rows(u, order, place, held), 0) * cot))(u)
    plain = jax.grad(lambda u: jnp.sum(jnp.where(
        live, u[order // k], 0) * cot))(u)
    assert float(jnp.abs(mine - plain).max()) < 1e-5
    out = jnp.where(live, jax.random.normal(ks[3], (N * k, F)), 0)
    weights = jnp.where(held, jax.random.uniform(ks[4], (N, k)), 0.0)
    cot = jax.random.normal(ks[2], (N, F))
    mine = jax.grad(lambda o, w: jnp.sum(weigh_back(o, w, order, place)
                                         * cot), argnums=(0, 1))(out, weights)
    plain = jax.grad(lambda o, w: jnp.sum(jnp.sum(
        o[place] * w[..., None], axis=1) * cot), argnums=(0, 1))(out, weights)
    for a, b in zip(mine, plain):
        assert float(jnp.abs(a - b).max()) < 1e-5


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
