"""The shell of a compiled training step and the application of one layer,
written once in ``nn/netcommon.py`` for both containers: what the four
builders (list and graph, standard and tBPTT) hand back, under which name,
and that one step from fixed seeds is the plain ``jax.value_and_grad`` +
``compute_updates`` written out here. Tiny sizes, CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.core import (
    CenterLossOutputLayer, DenseLayer, OutputLayer)
from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import compute_updates, l1_l2_penalty
from deeplearning4j_tpu.resilience.sentinel import DivergenceSentinel

B, T, F, C = 5, 4, 6, 3


def _builder(precision=None, remat=False):
    b = (NeuralNetConfiguration.builder().seed(7).updater("nesterovs")
         .learning_rate(0.05).l2(1e-3))
    if precision:
        b = b.precision(precision)
    return b.gradient_checkpointing() if remat else b


def _dense_layers(head=OutputLayer):
    return [DenseLayer(n_out=8, activation="tanh", dropout=0.8),
            DenseLayer(n_out=8, activation="relu"),
            head(n_out=C, activation="softmax")]


def _rnn_layers():
    return [GravesLSTM(n_out=8, activation="tanh", dropout=0.9),
            RnnOutputLayer(n_out=C, activation="softmax")]


def _net(container, step, **kw):
    """A list or a chain graph of the same layers; the input is named
    ``in`` and the graph's nodes ``l0, l1, ...`` with the head ``out``."""
    layers = _rnn_layers() if step == "tbptt" else _dense_layers()
    in_type = (InputType.recurrent(F) if step == "tbptt"
               else InputType.feed_forward(F))
    if container == "list":
        b = _builder(**kw).list()
        for layer in layers:
            b = b.layer(layer)
        if step == "tbptt":
            b = b.backprop_type("truncated_bptt", T, T)
        return MultiLayerNetwork(b.set_input_type(in_type).build()).init()
    g = _builder(**kw).graph_builder().add_inputs("in")
    names = [f"l{i}" for i in range(len(layers) - 1)] + ["out"]
    for name, layer, before in zip(names, layers, ["in"] + names):
        g = g.add_layer(name, layer, before)
    g = g.set_outputs("out").set_input_types(in_type)
    if step == "tbptt":
        g = g.backprop_type("truncated_bptt", T, T)
    return ComputationGraph(g.build()).init()


def _batch(container, step):
    rng = np.random.default_rng(0)
    shape = (B, T) if step == "tbptt" else (B,)
    x = jnp.asarray(rng.normal(size=shape + (F,)), jnp.float32)
    y = jnp.asarray(np.eye(C, dtype=np.float32)[
        rng.integers(0, C, shape)])
    if container == "list":
        return x, y, None, None
    return {"in": x}, {"out": y}, None, None


def _carries(net, container):
    if container == "list":
        return [layer.initial_carry(B, jnp.float32)
                if getattr(layer, "supports_carry", False) else None
                for layer in net.layers]
    return {name: net.conf.nodes[name].layer.initial_carry(B, jnp.float32)
            for name in net._layer_nodes
            if getattr(net.conf.nodes[name].layer, "supports_carry", False)}


def _layers(net, container):
    return net.layers if container == "list" else net._layer_list()


def _plain_step(net, container, step):
    """The step without the shell: float32 ``jax.value_and_grad`` of the
    container's loss, then ``compute_updates``."""
    layers = _layers(net, container)

    def tbptt_loss(p, states, x, y, carries, rng):
        if container == "list":
            h, _, new_states, new_carries, mask = net._forward(
                p, states, x, train=True, rng=rng, carries=carries)
            loss = (layers[-1].compute_loss(p[-1], h, y, mask=mask)
                    + l1_l2_penalty(p, layers))
        else:
            acts, masks, new_states, new_carries = net._forward(
                p, states, x, train=True, rng=rng, carries=carries)
            loss = (net._data_loss(p, acts, masks, y, None) + l1_l2_penalty(
                [p[n] for n in net._layer_nodes], layers))
        return loss, (new_states, new_carries)

    def plain(params, opt_state, states, x, y, carries, rng):
        if step == "tbptt":
            (loss, (new_states, new_carries)), grads = jax.value_and_grad(
                tbptt_loss, has_aux=True)(params, states, x, y, carries, rng)
        else:
            (loss, new_states), grads = jax.value_and_grad(
                lambda p: net._loss_fn(p, states, x, y, None, None, rng),
                has_aux=True)(params)
            new_carries = None
        new_params, new_opt = compute_updates(
            net._tx, grads, opt_state, params, layers, net.conf.training)
        return new_params, new_opt, new_states, new_carries, loss

    return jax.jit(plain)


def _assert_trees(got, want, exact):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if exact:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:   # bfloat16 forward and backward against float32
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=0.05, atol=2e-3)


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
@pytest.mark.parametrize("sentinel", [False, True], ids=["bare", "guarded"])
@pytest.mark.parametrize("step", ["standard", "tbptt"])
@pytest.mark.parametrize("container", ["list", "graph"])
def test_step_is_plain_grad_and_update(container, step, sentinel, precision):
    net = _net(container, step, precision=precision)
    if sentinel:
        net.set_divergence_sentinel(DivergenceSentinel("skip_batch"))
    x, y, fmask, lmask = _batch(container, step)
    rng = jax.random.PRNGKey(3)
    carries = _carries(net, container) if step == "tbptt" else None
    plain = _net(container, step)       # the same seed's weights, float32
    want = _plain_step(plain, container, step)(
        plain.params, plain.opt_state, plain.states, x, y, carries, rng)

    if step == "tbptt":
        fn = net._build_tbptt_step()
        args = (x, y, fmask, lmask, carries, rng)
    else:
        fn = net._build_train_step()
        args = (x, y, fmask, lmask, rng)
    # the name the trace's jit_train_step comes from
    assert fn.__name__ == ("step" if step == "tbptt" else "train_step")
    out = fn(net.params, net.opt_state, net.states, *args)

    exact = precision == "fp32"
    new_params, new_opt, new_states, new_carries, loss = want
    if step == "tbptt":     # (params, opt, states, carries, loss[, bad])
        assert len(out) == 5 + sentinel
        _assert_trees(out[3], new_carries, exact)
        got_loss = out[4]
    else:                   # (params, opt, states, loss, grads[, bad])
        assert len(out) == 5 + sentinel
        assert out[4] is None       # no listener asked for the gradients
        got_loss = out[3]
    _assert_trees(out[0], new_params, exact)
    _assert_trees(out[2], new_states, exact)
    _assert_trees(got_loss, loss, exact)
    if exact:
        _assert_trees(out[1], new_opt, exact)
    if sentinel:
        assert out[-1].shape == () and not bool(out[-1])


@pytest.mark.parametrize("container", ["list", "graph"])
def test_guarded_step_keeps_the_old_state_on_a_nan_batch(container):
    net = _net(container, "standard")
    net.set_divergence_sentinel(DivergenceSentinel("skip_batch"))
    x, y, fmask, lmask = _batch(container, "standard")
    x = jax.tree.map(lambda a: a.at[0, 0].set(jnp.nan), x)
    before = jax.tree.map(np.asarray, (net.params, net.opt_state))
    out = net._build_train_step()(net.params, net.opt_state, net.states,
                                  x, y, fmask, lmask, jax.random.PRNGKey(3))
    assert bool(out[-1])
    _assert_trees((out[0], out[1]), before, exact=True)


@pytest.mark.parametrize("container", ["list", "graph"])
def test_remat_is_the_form_that_waits_for_the_cotangent(container):
    """``gradient_checkpointing()`` wraps each applied layer in
    ``checkpoint_after_cotangent`` in both containers: two optimization
    barriers a layer in the backward pass (``jax.checkpoint`` lowers to
    one, and was measured not to bound memory on the chip)."""
    net = _net(container, "standard", remat=True)
    x, y, fmask, lmask = _batch(container, "standard")
    text = net._build_train_step().lower(
        net.params, net.opt_state, net.states, x, y, fmask, lmask,
        jax.random.PRNGKey(3)).as_text()
    applied = len(_layers(net, container)) - 1      # the head is not
    assert text.count("optimization_barrier") == 2 * applied


def test_collected_gradients_ride_in_the_fifth_place():
    net = _net("graph", "standard")
    net._collect_grads = True
    x, y, fmask, lmask = _batch("graph", "standard")
    out = net._build_train_step()(net.params, net.opt_state, net.states,
                                  x, y, fmask, lmask, jax.random.PRNGKey(3))
    assert jax.tree.structure(out[4]) == jax.tree.structure(out[0])


def test_center_loss_centers_move_by_the_ema_after_the_update():
    b = _builder().list()
    for layer in _dense_layers(head=lambda **kw: CenterLossOutputLayer(
            alpha=0.2, lambda_=0.01, **kw)):
        b = b.layer(layer)
    net = MultiLayerNetwork(
        b.set_input_type(InputType.feed_forward(F)).build()).init()
    x, y, fmask, lmask = _batch("list", "standard")
    rng = jax.random.PRNGKey(3)
    h_last = net._forward(net.params, net.states, x, train=True, rng=rng)[0]
    want = net.layers[-1].updated_centers(
        {"cL": net.params[-1]["cL"]}, h_last, y)
    assert not np.allclose(np.asarray(want), 0.0)
    out = net._build_train_step()(net.params, net.opt_state, net.states,
                                  x, y, fmask, lmask, rng)
    np.testing.assert_allclose(np.asarray(out[0][-1]["cL"]),
                               np.asarray(want), rtol=1e-6, atol=1e-7)
