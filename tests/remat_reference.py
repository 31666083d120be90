"""The remat wrapper as it was before a node could keep anything beyond its
inputs (``nn/remat.checkpoint_after_cotangent`` up to PR 37): the reference
form the tests hold the keeping form to. Its rebuild runs the node's whole
forward again, every kernel in it, so a step under it makes each flash
forward twice and is otherwise the same arithmetic, bit for bit."""

import re

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.nn import netcommon
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.remat import _together


def rebuilt_whole(fn):
    @jax.custom_vjp
    def node(*args):
        return fn(*args)

    def forward(*args):
        return fn(*args), args

    def backward(args, ct):
        args, ct = _together((args, ct))
        _, vjp = jax.vjp(fn, *args)
        return _together(vjp(ct))

    node.defvjp(forward, backward)
    return node


def kernel_calls(fn, *args):
    """How many of each flash kernel the jaxpr of ``fn(*args)`` holds."""
    text = str(jax.make_jaxpr(fn)(*args))
    return {part: len(re.findall(rf"name=flash_attention_{part}\b", text))
            for part in ("fwd", "dq", "dkv")}


def assert_a_models_step_keeps_its_flash_pairs(monkeypatch, build, batches,
                                               attention_nodes: int):
    """A decoder's training step (``build()`` gives the net, remat on, ids
    in at ``tokens`` and out at ``head``) holds one flash forward for each
    of its ``attention_nodes``, where the reference form holds two, and the
    losses and parameters after ``batches`` are the reference form's bit
    for bit."""
    def stepped():
        net = build()
        assert net.conf.training.remat and attention_nodes == sum(
            isinstance(layer, SelfAttentionLayer)
            for layer in net._layer_list())
        losses = []
        for x, y in batches:
            net.fit(DataSet(x, y))
            losses.append(np.asarray(net.score_value))
        x, y = batches[0]
        calls = kernel_calls(       # the step fit ran: its trace is cached
            net._train_step_fn, net.params, net.opt_state, net.states,
            {"tokens": jnp.asarray(x)}, {"head": jnp.asarray(y)}, None, None,
            jax.random.PRNGKey(0))
        return calls, losses, jax.device_get(net.params)

    calls, losses, params = stepped()
    n = attention_nodes
    assert calls == dict(fwd=n, dq=n, dkv=n)
    with monkeypatch.context() as m:    # the containers' walk, as it was
        m.setattr(netcommon, "checkpoint_after_cotangent", rebuilt_whole)
        ref_calls, ref_losses, ref_params = stepped()
    assert ref_calls == dict(fwd=2 * n, dq=n, dkv=n)
    assert all(np.isfinite(loss) for loss in losses)
    np.testing.assert_array_equal(losses, ref_losses)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(a, b)
