"""What a remat node keeps beyond its inputs (``nn/remat.kept``): the flash
kernels' output and logsumexp. The node's rebuild reads them, runs no
forward kernel, and gives the gradients of the node rebuilt whole
(``remat_reference.rebuilt_whole``, the wrapper before it could keep
anything) and of the node without remat, bit for bit. The kernels run
interpreted."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.layers.attention import (
    attention_reference, top_keys)
from deeplearning4j_tpu.nn.remat import checkpoint_after_cotangent
from deeplearning4j_tpu.ops.pallas_attention import flash_attention
from deeplearning4j_tpu.profiling.metrics import MetricsRegistry, set_registry
from remat_reference import kernel_calls, rebuilt_whole

B, H, D, F = 2, 4, 8, 16
flash = functools.partial(flash_attention, causal=True, interpret=True)


def _heads(a, n):
    return a.reshape(a.shape[0], a.shape[1], n, -1).transpose(0, 2, 1, 3)


def _merged(o):
    return o.transpose(0, 2, 1, 3).reshape(o.shape[0], o.shape[2], -1)


def _weights(key, **shapes):
    keys = jax.random.split(key, len(shapes))
    return {n: 0.3 * jax.random.normal(k, s)
            for k, (n, s) in zip(keys, shapes.items())}


def plain(w, x, select=None, window=None):
    q, k, v = (_heads(x @ w[n], H) for n in "qkv")
    return jnp.tanh(_merged(flash(q, k, v, window=window,
                                  select=select))) @ w["o"]


def fewer_kv_heads(w, x):
    """Two key/value heads under four query heads, repeated before the
    kernel as ``GroupedQueryAttentionLayer`` repeats them."""
    q = _heads(x @ w["q"], H)
    k, v = (jnp.repeat(_heads(x @ w[n], 2), H // 2, axis=1) for n in "kv")
    return _merged(flash(q, k, v)) @ w["o"]


def differential(w, x):
    """Two score maps of head ``D`` over one value of ``2 D``, their
    difference taken after the kernel (``DifferentialAttentionLayer``)."""
    q, k = (_heads(x @ w[n], H) for n in "qk")
    v = jnp.repeat(_heads(x @ w["v"], H // 2), 2, axis=1)      # [B, H, T, 2D]
    o = flash(q, k, v).reshape(B, H // 2, 2, -1, 2 * D)
    return _merged(o[:, :, 0] - 0.3 * o[:, :, 1]) @ w["o"]


def _case(name):
    """``(node, weights, x, further arguments)`` of a case."""
    key = jax.random.PRNGKey(7)
    T = 600 if name == "window_512" else 150
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, T, F))
    square = dict(q=(F, H * D), k=(F, H * D), v=(F, H * D), o=(H * D, F))
    if name == "fewer_kv_heads":
        shapes = dict(square, k=(F, 2 * D), v=(F, 2 * D))
        return fewer_kv_heads, _weights(key, **shapes), x, ()
    if name == "differential":
        shapes = dict(square, v=(F, H // 2 * 2 * D), o=(H // 2 * 2 * D, F))
        return differential, _weights(key, **shapes), x, ()
    w = _weights(key, **square)
    if name == "selection":
        select = top_keys(jax.random.normal(key, (B, T, T)), 0, 40)
        return plain, w, x, (select,)
    if name == "window_512":
        return functools.partial(plain, select=None, window=512), w, x, ()
    return plain, w, x, ()


CASES = pytest.mark.parametrize("case", [
    "plain_causal", "window_512", "selection", "fewer_kv_heads",
    "differential"])


def _value_and_grads(node, w, x, *rest):
    """The node's output and the gradients of a fixed functional of it to
    its weights and its input, from one jitted program."""
    def scalar(w, x):
        out = node(w, x, *rest)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape))
                       ), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=(0, 1), has_aux=True))(w, x)
    return out, grads


def _assert_same_bits(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@CASES
def test_kept_gives_the_bits_of_rebuilt_whole_and_of_no_remat(case):
    node, w, x, rest = _case(case)
    kept = _value_and_grads(checkpoint_after_cotangent(node), w, x, *rest)
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(kept[1]))
    _assert_same_bits(kept, _value_and_grads(rebuilt_whole(node), w, x,
                                             *rest))
    _assert_same_bits(kept, _value_and_grads(node, w, x, *rest))


def _grad(wrap, node):
    return jax.grad(lambda w, x, *rest: jnp.sum(wrap(node)(w, x, *rest)),
                    argnums=(0, 1))


@CASES
def test_the_gradient_holds_one_forward_kernel(case):
    """One forward, one dq and one dk/dv kernel in the node's gradient; the
    node rebuilt whole runs the forward twice, which is what is saved."""
    node, w, x, rest = _case(case)
    assert kernel_calls(_grad(checkpoint_after_cotangent, node), w, x,
                        *rest) == dict(fwd=1, dq=1, dkv=1)
    assert kernel_calls(_grad(rebuilt_whole, node), w, x,
                        *rest) == dict(fwd=2, dq=1, dkv=1)
    assert kernel_calls(_grad(lambda fn: fn, node), w, x,
                        *rest) == dict(fwd=1, dq=1, dkv=1)


def twice(w, x):
    """Two call sites of different shapes: full attention, then a window
    over the first one's output at half the heads."""
    first = plain(w["first"], x)
    q, k, v = (_heads(first @ w["second"][n], 2) for n in "qkv")
    return first + _merged(flash(q, k, v, window=64)) @ w["second"]["o"]


def _twice_case():
    node, first, x, _ = _case("plain_causal")
    second = _weights(jax.random.PRNGKey(9), q=(F, 2 * D), k=(F, 2 * D),
                      v=(F, 2 * D), o=(2 * D, F))
    return twice, dict(first=first, second=second), x


def test_a_node_with_two_call_sites_keeps_two_pairs_each_its_own():
    node, w, x = _twice_case()
    kept = _value_and_grads(checkpoint_after_cotangent(node), w, x)
    _assert_same_bits(kept, _value_and_grads(rebuilt_whole(node), w, x))
    assert kernel_calls(_grad(checkpoint_after_cotangent, node), w, x) \
        == dict(fwd=2, dq=2, dkv=2)
    eqns = jax.make_jaxpr(_grad(checkpoint_after_cotangent, node))(w, x).eqns
    pairs = [v for e in eqns if e.primitive.name == "pallas_call"
             and e.params["name"] == "flash_attention_fwd" for v in e.outvars]
    assert [v.aval.shape[0] for v in pairs] == [B * H, B * H, B * 2, B * 2]
    # out and lse of either call site pass the barrier, in their order
    barrier = _barriers(eqns)[0]
    assert [v for v in barrier.invars if v in pairs] == pairs


def _barriers(eqns):
    return [e for e in eqns if e.primitive.name == "optimization_barrier"]


def test_the_kept_values_pass_the_barrier_of_the_inputs_and_the_cotangent():
    """The forward kernel's ``out`` and ``lse``, as it wrote them, are
    operands of the first barrier, beside the node's inputs and the
    cotangent: the rebuild cannot start, nor the kept pair be read, before
    the backward pass has reached the node."""
    node, w, x, _ = _case("plain_causal")
    eqns = jax.make_jaxpr(_grad(checkpoint_after_cotangent, node))(w, x).eqns
    forward = next(e for e in eqns if e.primitive.name == "pallas_call")
    assert forward.params["name"] == "flash_attention_fwd"
    first, second = _barriers(eqns)
    assert all(v in first.invars for v in forward.outvars)
    # the four weights, the input and the cotangent, and the pair
    assert len(first.invars) == len(w) + 2 + 2
    assert len(second.invars) == len(w) + 1
    # without a kernel's pair the barriers hold what they always held
    bare = _barriers(jax.make_jaxpr(_grad(rebuilt_whole, node))(w, x).eqns)
    assert [len(b.invars) for b in bare] == [len(w) + 2, len(w) + 1]


def dense(w, x):
    return jnp.tanh(x @ w["q"]) @ w["q"].T


def gate_fallback(w, x):
    """The XLA path a refused shape takes."""
    q, k, v = (_heads(x @ w[n], H) for n in "qkv")
    return _merged(attention_reference(q, k, v, causal=True)) @ w["o"]


@pytest.mark.parametrize("node", [dense, gate_fallback])
def test_a_node_that_runs_no_kernel_is_the_parents_jaxpr(node):
    _, w, x, _ = _case("plain_causal")
    kept = jax.make_jaxpr(_grad(checkpoint_after_cotangent, node))(w, x)
    assert str(kept) == str(jax.make_jaxpr(_grad(rebuilt_whole, node))(w, x))
    first, _ = _barriers(kept.eqns)
    assert len(first.invars) == len(w) + 2


@pytest.fixture
def registry():
    fresh = MetricsRegistry()
    previous = set_registry(fresh)
    yield fresh
    set_registry(previous)


def _kept_count(registry):
    return registry.labeled_counter("remat_kept_total").labels(
        kernel="flash_attention").value


def test_the_counter_counts_a_call_site_a_trace_and_none_without_remat(
        registry):
    node, w, x, _ = _case("plain_causal")
    bare = jax.jit(_grad(lambda fn: fn, node))
    bare(w, x)
    assert _kept_count(registry) == 0
    # the forward alone reads nothing: no backward, no rebuild
    jax.jit(checkpoint_after_cotangent(node))(w, x)
    assert _kept_count(registry) == 0
    kept = jax.jit(_grad(checkpoint_after_cotangent, node))
    kept(w, x)
    kept(w, x)
    assert _kept_count(registry) == 1
    two, w2, _ = _twice_case()
    jax.jit(_grad(checkpoint_after_cotangent, two))(w2, x)
    assert _kept_count(registry) == 3
    assert registry.labeled_counter("remat_kept_total").value == 3


# ---------------------------------------------------------------------------
# call sites that a node cannot keep for take today's path
# ---------------------------------------------------------------------------

def scanned(w, x):
    """The kernel inside a scan's body: its values belong to the body's
    trace and cannot leave through the node."""
    def body(h, _):
        return h + plain(w, h), None
    return lax.scan(body, x, None, length=2)[0]


def nested(w, x):
    """A node inside a node (``GatedDeltaNetLayer`` wraps its chunk-local
    work so): the inner one keeps its own pair in the outer's rebuild."""
    return x + checkpoint_after_cotangent(plain)(w, jnp.tanh(x))


@pytest.mark.parametrize("jitted", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("node,calls", [
    (scanned, dict(fwd=2, dq=1, dkv=1)),
    (nested, dict(fwd=2, dq=1, dkv=1))])
def test_a_call_site_under_a_trace_of_its_own_keeps_nothing_and_is_right(
        node, calls, jitted, registry):
    _, w, x, _ = _case("plain_causal")
    x = x[:, :40]
    grads = [_grad(wrap, node) for wrap in (checkpoint_after_cotangent,
                                            rebuilt_whole)]
    if jitted:
        grads = [jax.jit(g) for g in grads]
    got = grads[0](w, x)
    # the inner node of ``nested`` reads its own pair in the outer's rebuild
    assert _kept_count(registry) == (node is nested)
    want = grads[1](w, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-6)
    assert kernel_calls(_grad(checkpoint_after_cotangent, node), w, x) \
        == calls


@pytest.mark.parametrize("kernel_in,said", [
    ("rebuild", "call site more than"), ("forward", "read 0 of the 1")])
def test_a_rebuild_that_is_another_trace_is_refused(kernel_in, said):
    """A function that runs the kernel in one of its two traces alone (it
    looks at what it is traced under) can neither be handed a pair nobody
    kept nor leave one unread: an order that slipped would hand a call site
    another one's pair."""
    met = []

    def moody(w, x):
        met.append(None)
        first = len(met) == 1
        return plain(w, x) if first == (kernel_in == "forward") \
            else dense(w, x)

    _, w, x, _ = _case("plain_causal")
    with pytest.raises(RuntimeError, match=said):
        _grad(checkpoint_after_cotangent, moody)(w, x)
