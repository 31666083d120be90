"""``BatchNormalization`` in training mode takes its statistics in one pass
(two sibling reductions of the input about the running mean). These tests
hold it to the two-pass form, kept here as the plain reference, and hold the
mechanism itself: in the jaxpr of a conv + batch-norm loss's gradient no
batch reduction waits for more than one other."""

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
import numpy as np
import pytest

from deeplearning4j_tpu.gradientcheck.check import enable_x64
from deeplearning4j_tpu.nn.layers import BatchNormalization

CONV, DENSE = (8, 6, 6, 5), (64, 7)
TOL = {"float32": 1e-6, "float64": 1e-12}


def _layer(n, **kw):
    bn = BatchNormalization(**kw)
    bn.n_features = n
    return bn


def _input(shape, dtype, mean=0.5, spread=2.0, seed=0):
    rng = np.random.default_rng(seed)
    scale = spread * (1 + 0.1 * np.arange(shape[-1]))
    return (mean + scale * rng.normal(size=shape)).astype(dtype)


def two_pass(bn, params, x, state):
    """The form the layer had: the variance centred on a finished mean."""
    axes = tuple(range(x.ndim - 1))
    xs = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    mean, var = jnp.mean(xs, axis=axes), jnp.var(xs, axis=axes)
    new_state = {"mean": bn.decay * state["mean"] + (1 - bn.decay) * mean,
                 "var": bn.decay * state["var"] + (1 - bn.decay) * var}
    xhat = (xs - mean) * jax.lax.rsqrt(var + bn.eps)
    out = params["gamma"] * xhat + params["beta"]
    return out.astype(x.dtype), new_state


def _batch_stats(bn, state, new_state):
    """The batch's mean and variance, read back from the running update."""
    return {k: (np.asarray(new_state[k], np.float64)
                - bn.decay * np.asarray(state[k], np.float64))
            / (1 - bn.decay) for k in ("mean", "var")}


def _rel(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.abs(want)))


@pytest.mark.parametrize("shape", [CONV, DENSE], ids=["conv", "dense"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_one_pass_statistics_agree_with_mean_and_var(dtype, shape):
    with enable_x64(dtype == "float64"):
        bn = _layer(shape[-1], decay=0.5)
        x = jnp.asarray(_input(shape, dtype))
        axes = tuple(range(x.ndim - 1))
        # a running mean part of the way to the batch's, as in training
        state = {"mean": 0.7 * jnp.mean(x, axis=axes),
                 "var": jnp.ones(shape[-1], dtype)}
        _, new_state = bn.apply(bn.init_params(None, dtype), x, state=state,
                                train=True, rng=None)
        assert new_state["mean"].dtype == new_state["var"].dtype == dtype
        x64 = np.asarray(x, np.float64)
        got = _batch_stats(bn, state, new_state)
        assert _rel(got["mean"], x64.mean(axis=axes)) < TOL[dtype]
        assert _rel(got["var"], x64.var(axis=axes)) < TOL[dtype]
        # and with the two numpy-style calls in the layer's own dtype
        assert _rel(got["mean"], np.asarray(jnp.mean(x, axis=axes),
                                            np.float64)) < TOL[dtype]
        assert _rel(got["var"], np.asarray(jnp.var(x, axis=axes),
                                           np.float64)) < TOL[dtype]


@pytest.mark.parametrize("followed,tol", [(True, 1e-4), (False, 2e-2)],
                         ids=["mean_followed", "zero_state"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_feature_whose_mean_dwarfs_its_spread(seed, followed, tol):
    """Mean 100, spread 1, float32: the moments about the running mean lose
    nothing once it has followed the feature (to 1 %, here; 1.5e-7 to 5e-7
    read on six seeds); from a fresh state they are the raw moments and
    agree to the looser figure the layer's docstring gives (2.8e-3 to
    4.4e-3 read; 0.015 to 0.037 before the moments went by example)."""
    shape = (32, 14, 14, 4)
    bn = _layer(shape[-1], decay=0.5)
    x = _input(shape, np.float32, mean=100.0, spread=1.0, seed=seed)
    x64 = x.astype(np.float64)
    state = bn.init_state()
    if followed:
        state = dict(state, mean=jnp.asarray(
            1.01 * x64.mean(axis=(0, 1, 2)), jnp.float32))
    out, new_state = bn.apply(bn.init_params(None), jnp.asarray(x),
                              state=state, train=True, rng=None)
    got = _batch_stats(bn, state, new_state)
    assert _rel(got["var"], x64.var(axis=(0, 1, 2))) < tol
    assert _rel(got["mean"], x64.mean(axis=(0, 1, 2))) < 1e-5
    xhat = (x64 - x64.mean(axis=(0, 1, 2))) / np.sqrt(
        x64.var(axis=(0, 1, 2)) + bn.eps)
    assert np.max(np.abs(np.asarray(out) - xhat) / (1 + np.abs(xhat))) < tol
    assert np.all(np.asarray(new_state["var"]) >= 0)


def test_moments_by_example_first_are_as_near_as_the_two_pass_variance():
    """Why ``_batch_mean`` is a mean of means: XLA's CPU reduction adds its
    terms one after another, and the difference of raw moments feels the
    rounding of ``m2``'s whole sum. Float32, 8x16x16 values a channel of
    mean 1.5 and spread 1, from a fresh state (c = 0, ``m2`` 3.25 times the
    variance): the layer's variance stands 3.1e-7 to 4.7e-7 from the
    float64 one in the median channel, 2.0 to 3.7 times as far as
    ``jnp.var``'s, where one reduction over all of (0, 1, 2) stands 4.6 to
    8.1 times further off again (six seeds)."""
    shape, axes = (8, 16, 16, 64), (0, 1, 2)
    bn = _layer(shape[-1], decay=0.5)
    ratios = []
    for seed in range(3):
        x = np.random.default_rng(seed).normal(1.5, 1.0, shape).astype(
            np.float32)
        want = x.astype(np.float64).var(axis=axes)
        err = lambda got: float(np.median(np.abs(
            np.asarray(got, np.float64) - want) / want))
        state = bn.init_state()
        _, new_state = jax.jit(lambda x: bn.apply(
            bn.init_params(None), x, state=state, train=True, rng=None))(x)
        got = err(_batch_stats(bn, state, new_state)["var"])
        two = err(jax.jit(lambda x: jnp.var(x, axis=axes))(x))
        flat = err(jax.jit(lambda x: jnp.mean(x * x, axis=axes)
                           - jnp.mean(x, axis=axes) ** 2)(x))
        assert got < 6 * two and got < 1e-6, (got, two)
        ratios.append(flat / got)
    assert min(ratios) > 3, ratios


def test_a_constant_feature_comes_out_as_beta():
    """No spread at all: the difference of the moments is rounding, of
    either sign; the variance is held at what ``m2`` resolves, never below,
    and the output stays at ``beta`` whatever the feature's size."""
    for value in (7.3, 7.3e4):
        bn = _layer(3, decay=0.5)
        x = jnp.full((16, 3), value, jnp.float32)
        out, new_state = bn.apply(bn.init_params(None), x,
                                  state=bn.init_state(), train=True, rng=None)
        var = _batch_stats(bn, bn.init_state(), new_state)["var"]
        assert np.all(var >= 0) and np.all(var <= 1e-5 * value ** 2)
        assert np.max(np.abs(np.asarray(out))) < 1e-2


# ---------------------------------------------------------------------------
# where raw moments are weak: few values a channel, and squares that overflow
# ---------------------------------------------------------------------------

def _pairs(size, gaps, n=64, seed=0):
    """(2, len(gaps) * n): two values a channel, ``size`` large and a
    relative ``gap`` apart (0: equal)."""
    rng = np.random.default_rng(seed)
    a = size * rng.uniform(1, 2, (len(gaps), n)) * rng.choice([-1, 1], n)
    b = a * (1 + np.asarray(gaps)[:, None] * rng.uniform(0.5, 1, a.shape))
    return np.stack([a.ravel(), b.ravel()]).astype(np.float32)


GAPS = (0.0, 1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 0.5)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("conv", [False, True], ids=["dense", "conv_1x1"])
@pytest.mark.parametrize("size", [1.0, 1e3, 1e6])
def test_two_values_a_channel_stay_bounded(size, conv, jit):
    """A batch of two at a 1x1 stage (``chip_smoke.py --dry-cpu``'s last
    one): the two-pass form gives +-1, and sqrt(2) where the two are an ulp
    apart and the mean rounds onto one of them, whatever the values' size.
    The raw moments (fresh state, c = 0) cannot resolve a spread under
    ``sqrt(eps) * |mean|``, and a variance clamped at 0 there emitted
    ``(x - mean) * 316 * gamma``, without bound; held at what ``m2``
    resolves, the output is within 1.5 too, and follows the two-pass form to
    0.05 where the spread is ten times what is resolved."""
    x = _pairs(size, GAPS)
    if conv:
        x = x.reshape(2, 1, 1, -1)
    n = x.shape[-1]
    bn = _layer(n)
    params, state = bn.init_params(None), bn.init_state()
    apply = lambda p, x: bn.apply(p, x, state=state, train=True, rng=None)
    out, new_state = (jax.jit(apply) if jit else apply)(params, jnp.asarray(x))
    want, _ = two_pass(bn, params, jnp.asarray(x), state)
    out, want = np.asarray(out).reshape(2, n), np.asarray(want).reshape(2, n)
    assert np.all(np.isfinite(out)) and np.all(np.abs(want) <= 1.5)
    assert np.max(np.abs(out)) <= 1.5
    assert all(np.all(np.isfinite(np.asarray(v))) for v in new_state.values())
    assert np.all(np.asarray(new_state["var"]) >= 0)
    x64 = x.reshape(2, n).astype(np.float64)
    resolved = x64.var(axis=0) > 10 * 8 * np.finfo(np.float32).eps * np.mean(
        x64 ** 2, axis=0)
    assert resolved.sum() >= 2 * 64         # the gaps of 0.1 and over
    assert np.max(np.abs(out - want)[:, resolved]) < 0.05
    # and the gradient through it is finite, where 316 a layer was not
    grads = jax.grad(lambda p, x: jnp.sum(apply(p, x)[0] ** 2),
                     argnums=(0, 1))(params, jnp.asarray(x))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("values", [4, 8, 32])
def test_few_values_a_channel_stay_within_the_two_pass_bound(values):
    """Mean 1e4, spread 1, fresh state: the spread is under what ``m2``
    resolves. The two-pass form keeps |xhat| under sqrt(values); so does
    this one (it reads the variance too high there, never too low)."""
    shape = (values, 16)
    bn = _layer(shape[-1])
    for seed in range(4):
        x = _input(shape, np.float32, mean=1e4, spread=1.0, seed=seed)
        out, _ = bn.apply(bn.init_params(None), jnp.asarray(x),
                          state=bn.init_state(), train=True, rng=None)
        assert np.all(np.isfinite(np.asarray(out)))
        assert np.max(np.abs(np.asarray(out))) <= 1.2 * np.sqrt(values)


def test_squares_that_overflow_give_beta_as_the_two_pass_form_does():
    """Where ``chip_smoke.py --dry-cpu`` went to nan: a diverged net feeds
    the layer 4e23, the raw second moment is inf in float32 and
    ``inf - inf`` is nan. The two-pass form reads the variance inf there and
    emits ``beta``; so does this one (the variance is never under what
    ``m2`` resolves, and that is inf)."""
    x = np.asarray([[3.8e23, -1.0, 2.0], [-1.2e23, 1.0, 5.0]], np.float32)
    bn = _layer(3)
    params = {"gamma": jnp.asarray([284.0, 1.0, 1.0]),
              "beta": jnp.asarray([0.25, 0.0, 0.0])}
    state = bn.init_state()
    out, new_state = bn.apply(params, jnp.asarray(x), state=state, train=True,
                              rng=None)
    want, want_state = two_pass(bn, params, jnp.asarray(x), state)
    np.testing.assert_allclose(out, want, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(out)[:, 0], [0.25, 0.25])
    assert np.isposinf(np.asarray(new_state["var"])[0])
    assert np.isposinf(np.asarray(want_state["var"])[0])
    np.testing.assert_allclose(new_state["mean"], want_state["mean"],
                               rtol=1e-6)


def _loss_and_grads(apply, params, x, state, target):
    def loss(params, x):
        out, new_state = apply(params, x, state)
        return jnp.sum((out - target) ** 2), (out, new_state)
    (value, (out, new_state)), (gp, gx) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(params, x)
    return {"loss": value, "out": out, "mean": new_state["mean"],
            "var": new_state["var"], "dx": gx, "dgamma": gp["gamma"],
            "dbeta": gp["beta"]}


@pytest.mark.parametrize("shape", [CONV, DENSE], ids=["conv", "dense"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("float64", 1e-11)])
def test_output_state_and_gradients_agree_with_the_two_pass_form(dtype, tol,
                                                                 shape):
    with enable_x64(dtype == "float64"):
        n = shape[-1]
        bn = _layer(n)
        x = jnp.asarray(_input(shape, dtype, seed=3))
        target = jnp.asarray(_input(shape, dtype, mean=0.0, spread=1.0,
                                    seed=4))
        params = {"gamma": jnp.asarray(1 + 0.1 * np.arange(n), dtype),
                  "beta": jnp.asarray(0.05 * np.arange(n), dtype)}
        state = {"mean": jnp.asarray(0.3 + 0.01 * np.arange(n), dtype),
                 "var": jnp.ones(n, dtype)}
        got = _loss_and_grads(
            lambda p, x, s: bn.apply(p, x, state=s, train=True, rng=None),
            params, x, state, target)
        want = _loss_and_grads(lambda p, x, s: two_pass(bn, p, x, s),
                               params, x, state, target)
        for key in want:
            scale = float(jnp.max(jnp.abs(want[key])))
            gap = float(jnp.max(jnp.abs(got[key] - want[key])))
            assert got[key].dtype == want[key].dtype == dtype, key
            assert gap <= tol * scale, (key, gap, scale)


def test_forward_mode_agrees_with_the_two_pass_form():
    """The mean's derivative is routed through the shift: it has to be
    whole in forward mode as well (no custom rule stands in the way)."""
    with enable_x64(True):
        n = CONV[-1]
        bn = _layer(n)
        x = jnp.asarray(_input(CONV, "float64", seed=6))
        dx = jnp.asarray(_input(CONV, "float64", mean=0.0, seed=7))
        params = {"gamma": jnp.asarray(1 + 0.1 * np.arange(n)),
                  "beta": jnp.asarray(0.05 * np.arange(n))}
        dparams = {"gamma": jnp.ones(n), "beta": jnp.full(n, 0.5)}
        state = bn.init_state()
        got = jax.jvp(lambda p, x: bn.apply(
            p, x, state=state, train=True, rng=None), (params, x),
            (dparams, dx))
        want = jax.jvp(lambda p, x: two_pass(bn, p, x, state), (params, x),
                       (dparams, dx))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, w, rtol=1e-9, atol=1e-11)


def test_bf16_input_gives_float32_state_and_bf16_output():
    bn = _layer(CONV[-1])
    x = jnp.asarray(_input(CONV, np.float32)).astype(jnp.bfloat16)
    params, state = bn.init_params(None), bn.init_state()
    out, new_state = bn.apply(params, x, state=state, train=True, rng=None)
    assert out.dtype == jnp.bfloat16
    assert new_state["mean"].dtype == new_state["var"].dtype == jnp.float32
    want, want_state = two_pass(bn, params, x, state)
    for k in want_state:
        np.testing.assert_allclose(new_state[k], want_state[k], rtol=1e-5)
    # one bfloat16 rounding apart at most
    gap = np.abs(np.asarray(out, np.float32) - np.asarray(want, np.float32))
    assert np.max(gap) <= 2.0 ** -7 * np.max(np.abs(np.asarray(
        want, np.float32)))
    # the gradient through the layer keeps the input's dtype too
    dx = jax.grad(lambda x: jnp.sum(bn.apply(
        params, x, state=state, train=True, rng=None)[0].astype(
            jnp.float32) ** 2))(x)
    assert dx.dtype == jnp.bfloat16


@pytest.mark.parametrize("kw", [{"lock_gamma_beta": True},
                                {"is_minibatch": False}],
                         ids=["lock_gamma_beta", "not_minibatch"])
def test_the_other_modes_are_as_they_were(kw):
    bn = _layer(CONV[-1], **kw)
    x = jnp.asarray(_input(CONV, np.float32))
    params, state = bn.init_params(None), bn.init_state()
    out, new_state = bn.apply(params, x, state=state, train=True, rng=None)
    if kw.get("lock_gamma_beta"):
        assert params == {}
        want, want_state = two_pass(
            bn, {"gamma": bn.gamma, "beta": bn.beta}, x, state)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(new_state["var"], want_state["var"],
                                   rtol=1e-6)
    else:       # the running statistics normalise, and stay as they are
        assert new_state is state
        np.testing.assert_allclose(
            out, x * jax.lax.rsqrt(1.0 + bn.eps), rtol=1e-6)


# ---------------------------------------------------------------------------
# the mechanism: how many batch reductions wait for one another
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        inner = getattr(value, "jaxpr", value)      # ClosedJaxpr or Jaxpr
        if hasattr(inner, "eqns") and len(inner.invars) == len(eqn.invars):
            yield inner


def longest_reduction_chain(jaxpr, shape, depths=None, found=None):
    """Over ``jaxpr`` (calls such as ``pjit`` walked into), the longest
    chain of ``reduce_sum`` of a ``shape``-d operand that keep its channels
    (over the batch axes or, as the layer's moments go, over each example's
    own first) in which
    each reduction is an ancestor of the next, by output; ``found`` collects
    every such reduction."""
    depth = {}
    read = lambda v: 0 if isinstance(v, Literal) else depth.get(v, 0)
    for var, d in zip(jaxpr.invars, depths or [0] * len(jaxpr.invars)):
        depth[var] = d
    for eqn in jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        d = max(ins, default=0)
        inner = next(_sub_jaxprs(eqn), None)
        if inner is not None:
            outs = longest_reduction_chain(inner, shape, ins, found)
        else:
            if (eqn.primitive.name == "reduce_sum"
                    and eqn.invars[0].aval.shape == tuple(shape)
                    and len(shape) - 1 not in eqn.params["axes"]):
                d += 1
                if found is not None:
                    found.append(eqn)
            outs = [d] * len(eqn.outvars)
        for var, o in zip(eqn.outvars, outs):
            depth[var] = o
    return [read(v) for v in jaxpr.outvars]


def _conv_bn_chain(apply):
    n, shape = 6, (4, 8, 8, 6)
    bn = _layer(n)
    x = jnp.asarray(_input((4, 8, 8, 3), np.float32))
    w = jnp.asarray(np.random.default_rng(5).normal(size=(3, 3, 3, n)),
                    jnp.float32)
    params, state = bn.init_params(None), bn.init_state()

    def loss(w, params, x):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        out, new_state = apply(bn, params, y, state)
        return jnp.sum(jax.nn.relu(out) ** 2), new_state

    closed = jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(w, params, x)
    found = []
    chain = max(longest_reduction_chain(closed.jaxpr, shape, found=found))
    return chain, len(found)


def test_no_batch_reduction_waits_for_more_than_one_other():
    """The two-pass form chains four activation-sized reductions (mean,
    variance, ``sum(dy * xc)``, the mean's cotangent) among eight; the
    layer's has four in all and chains two: the moments side by side, then
    the gradient's two sums side by side. A later edit that brings a second
    pass back, or a third sum into the backward, shows here."""
    assert _conv_bn_chain(two_pass) == (4, 8)   # the walker sees the old form
    assert _conv_bn_chain(lambda bn, p, y, s: bn.apply(
        p, y, state=s, train=True, rng=None)) == (2, 4)
