"""The selective scan (``nn/layers/state_space.py``) against the token by
token recurrence of the benchmark's plain reference
(``benchmark/reference/phi4_flash.py``, which imports nothing of the
program): outputs and gradients, float32 and float64, at lengths on both
sides of a block and with decays from none to ``exp(-30)`` a token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4_flash as reference
from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.gradientcheck.check import enable_x64
from deeplearning4j_tpu.nn.layers import (
    GatedMemoryUnitLayer, SelectiveScanLayer)
from deeplearning4j_tpu.nn.layers.state_space import (
    selective_scan_chunked, selective_scan_recurrent)
from deeplearning4j_tpu.profiling import MetricsRegistry
from deeplearning4j_tpu.profiling.metrics import set_registry

D, N = 24, 4
STEPS, LANES = 4, 4             # a block of 16 tokens
BLOCK = STEPS * LANES
TOL = {"float32": 2e-5, "float64": 1e-12}


def scan_inputs(T, decay, dtype, seed=0, B=2):
    """``decay``: ``Delta A`` a token: ``"none"`` (steps of 1e-6 to 1e-3 at
    rates under 1), ``"steep"`` (near -30: steps of 1 to 2 at rates of 15
    to 30) or ``"mixed"`` (what a trained layer sees, and both ends)."""
    rng = np.random.default_rng(seed)
    lo, hi, rate = {"none": (1e-6, 1e-3, (1e-2, 1.0)),
                    "steep": (1.0, 2.0, (15.0, 30.0)),
                    "mixed": (1e-4, 2.0, (1e-2, 16.0))}[decay]
    cast = lambda a: jnp.asarray(a, dtype)
    return (cast(rng.normal(size=(B, T, D))),
            cast(np.exp(rng.uniform(np.log(lo), np.log(hi), (B, T, D)))),
            cast(-np.exp(rng.uniform(*np.log(rate), (N, D)))),
            cast(rng.normal(size=(B, T, N))), cast(rng.normal(size=(B, T, N))))


def with_gradients(fn, args, seed=1):
    """``fn(*args)`` and the gradient of all its inputs, in one program."""
    cot = jnp.asarray(np.random.default_rng(seed).normal(
        size=args[0].shape), args[1].dtype)
    loss = lambda *a: jnp.sum(fn(*a) * cot)
    grads = jax.grad(loss, argnums=tuple(range(len(args))))
    return jax.jit(lambda *a: (fn(*a), *grads(*a)))(*args)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(
        jnp.max(jnp.abs(b)), 1e-30))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("decay", ["none", "steep", "mixed"])
@pytest.mark.parametrize("T", [7, BLOCK, 100, 3 * BLOCK + 5])
def test_chunked_scan_is_the_token_by_token_recurrence(T, decay, dtype):
    """Output and the gradient of all five inputs. With no decay the state
    is a plain running sum; with a steep one ``exp(Delta A)`` underflows
    within a run of four tokens, where a form that divides by a running
    decay would overflow."""
    with enable_x64(dtype == "float64"):
        args = scan_inputs(T, decay, dtype)
        got = with_gradients(lambda *a: selective_scan_chunked(
            *a, steps=STEPS, lanes=LANES), args)
        ref = with_gradients(reference.selective_scan_recurrent, args)
        for what, a, b in zip("y dx ddelta da db dc".split(), got, ref):
            assert a.dtype == b.dtype == jnp.dtype(dtype)
            assert np.all(np.isfinite(np.asarray(a))), what
            assert rel(a, b) < TOL[dtype], (what, rel(a, b))


@pytest.mark.parametrize("T", [100, 300])
def test_the_default_block_and_the_programs_own_recurrence(T):
    """At the layer's own block of 256 tokens (one padded block, and two),
    with a bfloat16 ``x`` as under the policy; and the program's small
    token-by-token form, which ``chip_smoke.py`` compares against on the
    chip, says what the reference's says."""
    x, *rest = scan_inputs(T, "mixed", "float32", seed=2)
    args = (x.astype(jnp.bfloat16), *rest)
    got = with_gradients(selective_scan_chunked, args)
    own = with_gradients(selective_scan_recurrent, args)
    ref = with_gradients(reference.selective_scan_recurrent,
                         (args[0].astype(jnp.float32), *rest))
    for what, a, b, c in zip("y dx ddelta da db dc".split(), got, own, ref):
        tol = 1e-2 if what == "dx" else 2e-5    # dx comes back in bfloat16
        assert rel(a.astype(jnp.float32), c) < tol, (what, rel(a, c))
        assert rel(b.astype(jnp.float32), c) < tol, (what, rel(b, c))


def _layer(cls, in_types, **kw):
    layer = cls(weight_init="xavier", **kw)
    layer.set_n_in(in_types[0])
    if len(in_types) > 1:
        layer.set_side_inputs(in_types[1:])
    return layer


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("T", [7, 100])
def test_the_scan_layer_follows_the_reference_in_every_parameter(T, dtype):
    """``SelectiveScanLayer`` against ``reference.scan_output`` on the
    reference's own seeded weights: the output and the gradient of the
    input and of all eight parameters."""
    F = 16
    cfg = {"hidden_size": F, "num_attention_heads": 2,
           "num_key_value_heads": 2, "mamba_d_inner": D, "mamba_d_state": N,
           "mamba_dt_rank": 3, "mamba_d_conv": 4}
    with enable_x64(dtype == "float64"):
        layer = _layer(SelectiveScanLayer, [InputType.recurrent(F, T)],
                       n_inner=D, n_state=N, dt_rank=3)
        rng = np.random.default_rng(5)
        shapes = jax.eval_shape(layer.init_params, jax.random.PRNGKey(0))
        params = {k: jnp.asarray(0.3 * rng.normal(size=s.shape), dtype)
                  for k, s in shapes.items()}
        assert sorted(params) == sorted(layer.param_order())
        u = jnp.asarray(rng.normal(size=(2, T, F)), dtype)
        cot = jnp.asarray(rng.normal(size=(2, T, D)), dtype)
        same = lambda a: a

        def program(p, u):
            return layer.apply(p, u, state={}, train=True, rng=None)[0]

        def plain(p, u):
            w = {f"s/{k}": v for k, v in p.items()}
            return reference.scan_output(w, "s", u, cfg, same, same)

        for fn in (program, plain):
            out, grads = jax.jit(lambda p, u: (fn(p, u), jax.grad(
                lambda p, u: jnp.sum(fn(p, u) * cot), argnums=(0, 1))(
                    p, u)))(params, u)
            if fn is program:
                got = (out, grads)
        assert rel(got[0], out) < TOL[dtype]
        assert rel(got[1][1], grads[1]) < TOL[dtype]
        for k in params:
            assert rel(got[1][0][k], grads[0][k]) < 5 * TOL[dtype], k


@pytest.mark.parametrize("masked", [False, True])
def test_the_memory_unit_gates_another_nodes_output(masked):
    """A Mamba mixer's own gate and a memory unit of a layer above are this
    one class; a masked token's output is nought."""
    F, T = 16, 9
    layer = _layer(GatedMemoryUnitLayer, [InputType.recurrent(F, T),
                                          InputType.recurrent(D, T)])
    assert (layer.N_INPUTS, layer.n_memory) == (2, D)
    assert layer.infer_output_type(InputType.recurrent(F, T)) == \
        InputType.recurrent(F, T)
    params = layer.init_params(jax.random.PRNGKey(3))
    assert {k: v.shape for k, v in params.items()} == {
        "W_in": (F, D), "W_out": (D, F)}
    rng = np.random.default_rng(4)
    u, m = (jnp.asarray(rng.normal(size=(2, T, n)), jnp.float32)
            for n in (F, D))
    mask = jnp.asarray(rng.integers(0, 2, (2, T)), jnp.float32) \
        if masked else None
    out, _ = layer.apply(params, (u, m), state={}, train=True, rng=None,
                         mask=mask)
    same = lambda a: a
    want = reference.gated_memory(
        {f"g/{k}": v for k, v in params.items()}, "g", u, m, same, same)
    if masked:
        assert 0 < mask.sum() < mask.size
        want = want * mask[..., None]
    assert rel(out, want) < 1e-6
    text = jax.jit(lambda p, u, m: layer.apply(
        p, (u, m), state={}, train=True, rng=None)[0]).lower(
            params, u, m).as_text(debug_info=True)
    assert "gmu:gate" in text
    with pytest.raises(ValueError, match="sequence"):
        layer.set_side_inputs([InputType.feed_forward(D)])


def test_scan_traces_are_counted_by_path():
    """``ssm_scan_traces_total{path="xla"}``: once a trace, not a call."""
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        fn = jax.jit(selective_scan_chunked)
        args = scan_inputs(20, "mixed", "float32")
        fn(*args)
        fn(*args)
        counted = registry.labeled_counter("ssm_scan_traces_total")
        assert counted.labels(path="xla").value == 1
        assert counted.labels(path="kernel").value == 0
    finally:
        set_registry(previous)


def test_the_scan_layer_takes_sequences_and_sizes_itself():
    layer = SelectiveScanLayer()
    with pytest.raises(ValueError, match="RNN input"):
        layer.set_n_in(InputType.feed_forward(8))
    layer.set_n_in(InputType.recurrent(40, 12))
    assert (layer.n_inner, layer.dt_rank, layer.n_state) == (80, 3, 16)
    assert layer.infer_output_type(InputType.recurrent(40, 12)) == \
        InputType.recurrent(80, 12)
    p = layer.init_params(jax.random.PRNGKey(0))
    assert p["A_log"].shape == (16, 80) and p["W_x"].shape == (80, 35)
    # state n decays at rate n + 1; steps within [DT_MIN, DT_MAX]
    assert np.allclose(np.exp(np.asarray(p["A_log"]))[:, 7], np.arange(1, 17))
    steps = np.asarray(jax.nn.softplus(p["b_dt"]))
    assert steps.min() >= 1e-3 * 0.999 and steps.max() <= 1e-1 * 1.001
