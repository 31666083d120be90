"""The Pallas kernels for the selective scan
(``ops/pallas_selective_scan.py``), interpreted on the CPU, against the
recurrence written token by token (``selective_scan_recurrent``) and, beside
it, the chunked XLA path they stand in for (``selective_scan_chunked``):
the output and the gradients of all five inputs by the element, at 128
and 384 channels of 16 states (one tile of channels and three: the
kernel's rule walks down from ``BLOCK_D`` to tiles of 128) and at 1,024
(the production tile, ``BLOCK_D`` whole), a batch of two, at lengths of one
short block (70 tokens as 80) and of three (300 as 384), with a stretch of
masked tokens in the middle, with ``x`` in bfloat16, and with decays that
underflow to zero; then the seam: what the gate refuses takes the XLA path
and counts, and a trace counts one path. A tile of 128 channels is one
lane-width of the kernels' loops, so the cases of a kind other than the
tile's own run at it and cost an eighth of the interpreted program.

Tolerances. Both sides are float32 and take the same steps in the same
order, so they part by the order of the sums over ``N`` and over channels
alone: 3e-7 of the largest entry is read, 2e-5 is held (a missing term or a
wrong token reads 1e-2 to 1). A bfloat16 ``x`` is widened by both; its
cotangent comes back in bfloat16 from the kernel and in float32 from the
recurrence, one rounding of 2^-8 apart."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.state_space import (
    SelectiveScanLayer, selective_scan, selective_scan_chunked,
    selective_scan_recurrent)
from deeplearning4j_tpu.ops import pallas_selective_scan as pss
from deeplearning4j_tpu.profiling.metrics import MetricsRegistry, set_registry

B, N = 2, 16
NAMES = "y dx ddelta da db dc".split()

DECAYS = {"mixed": (1e-4, 2.0, (1e-2, 16.0)),      # a trained layer's
          "steep": (1.0, 2.0, (60.0, 120.0))}      # Delta A of -60 to -240


def inputs(T, D, decay="mixed", x_dtype="float32", seed=0):
    rng = np.random.default_rng(seed)
    lo, hi, rate = DECAYS[decay]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (jnp.asarray(rng.normal(size=(B, T, D)), x_dtype),
            f32(np.exp(rng.uniform(np.log(lo), np.log(hi), (B, T, D)))),
            f32(-np.exp(rng.uniform(*np.log(rate), (N, D)))),
            f32(rng.normal(size=(B, T, N))), f32(rng.normal(size=(B, T, N))))


def with_gradients(fn, args, seed=1):
    cot = jnp.asarray(np.random.default_rng(seed).normal(
        size=args[0].shape), jnp.float32)
    loss = lambda *a: jnp.sum(fn(*a) * cot)
    return (jax.jit(fn)(*args),
            *jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))


def gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


kernels = functools.partial(pss.selective_scan, interpret=True)


def assert_same(got, want, tol=2e-5, tol_dx=None):
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape, name
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        assert gap(a, b) <= (tol_dx if tol_dx and name == "dx" else tol), \
            (name, gap(a, b))


@pytest.mark.parametrize("T,D,tiles", [(70, 128, 1), (70, 384, 3),
                                        (300, 384, 3), (300, 1024, 1)],
                         ids=["70-128", "70-384", "300-384", "300-1024"])
def test_kernels_are_the_recurrence_and_the_chunked_path(T, D, tiles):
    """One short block and three, one tile of channels and three, a batch
    of two, and the production tile of ``BLOCK_D`` channels over three
    blocks of ``BLOCK_T``: by the element against token by token, and the
    XLA path beside it."""
    args = inputs(T, D)
    assert pss.selective_scan_ok(T, D, N, jnp.float32, jnp.float32)
    assert D // pss.channel_block(D) == tiles
    got = with_gradients(kernels, args)
    assert got[0].dtype == jnp.float32 and got[1].dtype == jnp.float32
    assert_same(got, with_gradients(selective_scan_recurrent, args))
    assert_same(got, with_gradients(selective_scan_chunked, args))


def test_a_masked_stretch_passes_the_state_unchanged():
    """``Delta = 0`` over 90 tokens across a block's edge: the tokens after
    the stretch read what they would read with the stretch cut out, and the
    masked tokens' ``x``, ``B`` get no gradient."""
    T, D, lo, hi = 300, 128, 100, 190
    x, delta, a, b, c = inputs(T, D)
    delta = delta.at[:, lo:hi].set(0.0)
    args = (x, delta, a, b, c)
    got = with_gradients(kernels, args)
    assert_same(got, with_gradients(selective_scan_recurrent, args))
    cut = lambda z: jnp.concatenate([z[:, :lo], z[:, hi:]], axis=1)
    short = kernels(cut(x), cut(delta), a, cut(b), cut(c))
    assert gap(cut(got[0]), short) <= 2e-6
    assert float(jnp.max(jnp.abs(got[1][:, lo:hi]))) == 0.0      # dx
    assert float(jnp.max(jnp.abs(got[4][:, lo:hi]))) == 0.0      # dB


def test_x_in_bfloat16_is_widened_and_its_cotangent_narrowed():
    args = inputs(300, 128, x_dtype="bfloat16")
    got = with_gradients(kernels, args)
    assert got[0].dtype == jnp.float32 and got[1].dtype == jnp.bfloat16
    assert_same(got, with_gradients(selective_scan_recurrent, args),
                tol_dx=2 ** -7)


def test_decays_that_underflow_harm_nothing():
    """``Delta A`` of -60 to -240 a token: decays underflow to zero (and
    their inverses would overflow), and every number stays finite and the
    recurrence's."""
    args = inputs(300, 128, decay="steep")
    assert float(jnp.min(jnp.exp(args[1][..., None, :] * args[2]))) == 0.0
    assert_same(with_gradients(kernels, args),
                with_gradients(selective_scan_recurrent, args))


def _counters(monkeypatch, mode, fn, *args):
    monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        out = [fn(*args), fn(*args)][0]     # one trace, two calls
    finally:
        set_registry(previous)
    return (out, registry.labeled_counter("ssm_scan_traces_total"),
            registry.labeled_counter("pallas_gate_fallbacks_total"))


def test_a_trace_counts_one_path(monkeypatch):
    args = inputs(70, 128)
    for mode, path, other in (("interpret", "kernel", "xla"),
                              ("off", "xla", "kernel")):
        fn = jax.jit(functools.partial(selective_scan))     # a trace a mode
        _, traces, fallbacks = _counters(monkeypatch, mode, fn, *args)
        assert traces.labels(path=path).value == 1
        assert traces.labels(path=other).value == 0
        assert fallbacks.value == 0


@pytest.mark.parametrize("refused", ["float64", "d_in_96", "states_4"])
def test_what_the_gate_refuses_takes_the_xla_path_and_counts(monkeypatch,
                                                             refused):
    D = 96 if refused == "d_in_96" else 128
    args = inputs(70, D)
    if refused == "states_4":
        args = (args[0], args[1], args[2][:4], args[3][..., :4],
                args[4][..., :4])
    with jax.enable_x64(refused == "float64"):
        if refused == "float64":
            args = tuple(z.astype(jnp.float64) for z in args)
        assert not pss.selective_scan_ok(70, D, args[2].shape[0],
                                         args[1].dtype, args[0].dtype)
        fn = lambda: jax.jit(functools.partial(
            selective_scan, layer=SelectiveScanLayer(name="caller")))
        got, traces, fallbacks = _counters(monkeypatch, "interpret", fn(),
                                           *args)
        want, _, none = _counters(monkeypatch, "off", fn(), *args)
    assert traces.labels(path="xla").value == 1 and traces.value == 1
    assert fallbacks.labels(layer="caller",
                            kernel="selective_scan").value == 1
    assert fallbacks.value == 1 and none.value == 0
    assert got.dtype == args[1].dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_blocks_and_the_gate():
    assert pss.time_block(8192) == 128 and pss.time_block(300) == 128
    assert pss.time_block(70) == 80 and pss.time_block(128) == 128
    assert [pss.channel_block(d) for d in (5120, 2048, 1536, 1152, 128)] \
        == [1024, 1024, 512, 128, 128]
    ok = lambda **kw: pss.selective_scan_ok(**{
        "T": 8192, "D": 5120, "N": 16, "acc_dtype": jnp.float32,
        "x_dtype": jnp.bfloat16, **kw})
    assert ok() and ok(x_dtype=jnp.float32) and ok(D=128, N=8)
    assert not ok(D=5120 + 64) and not ok(N=12)
    assert not ok(acc_dtype=jnp.bfloat16)
    # the two carries hold every channel: past some width they fill VMEM
    assert pss.selective_scan_vmem_bytes(8192, 5120, 16) < 32 * 2 ** 20
    assert not ok(D=1024 * 1024)


def test_layer_runs_the_kernels_and_names_its_fallback(monkeypatch):
    """``SelectiveScanLayer.apply`` through the kernels (interpreted) is
    the layer on the XLA path, forward and backward; with channels that
    fill no lane the refusal is counted under the layer's name."""
    layer = SelectiveScanLayer(weight_init="xavier", name="ssm")
    layer.set_n_in(InputType.recurrent(64, 70))        # d_in 128
    params = layer.init_params(jax.random.PRNGKey(1))
    u = jnp.asarray(np.random.default_rng(2).standard_normal((B, 70, 64)),
                    jnp.float32)
    mask = jnp.ones((B, 70)).at[:, 30:45].set(0.0)

    def loss(params, u):
        return jnp.sum(layer.apply(params, u, state={}, train=True,
                                   rng=None, mask=mask)[0] ** 2)

    grads = lambda: jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    got, traces, fallbacks = _counters(monkeypatch, "interpret", grads(),
                                       params, u)
    want, xla, _ = _counters(monkeypatch, "off", grads(), params, u)
    assert traces.labels(path="kernel").value == 1 and traces.value == 1
    assert xla.labels(path="xla").value == 1 and xla.value == 1
    assert fallbacks.value == 0
    flat = lambda t: jax.tree.leaves(t)
    for a, b in zip(flat(got), flat(want)):
        assert gap(a, b) <= 2e-5

    narrow = SelectiveScanLayer(weight_init="xavier", name="narrow")
    narrow.set_n_in(InputType.recurrent(48, 70))       # d_in 96
    p = narrow.init_params(jax.random.PRNGKey(3))
    _, traces, fallbacks = _counters(
        monkeypatch, "interpret", jax.jit(lambda u: narrow.apply(
            p, u, state={}, train=True, rng=None)[0]), u[..., :48])
    assert traces.labels(path="xla").value == 1 and traces.value == 1
    assert fallbacks.labels(layer="narrow",
                            kernel="selective_scan").value == 1


def test_the_kernels_rule_waits_for_the_cotangent(monkeypatch):
    """At the seam the kernels' own rule stands between the two barriers
    of ``nn/remat`` (``ops/`` holds none of its own)."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    args = inputs(70, 128)
    loss = lambda *a: jnp.sum(selective_scan(*a))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).as_text()
    assert text.count("optimization_barrier") == 2
    direct = jax.jit(jax.grad(lambda *a: jnp.sum(kernels(*a)),
                              argnums=(0, 1, 2, 3, 4))).lower(*args).as_text()
    assert "optimization_barrier" not in direct
