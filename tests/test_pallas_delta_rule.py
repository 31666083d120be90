"""The Pallas kernels for the gated delta rule's chunk-local work
(``ops/pallas_delta_rule.py``), interpreted on the CPU, against the XLA path
they replace (``DL4J_TPU_PALLAS=off``) and against the recurrence written
token by token (``benchmark/reference/olmo_hybrid.delta_rule_recurrent``):
outputs and the gradients of all five inputs, with float32 and with
bfloat16 operands, in the regimes of ``tests/test_gated_delta_rule.py`` and
with keys that repeat inside a chunk, at one chunk (padded to the pair the
kernel works on), at lengths that are no multiple of the chunk, at
eighteen chunks, which run as five blocks of four, and at 33 and 66, which
run as 36 in blocks of four and as 72 in blocks of eight (lengths at which
the padding and the block once disagreed, and the kernels raised).

Tolerances. float32 operands: 2e-4 of the largest entry, against the XLA
path as against the recurrence, with the floors that file gives the
gradients: the kernel solves the same 64 x 64 system by the same doubling
of blocks in another order of float32 sums, and where beta is near 2 and
the keys repeat both stand 1e-5 to 1e-4 from the recurrence. bfloat16
operands, against the XLA path with bfloat16 operands: 4e-2 of the largest
entry. Both make the same four products in bfloat16; they part where each
rounds (the XLA path's cotangents meet their bfloat16 operands in float32 on
the CPU), a few of bfloat16's 2^-8 that eighteen chunks of a state that
never decays carry along (read: 2.1e-2 for the gradient of log alpha with
alpha near 1 at 1,152 tokens, 1.4e-2 and less elsewhere), where a wrong mask
or a missing term reads 0.1 to 1. And the kernel path stands no farther
from the float32 recurrence than the XLA path with bfloat16 operands does
(read: 0.8 to 1.3 times as far, held to 1.5 times and 1e-3)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.olmo_hybrid import delta_rule_recurrent
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.linear_attention import (
    GatedDeltaNetLayer, chunk_local_xla, gated_delta_rule_chunked)
from deeplearning4j_tpu.ops import pallas_delta_rule as pdr
from deeplearning4j_tpu.profiling.metrics import MetricsRegistry, set_registry

B, H, DK, DV = 2, 2, 8, 16

# (beta, alpha) corners, and whether a chunk's keys are eight, repeated
REGIMES = {
    "plain": ((0.2, 1.8), (0.5, 0.99), False),
    "beta_near_2": ((1.9, 2.0), (0.9, 0.999), False),
    "alpha_near_0": ((0.2, 1.8), (1e-6, 1e-3), False),
    "alpha_near_1": ((1.0, 2.0), (0.9999, 1.0), False),
    "keys_repeat": ((1.5, 2.0), (0.9, 0.999), True),
}


def inputs(T, regime, seed=0):
    rng = np.random.default_rng(seed)
    (b_lo, b_hi), (a_lo, a_hi), repeat = REGIMES[regime]
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((B, T, H, DK))) / np.sqrt(DK)
    k = unit(rng.standard_normal((B, T, H, DK)))
    if repeat:
        k = k[:, rng.integers(0, 8, T)]
    v = rng.standard_normal((B, T, H, DV))
    beta = rng.uniform(b_lo, b_hi, (B, T, H))
    log_alpha = np.log(rng.uniform(a_lo, a_hi, (B, T, H)))
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (q, k, v, log_alpha, beta))


def gap(got, want, floor=1e-30):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    scale = max(float(jnp.max(jnp.abs(want))), floor)
    return float(jnp.max(jnp.abs(got - want))) / scale


def close(got, want, tol, floor=1e-30):
    return gap(got, want, floor) <= tol


def out_and_grads(fn):
    def run(*args):
        weight = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)
                         ).reshape(args[2].shape)
        loss = lambda *a: jnp.sum(fn(*a) * weight)
        return fn(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def compiled(path, operands):
    """One jitted (output, gradients) a path and an operand dtype; which
    path a trace takes is read from the environment when it is made."""
    if path == "recurrent":
        return out_and_grads(delta_rule_recurrent)
    return out_and_grads(functools.partial(
        gated_delta_rule_chunked, compute_dtype=operands))


def run(monkeypatch, path, operands, args):
    monkeypatch.setenv("DL4J_TPU_PALLAS",
                       "interpret" if path == "kernel" else "off")
    return compiled(path, operands)(*args)


FLOORS = {"q": 1e-3, "k": 1e-3, "v": 1e-3, "log_alpha": 1e-2, "beta": 1e-3}


# every regime at one chunk, at lengths that are no multiple of the chunk
# and at eighteen chunks; and lengths whose padded count of chunks (36, 72)
# would take a larger block than the count it was padded from (33, 66)
LENGTHS = [(T, regime) for T in (64, 100, 192, 1152)
           for regime in sorted(REGIMES)] \
    + [(2100, "plain"), (2100, "keys_repeat"), (4200, "beta_near_2")]


@pytest.mark.parametrize("T,regime", LENGTHS)
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_kernel_path_is_the_xla_path_and_the_recurrence(monkeypatch, operands,
                                                        T, regime):
    args = inputs(T, regime)
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        got, g_got = run(monkeypatch, "kernel", operands, args)
        want, g_want = run(monkeypatch, "xla", operands, args)
    finally:
        set_registry(previous)
    assert registry.labeled_counter("pallas_gate_fallbacks_total").value == 0
    assert got.dtype == jnp.float32 and got.shape == (B, T, H, DV)
    tol = 2e-4 if operands == "float32" else 4e-2
    assert close(got, want, tol)
    for name, a, b in zip(FLOORS, g_got, g_want):
        assert close(a, b, tol, floor=FLOORS[name]), name
    exact, g_exact = run(monkeypatch, "recurrent", "float32", args)
    if operands == "float32":
        assert close(got, exact, 2e-4)
        for name, a, b in zip(FLOORS, g_got, g_exact):
            assert close(a, b, 2e-4, floor=FLOORS[name]), name
    else:       # as far from the recurrence as the XLA path with bfloat16
        assert close(got, exact, 5e-2) and not close(got, exact, 1e-6)
        for name, a, b, c in zip(FLOORS, g_got, g_want, g_exact):
            assert gap(a, c, FLOORS[name]) <= 1.5 * gap(
                b, c, FLOORS[name]) + 1e-3, name


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("N", [2, 4, 6])
def test_chunk_local_kernels_against_the_xla_function(operands, N):
    """The two kernels alone on chunked inputs: the five outputs in the
    order the scan reads them, and the cotangents of ``q, k, v, g, beta``
    from random cotangents of all five."""
    rng = np.random.default_rng(N)
    (q, k, v, log_alpha, beta) = inputs(N * 64, "beta_near_2", seed=N)
    chunks = lambda x: jnp.moveaxis(
        x.reshape((B, N, 64) + x.shape[2:]), 3, 1)
    q, k, v, log_alpha, beta = map(chunks, (q, k, v, log_alpha, beta))
    g = jnp.cumsum(log_alpha, axis=-1)
    v = v.astype(operands)
    xla = functools.partial(chunk_local_xla, compute_dtype=operands)
    kernel = functools.partial(pdr.gdn_chunk_local, compute_dtype=operands,
                               interpret=True)
    want = jax.jit(xla)(q, k, v, g, beta)
    got = jax.jit(kernel)(q, k, v, g, beta)
    tol = 2e-5 if operands == "float32" else 2e-2
    for name, a, b in zip("w u0 attn q_in k_out".split(), got, want):
        assert a.shape == b.shape == (N, B, H, 64, b.shape[-1]), name
        assert a.dtype == b.dtype, name
        assert close(a, b, tol), name
    weights = [jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
               for x in want]
    loss = lambda fn: lambda *a: sum(
        jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(fn(*a), weights))
    argnums = (0, 1, 2, 3, 4)
    g_want = jax.jit(jax.grad(loss(xla), argnums))(q, k, v, g, beta)
    g_got = jax.jit(jax.grad(loss(kernel), argnums))(q, k, v, g, beta)
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert close(a, b, tol), name


def _counters(monkeypatch, mode, fn, *args):
    monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        out = [fn(*args), fn(*args)][0]     # one trace, two calls
    finally:
        set_registry(previous)
    return (out, registry.labeled_counter("pallas_gdn_chunk_traces_total"),
            registry.labeled_counter("pallas_gate_fallbacks_total"))


def test_traces_are_counted_once_by_path(monkeypatch):
    args = inputs(100, "plain")
    for mode, path in (("interpret", "kernel"), ("off", "xla")):
        fn = jax.jit(functools.partial(gated_delta_rule_chunked))
        _, traces, fallbacks = _counters(monkeypatch, mode, fn, *args)
        assert traces.labels(path=path).value == 1
        assert traces.value == 1 and fallbacks.value == 0


@pytest.mark.parametrize("refused", ["float64", "chunk_of_32"])
def test_what_the_gate_refuses_takes_the_xla_path_and_counts(monkeypatch,
                                                             refused):
    args = inputs(100, "plain")
    kwargs = {"chunk_size": 32} if refused == "chunk_of_32" else {}
    with jax.enable_x64(refused == "float64"):
        if refused == "float64":
            args = tuple(a.astype(jnp.float64) for a in args)
        fn = lambda: jax.jit(functools.partial(
            gated_delta_rule_chunked, layer=GatedDeltaNetLayer(
                n_heads=H, key_dim=DK, value_dim=DV, name="caller"),
            **kwargs))
        got, traces, fallbacks = _counters(monkeypatch, "interpret", fn(),
                                           *args)
        want, _, none = _counters(monkeypatch, "off", fn(), *args)
    assert traces.labels(path="xla").value == 1
    assert traces.value == 1
    assert fallbacks.labels(layer="caller",
                            kernel="gdn_chunk_local").value == 1
    assert fallbacks.value == 1 and none.value == 0
    assert got.dtype == args[0].dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_gate_counts_bytes():
    """The cell's shape asks for a quarter of what the gate allows; a head
    wide enough to pass it is refused, whatever the length."""
    cell = pdr.gdn_vmem_bytes(pdr.block_chunks(128), 96, 192, 2)
    assert pdr.block_chunks(128) == 16 and cell < pdr.VMEM_GATE_BYTES // 3
    assert pdr.gdn_chunk_ok(128, 96, 192, 64, jnp.float32, jnp.bfloat16)
    assert pdr.gdn_chunk_ok(130, 96, 192, 64, jnp.float32, jnp.float32)
    assert not pdr.gdn_chunk_ok(128, 2048, 4096, 64, jnp.float32,
                                jnp.bfloat16)
    assert not pdr.gdn_chunk_ok(128, 96, 192, 64, jnp.float64, jnp.float64)
    assert not pdr.gdn_chunk_ok(128, 96, 192, 32, jnp.float32, jnp.bfloat16)
    assert [pdr.padded_chunks(n) for n in (1, 2, 3, 16, 18, 128, 130)] == [
        2, 2, 4, 16, 20, 128, 144]


def test_blocks_divide_the_padded_count():
    """The padding and the block are read from one rule, for every count
    of chunks up to 300: whole blocks, an even count, an eighth of padding
    at most past the pair, and the block is read from the padded count
    alone (33 chunks run as 36 in blocks of 4, though a sequence of 36
    would run as 40 in blocks of 8)."""
    for N in range(1, 301):
        padded = pdr.padded_chunks(N)
        nb = pdr.block_chunks(padded)
        even = N + N % 2
        assert padded >= N and padded % nb == 0 and nb % 2 == 0, N
        assert 8 * padded <= 9 * even, N
        if even <= pdr.BLOCK_CHUNKS:
            assert nb == padded == even, N
        else:       # no smaller than the block that chose the padding
            assert nb in (16, 8, 4, 2), N
            assert all(-(-N // b) * b != padded for b in (16, 8, 4, 2)
                       if b > nb), N
    assert [pdr.block_chunks(pdr.padded_chunks(n))
            for n in (33, 41, 66, 130)] == [4, 4, 8, 16]
    with pytest.raises(ValueError):
        pdr.block_chunks(37)


def test_layer_names_its_fallback_and_runs_the_kernel(monkeypatch):
    """``GatedDeltaNetLayer.apply`` through the kernels (interpreted) is
    the layer on the XLA path, and a refusal is counted under its name."""
    layer = GatedDeltaNetLayer(n_heads=2, key_dim=8, value_dim=16,
                               weight_init="xavier", name="mix")
    layer.set_n_in(InputType.recurrent(32, 100))
    params = layer.init_params(jax.random.PRNGKey(1))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((B, 100, 32)),
                    jnp.float32)
    apply = lambda x: layer.apply(params, x, state={}, train=True,
                                  rng=None)[0]
    got, traces, fallbacks = _counters(monkeypatch, "interpret", apply, x)
    want, _, _ = _counters(monkeypatch, "off", apply, x)
    assert traces.labels(path="kernel").value == 2
    assert fallbacks.value == 0 and close(got, want, 2e-5)
    with jax.enable_x64(True):
        wide = jax.tree.map(lambda a: a.astype(jnp.float64), params)
        layer.apply(wide, x.astype(jnp.float64), state={}, train=True,
                    rng=None)
        _, traces, fallbacks = _counters(
            monkeypatch, "interpret", lambda x: layer.apply(
                wide, x, state={}, train=True, rng=None)[0],
            x.astype(jnp.float64))
    assert fallbacks.labels(layer="mix", kernel="gdn_chunk_local").value == 2
    assert traces.labels(path="xla").value == 2


def test_backward_rule_waits_for_the_cotangent_on_both_paths(monkeypatch):
    """The kernels' rule stands between the two barriers of ``nn/remat``
    as the XLA path's checkpoint does, and ``ops/`` holds none of its own;
    ``backward_after_cotangent`` runs the function's own rule (here one
    that no derivative of the forward would give) and keeps what it
    keeps."""
    from deeplearning4j_tpu.nn.remat import backward_after_cotangent

    args = inputs(100, "plain")
    loss = lambda *a: jnp.sum(gated_delta_rule_chunked(*a))
    for mode in ("interpret", "off"):
        monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).as_text()
        assert text.count("optimization_barrier") == 2, mode

    @jax.custom_vjp
    def doubled(x, y):
        return 2.0 * x + y

    doubled.defvjp(lambda x, y: (2.0 * x + y, (y,)),
                   lambda kept, ct: (3.0 * ct, kept[0] * ct))
    x, y = jnp.arange(3.0), jnp.arange(3.0) + 1.0
    held = backward_after_cotangent(doubled)
    assert np.array_equal(held(x, y), doubled(x, y))
    for fn in (doubled, held):
        dx, dy = jax.grad(lambda x, y: jnp.sum(fn(x, y)), (0, 1))(x, y)
        assert np.array_equal(dx, 3.0 * jnp.ones(3)) and np.array_equal(dy, y)
    lowered = jax.jit(jax.grad(lambda x, y: jnp.sum(held(x, y)))).lower(x, y)
    assert lowered.as_text().count("optimization_barrier") == 2
