"""The flash kernels with a window and a value wider than its key
(interpret mode), and the differential attention layer that calls
them so, against masks and softmaxes written out: ``attention_reference``
for the kernels, the benchmark's plain reference (which imports nothing of
the program) for the layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4_flash as reference
from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.nn.layers import (
    DifferentialAttentionLayer, KeyValueProjectionLayer)
from deeplearning4j_tpu.nn.layers.attention import attention_reference
from deeplearning4j_tpu.ops.pallas_attention import (
    _first_key_block, _last_query_block, flash_attention)
from deeplearning4j_tpu.profiling import MetricsRegistry
from deeplearning4j_tpu.profiling.metrics import set_registry

D, DV = 16, 32


def rel(a, b):
    """Largest error over the largest entry, or over 1 (a window of one
    token has a map of one score: its dq and dk are nought, and what the
    kernel gives there is ``dO v - rowsum(dO o)``, rounding of values of
    order ten)."""
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(
        jnp.max(jnp.abs(b)), 1.0))


def written_out(q, k, v, window):
    """Two inequalities on the positions, nothing by blocks; the scale is
    that of the key's width, whatever the value's."""
    T = q.shape[2]
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (s <= t) & (t - s < window)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhke->bhqe", maps, v)


@pytest.mark.parametrize("T", [100, 300])
@pytest.mark.parametrize("window", [1, 24, "T-1", "T+5"])
def test_windowed_flash_is_the_written_out_mask(T, window):
    """Forward, dq, dk and dv with 4 key/value heads repeated to 8 query
    heads (the gradient of a repeated head is the sum over its readers) and
    a value twice as wide as its key. 300 tokens run as three blocks of
    128, so a window of 24 leaves whole blocks out of all three kernels'
    loops; 100 tokens are one padded block."""
    window = {"T-1": T - 1, "T+5": T + 5}.get(window, window)
    rng = np.random.default_rng(window + T)
    draw = lambda h, d: jnp.asarray(rng.normal(size=(2, h, T, d)), jnp.float32)
    q, k, v, cot = draw(8, D), draw(4, D), draw(4, DV), draw(8, DV)
    heads = lambda a: jnp.repeat(a, 2, axis=1)

    def run(attend):
        fn = lambda q, k, v: attend(q, heads(k), heads(v))
        loss = lambda *a: jnp.sum(fn(*a) * cot)
        return jax.jit(lambda *a: (fn(*a), *jax.grad(
            loss, argnums=(0, 1, 2))(*a)))(q, k, v)

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=True))
    want = run(lambda q, k, v: written_out(q, k, v, window))
    for what, a, b in zip("o dq dk dv".split(), got, want):
        assert a.shape == b.shape and rel(a, b) < 2e-5, (what, rel(a, b))
    # and the layers' own XLA path knows the same window
    plain = attention_reference(q, heads(k), heads(v), causal=True,
                                window=window)
    assert rel(plain, want[0]) < 2e-5


def test_the_loops_bounds_hold_every_block_the_window_reaches():
    """By brute force over positions: a block left out holds no pair that
    the two inequalities allow, and the first and last kept do."""
    for B, window, n_blocks in ((128, 24, 3), (128, 128, 5), (128, 129, 5),
                                (512, 512, 16), (512, 700, 16), (128, 1, 4)):
        T = B * n_blocks
        t, s = np.arange(T)[:, None], np.arange(T)[None, :]
        seen = (s <= t) & (t - s < window)
        blocks = seen.reshape(n_blocks, B, n_blocks, B).any(axis=(1, 3))
        for i in range(n_blocks):
            lo = int(_first_key_block(i, B, window))
            assert lo == np.flatnonzero(blocks[i])[0], (B, window, i)
            hi = int(_last_query_block(i, B, window, n_blocks))
            assert hi == np.flatnonzero(blocks[:, i])[-1] + 1, (B, window, i)


def test_flash_traces_are_counted_by_window():
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        q = jnp.ones((1, 2, 16, 8))
        for window in (None, 4):
            flash_attention(q, q, q, causal=True, window=window,
                            interpret=True)
        counted = registry.labeled_counter("pallas_flash_traces_total")
        assert counted.labels(
            operands="float32", window="none", select="none").value == 1
        assert counted.labels(
            operands="float32", window="4", select="none").value == 1
    finally:
        set_registry(previous)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=4, interpret=True)


F, H, G, HD = 64, 8, 4, 8       # 4 query pairs read 2 key/value pairs
CFG = {"hidden_size": F, "num_attention_heads": H, "num_key_value_heads": G,
       "mamba_d_inner": 0, "mamba_d_state": 0, "mamba_dt_rank": 0,
       "layer_norm_eps": 1e-5, "sliding_window": 10}


@pytest.mark.parametrize("mode", ["interpret", "off"])
@pytest.mark.parametrize("window", [None, 10])
def test_differential_attention_is_the_references_two_dense_softmaxes(
        window, mode, monkeypatch):
    """The layer over ``(u, kv)`` against ``reference
    .differential_attention`` on the same weights, through the flash
    kernels (interpret) and through the XLA path: the output and the
    gradient of both inputs and of all nine parameters; the keys and
    values come from ``KeyValueProjectionLayer`` as they do in the model."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
    T, depth = 40, 17
    kv_layer = KeyValueProjectionLayer(n_kv_heads=G, head_dim=HD,
                                       weight_init="xavier")
    kv_layer.set_n_in(InputType.recurrent(F, T))
    layer = DifferentialAttentionLayer(
        n_heads=H, n_kv_heads=G, head_dim=HD, window=window, depth=depth,
        weight_init="xavier")
    layer.set_n_in(InputType.recurrent(F, T))
    layer.set_side_inputs([kv_layer.infer_output_type(
        InputType.recurrent(F, T))])
    assert abs(layer.lambda_init - (0.8 - 0.6 * np.exp(-0.3 * 17))) < 1e-12
    rng = np.random.default_rng(7)
    shapes = jax.eval_shape(layer.init_params, jax.random.PRNGKey(0))
    assert sorted(shapes) == sorted(layer.param_order())
    params = {k: jnp.asarray(0.3 * rng.normal(size=s.shape), jnp.float32)
              for k, s in shapes.items()}
    kv_params = kv_layer.init_params(jax.random.PRNGKey(1))
    kv_params["b"] = jnp.asarray(0.1 * rng.normal(size=(2 * G * HD,)),
                                 jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, T, F)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(2, T, F)), jnp.float32)
    same = lambda a: a

    def program(p, kp, u):
        kv, _ = kv_layer.apply(kp, u, state={}, train=True, rng=None)
        return layer.apply(p, (u, kv), state={}, train=True, rng=None)[0]

    def plain(p, kp, u):
        w = {f"a/{k}": v for k, v in p.items()}
        w.update({f"kv/{k}": v for k, v in kp.items()})
        kv = reference.keys_values(w, "kv", u, same, same)
        return reference.differential_attention(
            w, "a", u, kv, CFG, depth, window, same, same)

    results = []
    for fn in (program, plain):
        results.append(jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2))(*a)))(
                params, kv_params, u))
    (out, grads), (want, want_grads) = results
    assert rel(out, want) < 2e-5
    flat = lambda g: {**{f"a/{k}": v for k, v in g[0].items()},
                      **{f"kv/{k}": v for k, v in g[1].items()}, "u": g[2]}
    got, ref = flat(grads), flat(want_grads)
    assert len(got) == 9 + 2 + 1
    for leaf in ref:
        assert rel(got[leaf], ref[leaf]) < 5e-5, (leaf, rel(got[leaf],
                                                            ref[leaf]))


def test_the_layer_checks_its_pairs_and_its_second_input():
    layer = DifferentialAttentionLayer(n_heads=6, n_kv_heads=4, head_dim=8)
    with pytest.raises(ValueError, match="pairs"):
        layer.set_n_in(InputType.recurrent(48, 5))
    layer = DifferentialAttentionLayer(n_heads=8, n_kv_heads=4, head_dim=8)
    layer.set_n_in(InputType.recurrent(64, 5))
    with pytest.raises(ValueError, match="width 64"):
        layer.set_side_inputs([InputType.recurrent(32, 5)])
    assert layer.N_INPUTS == 2 and not layer.supports_kv_cache
