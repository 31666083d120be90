"""Parity tests for the Pallas fused-LSTM kernel (ops/pallas_kernels.py).

Mirrors the reference's cuDNN-parity strategy (SURVEY §4: CuDNNGradientChecks
runs the same gradient-check harness with helpers active to prove
helper ≡ built-in path): the fused kernel runs in interpreter mode on CPU
and must match the lax.scan path in both forward values and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.recurrent import (
    LSTM, GravesLSTM, GravesBidirectionalLSTM,
)
from deeplearning4j_tpu.ops.pallas_kernels import fused_lstm

B, T, F, H = 3, 6, 5, 4


def _mk_layer(cls):
    layer = cls(n_out=H)
    layer.n_in = F
    return layer


def _params(layer, seed=0):
    return layer.init_params(jax.random.PRNGKey(seed))


def _x(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(B, T, F)), jnp.float32)


@pytest.mark.parametrize("cls", [LSTM, GravesLSTM])
def test_fused_forward_matches_scan(cls, monkeypatch):
    layer = _mk_layer(cls)
    params = _params(layer)
    x = _x()
    carry = layer.initial_carry(B)

    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    ys_scan, (h_s, c_s) = layer.scan(params, x, carry, None)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    assert layer._fused_kernel_ok(None)
    ys_fused, (h_f, c_f) = layer.scan(params, x, carry, None)

    np.testing.assert_allclose(ys_fused, ys_scan, atol=1e-5)
    np.testing.assert_allclose(h_f, h_s, atol=1e-5)
    np.testing.assert_allclose(c_f, c_s, atol=1e-5)


@pytest.mark.parametrize("cls", [LSTM, GravesLSTM])
def test_fused_gradients_match_scan(cls, monkeypatch):
    layer = _mk_layer(cls)
    params = _params(layer)
    x = _x(1)
    carry = layer.initial_carry(B)

    def loss(p, use_env):
        monkeypatch.setenv("DL4J_TPU_PALLAS", use_env)
        ys, (hT, cT) = layer.scan(p, x, carry, None)
        return (ys ** 2).sum() * 0.5 + (hT * 1.7).sum() + (cT * 0.3).sum()

    g_scan = jax.grad(lambda p: loss(p, "0"))(params)
    g_fused = jax.grad(lambda p: loss(p, "interpret"))(params)
    for k in params:
        np.testing.assert_allclose(g_fused[k], g_scan[k], atol=2e-4,
                                   err_msg=f"grad mismatch for {k}")


def test_fused_carry_grads(monkeypatch):
    """Cotangents of the initial carry (tBPTT backprop-through-slices path)."""
    layer = _mk_layer(LSTM)
    params = _params(layer)
    x = _x(2)

    def loss(h0, c0, env):
        monkeypatch.setenv("DL4J_TPU_PALLAS", env)
        ys, _ = layer.scan(params, x, (h0, c0), None)
        return (ys ** 2).sum()

    h0 = jnp.full((B, H), 0.3)
    c0 = jnp.full((B, H), -0.2)
    gs = jax.grad(lambda a, b: loss(a, b, "0"), argnums=(0, 1))(h0, c0)
    gf = jax.grad(lambda a, b: loss(a, b, "interpret"), argnums=(0, 1))(h0, c0)
    np.testing.assert_allclose(gf[0], gs[0], atol=2e-4)
    np.testing.assert_allclose(gf[1], gs[1], atol=2e-4)


def test_bidirectional_fused_matches_scan(monkeypatch):
    layer = _mk_layer(GravesBidirectionalLSTM)
    params = _params(layer)
    x = _x(3)

    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    ys_scan, _ = layer.apply(params, x, state={}, train=False, rng=None)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    ys_fused, _ = layer.apply(params, x, state={}, train=False, rng=None)
    np.testing.assert_allclose(ys_fused, ys_scan, atol=1e-5)


def test_masked_falls_back_to_scan(monkeypatch):
    """The kernel doesn't implement masking; the helper seam must decline."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    layer = _mk_layer(LSTM)
    mask = jnp.ones((B, T))
    assert not layer._fused_kernel_ok(mask)
    assert layer._fused_kernel_ok(None)


def test_fused_lstm_finite_difference():
    """Centered finite differences directly against the fused kernel —
    the GradientCheckUtil pattern (ref: gradientcheck/GradientCheckUtil.java:75)
    applied to the custom-VJP op itself, in f64-free form (f32, eps=1e-3)."""
    rng = np.random.default_rng(4)
    Bs, Ts, Fs, Hs = 2, 3, 3, 3
    x = jnp.asarray(rng.normal(size=(Bs, Ts, Fs)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(Fs, 4 * Hs)) * 0.3, jnp.float32)
    rw = jnp.asarray(rng.normal(size=(Hs, 4 * Hs)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(4 * Hs,)) * 0.1, jnp.float32)
    h0 = jnp.zeros((Bs, Hs))
    c0 = jnp.zeros((Bs, Hs))

    def loss(rw_):
        ys, _, _ = fused_lstm(x, w, rw_, b, None, h0, c0,
                              forget_bias=1.0, interpret=True)
        return (ys ** 2).sum() * 0.5

    g = np.asarray(jax.grad(loss)(rw))
    eps = 1e-3
    flat = np.asarray(rw).copy()
    for idx in [(0, 0), (1, 5), (2, 2 * Hs + 1), (0, 3 * Hs)]:
        p = flat.copy()
        p[idx] += eps
        up = float(loss(jnp.asarray(p)))
        p[idx] -= 2 * eps
        dn = float(loss(jnp.asarray(p)))
        fd = (up - dn) / (2 * eps)
        rel = abs(fd - g[idx]) / max(abs(fd) + abs(g[idx]), 1e-8)
        # f32 centered differences bottom out around 1e-5 absolute; accept
        # either a tight relative match or agreement at that noise floor.
        assert rel < 1e-2 or abs(fd - g[idx]) < 2e-5, (idx, fd, g[idx])


def test_padding_exact_nonaligned_shape(monkeypatch):
    """Pad-to-tile (VERDICT r3 #3): a shape far from the (8, 128) grid
    must produce bit-meaningful parity with scan, fwd AND grads — the
    same (H=200, B=6) check ``chip_smoke.py`` P3 runs compiled on the
    chip."""
    Bn, Tn, Fn, Hn = 6, 5, 72, 200
    layer = GravesLSTM(n_out=Hn)  # peephole: exercises [3, H] pad too
    layer.n_in = Fn
    params = layer.init_params(jax.random.PRNGKey(3))
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(Bn, Tn, Fn)), jnp.float32)
    carry = layer.initial_carry(Bn)

    def loss_of(pp, fused):
        monkeypatch.setenv("DL4J_TPU_PALLAS",
                           "interpret" if fused else "0")
        ys, (hT, cT) = layer.scan(pp, x, carry, None)
        return (ys ** 2).sum() + (hT * cT).sum()

    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    ys_s, (h_s, c_s) = layer.scan(params, x, carry, None)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    assert layer._fused_kernel_ok(None, batch=Bn)
    ys_f, (h_f, c_f) = layer.scan(params, x, carry, None)
    np.testing.assert_allclose(ys_f, ys_s, atol=2e-5)
    np.testing.assert_allclose(h_f, h_s, atol=2e-5)
    np.testing.assert_allclose(c_f, c_s, atol=2e-5)

    g_s = jax.grad(lambda p: loss_of(p, fused=False))(params)
    g_f = jax.grad(lambda p: loss_of(p, fused=True))(params)
    for k in g_s:
        np.testing.assert_allclose(np.asarray(g_f[k]), np.asarray(g_s[k]),
                                   atol=3e-4, err_msg=k)


@pytest.mark.parametrize(
    "shape", [(8, 16, 128, 128), (6, 16, 72, 200), (32, 64, 96, 256)],
    ids=["aligned", "unaligned", "lstm_rung"])
def test_fused_lstm_cross_lowers_for_tpu(shape):
    """Lowering for ("tpu",) runs the Pallas-to-Mosaic lowering on the
    CPU: a BlockSpec the (8, 128) tiling rejects raises here, in tier-1,
    not on the chip. (B, T, F, H); one kernel forward, two under grad."""
    b, t, f, h = shape
    args = [jnp.zeros(s, jnp.float32) for s in (
        (b, t, f), (f, 4 * h), (h, 4 * h), (4 * h,), (3 * h,), (b, h),
        (b, h))]
    fwd = lambda *a: fused_lstm(*a, forget_bias=1.0, interpret=False)
    bwd = jax.grad(lambda *a: fwd(*a)[0].sum(), argnums=tuple(range(7)))
    for fn, kernels in ((fwd, 1), (bwd, 2)):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == kernels


def test_compiled_gate_accepts_nonaligned(monkeypatch):
    """The H%128/B%8 fallback is gone: compiled mode accepts unaligned
    shapes (padding handles them); only the VMEM bound still declines."""
    from deeplearning4j_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "lstm_mode", lambda: "compiled")
    layer = _mk_layer(LSTM)
    layer.n_out = 200
    assert layer._fused_kernel_ok(None, batch=6)
    big = _mk_layer(LSTM)
    big.n_out = 8192  # RW alone = 1GB, double-buffered: past the gate
    assert not big._fused_kernel_ok(None, batch=8)
