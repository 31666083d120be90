"""Pallas flash-attention kernel parity vs the XLA reference paths
(interpret mode — how CPU CI exercises the kernel — plus a cross-lowering
for the TPU that needs no chip; the compiled-Mosaic verdict is
``chip_smoke.py`` phase P3's, on hardware)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.attention import attention_reference
from deeplearning4j_tpu.ops.pallas_attention import flash_attention, flash_ok

RNG = np.random.default_rng(3)


def _qkv(B=2, H=2, T=24, D=8):
    q = jnp.asarray(RNG.normal(size=(B, H, T, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, H, T, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, H, T, D)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_parity(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_forward_parity_masked():
    q, k, v = _qkv(T=20)
    mask = jnp.asarray((RNG.random((2, 20)) > 0.3).astype(np.float32))
    mask = mask.at[:, 0].set(1.0)  # at least one valid key per row
    ref = attention_reference(q, k, v, mask=mask)
    got = flash_attention(q, k, v, kv_mask=mask, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_forward_aligned_shape():
    q, k, v = _qkv(B=1, H=1, T=128, D=128)
    ref = attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradient_parity(causal):
    """FA2 backward (recompute + saved lse) == autodiff of the
    reference, for q, k AND v."""
    q, k, v = _qkv(B=1, H=2, T=12, D=8)
    cot = jnp.asarray(RNG.normal(size=q.shape).astype(np.float32))

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) * cot)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_flash_gradient_parity_masked():
    q, k, v = _qkv(B=2, H=1, T=10, D=4)
    mask = jnp.ones((2, 10)).at[0, 7:].set(0.0)
    cot = jnp.asarray(RNG.normal(size=q.shape).astype(np.float32))

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, mask=mask) * cot)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_mask=mask,
                                       interpret=True) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize(
    "shape", [(4, 4, 256, 128), (2, 2, 40, 24), (32, 8, 128, 32)],
    ids=["aligned", "unaligned", "lm_rung"])
def test_flash_cross_lowers_for_tpu(shape):
    """Lowering for ("tpu",) runs the Pallas-to-Mosaic lowering on the
    CPU: a BlockSpec the (8, 128) tiling rejects raises here, in tier-1,
    not on the chip. Forward is one kernel, backward adds dq and dk/dv."""
    q = k = v = jnp.zeros(shape, jnp.float32)
    fwd = functools.partial(flash_attention, causal=True, interpret=False)
    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).sum(), argnums=(0, 1, 2))
    for fn, kernels in ((fwd, 1), (bwd, 3)):
        text = jax.jit(fn).trace(q, k, v).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == kernels


def test_flash_ok_vmem_gate():
    assert flash_ok(2048)
    assert not flash_ok(200_000)
    # wide heads count too: [Tp, Dp] panels, not a hardcoded 128
    assert not flash_ok(4096, 1024)
    assert flash_ok(4096, 128)


def test_selfattention_layer_uses_flash_kernel(monkeypatch):
    """Layer-level seam: DL4J_TPU_PALLAS=interpret routes the
    single-device SelfAttentionLayer through the kernel with identical
    outputs to the XLA path."""
    from deeplearning4j_tpu import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import RnnOutputLayer
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(4)
            .updater("sgd", learning_rate=0.05).weight_init("xavier")
            .list()
            .layer(SelfAttentionLayer(n_heads=2, causal=True))
            .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(8, 12)).build())
    x = RNG.normal(size=(4, 12, 8)).astype(np.float32)

    def output():
        # a fresh net per path (same seed, same params): the jitted
        # infer fn is cached per net, and the mode is read while tracing
        return np.asarray(MultiLayerNetwork(conf).init().output(x))

    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    ref = output()
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    np.testing.assert_allclose(output(), ref, atol=2e-5, rtol=2e-5)
    # a shape the gate refuses takes the XLA path and says so
    from deeplearning4j_tpu.ops import pallas_attention
    from deeplearning4j_tpu.profiling.metrics import get_registry
    gated = get_registry().labeled_counter("pallas_gate_fallbacks_total")
    before = gated.value
    monkeypatch.setattr(pallas_attention, "VMEM_GATE_BYTES", 0)
    np.testing.assert_array_equal(output(), ref)
    assert gated.value == before + 1
    assert gated.labels(layer="SelfAttentionLayer",
                        kernel="flash_attention").value >= 1


def test_flash_multi_block_causal_masked():
    """T=300 spans three KV blocks: the cross-block online-softmax
    carry, causal block skipping (hi=qi+1 / lo=ki) and masked-block
    rescale all genuinely fire — fwd AND grads."""
    q, k, v = _qkv(B=1, H=1, T=300, D=8)
    mask = jnp.ones((1, 300)).at[0, 130:170].set(0.0)  # hole in block 2
    cot = jnp.asarray(RNG.normal(size=q.shape).astype(np.float32))

    ref = attention_reference(q, k, v, causal=True, mask=mask)
    got = flash_attention(q, k, v, causal=True, kv_mask=mask,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * cot)

    g_ref = jax.grad(loss(lambda q, k, v: attention_reference(
        q, k, v, causal=True, mask=mask)), argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, kv_mask=mask, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


def test_flash_zero_valid_key_row_fwd_bwd():
    """A batch row whose kv_mask has ZERO valid keys (all-padding
    sequence): forward emits exactly zero for that row, backward emits
    exactly zero (and finite) gradients — the lse == NEG_INF gate in
    _dq_kernel/_dkv_kernel (ADVICE r5: recomputed probabilities on
    fully-masked rows were float-absorption garbage, not inf, so the
    old l > 0 test never fired). The valid batch row keeps full fwd/bwd
    parity with the reference."""
    q, k, v = _qkv(B=2, H=2, T=12, D=8)
    mask = jnp.ones((2, 12)).at[0].set(0.0)  # batch 0: no valid key
    cot = jnp.asarray(RNG.normal(size=q.shape).astype(np.float32))

    out = flash_attention(q, k, v, kv_mask=mask, interpret=True)
    assert float(jnp.max(jnp.abs(out[0]))) == 0.0  # masked row: zeros

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * cot)

    g_fl = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, kv_mask=mask, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    for g, name in zip(g_fl, "qkv"):
        assert bool(jnp.all(jnp.isfinite(g))), f"d{name} not finite"
        assert float(jnp.max(jnp.abs(g[0]))) == 0.0, \
            f"d{name}: masked row must have zero gradients"

    # the valid batch row is untouched by the gate: parity holds
    ref1 = attention_reference(q[1:], k[1:], v[1:], mask=mask[1:])
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(ref1),
                               atol=2e-5, rtol=2e-5)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, mask=mask[1:]) * cot[1:]),
        argnums=(0, 1, 2))(q[1:], k[1:], v[1:])
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a[1:]), np.asarray(b),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} (valid row)")
