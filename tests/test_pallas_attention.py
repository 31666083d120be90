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

# the kernels multiply in the dtype they are given: every parity case runs
# in float32 (elementwise, at the tolerance it always had) and in bfloat16
# (the error's norm over the answer's, against the float32 reference on the
# same bfloat16 inputs)
DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
BF16_OUT, BF16_GRAD = 4e-3, 6e-3


def _qkv(B=2, H=2, T=24, D=8, dtype=jnp.float32, n=3):
    return tuple(jnp.asarray(RNG.normal(size=(B, H, T, D)), jnp.float32)
                 .astype(dtype) for _ in range(n))


def _f32(fn):
    """``fn`` on float32 copies of its array arguments: the reference the
    bfloat16 cases are held to."""
    return lambda *a: fn(*(x.astype(jnp.float32) for x in a))


def _assert_close(got, ref, tol, bf16_tol, err_msg=""):
    """float32: elementwise with atol = rtol = ``tol``. bfloat16: the norm
    of the error over the norm of the answer under ``bf16_tol``."""
    if got.dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=tol, rtol=tol, err_msg=err_msg)
        return
    assert got.dtype == jnp.bfloat16, got.dtype
    got, ref = (np.asarray(a.astype(jnp.float32), np.float64)
                for a in (got, ref))
    gap = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert gap < bf16_tol, f"{err_msg} gap of norms {gap:.2e}"


def _grads(fn, cot, *args):
    return jax.jit(jax.grad(lambda *a: jnp.sum(
        fn(*a).astype(jnp.float32) * cot.astype(jnp.float32)),
        argnums=(0, 1, 2)))(*args)


@DTYPES
@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_parity(causal, dtype):
    q, k, v = _qkv(dtype=dtype)
    ref = _f32(functools.partial(attention_reference, causal=causal))(q, k, v)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    _assert_close(got, ref, 2e-5, BF16_OUT)


@DTYPES
def test_flash_forward_parity_masked(dtype):
    q, k, v = _qkv(T=20, dtype=dtype)
    mask = jnp.asarray((RNG.random((2, 20)) > 0.3).astype(np.float32))
    mask = mask.at[:, 0].set(1.0)  # at least one valid key per row
    ref = _f32(functools.partial(attention_reference, mask=mask))(q, k, v)
    got = flash_attention(q, k, v, kv_mask=mask, interpret=True)
    _assert_close(got, ref, 2e-5, BF16_OUT)


def test_flash_forward_aligned_shape():
    q, k, v = _qkv(B=1, H=1, T=128, D=128)
    ref = attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@DTYPES
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradient_parity(causal, dtype):
    """FA2 backward (recompute + saved lse) == autodiff of the
    reference, for q, k AND v."""
    q, k, v, cot = _qkv(B=1, H=2, T=12, D=8, dtype=dtype, n=4)
    g_ref = _grads(_f32(functools.partial(attention_reference,
                                          causal=causal)), cot, q, k, v)
    g_fl = _grads(functools.partial(flash_attention, causal=causal,
                                    interpret=True), cot, q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        _assert_close(a, b, 5e-5, BF16_GRAD, f"d{name}")


@DTYPES
def test_flash_gradient_parity_masked(dtype):
    q, k, v, cot = _qkv(B=2, H=1, T=10, D=4, dtype=dtype, n=4)
    mask = jnp.ones((2, 10)).at[0, 7:].set(0.0)
    g_ref = _grads(_f32(functools.partial(attention_reference, mask=mask)),
                   cot, q, k, v)
    g_fl = _grads(functools.partial(flash_attention, kv_mask=mask,
                                    interpret=True), cot, q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        _assert_close(a, b, 5e-5, BF16_GRAD, f"d{name}")


@pytest.mark.parametrize(
    "shape,dtype",
    [((4, 4, 256, 128), jnp.float32), ((2, 2, 40, 24), jnp.float32),
     ((32, 8, 128, 32), jnp.float32), ((1, 2, 512, 128), jnp.bfloat16)],
    ids=["aligned", "unaligned", "lm_rung", "bfloat16"])
def test_flash_cross_lowers_for_tpu(shape, dtype):
    """Lowering for ("tpu",) runs the Pallas-to-Mosaic lowering on the
    CPU: a BlockSpec the (8, 128) tiling rejects raises here, in tier-1,
    not on the chip. Forward is one kernel, backward adds dq and dk/dv.
    The bfloat16 case has products contracted over dimension 0 of both
    operands (``P^T dO``, ``dS^T Q``) on 16-bit tiles."""
    q = k = v = jnp.zeros(shape, dtype)
    fwd = functools.partial(flash_attention, causal=True, interpret=False)
    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                   argnums=(0, 1, 2))
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
    # the cell's shape in both widths, and the length the gate still takes
    assert flash_ok(8192, 128, 2) and flash_ok(8192, 128, 4)
    assert flash_ok(32768, 128, 2) and not flash_ok(32768, 128, 4)


@pytest.mark.parametrize("T,padded,block", [
    (12, 128, 128), (300, 384, 128), (512, 512, 512), (520, 640, 128),
    (768, 768, 256), (1500, 1536, 512), (8192, 8192, 512),
    (8320, 8704, 512)])
def test_flash_block_follows_the_length(T, padded, block):
    """The largest block of 512, 256, 128 that pads T by no more than an
    eighth over the 128-padding: a length just off a multiple of 512 does
    not fall back to blocks of 128."""
    from deeplearning4j_tpu.ops.pallas_attention import _block, _padded_len
    assert _padded_len(T) == padded and _block(padded) == block


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


@pytest.mark.parametrize(
    "T,dtype", [(300, jnp.float32), (300, jnp.bfloat16),
                (1500, jnp.float32), (1500, jnp.bfloat16)],
    ids=["T300-float32", "T300-bfloat16", "T1500-float32", "T1500-bfloat16"])
def test_flash_multi_block_causal_masked(T, dtype):
    """T=300 spans three blocks of 128, T=1500 three of 512 (the block
    follows the length): the cross-block online-softmax carry, causal
    block skipping (hi=qi+1 / lo=ki) and masked-block rescale all
    genuinely fire — fwd AND grads."""
    from deeplearning4j_tpu.ops.pallas_attention import _block, _padded_len
    assert _padded_len(T) // _block(_padded_len(T)) == 3
    q, k, v, cot = _qkv(B=1, H=1, T=T, D=8, dtype=dtype, n=4)
    mask = jnp.ones((1, T)).at[0, 130:170].set(0.0)  # hole in block 2
    ref_fn = _f32(functools.partial(attention_reference, causal=True,
                                    mask=mask))
    fl_fn = functools.partial(flash_attention, causal=True, kv_mask=mask,
                              interpret=True)
    _assert_close(fl_fn(q, k, v), ref_fn(q, k, v), 3e-5, BF16_OUT)
    for a, b, name in zip(_grads(fl_fn, cot, q, k, v),
                          _grads(ref_fn, cot, q, k, v), "qkv"):
        _assert_close(a, b, 1e-4, BF16_GRAD, f"d{name}")


@DTYPES
def test_flash_zero_valid_key_row_fwd_bwd(dtype):
    """A batch row whose kv_mask has ZERO valid keys (all-padding
    sequence): forward emits exactly zero for that row, backward emits
    exactly zero (and finite) gradients — the lse == NEG_INF gate in
    _dq_kernel/_dkv_kernel (ADVICE r5: recomputed probabilities on
    fully-masked rows were float-absorption garbage, not inf, so the
    old l > 0 test never fired). The valid batch row keeps full fwd/bwd
    parity with the reference."""
    q, k, v, cot = _qkv(B=2, H=2, T=12, D=8, dtype=dtype, n=4)
    mask = jnp.ones((2, 12)).at[0].set(0.0)  # batch 0: no valid key
    fl_fn = functools.partial(flash_attention, kv_mask=mask, interpret=True)

    out = fl_fn(q, k, v)
    assert float(jnp.max(jnp.abs(out[0]))) == 0.0  # masked row: zeros
    g_fl = _grads(fl_fn, cot, q, k, v)
    for g, name in zip(g_fl, "qkv"):
        assert bool(jnp.all(jnp.isfinite(g))), f"d{name} not finite"
        assert float(jnp.max(jnp.abs(g[0]))) == 0.0, \
            f"d{name}: masked row must have zero gradients"

    # the valid batch row is untouched by the gate: parity holds
    ref_fn = _f32(functools.partial(attention_reference, mask=mask[1:]))
    _assert_close(out[1:], ref_fn(q[1:], k[1:], v[1:]), 2e-5, BF16_OUT)
    g_ref = _grads(ref_fn, cot[1:], q[1:], k[1:], v[1:])
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        _assert_close(a[1:], b, 5e-5, BF16_GRAD, f"d{name} (valid row)")


def _kernel_dots(jaxpr, inside=False):
    """Every ``dot_general`` inside a ``pallas_call`` of ``jaxpr``, at any
    depth (custom-vjp calls, the kernels' loops): ``[(lhs dtype, rhs dtype,
    result dtype), ...]``."""
    found = []
    for eqn in jaxpr.eqns:
        if inside and eqn.primitive.name == "dot_general":
            found.append((*(v.aval.dtype for v in eqn.invars),
                          eqn.outvars[0].aval.dtype))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_dots(
                sub, inside or eqn.primitive.name == "pallas_call")
    return found


@DTYPES
def test_flash_products_take_the_inputs_dtype(dtype):
    """The nine products of a forward and backward (two in the forward
    kernel, three in dq, four in dk/dv): operands in the inputs' dtype,
    results in float32. bfloat16 inputs are not widened on their way to
    the MXU, float32 inputs are not narrowed."""
    q, k, v, cot = _qkv(B=1, H=1, T=16, D=8, dtype=dtype, n=4)
    jaxpr = jax.make_jaxpr(lambda q, k, v: _grads(functools.partial(
        flash_attention, causal=True, interpret=True), cot, q, k, v))(
            q, k, v)
    dots = _kernel_dots(jaxpr.jaxpr)
    assert len(dots) == 9, dots
    want = (jnp.dtype(dtype), jnp.dtype(dtype), jnp.dtype(jnp.float32))
    assert set(dots) == {want}, dots


def test_flash_traces_are_counted_by_operand_dtype():
    """``pallas_flash_traces_total{operands=...}``: once a trace, not a
    call, under the dtype of q."""
    from deeplearning4j_tpu.profiling.metrics import (
        MetricsRegistry, set_registry)
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        fn = jax.jit(functools.partial(flash_attention, causal=True,
                                       interpret=True))
        args = _qkv(B=1, H=1, T=16, D=8, dtype=jnp.bfloat16)
        fn(*args)
        fn(*args)
        counted = registry.labeled_counter("pallas_flash_traces_total")
        assert counted.labels(
            operands="bfloat16", window="none", select="none").value == 1
        assert counted.labels(
            operands="float32", window="none", select="none").value == 0
        fn(*_qkv(B=1, H=1, T=16, D=8))
        assert counted.labels(
            operands="float32", window="none", select="none").value == 1
        assert counted.value == 2
    finally:
        set_registry(previous)


# ---------------------------------------------------------------------------
# a selection of keys as an operand (PR 35)
# ---------------------------------------------------------------------------

def _written_out_selection(q, k, v, select):
    """The mask written out over [T, T]: key ``s`` is read from ``t`` when
    ``select[b, t, s]`` is not nought and ``s <= t``; a query with no key
    gives zeros."""
    T = q.shape[2]
    seen = (select != 0)[:, None] & jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    maps = jnp.where(jnp.any(seen, -1, keepdims=True), maps, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", maps, v)


def _top_selection(key, B, T, topk):
    """The ``topk`` keys ``s <= t`` of largest random score a query, all of
    them while ``t < topk``: what an indexer hands the kernels."""
    from deeplearning4j_tpu.nn.layers.attention import top_keys
    return top_keys(jax.random.normal(key, (B, T, T)), 0, topk)


@pytest.mark.parametrize("T,topk", [(256, 40), (300, 40), (100, 200),
                                    (640, 128)],
                         ids=["aligned", "padded", "t_below_topk",
                              "blocks_of_128"])
def test_flash_with_a_selection_is_the_written_out_mask(T, topk):
    """Forward, dq, dk and dv under ``select=`` against the mask written
    out, with 2 key/value heads repeated to 4 query heads: at an aligned
    length, at a padded one (300 runs as 384: the padded queries keep no
    key), with fewer tokens than ``topk`` (every ``s <= t`` is kept: the
    causal mask) and over several blocks."""
    ks = jax.random.split(jax.random.PRNGKey(T + topk), 5)
    B, H, G, D = 2, 4, 2, 16
    q = jax.random.normal(ks[0], (B, H, T, D))
    k, v = (jax.random.normal(kk, (B, G, T, D)) for kk in ks[1:3])
    cot = jax.random.normal(ks[3], (B, H, T, D))
    select = _top_selection(ks[4], B, T, topk)
    assert select.dtype == jnp.int8
    if topk >= T:
        assert bool(jnp.all((select != 0) == jnp.tril(jnp.ones((T, T), bool))))
    heads = lambda a: jnp.repeat(a, H // G, axis=1)

    def run(attend):
        def both(q, k, v):
            fn = lambda q, k, v: attend(q, heads(k), heads(v))
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(cot)
        return jax.jit(both)(q, k, v)

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, select=select))
    want = run(lambda q, k, v: _written_out_selection(q, k, v, select))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < 2e-5, (name, err)


def test_a_query_that_keeps_no_key_gives_zeros_and_no_gradient():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (1, 2, 130, 8)) for kk in ks[:3])
    select = _top_selection(ks[3], 1, 130, 9).at[:, 7].set(0)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                         interpret=True, select=select)
    out = fn(q, k, v)
    assert not np.asarray(out[:, :, 7]).any()
    dq, dk, dv = jax.jit(jax.grad(lambda *a: fn(*a).sum(),
                                  argnums=(0, 1, 2)))(q, k, v)
    assert not np.asarray(dq[:, :, 7]).any()
    want = jax.jit(jax.grad(
        lambda *a: _written_out_selection(*a, select).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip((dq, dk, dv), want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5


def test_a_selection_is_counted_and_widens_the_vmem_gate():
    from deeplearning4j_tpu.ops.pallas_attention import flash_vmem_bytes
    from deeplearning4j_tpu.profiling.metrics import (
        MetricsRegistry, set_registry)
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        q, k, v = _qkv(B=1, H=1, T=16, D=8)
        flash_attention(q, k, v, causal=True, interpret=True,
                        select=jnp.ones((1, 16, 16), jnp.int8))
        counted = registry.labeled_counter("pallas_flash_traces_total")
        assert counted.labels(operands="float32", window="none",
                              select="rows").value == 1
        assert counted.value == 1
    finally:
        set_registry(previous)
    # the cell's shape: int8 rows of [512, 8192], double-buffered, 8 MB
    # more, inside the gate; 32,768 tokens with a selection are not
    plain = flash_vmem_bytes(8192, 128, 2)
    assert flash_vmem_bytes(8192, 128, 2, selected=True) - plain \
        == 2 * (512 * 8192 + 2 * 512 * 512)
    assert flash_ok(8192, 128, 2, selected=True)
    assert flash_ok(32768, 128, 2) and not flash_ok(32768, 128, 2,
                                                    selected=True)
