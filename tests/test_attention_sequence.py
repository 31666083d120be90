"""Attention + ring attention tests: blockwise == reference softmax
attention; ring attention over the 8-device mesh == single-device result."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.layers.attention import (
    SelfAttentionLayer, attention_reference, blockwise_attention,
    finalize_attention,
)
from deeplearning4j_tpu.parallel.sequence import ring_self_attention

RNG = np.random.default_rng(7)


def _qkv(B=2, H=2, T=32, D=8):
    q = jnp.asarray(RNG.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, T, D)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_size", [8, 16, 100])
def test_blockwise_matches_reference(causal, block_size):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out, _, lse = blockwise_attention(q, k, v, block_size=block_size,
                                      causal=causal)
    np.testing.assert_allclose(np.asarray(finalize_attention(out, lse)),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_single_device(causal):
    B, H, T, D, F = 2, 2, 64, 8, 16
    n_heads, head_dim = H, D
    x = jnp.asarray(RNG.normal(size=(B, T, F)), jnp.float32)
    params = {
        "Wq": jnp.asarray(RNG.normal(size=(F, H * D)) * 0.1, jnp.float32),
        "Wk": jnp.asarray(RNG.normal(size=(F, H * D)) * 0.1, jnp.float32),
        "Wv": jnp.asarray(RNG.normal(size=(F, H * D)) * 0.1, jnp.float32),
        "Wo": jnp.asarray(RNG.normal(size=(H * D, F)) * 0.1, jnp.float32),
    }
    mesh = Mesh(np.array(jax.devices()).reshape(8), axis_names=("sp",))
    out = ring_self_attention(x, params, mesh, n_heads=n_heads,
                              head_dim=head_dim, seq_axis="sp",
                              causal=causal, block_size=8)

    # single-device reference
    def split(h):
        return h.reshape(B, T, H, D).transpose(0, 2, 1, 3)

    ref = attention_reference(split(x @ params["Wq"]), split(x @ params["Wk"]),
                              split(x @ params["Wv"]), causal=causal)
    ref = ref.transpose(0, 2, 1, 3).reshape(B, T, H * D) @ params["Wo"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_self_attention_layer_in_network():
    from deeplearning4j_tpu import InputType, MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers import RnnOutputLayer

    conf = (NeuralNetConfiguration.builder()
            .seed(1).updater("adam", learning_rate=0.01)
            .list()
            .layer(SelfAttentionLayer(n_heads=2, causal=True, block_size=8))
            .layer(RnnOutputLayer(n_out=5, activation="softmax"))
            .set_input_type(InputType.recurrent(12))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = RNG.normal(size=(3, 16, 12)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[RNG.integers(0, 5, (3, 16))]
    s0 = net.score(DataSet(x, y))
    for _ in range(10):
        net.fit(DataSet(x, y), use_async=False)
    assert net.score(DataSet(x, y)) < s0


def test_ring_attention_gradients_flow():
    """grad through shard_map + ppermute compiles and is finite."""
    B, H, T, D, F = 1, 1, 16, 4, 4
    x = jnp.asarray(RNG.normal(size=(B, T, F)), jnp.float32)
    params = {k: jnp.asarray(RNG.normal(size=(F, H * D)) * 0.1, jnp.float32)
              for k in ("Wq", "Wk", "Wv")}
    params["Wo"] = jnp.asarray(RNG.normal(size=(H * D, F)) * 0.1, jnp.float32)
    mesh = Mesh(np.array(jax.devices()).reshape(8), axis_names=("sp",))

    def loss(p):
        out = ring_self_attention(x, p, mesh, n_heads=H, head_dim=D,
                                  seq_axis="sp", causal=True, block_size=4)
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.all(np.isfinite(np.asarray(leaf)))


@pytest.mark.parametrize("causal", [False, True])
def test_container_sequence_parallel_loss_parity(causal):
    """VERDICT r3 #5: SelfAttentionLayer inside a MultiLayerNetwork routes
    through ring attention when ParallelTrainer's mesh has an 'sp' axis;
    the training loss must match the unsharded single-device step."""
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers import RnnOutputLayer
    from deeplearning4j_tpu.parallel.mesh import MeshContext
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    def build():
        return MultiLayerNetwork(
            NeuralNetConfiguration.builder().seed(5)
            .updater("sgd", learning_rate=0.05).weight_init("xavier")
            .list()
            .layer(SelfAttentionLayer(n_heads=2, causal=causal,
                                      block_size=4))
            .layer(RnnOutputLayer(n_out=5, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(8, 16)).build()).init()

    rng = np.random.default_rng(21)
    B, T, F, K = 4, 16, 8, 5
    x = rng.normal(size=(B, T, F)).astype(np.float32)
    y = np.eye(K, dtype=np.float32)[rng.integers(0, K, (B, T))]
    y = np.swapaxes(y, 1, 2) if y.shape[1] != T else y  # [B, T, K]
    batch = DataSet(x, y)

    ref = build()
    loss_ref = float(ref.fit_batch(batch))

    net = build()
    ctx = MeshContext.create(n_data=2, n_model=1, n_seq=4)
    assert ctx.seq_axis == "sp"
    trainer = ParallelTrainer(net, mesh=ctx)
    loss_sp = float(trainer.fit_batch(batch))
    assert abs(loss_sp - loss_ref) < 2e-5

    # updated attention params must match the single-device step too
    for k in ("Wq", "Wo"):
        np.testing.assert_allclose(np.asarray(net.params[0][k]),
                                   np.asarray(ref.params[0][k]),
                                   atol=1e-5, err_msg=k)


def test_sequence_parallel_opt_out_flag():
    """sequence_parallel=False pins local attention even inside a scope."""
    from deeplearning4j_tpu.parallel.mesh import (
        MeshContext, sequence_parallel_scope)

    layer = SelfAttentionLayer(n_heads=2, sequence_parallel=False)
    layer.set_n_in(__import__(
        "deeplearning4j_tpu").InputType.recurrent(8, 16))
    x = jnp.zeros((2, 16, 8))
    ctx = MeshContext.create(n_data=2, n_model=1, n_seq=4)
    with sequence_parallel_scope(ctx):
        assert layer._ring_context(x, None) is None
        layer.sequence_parallel = True
        assert layer._ring_context(x, None) is not None
        # masked input now rides the ring too (kv shards rotate with
        # their validity mask)
        assert layer._ring_context(x, jnp.ones((2, 16))) is not None
        # T not divisible by sp size declines
        assert layer._ring_context(jnp.zeros((2, 15, 8)), None) is None
    assert layer._ring_context(x, None) is None  # scope exited


def test_shard_batch_nondivisible_T_falls_back():
    """A [B, 15, F] batch on an sp=4 mesh must not crash shard_batch —
    it falls back to data-only sharding and the layer declines the ring
    path (review r4)."""
    from deeplearning4j_tpu.parallel.mesh import MeshContext
    ctx = MeshContext.create(n_data=2, n_model=1, n_seq=4)
    a = np.zeros((4, 15, 8), np.float32)
    out = ctx.shard_batch(a)
    assert out.shape == (4, 15, 8)
    assert out.sharding.spec[1] is None  # T not sharded
    ok = ctx.shard_batch(np.zeros((4, 16, 8), np.float32))
    assert ok.sharding.spec[1] == "sp"


def test_masked_ring_attention_matches_local():
    """Sequence-padding masks ride the ring: ring output == the local
    blockwise layer path with the same mask."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.parallel.sequence import ring_self_attention

    rng = np.random.default_rng(8)
    B, T, F, H = 4, 16, 8, 2
    layer = SelfAttentionLayer(n_heads=H, block_size=4)
    layer.set_n_in(__import__(
        "deeplearning4j_tpu").InputType.recurrent(F, T))
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(rng.normal(size=(B, T, F)), jnp.float32)
    lengths = rng.integers(5, T + 1, B)
    mask = jnp.asarray((np.arange(T)[None] < lengths[:, None])
                       .astype(np.float32))

    local, _ = layer.apply(params, x, state={}, train=False, rng=None,
                           mask=mask)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                axis_names=("data", "sp"))
    ring = ring_self_attention(x, params, mesh, n_heads=H,
                               head_dim=layer.head_dim, seq_axis="sp",
                               batch_axis="data", block_size=4, mask=mask)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(local),
                               rtol=2e-5, atol=2e-5)


def test_masked_container_sequence_parallel_parity():
    """Masked time-series training through ParallelTrainer with an sp
    axis matches the single-device step."""
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers import RnnOutputLayer
    from deeplearning4j_tpu.parallel.mesh import MeshContext
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    def build():
        return MultiLayerNetwork(
            NeuralNetConfiguration.builder().seed(5)
            .updater("sgd", learning_rate=0.05).weight_init("xavier")
            .list()
            .layer(SelfAttentionLayer(n_heads=2, block_size=4))
            .layer(RnnOutputLayer(n_out=5, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(8, 16)).build()).init()

    rng = np.random.default_rng(23)
    B, T, F, K = 4, 16, 8, 5
    x = rng.normal(size=(B, T, F)).astype(np.float32)
    y = np.eye(K, dtype=np.float32)[rng.integers(0, K, (B, T))]
    lengths = rng.integers(6, T + 1, B)
    m = (np.arange(T)[None] < lengths[:, None]).astype(np.float32)
    batch = DataSet(x, y, features_mask=m, labels_mask=m)

    ref = build()
    loss_ref = float(ref.fit_batch(batch))
    net = build()
    trainer = ParallelTrainer(net, MeshContext.create(n_data=2, n_model=1,
                                                      n_seq=4))
    loss_sp = float(trainer.fit_batch(batch))
    assert abs(loss_sp - loss_ref) < 2e-5, (loss_sp, loss_ref)


def test_masked_causal_ring_attention_matches_local():
    """Causal + padding mask together on the ring (the diagonal-block
    recompute must see the rotated kv mask) — review r4."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.parallel.sequence import ring_self_attention

    rng = np.random.default_rng(17)
    B, T, F, H = 4, 16, 8, 2
    layer = SelfAttentionLayer(n_heads=H, causal=True, block_size=4)
    layer.set_n_in(__import__(
        "deeplearning4j_tpu").InputType.recurrent(F, T))
    params = layer.init_params(jax.random.PRNGKey(1))
    x = jnp.asarray(rng.normal(size=(B, T, F)), jnp.float32)
    lengths = rng.integers(5, T + 1, B)
    mask = jnp.asarray((np.arange(T)[None] < lengths[:, None])
                       .astype(np.float32))
    local, _ = layer.apply(params, x, state={}, train=False, rng=None,
                           mask=mask)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                axis_names=("data", "sp"))
    ring = ring_self_attention(x, params, mesh, n_heads=H,
                               head_dim=layer.head_dim, seq_axis="sp",
                               batch_axis="data", causal=True,
                               block_size=4, mask=mask)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(local),
                               rtol=2e-5, atol=2e-5)
