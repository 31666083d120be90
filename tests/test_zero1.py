"""ZeRO-1 weight-update sharding (ISSUE 5): exact loss parity with the
replicated layout, 1/dp sharded optax state, sharded-updater checkpoint
round-trips (incl. torn-write chaos), sentinel behavior, wrapper
placement, graphcheck/memory/cost satellites.

The parity tests assert BITWISE equality: zero1 is an execution-layout
change (flattened pad-to-divisible shards + reduce-scatter/all-gather),
not an algorithm change — every post-gradient op is elementwise on the
same values, so fp32 trajectories must be identical, not merely close.
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (
    MeshContext, ParallelTrainer, ParallelWrapper, WeightUpdateSharding,
)


def _net(seed=12345, lr=0.05, updater="adam"):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(updater, learning_rate=lr)
            .weight_init("xavier")
            .list()
            # 17 is deliberately odd: every leaf needs pad-to-divisible
            .layer(DenseLayer(n_out=17, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _batch(seed=0, n=16, masked=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    ds = DataSet(x, y)
    if masked:
        ds.labels_mask = (rng.random(n) > 0.3).astype(np.float32)
    return ds


def _mesh():
    return MeshContext.create(n_data=2, n_model=1)


def _f32(v):
    return np.float32(np.asarray(v))


# ---------------------------------------------------------------------------
# exact parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("masked", [False, True])
def test_zero1_loss_parity_bitwise(accum, masked):
    """dp=2, with/without gradient accumulation and label masks: the
    fp32 loss sequence AND the final params must be bitwise equal to
    the replicated layout's."""
    ds = _batch(masked=masked)
    net_a, net_b = _net(), _net()
    tr_a = ParallelTrainer(net_a, _mesh(), gradient_accumulation=accum)
    tr_b = ParallelTrainer(net_b, _mesh(), gradient_accumulation=accum,
                           weight_update_sharding="zero1")
    la = [_f32(tr_a.fit_batch(ds)) for _ in range(5)]
    lb = [_f32(tr_b.fit_batch(ds)) for _ in range(5)]
    assert [a.tobytes() for a in la] == [b.tobytes() for b in lb]
    assert (np.asarray(net_a.params_flat()).tobytes()
            == np.asarray(net_b.params_flat()).tobytes())


def test_zero1_scan_window_parity():
    """fit_batches_scan compiles the zero1 step into its lax.scan
    program — the windowed losses must match the per-batch replicated
    loop bitwise."""
    ds = _batch()
    net_a, net_b = _net(), _net()
    tr_a = ParallelTrainer(net_a, _mesh())
    tr_b = ParallelTrainer(net_b, _mesh(), weight_update_sharding="zero1")
    la = [_f32(tr_a.fit_batch(ds)) for _ in range(4)]
    lb = np.asarray(tr_b.fit_batches_scan([ds] * 4))
    assert [a.tobytes() for a in la] == [_f32(b).tobytes() for b in lb]


# ---------------------------------------------------------------------------
# sharded updater state
# ---------------------------------------------------------------------------

def test_zero1_updater_state_is_sharded_1_over_dp():
    net = _net()
    trainer = ParallelTrainer(net, _mesh(), weight_update_sharding="zero1")
    trainer.fit_batch(_batch())
    leaves = [l for l in jax.tree_util.tree_leaves(net.opt_state)
              if getattr(l, "ndim", 0) >= 1]
    assert leaves, "adam state should carry array leaves"
    for leaf in leaves:
        assert leaf.shape[0] == 2  # (dp, chunk) view
        assert str(leaf.sharding.spec) == "PartitionSpec('data',)"
        # each data replica addresses exactly one row
        dev0 = leaf.sharding.mesh.devices.ravel()[0]
        local = sum(s.data.size for s in leaf.addressable_shards
                    if s.device == dev0)
        assert local * 2 == leaf.size


def test_zero1_gather_opt_state_roundtrip():
    """gather restores the original leaf shapes (padding dropped); a
    later fit re-shards and the trajectory stays bitwise on par with
    the replicated twin."""
    ds = _batch()
    net_a, net_b = _net(), _net()
    tr_a = ParallelTrainer(net_a, _mesh())
    tr_b = ParallelTrainer(net_b, _mesh(), weight_update_sharding="zero1")
    for _ in range(2):
        tr_a.fit_batch(ds)
        tr_b.fit_batch(ds)
    opt = tr_b.gather_opt_state()
    got = sorted(tuple(l.shape) for l in jax.tree_util.tree_leaves(opt)
                 if getattr(l, "ndim", 0) >= 1)
    want = sorted([tuple(l.shape) for l in
                   jax.tree_util.tree_leaves(net_b.params)] * 2)  # m and v
    assert got == want
    tr_a.fit_batch(ds)
    tr_b.fit_batch(ds)  # re-shards transparently
    assert (np.asarray(net_a.params_flat()).tobytes()
            == np.asarray(net_b.params_flat()).tobytes())


# ---------------------------------------------------------------------------
# checkpoint integration (resilience/)
# ---------------------------------------------------------------------------

def test_zero1_sharded_checkpoint_roundtrip(tmp_path):
    """Sharded optax leaves round-trip through CheckpointManager's
    atomic sharded format: restore into a fresh zero1 trainer and the
    continued trajectory is bitwise the uninterrupted one."""
    from deeplearning4j_tpu.resilience import CheckpointManager

    ds = _batch()
    mesh = _mesh()
    net = _net()
    trainer = ParallelTrainer(net, mesh, weight_update_sharding="zero1")
    trainer.fit_batch(ds)
    mgr = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh)
    mgr.save(net)
    ref = [_f32(trainer.fit_batch(ds)) for _ in range(2)]  # uninterrupted

    mesh2 = _mesh()
    net2 = _net(seed=777)  # different init — restore must overwrite
    tr2 = ParallelTrainer(net2, mesh2, weight_update_sharding="zero1")
    mgr2 = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh2)
    assert mgr2.restore(net2) is not None
    # restored leaves keep the sharded layout (template shapes matched)
    for leaf in jax.tree_util.tree_leaves(net2.opt_state):
        if getattr(leaf, "ndim", 0) >= 1:
            assert leaf.shape[0] == 2
    got = [_f32(tr2.fit_batch(ds)) for _ in range(2)]
    assert [a.tobytes() for a in ref] == [b.tobytes() for b in got]


def test_zero1_torn_checkpoint_skipped_by_latest_valid(tmp_path):
    """Torn-write chaos: a truncate_checkpoint fault tears the newest
    sharded save; latest_valid() must fall back to the previous intact
    checkpoint (COMMIT + CRC discipline survives sharded optax leaves)."""
    from deeplearning4j_tpu.resilience import (CheckpointManager, Fault,
                                               FaultSchedule, faultinject)

    ds = _batch()
    mesh = _mesh()
    net = _net()
    trainer = ParallelTrainer(net, mesh, weight_update_sharding="zero1")
    trainer.fit_batch(ds)
    mgr = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh)
    mgr.save(net)
    good_step = net.iteration_count
    trainer.fit_batch(ds)
    faultinject.set_schedule(FaultSchedule(
        [Fault("truncate_checkpoint", at_call=1, mode="torn")]))
    try:
        mgr.save(net)  # shard npz lands truncated, COMMIT CRC mismatches
    finally:
        faultinject.clear()
    info = mgr.latest_valid()
    assert info is not None and info.step == good_step


# ---------------------------------------------------------------------------
# divergence sentinel
# ---------------------------------------------------------------------------

def test_zero1_sentinel_skip_batch_fires_identically():
    """NaN batch at step 2 under skip_batch: the in-step guard (now a
    psum of local-shard grad norms) must fire exactly once, keep params
    finite, and leave the zero1 net bitwise equal to the replicated
    sentinel run."""
    from deeplearning4j_tpu.resilience import DivergenceSentinel

    clean = _batch()
    poison = _batch()
    feats = np.asarray(poison.features).copy()
    feats[0, 0] = np.nan
    poison.features = feats

    nets = []
    for mode in ("off", "zero1"):
        net = _net()
        sentinel = DivergenceSentinel(policy="skip_batch", lag=0)
        net.set_divergence_sentinel(sentinel)
        trainer = ParallelTrainer(net, _mesh(), weight_update_sharding=mode)
        for step, b in enumerate([clean, poison, clean]):
            trainer.fit_batch(b)
        sentinel.flush()
        assert sentinel.skipped_batches == 1, mode
        assert np.isfinite(net.params_flat()).all(), mode
        nets.append(net)
    assert (np.asarray(nets[0].params_flat()).tobytes()
            == np.asarray(nets[1].params_flat()).tobytes())


# ---------------------------------------------------------------------------
# ParallelWrapper placement mode
# ---------------------------------------------------------------------------

def test_zero1_wrapper_worker_sharded_state():
    """Wrapper zero1: each device holds only its own worker's replica of
    the stacked updater state, and averaging still re-syncs params."""
    net = _net()
    wrapper = ParallelWrapper(net, workers=8, averaging_frequency=1,
                              mesh=MeshContext.create(n_data=8, n_model=1),
                              weight_update_sharding="zero1")
    it = [_batch(seed=s, n=8) for s in range(8)]
    wrapper._ensure_vstep()
    wrapper._parallel_iteration(it)
    for leaf in jax.tree_util.tree_leaves(wrapper._stacked_opt):
        if getattr(leaf, "ndim", 0) < 1:
            continue
        assert str(leaf.sharding.spec).startswith("PartitionSpec('data'")
        dev0 = leaf.sharding.mesh.devices.ravel()[0]
        local = sum(s.data.size for s in leaf.addressable_shards
                    if s.device == dev0)
        assert local * 8 == leaf.size
    # averaging_frequency=1: replicas already re-synced this iteration
    w0 = jax.tree_util.tree_leaves(wrapper._stacked_params)[0]
    np.testing.assert_allclose(np.asarray(w0[0]), np.asarray(w0[7]),
                               rtol=1e-6, atol=1e-7)


def test_zero1_wrapper_rejects_indivisible_workers():
    with pytest.raises(ValueError):
        ParallelWrapper(_net(), workers=3,
                        mesh=MeshContext.create(n_data=2, n_model=1),
                        weight_update_sharding="zero1")


# ---------------------------------------------------------------------------
# config validation + trainers reject illegal meshes
# ---------------------------------------------------------------------------

def test_zero1_rejects_illegal_meshes():
    with pytest.raises(ValueError, match="at least 2 replicas"):
        ParallelTrainer(_net(), MeshContext.create(n_data=1, n_model=1),
                        weight_update_sharding="zero1")
    with pytest.raises(ValueError, match="data parallelism only"):
        ParallelTrainer(_net(), MeshContext.create(n_data=2, n_model=4),
                        weight_update_sharding="zero1")
    with pytest.raises(ValueError, match="mode must be one of"):
        WeightUpdateSharding.parse("zero3")


def test_zero1_graphcheck_rules():
    from deeplearning4j_tpu.analysis.fixtures import (bad_zero1_no_dp,
                                                      bad_zero1_padding,
                                                      good_mlp)
    from deeplearning4j_tpu.analysis.findings import Severity
    from deeplearning4j_tpu.analysis.graphcheck import validate_config

    conf, kw = bad_zero1_no_dp()
    finds = [f for f in validate_config(conf, **kw) if f.rule == "GC011"]
    assert finds and finds[0].severity == Severity.ERROR

    conf, kw = bad_zero1_padding()
    finds = [f for f in validate_config(conf, **kw) if f.rule == "GC011"]
    assert finds and finds[0].severity == Severity.WARNING

    conf, kw = good_mlp()
    kw["weight_update_sharding"] = "zero1"
    assert not validate_config(conf, **kw)


def test_zero1_memory_report_divides_updater_state():
    net = _net()
    rep_off = net.conf.memory_report(batch_size=32)
    from deeplearning4j_tpu.analysis.memory import memory_report
    rep_z = memory_report(net.conf, batch_size=32,
                          weight_update_sharding="zero1", dp=8)
    assert rep_off.updater_state_bytes == rep_off.param_bytes * 2  # adam m+v
    assert rep_z.updater_state_bytes == -(-rep_off.updater_state_bytes // 8)
    assert "zero1: 1/8 per replica" in rep_z.to_text()


def test_zero1_comm_bytes_model():
    from deeplearning4j_tpu.profiling.cost import dp_comm_bytes_per_update
    P, dp = 1_000_000, 8
    # accumulation k=4: 2k units replicated vs k+1 units zero1
    rep = dp_comm_bytes_per_update(P, dp, 4, gradient_accumulation=4)
    z = dp_comm_bytes_per_update(P, dp, 4, gradient_accumulation=4,
                                 weight_update_sharding="zero1")
    assert z < rep and z == rep * 5 // 8
    # no accumulation: reduce-scatter + all-gather == all-reduce traffic
    assert (dp_comm_bytes_per_update(P, dp, 4, 1, "zero1")
            == dp_comm_bytes_per_update(P, dp, 4, 1, "off"))
    assert dp_comm_bytes_per_update(P, 1, 4, 4, "zero1") == 0


def test_zero1_earlystopping_passthrough():
    from deeplearning4j_tpu.datasets import IrisDataSetIterator
    from deeplearning4j_tpu.earlystopping.config import (
        EarlyStoppingConfiguration, MaxEpochsTerminationCondition,
    )
    from deeplearning4j_tpu.earlystopping.parallel_trainer import \
        EarlyStoppingParallelTrainer

    conf = (NeuralNetConfiguration.builder()
            .seed(1).updater("adam", learning_rate=0.05)
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    net = MultiLayerNetwork(conf).init()
    es = EarlyStoppingConfiguration(
        epoch_termination_conditions=[MaxEpochsTerminationCondition(2)])
    trainer = EarlyStoppingParallelTrainer(
        es, net, IrisDataSetIterator(batch_size=48, num_examples=96),
        mesh=_mesh(), weight_update_sharding="zero1")
    assert trainer.trainer.weight_update_sharding.enabled
    result = trainer.fit()
    assert result.total_epochs >= 1
    # the run actually trained on sharded updater state
    leaves = [l for l in jax.tree_util.tree_leaves(net.opt_state)
              if getattr(l, "ndim", 0) >= 1]
    assert all(l.shape[0] == 2 for l in leaves)
