"""ZeRO-2 gradient sharding + bf16 mixed-precision master weights
(ISSUE 10): exact fp32 loss/param parity with the replicated layout
(incl. gradient accumulation, masks, the divergence sentinel, and the
scan-window path), gradients living as (dp, chunk) shards, cross-width
checkpoint topology (clear up-front error / bitwise reshard), the bf16
fp32-master checkpoint round trip, and the cost/memory/graphcheck
satellites.

fp32-policy parity tests assert BITWISE equality — zero2, like zero1,
is an execution-layout change. bf16 parity is vs a bf16 single-replica
reference (tolerance, not bitwise — see PARITY.md).
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updater import PrecisionPolicy
from deeplearning4j_tpu.parallel import (
    MeshContext, ParallelTrainer, ParallelWrapper, WeightUpdateSharding,
)


def _net(seed=12345, lr=0.05, precision=None, loss_scale=None):
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater("adam", learning_rate=lr)
         .weight_init("xavier"))
    if precision is not None:
        b = b.precision(precision, loss_scale=loss_scale)
    conf = (b.list()
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


def _mesh(dp=2):
    return MeshContext.create(n_data=dp, n_model=1,
                              devices=jax.devices()[:dp])


def _f32(v):
    return np.float32(np.asarray(v))


def _flat(tree):
    return np.concatenate([np.asarray(t).ravel()
                           for t in jax.tree_util.tree_leaves(tree)])


# ---------------------------------------------------------------------------
# exact parity (fp32 policy)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("accum", [1, 4])
def test_zero2_loss_parity_bitwise(accum):
    """dp=2, with gradient accumulation and a label mask: the fp32 loss
    sequence AND the final params must be bitwise equal to the
    replicated layout's."""
    ds = _batch(masked=True)
    net_a, net_b = _net(), _net()
    tr_a = ParallelTrainer(net_a, _mesh(), gradient_accumulation=accum)
    tr_b = ParallelTrainer(net_b, _mesh(), gradient_accumulation=accum,
                           weight_update_sharding="zero2")
    la = [_f32(tr_a.fit_batch(ds)) for _ in range(5)]
    lb = [_f32(tr_b.fit_batch(ds)) for _ in range(5)]
    assert [a.tobytes() for a in la] == [b.tobytes() for b in lb]
    assert (np.asarray(net_a.params_flat()).tobytes()
            == np.asarray(net_b.params_flat()).tobytes())


def test_zero2_matches_zero1_bitwise():
    """zero1 and zero2 are the same algorithm in different gradient
    layouts — their trajectories must agree bitwise with each other
    (both are gated against replicated separately)."""
    ds = _batch()
    net_a, net_b = _net(), _net()
    tr_a = ParallelTrainer(net_a, _mesh(), gradient_accumulation=4,
                           weight_update_sharding="zero1")
    tr_b = ParallelTrainer(net_b, _mesh(), gradient_accumulation=4,
                           weight_update_sharding="zero2")
    la = [_f32(tr_a.fit_batch(ds)) for _ in range(4)]
    lb = [_f32(tr_b.fit_batch(ds)) for _ in range(4)]
    assert [a.tobytes() for a in la] == [b.tobytes() for b in lb]
    assert (np.asarray(net_a.params_flat()).tobytes()
            == np.asarray(net_b.params_flat()).tobytes())


def test_zero2_scan_window_parity():
    """fit_batches_scan compiles the zero2 step into its lax.scan
    program — the windowed losses must match the per-batch replicated
    loop bitwise."""
    ds = _batch()
    net_a, net_b = _net(), _net()
    tr_a = ParallelTrainer(net_a, _mesh())
    tr_b = ParallelTrainer(net_b, _mesh(), weight_update_sharding="zero2")
    la = [_f32(tr_a.fit_batch(ds)) for _ in range(4)]
    lb = np.asarray(tr_b.fit_batches_scan([ds] * 4))
    assert [a.tobytes() for a in la] == [_f32(b).tobytes() for b in lb]


def test_zero2_updater_state_is_sharded_1_over_dp():
    net = _net()
    trainer = ParallelTrainer(net, _mesh(), weight_update_sharding="zero2")
    trainer.fit_batch(_batch())
    leaves = [l for l in jax.tree_util.tree_leaves(net.opt_state)
              if getattr(l, "ndim", 0) >= 1]
    assert leaves, "adam state should carry array leaves"
    for leaf in leaves:
        assert leaf.shape[0] == 2  # (dp, chunk) view
        assert str(leaf.sharding.spec) == "PartitionSpec('data',)"
        dev0 = leaf.sharding.mesh.devices.ravel()[0]
        local = sum(s.data.size for s in leaf.addressable_shards
                    if s.device == dev0)
        assert local * 2 == leaf.size


def test_zero2_sentinel_skip_batch_fires_identically():
    """NaN batch at step 2 under skip_batch: the in-step guard (a psum
    of local-shard grad norms under zero2) must fire exactly once, keep
    params finite, and leave the zero2 net bitwise equal to the
    replicated sentinel run."""
    from deeplearning4j_tpu.resilience import DivergenceSentinel

    clean = _batch()
    poison = _batch()
    feats = np.asarray(poison.features).copy()
    feats[0, 0] = np.nan
    poison.features = feats

    nets = []
    for mode in ("off", "zero2"):
        net = _net()
        sentinel = DivergenceSentinel(policy="skip_batch", lag=0)
        net.set_divergence_sentinel(sentinel)
        trainer = ParallelTrainer(net, _mesh(), weight_update_sharding=mode)
        for b in [clean, poison, clean]:
            trainer.fit_batch(b)
        sentinel.flush()
        assert sentinel.skipped_batches == 1, mode
        assert np.isfinite(net.params_flat()).all(), mode
        nets.append(net)
    assert (np.asarray(nets[0].params_flat()).tobytes()
            == np.asarray(nets[1].params_flat()).tobytes())


# ---------------------------------------------------------------------------
# mode plumbing: wrapper, validation, parse
# ---------------------------------------------------------------------------

def test_zero2_mode_parse_and_flags():
    wus = WeightUpdateSharding.parse("zero2")
    assert wus.enabled and wus.zero2
    assert WeightUpdateSharding.parse("zero1").enabled
    assert not WeightUpdateSharding.parse("zero1").zero2
    assert not WeightUpdateSharding.parse(None).enabled
    with pytest.raises(ValueError, match="mode must be one of"):
        WeightUpdateSharding.parse("zero3")


def test_zero2_rejects_illegal_meshes():
    with pytest.raises(ValueError, match="at least 2 replicas"):
        ParallelTrainer(_net(), MeshContext.create(n_data=1, n_model=1),
                        weight_update_sharding="zero2")
    with pytest.raises(ValueError, match="data parallelism only"):
        ParallelTrainer(_net(), MeshContext.create(n_data=2, n_model=4),
                        weight_update_sharding="zero2")


def test_zero2_wrapper_worker_sharded_state():
    """Wrapper zero2 == zero1 placement (the vmapped step's per-worker
    gradients are transient by construction): each device holds only
    its own worker's replica of the stacked updater state."""
    net = _net()
    wrapper = ParallelWrapper(net, workers=8, averaging_frequency=1,
                              mesh=MeshContext.create(n_data=8, n_model=1),
                              weight_update_sharding="zero2")
    it = [_batch(seed=s, n=8) for s in range(8)]
    wrapper._ensure_vstep()
    wrapper._parallel_iteration(it)
    for leaf in jax.tree_util.tree_leaves(wrapper._stacked_opt):
        if getattr(leaf, "ndim", 0) < 1:
            continue
        assert str(leaf.sharding.spec).startswith("PartitionSpec('data'")


# ---------------------------------------------------------------------------
# checkpoint topology (cross-width zero2)
# ---------------------------------------------------------------------------

def test_zero2_cross_width_restore_raises_named_error(tmp_path):
    """A zero2 checkpoint cut at dp=4 restored at dp=2 without
    reshard=True must fail up front with a CheckpointError naming the
    recorded AND requested mode/width."""
    from deeplearning4j_tpu.resilience import CheckpointManager
    from deeplearning4j_tpu.resilience.atomic import CheckpointError

    ds = _batch()
    mesh4 = _mesh(4)
    net = _net()
    ParallelTrainer(net, mesh4, weight_update_sharding="zero2").fit_batch(ds)
    mgr = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh4,
                            weight_update_sharding="zero2")
    mgr.save(net)

    mesh2 = _mesh(2)
    net2 = _net(seed=9)
    ParallelTrainer(net2, mesh2, weight_update_sharding="zero2")
    mgr2 = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh2,
                             weight_update_sharding="zero2")
    with pytest.raises(CheckpointError) as ei:
        mgr2.restore(net2)
    msg = str(ei.value)
    assert "dp=4" in msg and "dp=2" in msg
    assert "weight_update_sharding=zero2" in msg
    assert "reshard=True" in msg


def test_zero2_cross_width_reshard_restore_bitwise(tmp_path):
    """With reshard=True the (dp_old, chunk) views are un-padded into a
    fresh net's full-shape updater state BITWISE equal to a replicated
    gather, and the new-width trainer resumes on them."""
    from deeplearning4j_tpu.resilience import CheckpointManager

    ds = _batch()
    net = _net()
    tr = ParallelTrainer(net, _mesh(4), weight_update_sharding="zero2")
    tr.fit_batch(ds)
    mgr = CheckpointManager(tmp_path, sharded=True, mesh_ctx=tr.mesh,
                            weight_update_sharding="zero2")
    mgr.save(net)
    gathered = tr.gather_opt_state()

    mesh2 = _mesh(2)
    net2 = _net(seed=9)
    mgr2 = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh2,
                             weight_update_sharding="zero2")
    assert mgr2.restore(net2, reshard=True) is not None
    assert _flat(gathered).tobytes() == _flat(net2.opt_state).tobytes()
    # the new-width trainer re-flattens and continues
    tr2 = ParallelTrainer(net2, mesh2, weight_update_sharding="zero2")
    assert np.isfinite(_f32(tr2.fit_batch(ds)))


# ---------------------------------------------------------------------------
# mixed precision (bf16 compute / fp32 masters)
# ---------------------------------------------------------------------------

def test_precision_policy_parse():
    pol = PrecisionPolicy.parse("bf16")
    assert pol.compute_dtype == "bfloat16"
    assert pol.params_dtype == "float32"
    assert pol.mixed
    assert not PrecisionPolicy.parse(None).mixed
    assert not PrecisionPolicy.parse("fp32").mixed
    assert PrecisionPolicy.parse(pol) is pol
    with pytest.raises(ValueError, match="float dtype"):
        PrecisionPolicy.parse("int8")
    with pytest.raises(ValueError, match="loss_scale"):
        PrecisionPolicy(compute_dtype="bfloat16", loss_scale=-1.0)


def test_fp32_policy_is_bitwise_neutral():
    """The default/fp32 policy must compile the exact pre-policy
    program: a net built with .precision('fp32') trains bitwise
    identically to one that never names a policy."""
    ds = _batch()
    na, nb = _net(), _net(precision="fp32")
    na.fit_batch(ds)
    nb.fit_batch(ds)
    assert (np.asarray(na.params_flat()).tobytes()
            == np.asarray(nb.params_flat()).tobytes())


def test_bf16_masters_stay_fp32_and_composes_with_all_modes():
    ds = _batch()
    for mode in ("off", "zero1", "zero2"):
        net = _net()
        tr = ParallelTrainer(net, _mesh(), weight_update_sharding=mode,
                             precision="bf16")
        losses = [float(tr.fit_batch(ds)) for _ in range(2)]
        assert all(np.isfinite(losses)), (mode, losses)
        for leaf in jax.tree_util.tree_leaves(net.params):
            assert leaf.dtype == np.float32, mode
        for leaf in jax.tree_util.tree_leaves(net.opt_state):
            if getattr(leaf, "ndim", 0) >= 1:
                assert leaf.dtype == np.float32, mode


def test_bf16_parity_vs_bf16_single_replica():
    """The bf16 carve-out (PARITY.md): a bf16 dp=2 zero2 run is
    compared against a bf16 SINGLE-replica reference with tolerance —
    the psum order differs across widths, so bitwise is out of scope;
    the trajectories must still track closely (same casts, same
    fp32 update math)."""
    ds = _batch()
    net_ref = _net(precision="bf16")
    tr_ref = ParallelTrainer(net_ref, _mesh(1))
    net_z = _net(precision="bf16")
    tr_z = ParallelTrainer(net_z, _mesh(), weight_update_sharding="zero2")
    lr = [float(tr_ref.fit_batch(ds)) for _ in range(4)]
    lz = [float(tr_z.fit_batch(ds)) for _ in range(4)]
    np.testing.assert_allclose(lr, lz, rtol=2e-2, atol=2e-2)


def test_bf16_loss_scale_changes_nothing_material():
    """A static loss scale is unscaled in fp32 after the backward: the
    trajectory must stay close to the unscaled bf16 run (bf16 rounding
    of the scaled loss differs, hence tolerance not bitwise)."""
    ds = _batch()
    na = _net(precision="bf16")
    nb = _net(precision="bf16", loss_scale=1024.0)
    na.fit_batch(ds)
    nb.fit_batch(ds)
    np.testing.assert_allclose(np.asarray(na.params_flat()),
                               np.asarray(nb.params_flat()),
                               rtol=1e-2, atol=1e-2)


def test_bf16_master_checkpoint_roundtrip(tmp_path):
    """Save under the bf16 policy + zero2, restore into a fresh net:
    the fp32 master tree must be bitwise identical and a resumed step
    must match the unbroken run bitwise (same policy, same program)."""
    from deeplearning4j_tpu.resilience import CheckpointManager

    ds = _batch()
    mesh = _mesh()
    net = _net()
    tr = ParallelTrainer(net, mesh, weight_update_sharding="zero2",
                         precision="bf16")
    tr.fit_batch(ds)
    mgr = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh,
                            weight_update_sharding="zero2")
    mgr.save(net)
    saved_params = np.asarray(net.params_flat()).copy()
    ref = [_f32(tr.fit_batch(ds)) for _ in range(2)]  # unbroken run

    mesh2 = _mesh()
    net2 = _net(seed=777)  # different init — restore must overwrite
    tr2 = ParallelTrainer(net2, mesh2, weight_update_sharding="zero2",
                          precision="bf16")
    mgr2 = CheckpointManager(tmp_path, sharded=True, mesh_ctx=mesh2,
                             weight_update_sharding="zero2")
    assert mgr2.restore(net2) is not None
    assert np.asarray(net2.params_flat()).tobytes() == saved_params.tobytes()
    for leaf in jax.tree_util.tree_leaves(net2.params):
        assert leaf.dtype == np.float32
    got = [_f32(tr2.fit_batch(ds)) for _ in range(2)]
    assert [a.tobytes() for a in ref] == [b.tobytes() for b in got]


# ---------------------------------------------------------------------------
# satellites: graphcheck, cost model, memory report, conf serde
# ---------------------------------------------------------------------------

def test_zero2_graphcheck_rules():
    from deeplearning4j_tpu.analysis.fixtures import (bad_zero2_no_dp,
                                                      bad_zero2_padding,
                                                      good_mlp_zero2)
    from deeplearning4j_tpu.analysis.findings import Severity
    from deeplearning4j_tpu.analysis.graphcheck import validate_config

    conf, kw = bad_zero2_no_dp()
    finds = [f for f in validate_config(conf, **kw) if f.rule == "GC011"]
    assert finds and finds[0].severity == Severity.ERROR
    assert "zero2" in finds[0].message

    conf, kw = bad_zero2_padding()
    finds = [f for f in validate_config(conf, **kw) if f.rule == "GC011"]
    assert finds and finds[0].severity == Severity.WARNING

    conf, kw = good_mlp_zero2()
    assert not validate_config(conf, **kw)


def test_gc015_precision_rule():
    from deeplearning4j_tpu.analysis.findings import Severity
    from deeplearning4j_tpu.analysis.graphcheck import validate_config

    conf = _net().conf
    # bf16 without a loss scale -> warning
    conf.training.precision = "bf16"
    conf.training.loss_scale = None
    finds = [f for f in validate_config(conf) if f.rule == "GC015"]
    assert finds and finds[0].severity == Severity.WARNING
    # with a loss scale -> clean
    conf.training.loss_scale = 1024.0
    assert not [f for f in validate_config(conf) if f.rule == "GC015"]
    # non-float compute dtype -> error
    conf.training.precision = "int8"
    finds = [f for f in validate_config(conf) if f.rule == "GC015"]
    assert finds and finds[0].severity == Severity.ERROR
    # an explicit kwarg wins over the conf's policy — but a preset
    # string still inherits the conf's loss_scale, exactly as the
    # trainers' PrecisionPolicy.parse does (loss_scale is 1024.0 here,
    # so the runtime would scale and the validator must stay quiet)
    conf.training.precision = "fp32"
    assert not [f for f in validate_config(conf, precision="fp16")
                if f.rule == "GC015"]
    conf.training.loss_scale = None
    finds = [f for f in validate_config(conf, precision="fp16")
             if f.rule == "GC015"]
    assert finds and finds[0].severity == Severity.WARNING
    # an instance policy carries its OWN loss_scale: conf scale ignored
    conf.training.loss_scale = 1024.0
    finds = [f for f in validate_config(
        conf, precision=PrecisionPolicy(compute_dtype="float16"))
        if f.rule == "GC015"]
    assert finds and finds[0].severity == Severity.WARNING


def test_zero2_cost_model():
    from deeplearning4j_tpu.profiling.cost import (dp_comm_bytes_per_update,
                                                   dp_gradient_hbm_bytes)
    P, dp = 1_000_000, 8
    # zero2 comm == zero1 comm <= replicated at every accumulation depth
    for k in (1, 4):
        z1 = dp_comm_bytes_per_update(P, dp, 4, k, "zero1")
        z2 = dp_comm_bytes_per_update(P, dp, 4, k, "zero2")
        off = dp_comm_bytes_per_update(P, dp, 4, k, "off")
        assert z2 == z1 <= off
    # gradient HBM: full under off/zero1, 1/dp under zero2
    assert dp_gradient_hbm_bytes(P, dp, 4, "off") == 4 * P
    assert dp_gradient_hbm_bytes(P, dp, 4, "zero1") == 4 * P
    assert dp_gradient_hbm_bytes(P, dp, 4, "zero2") == -(-4 * P // dp)
    assert dp_gradient_hbm_bytes(P, 1, 4, "zero2") == 4 * P  # dp=1 degrades


def test_zero2_memory_report_divides_gradients():
    from deeplearning4j_tpu.analysis.memory import memory_report
    net = _net()
    rep_off = memory_report(net.conf, batch_size=32)
    rep_z1 = memory_report(net.conf, batch_size=32,
                           weight_update_sharding="zero1", dp=8)
    rep_z2 = memory_report(net.conf, batch_size=32,
                           weight_update_sharding="zero2", dp=8)
    assert rep_z1.gradient_bytes == rep_off.gradient_bytes
    assert rep_z2.gradient_bytes == -(-rep_off.gradient_bytes // 8)
    # updater state divides under both sharded modes
    assert (rep_z2.updater_state_bytes == rep_z1.updater_state_bytes
            == -(-rep_off.updater_state_bytes // 8))
    assert "zero2: 1/8 per replica" in rep_z2.to_text()


def test_precision_conf_serde_roundtrip():
    from deeplearning4j_tpu.nn.conf.builder import MultiLayerConfiguration
    conf = _net(precision="bf16", loss_scale=512.0).conf
    clone = MultiLayerConfiguration.from_json(conf.to_json())
    assert clone.training.precision == "bf16"
    assert clone.training.loss_scale == 512.0
    # configs that predate the fields deserialize to the fp32 default
    d = conf.to_dict()
    d["training"].pop("precision")
    d["training"].pop("loss_scale")
    old = MultiLayerConfiguration.from_dict(d)
    assert old.training.precision == "fp32"
    assert old.training.loss_scale is None
