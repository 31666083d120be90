"""autotune subsystem tests (ISSUE 13): search determinism, pruning
correctness (HBM-over-budget and graphcheck-illegal configs never
probed), probe parity (tuned == hand-built, bitwise), TunedConfig JSON
round-trip, tuned= acceptance on every consumer, the GC016 mistuning
rule, the autotune_* metrics, and the cost.py census memoization.

Runs on the 8-virtual-CPU-device conftest mesh; probe-bearing tests
use small dp=2 searches so the whole module stays seconds-scale."""

import json
import math

import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.autotune import (AutotuneError, Candidate,
                                         TunedConfig, autotune,
                                         default_candidate,
                                         enumerate_space, mesh_shapes,
                                         serve_bucket_set)
from deeplearning4j_tpu.autotune.config import ProbeRecord
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer


def small_conf(seed=7):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater("adam", learning_rate=1e-3)
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(16))
            .build())


def small_net(seed=7):
    return MultiLayerNetwork(small_conf(seed)).init()


def fake_probe(net, candidate, batch, steps=3, warmup=1, devices=None):
    """Deterministic measurement stub: 'measures' a value derived from
    the candidate's shape alone, so two searches see identical
    measurements and the selection must be reproducible."""
    base = (candidate.dp * 1.0 + candidate.tp * 2.0 + candidate.sp * 3.0
            + candidate.gradient_accumulation * 0.25
            + (0.5 if candidate.weight_update_sharding != "off" else 0.0)
            + (0.5 if candidate.precision != "fp32" else 0.0))
    return {"measured_step_s": 1e-4 * base, "compile_s": 0.0,
            "losses": [0.0]}


# ---------------------------------------------------------------- space

def test_mesh_shapes_cover_exact_device_count():
    shapes = mesh_shapes(8)
    assert all(d * t * p * s == 8 for d, t, p, s in shapes)
    assert (8, 1, 1, 1) in shapes and (1, 8, 1, 1) in shapes
    assert (2, 2, 2, 1) in shapes
    assert len(set(shapes)) == len(shapes)


def test_enumerate_space_structural_constraints():
    cands = list(enumerate_space(4, 12, accum_choices=(1, 2, 4, 5)))
    # 12 % 5 != 0: accum=5 never appears; mesh always uses all 4 chips
    assert cands and all(c.devices == 4 for c in cands)
    assert all(c.gradient_accumulation != 5 for c in cands)


def test_default_candidate_and_buckets():
    assert default_candidate(8, 64) == Candidate(dp=8)
    assert default_candidate(8, 63) == Candidate(dp=1)  # indivisible
    assert serve_bucket_set(16) == (1, 2, 4, 8, 16)
    assert serve_bucket_set(48) == (1, 2, 4, 8, 16, 32)  # pow2 floor
    assert max(serve_bucket_set(10_000)) == 128          # capped


# ------------------------------------------------------------ the search

def test_autotune_deterministic_with_fixed_measurements():
    t1 = autotune(small_net(), devices=2, global_batch=16, top_k=3,
                  probe_fn=fake_probe)
    t2 = autotune(small_net(), devices=2, global_batch=16, top_k=3,
                  probe_fn=fake_probe)
    assert t1.to_dict() == t2.to_dict()


def test_autotune_analytic_only_deterministic():
    t1 = autotune(small_net(), devices=2, global_batch=16, top_k=0)
    t2 = autotune(small_net(), devices=2, global_batch=16, top_k=0)
    assert t1.to_dict() == t2.to_dict()
    assert t1.measured_step_s is None
    assert t1.measured_vs_predicted_gap is None


def test_pruning_illegal_configs_never_probed():
    # batch 9 on 2 devices: no dp=2 shape divides it, so every legal
    # candidate is dp=1 with the weight update replicated (GC008 and
    # GC011 — via validate_config, not re-implemented — rule the rest
    # out). Probed configs must all come from the legal set.
    probed = []

    def spy(net, cand, batch, **kw):
        probed.append(cand)
        return fake_probe(net, cand, batch, **kw)

    tuned = autotune(small_net(), devices=2, global_batch=9, top_k=4,
                     probe_fn=spy)
    assert probed, "search probed nothing"
    assert all(c.dp == 1 for c in probed)
    assert all(c.weight_update_sharding == "off" for c in probed)
    assert tuned.dp == 1
    assert tuned.search["pruned_illegal"] > 0


def test_pruning_hbm_budget():
    # a 1-byte budget rules out every candidate -> explicit error
    with pytest.raises(AutotuneError):
        autotune(small_net(), devices=2, global_batch=16, hbm_budget=1,
                 top_k=0)
    # a sane budget keeps the space alive and records the counter
    tuned = autotune(small_net(), devices=2, global_batch=16,
                     hbm_budget=1 << 30, top_k=0)
    assert tuned.search["pruned_hbm"] == 0
    assert tuned.predicted_hbm_bytes is not None
    assert tuned.predicted_hbm_bytes <= 1 << 30


def test_winner_measured_no_slower_than_default():
    tuned = autotune(small_net(), devices=2, global_batch=16, top_k=2,
                     probe_steps=2)
    by_cfg = {p.config: p for p in tuned.probes}
    default = default_candidate(2, 16)
    assert default.slug() in by_cfg, "default config must be probed"
    assert tuned.measured_step_s is not None
    assert tuned.measured_step_s <= by_cfg[default.slug()].measured_step_s
    for p in tuned.probes:
        assert math.isfinite(p.measured_vs_predicted_gap)
        assert p.measured_vs_predicted_gap > 0


def test_probe_parity_tuned_equals_hand_built_bitwise():
    from deeplearning4j_tpu.autotune.probe import synthesize_batch
    from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer
    tuned = autotune(small_net(), devices=2, global_batch=16, top_k=1,
                     probe_steps=1)
    ds = synthesize_batch(small_conf(), 16)

    def run(build):
        fresh = small_net()
        trainer = build(fresh)
        losses = [np.float32(np.asarray(trainer.fit_batch(ds)))
                  for _ in range(3)]
        return losses, np.asarray(fresh.params_flat())

    losses_t, params_t = run(lambda n: tuned.trainer(n))
    losses_h, params_h = run(lambda n: ParallelTrainer(
        n, MeshContext.create(n_data=tuned.dp, n_model=tuned.tp,
                              n_seq=tuned.sp),
        **tuned.trainer_kwargs()))
    assert [l.tobytes() for l in losses_t] == [l.tobytes()
                                               for l in losses_h]
    assert params_t.tobytes() == params_h.tobytes()


# ------------------------------------------------------------ TunedConfig

def test_tuned_config_json_round_trip():
    tuned = TunedConfig(
        dp=4, tp=2, gradient_accumulation=2, precision="bf16",
        weight_update_sharding="zero2", global_batch=64, device_count=8,
        hbm_budget_bytes=1 << 34, serve_buckets=(1, 2, 4, 8),
        predicted_step_s=1e-3, measured_step_s=2e-3,
        measured_vs_predicted_gap=2.0, predicted_hbm_bytes=123,
        predicted_mfu=0.5,
        probes=[ProbeRecord("dp4_tp2_ga2_bf16_zero2", 1e-3, 2e-3, 2.0,
                            0.1)],
        search={"candidates": 10, "pruned_illegal": 2})
    rt = TunedConfig.from_json(tuned.to_json())
    assert rt == tuned
    assert rt.to_dict() == tuned.to_dict()
    # the JSON is a plain checked-in artifact: stable format tag, plain
    # types only
    d = json.loads(tuned.to_json())
    assert d["format"] == TunedConfig.FORMAT
    with pytest.raises(ValueError):
        TunedConfig.from_dict(dict(d, format="TunedConfig.v999"))


def test_tuned_config_save_load_atomic(tmp_path):
    tuned = TunedConfig(dp=2, global_batch=16, device_count=2)
    path = str(tmp_path / "tuned.json")
    tuned.save(path)
    assert TunedConfig.load(path) == tuned


def test_tuned_config_pp_refuses_flat_mesh():
    with pytest.raises(ValueError):
        TunedConfig(pp=2).mesh_context()


# ------------------------------------------------- consumers accept tuned=

def test_parallel_trainer_accepts_tuned():
    from deeplearning4j_tpu.parallel import ParallelTrainer
    tuned = TunedConfig(dp=2, gradient_accumulation=2, precision="bf16",
                        weight_update_sharding="zero1", global_batch=16,
                        device_count=2)
    tr = ParallelTrainer(small_net(), tuned=tuned)
    assert tr.mesh.n_data == 2
    assert tr.gradient_accumulation == 2
    assert tr.weight_update_sharding.mode == "zero1"
    assert tr.precision.compute_dtype == "bfloat16"
    # explicit kwargs beat the tuned values
    tr2 = ParallelTrainer(small_net(), tuned=tuned, precision="fp32",
                          weight_update_sharding="off")
    assert tr2.precision.compute_dtype == "float32"
    assert tr2.weight_update_sharding.mode == "off"


def test_parallel_wrapper_accepts_tuned():
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    tuned = TunedConfig(dp=2, gradient_accumulation=3, global_batch=16,
                        device_count=2)
    pw = ParallelWrapper(small_net(), tuned=tuned)
    assert pw.workers == 2
    assert pw.averaging_frequency == 3


def test_data_parallel_trainer_accepts_tuned():
    from deeplearning4j_tpu.parallel import multihost
    tuned = TunedConfig(dp=8, gradient_accumulation=2, global_batch=32,
                        device_count=8)
    tr = multihost.data_parallel_trainer(small_net(), tuned=tuned)
    assert tr.gradient_accumulation == 2
    assert tr.mesh.n_data == 8
    # a pipeline plan cannot ride the flat mesh silently
    with pytest.raises(ValueError):
        multihost.data_parallel_trainer(
            small_net(), tuned=TunedConfig(dp=2, pp=2, device_count=4))


def test_autotune_rejects_batch_size_mismatch():
    from deeplearning4j_tpu.autotune.probe import synthesize_batch
    with pytest.raises(AutotuneError):
        autotune(small_net(), devices=2,
                 batch=synthesize_batch(small_conf(), 16),
                 global_batch=64, top_k=0)


def test_keras_server_accepts_tuned():
    from deeplearning4j_tpu.keras.server import KerasServer
    tuned = TunedConfig(dp=2, global_batch=16, device_count=2,
                        serve_buckets=(1, 2, 4, 8))
    srv = KerasServer(tuned=tuned)
    try:
        assert srv._batcher.max_batch == 8
    finally:
        srv.stop()


# ------------------------------------------------------------------ GC016

def test_gc016_warns_on_mistuned_config():
    from deeplearning4j_tpu.analysis.fixtures import good_mlp
    from deeplearning4j_tpu.analysis.graphcheck import validate_config
    conf, _ = good_mlp()
    findings = validate_config(conf, mesh={"dp": 1}, batch_size=64,
                               autotune_devices=8)
    assert any(f.rule == "GC016" for f in findings)


def test_gc016_quiet_without_device_count_and_when_tuned():
    from deeplearning4j_tpu.analysis.fixtures import good_mlp
    from deeplearning4j_tpu.analysis.graphcheck import validate_config
    conf, _ = good_mlp()
    # no autotune_devices: the rule never runs
    assert not any(f.rule == "GC016" for f in validate_config(
        conf, mesh={"dp": 1}, batch_size=64))
    # a well-tuned compute-dominant shape stays quiet
    assert not any(f.rule == "GC016" for f in validate_config(
        conf, mesh={"dp": 8}, batch_size=256, autotune_devices=8))


# ------------------------------------------------------------ observability

def test_autotune_metrics_exported():
    from deeplearning4j_tpu.profiling.metrics import get_registry
    before = dict(get_registry().snapshot("autotune_"))
    tuned = autotune(small_net(), devices=2, global_batch=16, top_k=2,
                     probe_fn=fake_probe)
    snap = get_registry().snapshot("autotune_")
    assert snap["autotune_searches_total"] \
        == before.get("autotune_searches_total", 0) + 1
    assert snap["autotune_probes_total"] \
        >= before.get("autotune_probes_total", 0) + len(tuned.probes)
    assert math.isfinite(snap["autotune_measured_vs_predicted_gap"])
    for p in tuned.probes:
        assert f"autotune_gap_{p.config}" in snap


# ------------------------------------------------- cost census memoization

def test_param_census_memoized_on_net_identity():
    from deeplearning4j_tpu.profiling import cost
    net = small_net()
    c1 = cost.param_census(net)
    c2 = cost.param_census(net)
    assert c1 is c2          # cache hit: the same dict object
    other = small_net()
    assert cost.param_census(other) is not c1
    assert cost.param_census(other) == c1  # same architecture, same census


def test_train_step_cost_memoized_on_batch_signature():
    from deeplearning4j_tpu.autotune.probe import synthesize_batch
    from deeplearning4j_tpu.profiling import cost
    net = small_net()
    ds = synthesize_batch(small_conf(), 16)
    c1 = cost.train_step_cost(net, ds)
    # same (step fn, batch signature): served from the cache, as a COPY
    # (callers mutate the dicts)
    c2 = cost.train_step_cost(net, ds)
    assert c2 == c1
    assert c2 is not c1
    # entry = (weak step-fn ref, {key: result}); nothing in it may
    # strongly reach the net or the weak key is immortal
    ref, results = cost._STEP_COST[net]
    assert ref() is net._train_step_fn and results
    c1["flops_per_step"] = -1.0  # mutating a result must not poison it
    assert cost.train_step_cost(net, ds)["flops_per_step"] != -1.0
    # a different batch shape is a different program: fresh numbers
    c3 = cost.train_step_cost(net, synthesize_batch(small_conf(), 8))
    assert c3["batch"] == 8
    assert len(cost._STEP_COST[net][1]) == 2
    # a REBUILT step (sentinel attach/detach) invalidates the programs
    net._train_step_fn = net._build_train_step()
    cost.train_step_cost(net, ds)
    assert len(cost._STEP_COST[net][1]) == 1
