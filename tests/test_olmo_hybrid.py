"""The hybrid decoder (``models/olmo_hybrid.py``) and what it brought into
the trainer: integer ids in and integer targets out through
``ComputationGraph.fit``, the new layers, remat on a real model, and the
spans and counters that cover it. All at a tiny size on the CPU, float32;
the plain reference is the benchmark's (``benchmark/reference/
olmo_hybrid.py``), which imports nothing of the program."""

import json
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, program, traffic
from benchmark.reference import olmo_hybrid as reference
from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    DevicePrefetchIterator, ListDataSetIterator)
from deeplearning4j_tpu.models.olmo_hybrid import olmo_hybrid_tiny
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    GatedDeltaNetLayer, GatedFeedForwardLayer, QKNormAttentionLayer, RMSNorm,
    RnnOutputLayer, TiedRnnOutputLayer, TokenEmbeddingLayer, layer_from_dict)
from deeplearning4j_tpu.nn.layers.attention import attention_reference
from deeplearning4j_tpu.nn.layers.normalization import rms_normalize
from deeplearning4j_tpu.profiling import MetricsRegistry, Tracer, set_tracer
from deeplearning4j_tpu.profiling.metrics import set_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, B = 64, 100, 2


def tiny_cfg(**over):
    """The benchmark's configuration at its ``dry_cpu`` sizes."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = traffic.with_dry(json.load(f), True)
    cfg.update(over)
    return cfg


def id_batches(n, seed=0, t=T):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (n, B, t + 1), dtype=np.int32)
    return [(i[:, :-1], i[:, 1:]) for i in ids]


# limits of the tiny float32 check, each read on seeds 0 and 3 with
# room: both sides are float32 and follow the same equations in another
# order (chunks of 64 against one token after another; a fused step
# against a plain one), so every gap is rounding. Losses of 400 agree to
# 1.5e-7 (two float32 ulps); the first gradient's norms to 2e-6 by the
# worst leaf (a convolution's filter, 64 numbers summed over 200 tokens);
# after three steps the parameters' change to 8e-5 by the worst leaf
# (A_log, two numbers moved by 1e-6: the change itself is a few hundred
# ulps of the parameter) and 3e-6 by the median leaf.
TINY_LIMITS = {"loss1_gap": 2e-6, "loss2_gap": 2e-6, "loss3_gap": 2e-6,
               "grad_norm_gap": 5e-5, "grad_norm_gap_median": 5e-6,
               "delta_norm_gap": 2e-3, "delta_norm_gap_median": 1e-4}


@pytest.mark.parametrize("seed", [0, 3])
def test_three_train_steps_follow_the_reference(seed):
    cfg = tiny_cfg()
    weights = reference.make_weights(cfg, seed)
    start = jax.device_get(weights)
    net = program.build_net(cfg, weights)
    assert net.conf.training.remat and net.num_params() == sum(
        int(np.prod(s)) for s in reference.param_shapes(cfg).values())
    batches = id_batches(3, seed)
    prog = {"losses": []}
    for i, (x, y) in enumerate(batches):
        net.fit(DataSet(x, y))
        prog["losses"].append(float(net.score_value))
        if i == 0:
            prog["grad_norm"] = program.leaf_norms(
                program.first_moment(net.opt_state))
    prog["delta_norm"] = program.change_norms(
        program.flatten(net.params), start)
    ref = reference.train_steps(cfg, weights, batches)
    ok, compared = compare.decide(compare.training_numbers(prog, ref),
                                  TINY_LIMITS)
    assert ok, compared
    # the planted fault and the control in the precision below come out
    for planted in (dict(fault="half_batch"), dict(precision="fp8")):
        bad = reference.train_steps(cfg, reference.make_weights(cfg, seed),
                                    batches, **planted)
        ok, compared = compare.decide(compare.training_numbers(bad, ref),
                                      TINY_LIMITS)
        assert not ok, (planted, compared)


def test_the_step_keeps_each_flash_pair_and_is_the_rebuilt_steps_bits(
        monkeypatch):
    """The full-attention node behind its QK-norm runs its flash forward once
    a step (``nn/remat.kept``), not again in the rebuild."""
    from remat_reference import assert_a_models_step_keeps_its_flash_pairs
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    cfg = tiny_cfg()
    assert_a_models_step_keeps_its_flash_pairs(
        monkeypatch,
        lambda: program.build_net(cfg, reference.make_weights(cfg, 2)),
        id_batches(3, seed=2), attention_nodes=1)


def test_remat_on_and_off_give_the_same_gradients():
    """One step each from the same weights: the first moments (the
    gradients as the optimizer got them) agree to float32 rounding, 5e-5 of
    each leaf's norm: the rebuilt forward is the same arithmetic, fused
    otherwise (1e-5 read by the worst leaf, an A_log of two numbers, each
    the sum of thousands of terms that cancel; 1e-7 by the matrices)."""
    x, y = id_batches(1)[0]
    moments = []
    for remat in (True, False):
        cfg = tiny_cfg(remat=remat)
        net = program.build_net(cfg, reference.make_weights(cfg, 1))
        assert net.conf.training.remat is remat
        net.fit(DataSet(x, y))
        moments.append(jax.device_get(program.first_moment(net.opt_state)))
    for leaf, a in moments[0].items():
        gap = np.linalg.norm(a - moments[1][leaf]) / np.linalg.norm(a)
        assert gap < 5e-5, (leaf, gap)


@pytest.mark.parametrize("head", ["untied", "tied"])
def test_integer_targets_give_the_one_hot_loss_bit_for_bit(head):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((B, 12, 16)), jnp.float32)
    ids = rng.integers(0, V, (B, 12))
    rows = jnp.asarray(np.eye(V, dtype=np.float32)[ids])
    mask = jnp.asarray(rng.integers(0, 2, (B, 12)), jnp.float32)
    if head == "untied":
        layer = RnnOutputLayer(n_in=16, n_out=V, activation="softmax",
                               has_bias=False, weight_init="xavier")
        params = layer.init_params(jax.random.PRNGKey(0))
        assert sorted(params) == ["W"]
    else:
        layer = TiedRnnOutputLayer(n_in=16, n_out=V, activation="softmax",
                                   tied_to="embed")
        params = {"W_tok": jnp.asarray(rng.standard_normal((V, 16)),
                                       jnp.float32)}
    for m in (None, mask):
        for average in (True, False):
            a = layer.compute_loss(params, x, jnp.asarray(ids, jnp.int32),
                                   mask=m, average=average)
            b = layer.compute_loss(params, x, rows, mask=m, average=average)
            assert a.dtype == jnp.float32
            if head == "tied" and average:
                # the tied head's rank-3 route sums a one-hot row's V terms
                # and the T steps in one reduction, the ids' T picked terms
                # in another order: the same numbers, an ulp or two apart
                assert np.allclose(a, b, rtol=3e-7, atol=0)
            else:
                assert np.array_equal(np.asarray(a), np.asarray(b))


def test_flash_in_interpret_mode_is_the_plain_attention_with_qk_norm(
        monkeypatch):
    """The full layer through the Pallas kernel (interpreted) against
    ``attention_reference`` on the same normalised projections: 2e-5 of the
    largest output, the online softmax's other order of float32 sums over
    128 keys."""
    layer = QKNormAttentionLayer(n_heads=2, weight_init="xavier", name="full")
    layer.set_n_in(InputType.recurrent(32, 128))
    params = layer.init_params(jax.random.PRNGKey(3))
    params["q_gamma"] = params["q_gamma"] * 1.5
    x = jnp.asarray(np.random.default_rng(4).standard_normal((B, 128, 32)),
                    jnp.float32)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        got, _ = layer.apply(params, x, state={}, train=True, rng=None)
    finally:
        set_registry(previous)
    assert registry.labeled_counter("pallas_gate_fallbacks_total").value == 0
    heads = lambda a: a.reshape(B, 128, 2, 16).transpose(0, 2, 1, 3)
    q = rms_normalize(x @ params["Wq"], 1e-6) * params["q_gamma"]
    k = rms_normalize(x @ params["Wk"], 1e-6) * params["k_gamma"]
    want = attention_reference(heads(q), heads(k), heads(x @ params["Wv"]),
                               causal=True)
    want = want.transpose(0, 2, 1, 3).reshape(B, 128, 32) @ params["Wo"]
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(
        jnp.max(jnp.abs(want)))
    # causal: a later token does not move an earlier output
    moved, _ = layer.apply(params, x.at[:, 100:].add(1.0), state={},
                           train=True, rng=None)
    assert np.array_equal(np.asarray(moved[:, :100]), np.asarray(got[:, :100]))


def test_bf16_full_attention_through_the_kernel_follows_the_xla_path(
        monkeypatch):
    """The tiny model under ``PrecisionPolicy("bf16")``, where the flash
    kernels take bfloat16 operands: the full-attention node's output and
    the first gradient (the optimizer's first moment after one step) with
    the kernel (interpreted) against ``DL4J_TPU_PALLAS=off``. Both sides
    are bfloat16 programs, and the XLA path is the coarser one (it rounds
    its scores to bfloat16 before the softmax, the kernel keeps them
    float32), so the bounds are bfloat16's own: a few of its 2^-8 for the
    node's output (read 5.1e-3 by the gap of norms) and for the gradients
    of the node's own parameters (read 0.8e-2 to 1.6e-2 on three batches).
    Below the node the gradients pass three recurrent layers: the median
    leaf reads 1.5e-2 to 1.7e-2, the worst 0.03 to 0.17 (an ``A_log`` or
    ``dt_bias`` of two numbers, each a sum of thousands of terms that
    cancel), which is held to a half: a wrong kernel reads 1 and more."""
    from deeplearning4j_tpu.nn.updater import cast_floats
    t = 160
    x, y = id_batches(1, seed=5, t=t)[0]
    seen = {}
    for mode in ("off", "interpret"):
        monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
        registry = MetricsRegistry()
        previous = set_registry(registry)
        try:
            net = ComputationGraph(
                olmo_hybrid_tiny(V, t, precision="bf16", seed=5)).init()
            acts, _, _ = net._forward(
                cast_floats(net.params, jnp.bfloat16), net.states,
                {"tokens": jnp.asarray(x)}, train=True, rng=None)
            net.fit(DataSet(x, y))
        finally:
            set_registry(previous)
        assert acts["b3_mix"].dtype == jnp.bfloat16
        traces = registry.labeled_counter("pallas_flash_traces_total")
        assert traces.labels(
            operands="float32", window="none", select="none").value == 0
        bf16 = traces.labels(
            operands="bfloat16", window="none", select="none").value
        assert (bf16 > 0) == (mode == "interpret")
        seen[mode] = (np.asarray(acts["b3_mix"], np.float32), jax.device_get(
            program.first_moment(net.opt_state)))

    def gap(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    (out, grads), (out_xla, grads_xla) = seen["interpret"], seen["off"]
    assert gap(out, out_xla) < 1.5e-2
    gaps = {leaf: gap(g, grads_xla[leaf]) for leaf, g in grads.items()}
    own = {leaf: g for leaf, g in gaps.items() if leaf.startswith("b3_mix/")}
    assert len(own) == 6 and max(own.values()) < 4e-2, own
    assert np.median(list(gaps.values())) < 4e-2, sorted(gaps.values())
    assert max(gaps.values()) < 0.5, max(gaps, key=gaps.get)


NEW_LAYERS = [
    GatedDeltaNetLayer(n_heads=2, key_dim=8, value_dim=16, conv_kernel=4,
                       allow_neg_eigval=False),
    QKNormAttentionLayer(n_heads=2, norm_eps=1e-5),
    GatedFeedForwardLayer(n_hidden=96, activation="silu"),
    RMSNorm(eps=1e-5),
    TokenEmbeddingLayer(n_out=24),
    RnnOutputLayer(n_out=9, has_bias=False),
]


@pytest.mark.parametrize("layer", NEW_LAYERS, ids=lambda l: type(l).__name__)
def test_new_layer_confs_round_trip_through_json(layer):
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(again) is type(layer) and again == layer


def test_model_conf_round_trips_and_graphcheck_finds_nothing():
    conf = olmo_hybrid_tiny(V, T, remat=True, precision="bf16")
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    assert again.topological_order == conf.topological_order
    assert again.input_types["tokens"] == InputType.token_ids(V, T)
    kinds = Counter(type(n.layer).__name__ for n in conf.nodes.values()
                    if n.kind == "layer")
    assert kinds == {"TokenEmbeddingLayer": 1, "GatedDeltaNetLayer": 3,
                     "QKNormAttentionLayer": 1, "GatedFeedForwardLayer": 4,
                     "RMSNorm": 9, "RnnOutputLayer": 1}
    assert olmo_hybrid_tiny(V, T, remat=True).validate() == []


def test_the_fit_spans_and_the_token_counter_cover_the_model():
    """``fit`` over the prefetch feed, fed int32 ids: the loop's and the
    feed's spans are there as for any model (``PERF.md`` section 3), the
    ids reach the step as int32, and ``train_tokens_total`` counts them."""
    tracer, registry = Tracer(), MetricsRegistry()
    previous = set_tracer(tracer), set_registry(registry)
    try:
        net = ComputationGraph(olmo_hybrid_tiny(V, 32)).init()
        batches = [DataSet(x, y) for x, y in id_batches(3, t=32)]
        net.fit(DevicePrefetchIterator(ListDataSetIterator(batches)))
        events = tracer.export()["traceEvents"]
    finally:
        set_tracer(previous[0])
        set_registry(previous[1])
    names = Counter(e["name"] for e in events)
    assert names["fit"] == 1 and names["fit_batch"] == 3
    for span in ("fit:split", "fit:rng", "fit:dispatch", "fit:listeners"):
        assert names[span] == 3, (span, names)
    # the feed stages an epoch twice (fit resets a feed that is running:
    # PERF.md, Open questions), so its spans are at least the batches'
    for span in ("input:wait", "input:produce", "input:read", "input:h2d",
                 "input:cast", "input:put_wait"):
        assert names[span] >= 3, (span, names)
    assert registry.counter("fit_steps_total").value == 3
    assert registry.counter("train_tokens_total").value == 3 * B * 32
    assert np.isfinite(float(net.score_value))


def test_float_batches_leave_the_token_counter_alone():
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        conf = (NeuralNetConfiguration.builder().seed(1).graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_out=4, activation="tanh"), "in")
                .add_layer("out", OutputLayer(n_out=2, activation="softmax"),
                           "d").set_outputs("out")
                .set_input_types(InputType.feed_forward(3)).build())
        ComputationGraph(conf).init().fit(DataSet(
            np.ones((4, 3), np.float32), np.eye(2, dtype=np.float32)[[0, 1, 0, 1]]))
    finally:
        set_registry(previous)
    assert registry.counter("fit_steps_total").value == 1
    assert registry.counter("train_tokens_total").value == 0


def test_token_embedding_takes_ids_only():
    layer = TokenEmbeddingLayer(n_out=8, weight_init="xavier")
    with pytest.raises(ValueError, match="token ids"):
        layer.set_n_in(InputType.recurrent(V, T))
    layer.set_n_in(InputType.token_ids(V, T))
    assert layer.infer_output_type(InputType.token_ids(V, T)) == \
        InputType.recurrent(8, T)
    params = layer.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray([[1, 5, 1]], jnp.int32)
    out, _ = layer.apply(params, ids, state={}, train=True, rng=None)
    assert np.array_equal(np.asarray(out[0]), np.asarray(params["W"])[[1, 5, 1]])
    with pytest.raises(ValueError, match="integer ids"):
        layer.apply(params, jnp.ones((1, 3, V)), state={}, train=True,
                    rng=None)
