"""The SambaY decoder (``models/phi4_flash.py``) and what it brought into
the trainer: layer nodes of two inputs, one scan and one set of keys and
values read by several layers, a tied head on the id-fed path. All at a
tiny size on the CPU, float32, the flash kernels interpreted; the plain
reference is the benchmark's (``benchmark/reference/phi4_flash.py``), which
imports nothing of the program."""

import json
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, program, traffic
from benchmark.reference import phi4_flash as reference
from deeplearning4j_tpu import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    DevicePrefetchIterator, ListDataSetIterator)
from deeplearning4j_tpu.models.phi4_flash import (
    TINY_LAYERS, layer_kinds, phi4_flash_tiny)
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    DifferentialAttentionLayer, GatedMemoryUnitLayer,
    KeyValueProjectionLayer, LayerNormalization,
    RnnOutputLayer, SelectiveScanLayer, layer_from_dict)
from deeplearning4j_tpu.profiling import MetricsRegistry, Tracer, set_tracer
from deeplearning4j_tpu.profiling.metrics import set_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, B = 64, 100, 2


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")


def tiny_cfg(**over):
    """The benchmark's configuration at its ``dry_cpu`` sizes: twelve
    layers, so that the memory and the keys and values have three readers
    each (their own layer's and two above)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = traffic.with_dry(json.load(f), True)
    cfg.update(over)
    return cfg


def id_batches(n, seed=0, t=T):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (n, B, t + 1), dtype=np.int32)
    return [(i[:, :-1], i[:, 1:]) for i in ids]


# limits of the tiny float32 check, each read on seeds 0 and 3 with room:
# both sides are float32 and follow the same equations in another order
# (blocks of 256 tokens against one token after another, flash tiles
# against dense softmaxes, a fused step against a plain one), so every gap
# is rounding. Losses of 417 agree to 7e-8 (one float32 ulp); the first
# gradient's norms to 5e-6 by the worst leaf (an l_* of 16 numbers, each a
# sum over every score of the layer); after three steps the parameters'
# change to 4e-5 by the worst leaf and 4e-9 by the median leaf.
TINY_LIMITS = {"loss1_gap": 2e-6, "loss2_gap": 2e-6, "loss3_gap": 2e-6,
               "grad_norm_gap": 1e-4, "grad_norm_gap_median": 5e-6,
               "delta_norm_gap": 2e-3, "delta_norm_gap_median": 1e-4}


@pytest.mark.parametrize("seed", [0, 3])
def test_three_train_steps_follow_the_reference(seed):
    cfg = tiny_cfg()
    assert cfg["layers"] == list(TINY_LAYERS)
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
    assert set(prog["grad_norm"]) == set(ref["grad_norm"])
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


@pytest.fixture(scope="module")
def first_gradients():
    """One step of the program from the reference's weights (the first
    moment is the gradient as the optimizer got it), and the reference's
    gradient of the same loss, the tied leaf's in its two parts."""
    os.environ["DL4J_TPU_PALLAS"] = "interpret"
    cfg = tiny_cfg()
    x, y = id_batches(1, seed=5)[0]
    w = reference.make_weights(cfg, 5)
    net = program.build_net(cfg, w)
    net.fit(DataSet(x, y))
    got = jax.device_get(program.first_moment(net.opt_state))
    frozen = reference.FrozenCfg(cfg)
    want = jax.grad(reference.loss_fn)(w, x, y, frozen)
    parts = jax.grad(
        lambda e, h: reference.loss_fn({**w, "embed/W": e}, x, y, frozen,
                                       head=h), argnums=(0, 1))(
            w["embed/W"], w["embed/W"])
    return got, jax.device_get(want), jax.device_get(parts), x


def gap(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("node,leaves,readers", [
    ("b16_ssm", 8, ["b16_mix", "b18_mix", "b20_mix", "b22_mix"]),
    ("b17_kv", 2, ["b17_mix", "b19_mix", "b21_mix", "b23_mix"])])
def test_a_shared_nodes_gradient_sums_over_its_readers(
        first_gradients, node, leaves, readers):
    """The scan of layer 16 and the keys and values of layer 17 are read by
    their own layer and by three above: the gradient of each of their
    parameters, by the element, is the reference's (which adds up what the
    readers send back by plain autodiff of one function)."""
    got, want, _, _ = first_gradients
    conf = phi4_flash_tiny(V, T)
    for reader in readers:
        assert node in conf.nodes[reader].inputs
    own = [k for k in want if k.startswith(node + "/")]
    assert len(own) == leaves
    for leaf in own:
        assert gap(got[leaf], want[leaf]) < 2e-5, (leaf, gap(got[leaf],
                                                             want[leaf]))


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_parts(
        first_gradients):
    """``embed/W`` is gathered at the bottom and multiplied at the top: its
    gradient is a scatter into the rows that were read plus the head's
    product, one leaf for the updater."""
    got, want, (gathered, multiplied), ids = first_gradients
    assert gap(got["embed/W"], gathered + multiplied) < 2e-5
    assert gap(want["embed/W"], gathered + multiplied) < 1e-6
    unread = np.setdiff1d(np.arange(V), np.unique(ids))
    assert len(unread) and not np.any(gathered[unread])
    assert np.all(np.linalg.norm(multiplied, axis=1) > 0)
    assert gap(got["embed/W"], multiplied) > 1e-2       # both parts count
    net = ComputationGraph(phi4_flash_tiny(V, T)).init()
    assert "head" in net.params and net.params["head"] == {}


def test_the_step_keeps_each_flash_pair_and_is_the_rebuilt_steps_bits(
        monkeypatch):
    """The windowed, the full and the cross attention nodes each run their
    flash forward once a step (``nn/remat.kept``), not again in the rebuild."""
    from remat_reference import assert_a_models_step_keeps_its_flash_pairs
    cfg = tiny_cfg()
    assert_a_models_step_keeps_its_flash_pairs(
        monkeypatch,
        lambda: program.build_net(cfg, reference.make_weights(cfg, 2)),
        id_batches(3, seed=2), attention_nodes=6)


def test_remat_on_and_off_give_the_same_gradients():
    """One step each from the same weights: the first moments agree to
    float32 rounding, 5e-5 of each leaf's norm. Under remat a reader keeps
    the shared array and rebuilds neither the scan nor the keys and
    values."""
    x, y = id_batches(1)[0]
    moments = []
    for remat in (True, False):
        cfg = tiny_cfg(remat=remat)
        net = program.build_net(cfg, reference.make_weights(cfg, 1))
        assert net.conf.training.remat is remat
        net.fit(DataSet(x, y))
        moments.append(jax.device_get(program.first_moment(net.opt_state)))
    for leaf, a in moments[0].items():
        assert gap(a, moments[1][leaf]) < 5e-5, leaf


NEW_LAYERS = [
    SelectiveScanLayer(n_inner=24, n_state=4, dt_rank=3, conv_kernel=4),
    GatedMemoryUnitLayer(n_memory=24),
    KeyValueProjectionLayer(n_kv_heads=2, head_dim=8),
    DifferentialAttentionLayer(n_heads=4, n_kv_heads=2, head_dim=8,
                               window=24, depth=3),
    DifferentialAttentionLayer(n_heads=4, head_dim=8, depth=19),
]


@pytest.mark.parametrize("layer", NEW_LAYERS, ids=[
    "SelectiveScanLayer", "GatedMemoryUnitLayer", "KeyValueProjectionLayer",
    "DifferentialAttentionLayer-window", "DifferentialAttentionLayer-cross"])
def test_new_layer_confs_round_trip_through_json(layer):
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(again) is type(layer) and again == layer


def test_model_conf_round_trips_and_graphcheck_finds_nothing():
    conf = phi4_flash_tiny(V, T, remat=True, precision="bf16")
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    assert again.topological_order == conf.topological_order
    assert again.input_types["tokens"] == InputType.token_ids(V, T)
    kinds = Counter(type(n.layer).__name__ for n in conf.nodes.values()
                    if n.kind == "layer")
    assert kinds == {
        "TokenEmbeddingLayer": 1, "SelectiveScanLayer": 3,
        "GatedMemoryUnitLayer": 6,
        "KeyValueProjectionLayer": 3, "DifferentialAttentionLayer": 6,
        "GatedFeedForwardLayer": 12, "LayerNormalization": 25,
        "TiedRnnOutputLayer": 1}
    windows = {n: conf.nodes[f"b{n}_mix"].layer.window
               for n in (1, 3, 17, 19)}
    assert windows == {1: 24, 3: 24, 17: None, 19: None}
    assert conf.nodes["b19_mix"].layer.depth == 19
    assert phi4_flash_tiny(V, T, remat=True).validate() == []


def test_the_published_rule_of_layer_kinds():
    kinds = layer_kinds(32, 2)
    assert Counter(kinds) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                              "cross": 7}
    assert [kinds[i] for i in (0, 1, 2, 3, 16, 17, 18, 19)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu",
        "cross"]
    with pytest.raises(ValueError, match="no state-space layer"):
        phi4_flash_tiny(V, T, layers=(1, 18))
    with pytest.raises(ValueError, match="no full-attention layer"):
        phi4_flash_tiny(V, T, layers=(0, 19))


def test_a_two_input_layer_node_is_wired_to_two():
    """The builder refuses the wrong count, and graphcheck reports it
    (GC012) where the builder was not asked."""
    def graph(*inputs):
        return (NeuralNetConfiguration.builder().graph_builder()
                .add_inputs("in")
                .add_layer("norm", LayerNormalization(), "in")
                .add_layer("gmu", GatedMemoryUnitLayer(), *inputs)
                .add_layer("out", RnnOutputLayer(n_out=3), "gmu")
                .set_outputs("out")
                .set_input_types(InputType.recurrent(8, 5)))
    with pytest.raises(ValueError, match="takes 2 input"):
        graph("in").build()
    findings = graph("in").validate()
    assert any(f.rule == "GC012" and "exactly 2" in f.message
               for f in findings), findings
    conf = graph("in", "norm").build()
    assert conf.validate() == []
    net = ComputationGraph(conf).init()
    assert net.params["gmu"]["W_in"].shape == (8, 8)
    out = net.output(np.ones((2, 5, 8), np.float32))
    assert out.shape == (2, 5, 3)


def test_the_fit_spans_and_the_counters_cover_the_model():
    """``fit`` over the prefetch feed, fed int32 ids: the loop's and the
    feed's spans are there as for any model (``PERF.md`` section 3),
    ``train_tokens_total`` counts the ids, the scans and the flash kernels
    count their traces by path and by window, and no attention layer fell
    back from the kernels."""
    tracer, registry = Tracer(), MetricsRegistry()
    previous = set_tracer(tracer), set_registry(registry)
    try:
        net = ComputationGraph(phi4_flash_tiny(V, 32)).init()
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
    for span in ("input:wait", "input:produce", "input:read", "input:h2d",
                 "input:cast", "input:put_wait"):
        assert names[span] >= 3, (span, names)
    assert registry.counter("fit_steps_total").value == 3
    assert registry.counter("train_tokens_total").value == 3 * B * 32
    scans = registry.labeled_counter("ssm_scan_traces_total")
    assert scans.labels(path="xla").value == 3      # a layer, one trace
    flash = registry.labeled_counter("pallas_flash_traces_total")
    assert flash.labels(operands="float32", window="24",
                        select="none").value == 2
    assert flash.labels(operands="float32", window="none",
                        select="none").value == 4
    # four states fill no sublane tile: the scan's gate (PR 34) sends the
    # three layers to the XLA path by name; no attention layer fell back
    fallbacks = registry.labeled_counter("pallas_gate_fallbacks_total")
    assert fallbacks.value == 3 == sum(
        fallbacks.labels(layer=name, kernel="selective_scan").value
        for name in ("b0_ssm", "b2_ssm", "b16_ssm"))
    assert np.isfinite(float(net.score_value))


def test_the_named_scopes_reach_the_lowered_step():
    net = ComputationGraph(phi4_flash_tiny(V, 16)).init()
    x, y = id_batches(1, t=16)[0]
    text = net._build_train_step().lower(
        net.params, net.opt_state, net.states, {"tokens": jnp.asarray(x)},
        {"head": jnp.asarray(y)}, None, None,
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("ssm:in_conv", "ssm:dt_bc", "ssm:scan", "gmu:gate",
                  "attn:diff_norm"):
        assert scope in text, scope
    # a Mamba mixer's gate and a memory unit are one class and one scope:
    # the node's name before it tells them apart
    for node in ("b0_mix", "b16_mix", "b18_mix"):
        assert re.search(node + r'\)?/gmu:gate', text), node
