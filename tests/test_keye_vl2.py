"""The sparse-attention mixture-of-experts decoder (``models/keye_vl2.py``)
and what it brought into the trainer: rotary positions, a learned indexer
whose selection is a node's own output, attention over the selected keys
alone, routed experts held as a share, a frozen node in a fine-tune. All at
a tiny size on the CPU, float32, the flash kernels interpreted; the plain
reference is the benchmark's (``benchmark/reference/keye_vl2.py``), which
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
from benchmark.reference import keye_vl2 as reference
from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    DevicePrefetchIterator, ListDataSetIterator)
from deeplearning4j_tpu.models.keye_vl2 import (
    TINY_SA_CONFIG, keye_vl2_tiny)
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    GroupedQueryAttentionLayer, RoutedExpertsLayer, SparseIndexerLayer,
    layer_from_dict)
from deeplearning4j_tpu.nn.layers.attention import (
    attention_selected, rotary, top_keys)
from deeplearning4j_tpu.profiling import MetricsRegistry, Tracer, set_tracer
from deeplearning4j_tpu.profiling.metrics import set_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, B = 64, 100, 2


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")


def tiny_cfg(**over):
    """The benchmark's configuration at its ``dry_cpu`` sizes: two layers,
    24 keys kept of up to 100, experts 2 to 5 of 8 held, 2 a token."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        cfg = traffic.with_dry(json.load(f), True)
    cfg.update(over)
    return cfg


def id_batches(n, seed=0, t=T):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (n, B, t + 1), dtype=np.int32)
    return [(i[:, :-1], i[:, 1:]) for i in ids]


def index_leaves(names):
    return {k for k in names if "_index/" in k}


def gap(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# limits of the tiny float32 check: both sides are float32 and follow the
# same equations in another order (flash tiles under a selection against a
# dense softmax under a written-out mask, sorted grouped products against a
# loop over experts), so every gap is rounding unless a key or an expert
# changes sides, which at this size and in float32 none does on these seeds
TINY_LIMITS = {"loss1_gap": 2e-6, "loss2_gap": 2e-6, "loss3_gap": 2e-6,
               "grad_norm_gap": 1e-4, "grad_norm_gap_median": 5e-6,
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
    after = program.flatten(net.params)
    prog["delta_norm"] = program.change_norms(after, start)
    ref = reference.train_steps(cfg, weights, batches)
    # the reference hands back the trained leaves and no others; the
    # program's frozen ones neither moved nor gathered momentum
    frozen = index_leaves(prog["grad_norm"])
    assert len(frozen) == 5 * cfg["num_hidden_layers"]
    assert set(prog["grad_norm"]) - frozen == set(ref["grad_norm"])
    for leaf in frozen:
        assert prog["grad_norm"][leaf] == 0.0 == prog["delta_norm"][leaf]
        assert np.array_equal(np.asarray(after[leaf]), start[leaf]), leaf
    ok, compared = compare.decide(compare.training_numbers(prog, ref),
                                  TINY_LIMITS)
    assert ok, compared
    # the planted faults, the mechanisms' own two among them, and the
    # control in the precision below come out
    for planted in (dict(fault="half_batch"), dict(fault="dense"),
                    dict(fault="raw_weights"), dict(precision="fp8")):
        bad = reference.train_steps(cfg, reference.make_weights(cfg, seed),
                                    batches, **planted)
        ok, compared = compare.decide(compare.training_numbers(bad, ref),
                                      TINY_LIMITS)
        assert not ok, (planted, compared)


def test_loss_and_gradients_agree_by_the_element():
    cfg = tiny_cfg()
    x, y = id_batches(1, seed=5)[0]
    w = reference.make_weights(cfg, 5)
    net = program.build_net(cfg, w)
    net.fit(DataSet(x, y))
    got = jax.device_get(program.first_moment(net.opt_state))
    frozen = reference.FrozenCfg(cfg)
    loss, want = jax.value_and_grad(reference.loss_fn)(w, x, y, frozen)
    assert abs(float(net.score_value) - float(loss)) < 2e-6 * float(loss)
    for leaf, g in jax.device_get(want).items():
        if "_index/" in leaf:
            assert not g.any() and not got[leaf].any(), leaf
        else:
            assert gap(got[leaf], g) < 5e-5, leaf


def test_the_step_keeps_each_flash_pair_and_is_the_rebuilt_steps_bits(
        monkeypatch):
    """Every layer's attention node runs its flash forward under the selection
    once a step (``nn/remat.kept``), not again in the rebuild."""
    from remat_reference import assert_a_models_step_keeps_its_flash_pairs
    cfg = tiny_cfg()
    assert_a_models_step_keeps_its_flash_pairs(
        monkeypatch,
        lambda: program.build_net(cfg, reference.make_weights(cfg, 2)),
        id_batches(3, seed=2), attention_nodes=cfg["num_hidden_layers"])


def test_remat_on_and_off_give_the_same_gradients():
    """One step each from the same weights: the first moments agree to
    float32 rounding. Under remat the attention node keeps the selection
    and the backward selects nothing again."""
    x, y = id_batches(1)[0]
    moments = []
    for remat in (True, False):
        cfg = tiny_cfg(remat=remat)
        net = program.build_net(cfg, reference.make_weights(cfg, 1))
        assert net.conf.training.remat is remat
        net.fit(DataSet(x, y))
        moments.append(jax.device_get(program.first_moment(net.opt_state)))
    for leaf, a in moments[0].items():
        if "_index/" not in leaf:
            assert gap(a, moments[1][leaf]) < 5e-5, leaf


def test_the_selection_is_the_references_written_out_mask():
    """The indexer node's output against the reference's ranking by a
    stable sort, on scores with planted ties: the same keys, ``topk`` a
    query once ``t >= topk``, every ``s <= t`` before."""
    rng = np.random.default_rng(0)
    scores = rng.integers(-3, 4, (2, 40, 40)).astype(np.float32)  # ties
    for topk in (8, 39, 40, 64):
        got = np.asarray(top_keys(jnp.asarray(scores), 0, topk))
        want = np.asarray(reference.selection_rows(
            jnp.asarray(scores), 0, topk))
        assert np.array_equal(got != 0, want), topk
        assert (got.sum(-1) == np.minimum(np.arange(40) + 1, topk)).all()
    # a block of queries that starts further on
    got = np.asarray(top_keys(jnp.asarray(scores[:, 16:]), 16, 8))
    want = np.asarray(reference.selection_rows(
        jnp.asarray(scores[:, 16:]), 16, 8))
    assert np.array_equal(got != 0, want)


def test_the_indexer_node_selects_as_the_reference_scores():
    cfg = tiny_cfg()
    w = reference.make_weights(cfg, 2)
    layer = SparseIndexerLayer(n_heads=2, head_dim=8, topk=24,
                               rope_theta=cfg["rope_theta"], query_chunk=32)
    layer.set_n_in(InputType.recurrent(64, T))
    assert layer.frozen
    u = jax.random.normal(jax.random.PRNGKey(0), (B, T, 64))
    params = {k.split("/")[1]: v for k, v in w.items()
              if k.startswith("b0_index/")}
    sel, _ = layer.apply(params, u, state={}, train=True, rng=None)
    assert sel.shape == (B, T, T) and sel.dtype == jnp.int8
    q, k, wt = layer.index_parts(params, u, jnp.arange(T))
    index = jnp.einsum("bhqk,bqh->bqk", jax.nn.relu(jnp.einsum(
        "bhqd,bkd->bhqk", q, k)), wt)
    want = reference.selection_rows(index, 0, 24)
    assert float(jnp.mean((sel != 0) == want)) > 0.999
    assert (np.asarray(sel).sum(-1) == np.minimum(np.arange(T) + 1, 24)).all()
    # no gradient passes the node
    g = jax.grad(lambda p: layer.apply(p, u, state={}, train=True, rng=None
                                       )[0].astype(jnp.float32).sum())(params)
    assert all(not np.asarray(v).any() for v in g.values())


def test_rotary_against_the_complex_form():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 3, 17, 16)).astype(np.float32)
    pos = np.arange(17) + 5
    theta = 1e4
    z = x[..., :8] + 1j * x[..., 8:]
    turn = np.exp(1j * pos[:, None] * theta ** (-np.arange(8) / 8.0))
    want = z * turn
    got = np.asarray(rotary(jnp.asarray(x), jnp.asarray(pos), theta))
    assert np.allclose(got[..., :8], want.real, atol=1e-5)
    assert np.allclose(got[..., 8:], want.imag, atol=1e-5)
    # the reference's, at arange(T) and time on axis 1
    mine = reference.rotate(jnp.asarray(x.transpose(0, 2, 1, 3)), theta)
    ours = rotary(jnp.asarray(x), jnp.arange(17), theta)
    assert np.allclose(np.asarray(mine).transpose(0, 2, 1, 3),
                       np.asarray(ours), atol=1e-5)
    # a rotation: norms stay, and q . k depends on t - s alone
    assert np.allclose(np.linalg.norm(got, axis=-1),
                       np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_attention_selected_is_the_flash_kernels_fallback():
    """The XLA path of "off" and of refused shapes against the kernels
    interpreted, with a query that keeps no key."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(kk, (2, 4, 70, 16)) for kk in ks[:3])
    sel = top_keys(jax.random.normal(ks[3], (2, 70, 70)), 0, 9)
    sel = sel.at[:, 5].set(0)
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention
    a = attention_selected(q, k, v, sel, chunk=32)
    b = flash_attention(q, k, v, causal=True, interpret=True, select=sel)
    assert float(jnp.abs(a - b).max()) < 1e-5
    assert not np.asarray(a[:, :, 5]).any()


NEW_LAYERS = [
    SparseIndexerLayer(n_heads=2, head_dim=8, topk=12, rope_theta=1e7),
    GroupedQueryAttentionLayer(n_heads=4, n_kv_heads=2, head_dim=8,
                               rope_theta=1e7),
    RoutedExpertsLayer(n_experts=8, top_k=2, n_hidden=16, first=2, count=4),
]


@pytest.mark.parametrize("layer", NEW_LAYERS,
                         ids=[type(l).__name__ for l in NEW_LAYERS])
def test_new_layer_confs_round_trip_through_json(layer):
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(again) is type(layer) and again == layer


def test_model_conf_round_trips_and_graphcheck_finds_nothing():
    conf = keye_vl2_tiny(V, T, remat=True, precision="bf16")
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    assert again.topological_order == conf.topological_order
    kinds = Counter(type(n.layer).__name__ for n in conf.nodes.values()
                    if n.kind == "layer")
    assert kinds == {
        "TokenEmbeddingLayer": 1, "RMSNorm": 5, "SparseIndexerLayer": 2,
        "GroupedQueryAttentionLayer": 2, "RoutedExpertsLayer": 2,
        "RnnOutputLayer": 1}
    assert conf.nodes["b1_mix"].inputs == ["b1_norm1", "b1_index"]
    assert conf.nodes["b0_index"].layer.frozen
    moe = conf.nodes["b0_moe"].layer
    assert (moe.n_experts, moe.first, moe.count, moe.top_k) == (8, 2, 4, 2)
    assert keye_vl2_tiny(V, T, remat=True).validate() == []


def test_the_fit_spans_and_the_counters_cover_the_model():
    """``fit`` over the prefetch feed, fed int32 ids: the loop's and the
    feed's spans are there as for any model, the experts, the selection
    and the flash kernels count their traces by path, no layer fell back,
    and the experts' state holds the last step's assignments."""
    tracer, registry = Tracer(), MetricsRegistry()
    previous = set_tracer(tracer), set_registry(registry)
    try:
        net = ComputationGraph(keye_vl2_tiny(V, 32)).init()
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
    assert registry.counter("fit_steps_total").value == 3
    assert registry.counter("train_tokens_total").value == 3 * B * 32
    moe = registry.labeled_counter("moe_grouped_traces_total")
    assert moe.labels(path="ragged_dot").value == 2     # a layer, one trace
    select = registry.labeled_counter("sparse_select_traces_total")
    assert select.labels(path="threshold").value == 2
    flash = registry.labeled_counter("pallas_flash_traces_total")
    assert flash.labels(operands="float32", window="none",
                        select="rows").value == 2
    assert registry.labeled_counter("pallas_gate_fallbacks_total").value == 0
    assigned = np.asarray(net.states["b1_moe"]["assigned"])
    assert assigned.dtype == np.int32 and assigned.shape == (4,)
    assert 0 < assigned.sum() <= B * 32 * 2
    assert np.isfinite(float(net.score_value))


def test_the_named_scopes_reach_the_compiled_step():
    """Every scope is in the lowered step, and in the compiled step's
    ``op_name`` under its node's name, the selection's inside the loop over
    query chunks of the indexer's own node."""
    net = ComputationGraph(keye_vl2_tiny(
        V, 16, sa_config=dict(TINY_SA_CONFIG, topk=6))).init()
    x, y = id_batches(1, t=16)[0]
    lowered = net._build_train_step().lower(
        net.params, net.opt_state, net.states, {"tokens": jnp.asarray(x)},
        {"head": jnp.asarray(y)}, None, None, jax.random.PRNGKey(0))
    text = lowered.as_text(debug_info=True)
    for scope in ("moe:route", "moe:dispatch", "moe:experts", "moe:combine",
                  "attn:rope", "dsa:index", "dsa:topk"):
        assert scope in text, scope
    names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    for node, scope in (("b0_index", "dsa:index"), ("b1_index", "dsa:topk"),
                        ("b1_index", "attn:rope"), ("b0_mix", "attn:rope"),
                        ("b0_moe", "moe:route"), ("b1_moe", "moe:experts"),
                        ("b1_moe", "moe:combine")):
        assert any(node in n and scope in n for n in names), (node, scope)


def test_a_gate_refusal_runs_the_selection_in_xla_and_is_counted(monkeypatch):
    from deeplearning4j_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "VMEM_GATE_BYTES", 1)
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        net = ComputationGraph(keye_vl2_tiny(V, 32)).init()
        x, y = id_batches(1, t=32)[0]
        net.fit(DataSet(x, y))
    finally:
        set_registry(previous)
    fallbacks = registry.labeled_counter("pallas_gate_fallbacks_total")
    assert fallbacks.value == 2 == sum(
        fallbacks.labels(layer=f"b{i}_mix", kernel="flash_select").value
        for i in (0, 1))
    assert np.isfinite(float(net.score_value))
