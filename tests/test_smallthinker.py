"""The window-and-NoPE mixture-of-experts decoder (``models/smallthinker.py``)
and what it brought into the trainer: grouped-query attention without a
selection, over a window of keys, with its rotation and its norm of q and k
each able to be off, and routed experts whose router reads a second input.
All at a tiny size on the CPU, float32, the flash kernels interpreted; the
plain reference is the benchmark's (``benchmark/reference/smallthinker.py``),
which imports nothing of the program."""

import json
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, program, traffic
from benchmark.reference import smallthinker as reference
from benchmark.reference.olmo_hybrid import rounders
from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.models.smallthinker import smallthinker_tiny
from deeplearning4j_tpu.nn.conf.graph_builder import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.layers import (
    GroupedQueryAttentionLayer, RoutedExpertsLayer, layer_from_dict)
from deeplearning4j_tpu.nn.layers.attention import (
    attention_reference, rotary)
from model_reference import SoundRuns, three_steps

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, B = 64, 40, 2


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")


def tiny_cfg(**over):
    """The benchmark's configuration at its ``dry_cpu`` sizes: one period
    of four layers, a window of 8 keys of up to 40, experts 2 to 5 of 8
    held, 2 a token."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21ba3b-instruct.json")) as f:
        cfg = traffic.with_dry(json.load(f), True)
    cfg.update(over)
    return cfg


def id_batches(n, seed=0, t=T):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (n, B, t + 1), dtype=np.int32)
    return [(i[:, :-1], i[:, 1:]) for i in ids]


def gap(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# limits of the tiny float32 check: both sides are float32 and follow the
# same equations in another order (flash tiles against a dense softmax under
# a written-out mask, sorted grouped products against a loop over experts),
# so every gap is rounding unless an expert changes sides
TINY_LIMITS = {"loss1_gap": 2e-6, "loss2_gap": 2e-6, "loss3_gap": 2e-6,
               "grad_norm_gap": 1e-4, "grad_norm_gap_median": 5e-6,
               "delta_norm_gap": 2e-3, "delta_norm_gap_median": 1e-4}


@pytest.fixture(scope="module")
def sound():
    return SoundRuns(tiny_cfg(), reference, lambda seed: id_batches(3, seed))


@pytest.mark.parametrize("seed", [0, 3])
def test_three_train_steps_follow_the_reference(sound, seed):
    cfg = tiny_cfg()
    start, batches, ref = sound(seed)
    net, prog = three_steps(cfg, start, batches)
    assert net.conf.training.remat and net.num_params() == sum(
        int(np.prod(s)) for s in reference.param_shapes(cfg).values())
    assert set(prog["grad_norm"]) == set(ref["grad_norm"])
    ok, compared = compare.decide(compare.training_numbers(prog, ref),
                                  TINY_LIMITS)
    assert ok, compared


@pytest.mark.parametrize("planted", [
    dict(fault="half_batch"), dict(fault="no_window"),
    dict(fault="rope_everywhere"), dict(fault="route_after"),
    dict(precision="fp8")],
    ids=["half_batch", "no_window", "rope_everywhere", "route_after", "fp8"])
def test_the_planted_faults_and_the_control_come_out(sound, planted):
    """The planted faults, the mechanisms' own three among them, and the
    control in the precision below, each run by the reference from seed 0's
    weights and batches, fail the check against the sound run under the
    same limits."""
    bad = sound.planted(0, **planted)
    ok, compared = compare.decide(
        compare.training_numbers(bad, sound(0)[2]), TINY_LIMITS)
    assert not ok, (planted, compared)


def test_loss_and_gradients_agree_by_the_element():
    cfg = tiny_cfg()
    x, y = id_batches(1, seed=5)[0]
    w = reference.make_weights(cfg, 5)
    net = program.build_net(cfg, w)
    net.fit(DataSet(x, y))
    got = jax.device_get(program.first_moment(net.opt_state))
    loss, want = jax.jit(jax.value_and_grad(reference.loss_fn),
                         static_argnums=3)(w, x, y, reference.FrozenCfg(cfg))
    assert abs(float(net.score_value) - float(loss)) < 2e-6 * float(loss)
    assert set(got) == set(want)
    for leaf, g in jax.device_get(want).items():
        assert gap(got[leaf], g) < 5e-5, leaf


def test_the_step_keeps_each_flash_pair_and_is_the_rebuilt_steps_bits(
        monkeypatch):
    """Every layer's attention node, the window layers' and the full
    layer's, runs its flash forward once a step (``nn/remat.kept``)."""
    from remat_reference import assert_a_models_step_keeps_its_flash_pairs
    cfg = tiny_cfg()
    assert_a_models_step_keeps_its_flash_pairs(
        monkeypatch,
        lambda: program.build_net(cfg, reference.make_weights(cfg, 2)),
        id_batches(3, seed=2), attention_nodes=cfg["num_hidden_layers"])


def test_remat_on_and_off_give_the_same_gradients():
    """Under remat the experts' node keeps both its inputs and routes the
    rebuild from the kept ``u``: one step each from the same weights gives
    the same first moments to float32 rounding."""
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


# ---------------------------------------------------- the attention layer

FORMS = {   # (window, rotate, qk_norm)
    "full_nope": (None, False, False),
    "window_rope": (8, True, False),
    "window_nope": (8, False, False),
    "full_rope_norm": (None, True, True),
    "window_whole_sequence": (T, True, False),
}


def attention_by_hand(params, u, layer):
    """The layer's equations in plain ``jax.numpy`` over
    ``attention_reference``: projections, a norm by head and the rotation
    where the layer has them, keys repeated to the query heads."""
    H, G, D = layer.n_heads, layer.n_kv_heads, layer.head_dim
    heads = lambda a, n: a.reshape(B, T, n, D).transpose(0, 2, 1, 3)
    q, k = heads(u @ params["Wq"], H), heads(u @ params["Wk"], G)
    v = heads(u @ params["Wv"], G)
    if layer.qk_norm:
        norm = lambda a, g: a * jax.lax.rsqrt(jnp.mean(
            a * a, axis=-1, keepdims=True) + layer.norm_eps) * g
        q, k = norm(q, params["q_gamma"]), norm(k, params["k_gamma"])
    if layer.rotate:
        q = rotary(q, jnp.arange(T), layer.rope_theta)
        k = rotary(k, jnp.arange(T), layer.rope_theta)
    k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
    o = attention_reference(q, k, v, causal=True, window=layer.window)
    return o.transpose(0, 2, 1, 3).reshape(B, T, H * D) @ params["Wo"]


@pytest.mark.parametrize("form", FORMS)
def test_attention_without_a_selection_against_the_reference(form):
    window, rotate, qk_norm = FORMS[form]
    layer = GroupedQueryAttentionLayer(
        n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=1.5e6,
        selected=False, window=window, rotate=rotate, qk_norm=qk_norm)
    layer.set_n_in(InputType.recurrent(32, T))
    assert layer.N_INPUTS == 1
    params = layer.init_params(jax.random.PRNGKey(4))
    assert ("q_gamma" in params) is qk_norm
    assert set(params) == set(layer.param_order())
    u = jax.random.normal(jax.random.PRNGKey(5), (B, T, 32))
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(params, u, state={}, train=True, rng=None)
        want = attention_by_hand(params, u, layer)
        # and the gradients of the input and of every parameter
        cot = jax.random.normal(jax.random.PRNGKey(6), want.shape)
        g_got = jax.grad(lambda p, u: jnp.sum(layer.apply(
            p, u, state={}, train=True, rng=None)[0] * cot),
            argnums=(0, 1))(params, u)
        g_want = jax.grad(lambda p, u: jnp.sum(
            attention_by_hand(p, u, layer) * cot), argnums=(0, 1))(params, u)
    assert gap(np.asarray(got), np.asarray(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert gap(np.asarray(a), np.asarray(b)) < 1e-4


def test_a_selection_and_a_window_together_are_refused():
    layer = GroupedQueryAttentionLayer(n_heads=4, n_kv_heads=2, head_dim=16,
                                       window=8)
    with pytest.raises(ValueError, match="window"):
        layer.set_n_in(InputType.recurrent(32, T))


def test_a_window_layers_attention_is_the_references_mask():
    """The layer against the benchmark reference's own attention, whose mask
    is written out (``s <= t`` and ``t - s < window``)."""
    cfg = tiny_cfg()
    w = reference.make_weights(cfg, 7)
    u = jax.random.normal(jax.random.PRNGKey(8), (B, T, 64))
    for i in range(cfg["num_hidden_layers"]):
        window, rotated = reference.layer_kind(cfg, i)
        layer = GroupedQueryAttentionLayer(
            n_heads=4, n_kv_heads=2, head_dim=16,
            rope_theta=cfg["rope_theta"], selected=False, qk_norm=False,
            window=window, rotate=rotated)
        layer.set_n_in(InputType.recurrent(64, T))
        params = {k.split("/")[1]: v for k, v in w.items()
                  if k.startswith(f"b{i}_mix/")}
        with jax.default_matmul_precision("highest"):
            got, _ = layer.apply(params, u, state={}, train=True, rng=None)
            want = reference.attention(w, f"b{i}", u, cfg,
                                       (window, rotated), *rounders("float32"))
        assert gap(np.asarray(got), np.asarray(want)) < 1e-5, i
    assert [reference.layer_kind(cfg, i) for i in range(4)] == [
        (None, False), (8, True), (8, True), (8, True)]
    assert reference.layer_kind(cfg, 0, "rope_everywhere") == (None, True)
    assert reference.layer_kind(cfg, 1, "no_window") == (None, True)


# ------------------------------------------------------ route-ahead experts

F, M, E, K = 32, 16, 64, 6


def whole_weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"moe/W_r": jax.random.normal(ks[0], (F, E)) * 0.5,
            "moe/W_gate": jax.random.normal(ks[1], (E, F, M)) * 0.3,
            "moe/W_up": jax.random.normal(ks[2], (E, F, M)) * 0.3,
            "moe/W_down": jax.random.normal(ks[3], (E, M, F)) * 0.3}


def reference_layer(w, v, r, first, count):
    cfg = {"moe_num_active_primary_experts": K, "norm_topk_prob": True,
           "first_expert": first, "moe_num_primary_experts": count}
    held = dict(w)
    for leaf in ("W_gate", "W_up", "W_down"):
        held[f"moe/{leaf}"] = w[f"moe/{leaf}"][first:first + count]
    return reference.routed_experts(held, "moe", v, r, cfg,
                                    *rounders("float32"))


def test_the_shares_of_eight_route_ahead_chips_add_up_to_the_whole_layer():
    """Eight held ranges of 8 over the same tokens, each routed from its
    second input, sum to the uncut reference layer over all 64 experts;
    each share is the reference's own cut; and the router reads the second
    input, not the first."""
    w = whole_weights()
    v = jax.random.normal(jax.random.PRNGKey(9), (2, 50, F))
    r = jax.random.normal(jax.random.PRNGKey(10), (2, 50, F))
    with jax.default_matmul_precision("highest"):
        whole = reference_layer(w, v, r, 0, E)
        total, assigned = 0.0, 0
        for chip in range(8):
            layer = RoutedExpertsLayer(
                n_experts=E, top_k=K, n_hidden=M, first=8 * chip, count=8,
                activation="relu", route_from_side=True)
            layer.set_n_in(InputType.recurrent(F, None))
            layer.set_side_inputs([InputType.recurrent(F, None)])
            assert layer.N_INPUTS == 2
            params = {"W_r": w["moe/W_r"], **{
                leaf: w[f"moe/{leaf}"][8 * chip:8 * chip + 8]
                for leaf in ("W_gate", "W_up", "W_down")}}
            y, state = layer.apply(params, (v, r), state=layer.init_state(),
                                   train=True, rng=None)
            want = reference_layer(w, v, r, 8 * chip, 8)
            assert float(jnp.abs(y - want).max()) < 2e-5, chip
            total = total + y
            assigned += int(state["assigned"].sum())
        routed_by_v = reference_layer(w, v, v, 0, E)
    assert float(jnp.abs(total - whole).max()) < 5e-5
    assert assigned == 2 * 50 * K       # every assignment lives somewhere
    assert float(jnp.abs(whole - routed_by_v).max()) > 1e-2


def test_a_router_input_of_another_width_is_refused():
    layer = RoutedExpertsLayer(n_experts=8, top_k=2, n_hidden=16,
                               route_from_side=True)
    layer.set_n_in(InputType.recurrent(32, None))
    with pytest.raises(ValueError, match="router"):
        layer.set_side_inputs([InputType.recurrent(16, None)])


# ---------------------------------------------------------- configuration

NEW_LAYERS = [
    GroupedQueryAttentionLayer(n_heads=4, n_kv_heads=2, head_dim=8,
                               rope_theta=1.5e6, selected=False, window=8,
                               rotate=True, qk_norm=False),
    GroupedQueryAttentionLayer(n_heads=4, n_kv_heads=2, head_dim=8,
                               selected=False, rotate=False, qk_norm=False),
    RoutedExpertsLayer(n_experts=8, top_k=2, n_hidden=16, first=2, count=4,
                       activation="relu", route_from_side=True),
]


@pytest.mark.parametrize("layer", NEW_LAYERS,
                         ids=["window_rope", "full_nope", "route_ahead"])
def test_new_layer_forms_round_trip_through_json(layer):
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(again) is type(layer) and again == layer
    assert again.N_INPUTS == layer.N_INPUTS


def test_model_conf_round_trips_and_graphcheck_finds_nothing():
    conf = smallthinker_tiny(V, T, remat=True, precision="bf16")
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    kinds = Counter(type(n.layer).__name__ for n in conf.nodes.values()
                    if n.kind == "layer")
    assert kinds == {"TokenEmbeddingLayer": 1, "RMSNorm": 9,
                     "GroupedQueryAttentionLayer": 4,
                     "RoutedExpertsLayer": 4, "RnnOutputLayer": 1}
    assert conf.nodes["b0_mix"].inputs == ["b0_norm1"]
    assert conf.nodes["b2_moe"].inputs == ["b2_norm2", "b2_norm1"]
    mixes = [conf.nodes[f"b{i}_mix"].layer for i in range(4)]
    assert [(m.window, m.rotate, m.qk_norm, m.selected) for m in mixes] == [
        (None, False, False, False)] + [(8, True, False, False)] * 3
    moe = conf.nodes["b0_moe"].layer
    assert (moe.n_experts, moe.first, moe.count, moe.top_k, moe.activation,
            moe.route_from_side) == (8, 2, 4, 2, "relu", True)
    assert smallthinker_tiny(V, T, remat=True).validate() == []
