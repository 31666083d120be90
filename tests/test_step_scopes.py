"""``profiling/scopes.py``: the compiled step's own scope table, an
``op_name`` read as (node, scope, phase), and a device line's time by scope.
Tiny nets on the CPU; the captured program is the one the fit loop ran."""

import gc
import weakref

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiling import (
    CompileWatcher, MetricsRegistry, scopes)
from deeplearning4j_tpu.profiling.metrics import set_registry

B, T, F, C = 5, 4, 6, 3
STEP = "jit_train_step"


@pytest.fixture
def registry():
    """An empty scope registry, and a metrics registry that counts this
    test's compiles alone."""
    scopes.clear()
    mine = MetricsRegistry()
    old = set_registry(mine)
    watcher = CompileWatcher(registry=mine).install()
    try:
        yield mine
    finally:
        watcher.uninstall()
        set_registry(old)
        scopes.clear()


def _net(container, step, precision="bf16"):
    """A list or a chain graph of the same layers: the graph's nodes are
    ``l0, ..., out``, the list's layers ``layer0, ...``."""
    b = (NeuralNetConfiguration.builder().seed(7).updater("nesterovs")
         .learning_rate(0.05).precision(precision))
    if step == "tbptt":
        layers = [GravesLSTM(n_out=8, activation="tanh"),
                  RnnOutputLayer(n_out=C, activation="softmax")]
        in_type = InputType.recurrent(F)
    else:
        layers = [DenseLayer(n_out=8, activation="tanh"),
                  DenseLayer(n_out=8, activation="relu"),
                  OutputLayer(n_out=C, activation="softmax")]
        in_type = InputType.feed_forward(F)
    if container == "list":
        b = b.list()
        for layer in layers:
            b = b.layer(layer)
        if step == "tbptt":
            b = b.backprop_type("truncated_bptt", 2, 2)
        return MultiLayerNetwork(b.set_input_type(in_type).build()).init()
    g = b.graph_builder().add_inputs("in")
    names = [f"l{i}" for i in range(len(layers) - 1)] + ["out"]
    for name, layer, before in zip(names, layers, ["in"] + names):
        g = g.add_layer(name, layer, before)
    g = g.set_outputs("out").set_input_types(in_type)
    if step == "tbptt":
        g = g.backprop_type("truncated_bptt", 2, 2)
    return ComputationGraph(g.build()).init()


def _data(step):
    rng = np.random.default_rng(0)
    shape = (B, T) if step == "tbptt" else (B,)
    return DataSet(rng.normal(size=shape + (F,)).astype(np.float32),
                   np.eye(C, dtype=np.float32)[rng.integers(0, C, shape)])


CASES = [(c, s) for c in ("list", "graph") for s in ("standard", "tbptt")]


@pytest.mark.parametrize("container,step", CASES)
def test_the_fit_loop_keeps_its_step_once_and_compiles_nothing_for_it(
        registry, container, step):
    net, data = _net(container, step), _data(step)
    net.fit_batch(data)
    compiles = registry.counter("jax_compile_total").value
    assert compiles > 0                 # the watcher counts
    kept = scopes.kept()
    if step == "tbptt":
        # no reader asks for a tBPTT step's table: the loop keeps none
        assert kept == {} and scopes.step_table() is None
        return
    assert list(kept) == [STEP]
    for _ in range(3):
        net.fit_batch(data)
    # once a net, however many steps run: the same module still
    assert scopes.kept()[STEP] is kept[STEP]
    table = scopes.step_table(STEP)
    # asking for the table compiled nothing either
    assert registry.counter("jax_compile_total").value == compiles
    found = {scopes.split(op) for op in table.values()}
    nodes = {s.node for s in found}
    wanted = {"layer0"} if container == "list" else {"l0", "out"}
    assert wanted <= nodes, nodes
    assert {"train:update", "train:cast"} <= {s.scope for s in found}
    assert {"fwd", "bwd"} <= {s.phase for s in found if s.node in wanted}
    assert set(table.opcode) == set(table)
    # the table has taken the module's place, and is made once
    assert scopes.kept()[STEP] is table and scopes.step_table() is table


def test_a_capture_that_would_compile_is_refused_and_said_once(
        registry, caplog):
    net, data = _net("graph", "standard"), _data("standard")
    net.fit_batch(data)
    scopes.clear()
    args = (net.params, net.opt_state, net.states,
            *net._split(DataSet(np.zeros((B + 1, F), np.float32),
                                np.zeros((B + 1, C), np.float32))),
            jax.random.PRNGKey(0))
    scopes._warned = False
    with caplog.at_level("WARNING"):
        assert not scopes.record_step(STEP, net._train_step_fn, args)
        assert not scopes.record_step(STEP, net._train_step_fn, (1,))
    assert scopes.kept() == {}
    said = [r for r in caplog.records if "no table kept" in r.getMessage()]
    assert len(said) == 1 and "compiled again" in said[0].getMessage()


@pytest.mark.parametrize("container,step", CASES)
def test_a_dropped_net_dies_at_once_and_its_table_stays(registry, container,
                                                        step):
    """No collector needed: neither the registry nor the step's own
    closures hold the net, so its device memory goes when it is dropped."""
    net = _net(container, step)
    net.fit_batch(_data(step))
    gc.collect()
    gc.disable()
    try:
        ref, leaf = weakref.ref(net), weakref.ref(
            jax.tree.leaves(net.params)[0])
        del net
        assert ref() is None and leaf() is None
    finally:
        gc.enable()
    if step == "standard":
        table = scopes.step_table(STEP)
        assert table and "train:update" in {scopes.split(v).scope
                                            for v in table.values()}


def test_a_newer_step_takes_the_older_ones_place(registry):
    """One module a name: what the process holds for the table does not
    grow with the nets it has trained."""
    data = _data("standard")
    first = _net("list", "standard")
    first.fit_batch(data)
    older = scopes.kept()[STEP]
    second = _net("graph", "standard")
    second.fit_batch(data)
    kept = scopes.kept()
    assert list(kept) == [STEP] and kept[STEP] is not older
    nodes = {scopes.split(op).node for op in scopes.step_table().values()}
    assert "l0" in nodes and "layer0" not in nodes      # the graph's
    assert scopes.step_table("jit_step") is None


TEXT = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %multiply.3 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/jvp(b0_mix)/gdn:conv/mul" stack_frame_id=3}
  %tanh.1 = f32[4]{0} tanh(%multiply.3), metadata={op_name="jit(train_step)/jvp(b0_mix)/gdn:conv/tanh"}
  ROOT %add.9 = f32[4]{0} add(%tanh.1, %param_0), metadata={op_name="jit(train_step)/jvp(b0_ffn)/add"}
}

%region_1.2 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple.2 = (s32[], /*index=1*/f32[4]{0}) tuple(%gte.1, %fusion.7)
}

ENTRY %main.5 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0), metadata={op_name="params"}
  %tuple.1 = (s32[], f32[4]{0}) tuple(%p, %p)
  %while.3 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond.1, body=%region_1.2
  %custom-call.4 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(b3_mix))/pallas_call"}
  %fusion.8 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/train:update/sub"}
  ROOT %copy.2 = f32[4]{0} copy(%fusion.8)
}
"""


def test_a_fusion_without_an_op_name_inherits_its_fused_computations():
    table = scopes.parse(TEXT)
    assert set(table) == {
        "param_0", "multiply.3", "tanh.1", "add.9", "arg", "gte.1",
        "fusion.7", "tuple.2", "p", "tuple.1", "while.3", "custom-call.4",
        "fusion.8", "copy.2"}
    # two of three are gdn:conv's, so the fusion is, and so the loop
    assert table["fusion.7"] == table["multiply.3"] or \
        table["fusion.7"] == table["tanh.1"]
    assert scopes.split(table["fusion.7"]) == ("b0_mix", "gdn:conv", "fwd")
    assert scopes.split(table["while.3"]) == ("b0_mix", "gdn:conv", "fwd")
    assert table.inherited == {"fusion.7", "while.3"}
    assert table["fusion.8"].endswith("train:update/sub")   # its own
    assert table["copy.2"] == "" and table["tuple.1"] == ""
    assert table.opcode["while.3"] == "while"
    assert table.opcode["tuple.2"] == "tuple"
    assert table.opcode["custom-call.4"] == "custom-call"
    assert table.opcode["fusion.7"] == "fusion"
    assert table.products == set()


TPU_LINES = {
    "add.2412": '  %add.2412 = f32[2048,512]{1,0:T(8,128)} add(%p.1, %p.2), '
                'metadata={op_name="jit(train_step)/train:update/add" '
                'stack_frame_id=346}',
    "fusion.54": '  %fusion.54 = pred[65536]{0:T(1024)(128)(4,1)} fusion('
                 '%gte.1), kind=kLoop, calls=%fused_computation.2, metadata='
                 '{op_name="jit(train_step)/jvp(b5_moe)/moe:dispatch/lt"}',
    "slice-start.185": '  %slice-start.185 = ((f32[8192,64]{0,1:T(8,128)}), '
                       'f32[8192,16]{0,1:T(8,128)S(1)}, s32[]{:S(2)}) '
                       'slice-start(%copy-done.18), slice={[0:8192], [16:32]}',
    "constant.4442": '  %constant.4442 = f32[]{:T(128)} constant(0)',
    "custom-call.40": '  %custom-call.40 = f32[3840,3840]{1,0:T(8,128)S(1)} '
                      'custom-call(%a, %b), custom_call_target="ConcatBitcast"',
}


@pytest.mark.parametrize("name", sorted(TPU_LINES))
def test_an_instruction_with_a_tpu_layout_is_read(name):
    """``analysis/shardcheck.parse_hlo_module`` read 84 of the sparse step's
    27,755 instructions before its type took a layout's brackets."""
    text = ("HloModule jit_train_step\n\nENTRY %main.1 (p: f32[4]) -> "
            "f32[4] {\n" + TPU_LINES[name] + "\n}\n")
    table = scopes.parse(text)
    assert list(table) == [name]
    assert table.opcode[name] == name.rsplit(".", 1)[0]
    assert ("op_name" in TPU_LINES[name]) == bool(table[name])


def test_a_fusion_that_holds_a_product_is_a_product():
    text = TEXT.replace("f32[4]{0} tanh(%multiply.3)",
                        "f32[4]{0} convolution(%multiply.3, %multiply.3)")
    table = scopes.parse(text)
    # the convolution, both fusions of its computation; not the loop
    assert table.products == {"tanh.1", "fusion.7", "fusion.8"}


@pytest.mark.parametrize("op_name,want", [
    # the forms the hand joins met (jax 0.9.0), nn/remat.py's rebuild first
    ("jit(train_step)/jvp(b0_mix)/gdn:conv/mul",
     ("b0_mix", "gdn:conv", "fwd")),
    ("jit(train_step)/transpose(jvp(b0_mix))/jvp(gdn:conv)/jit(silu)/mul",
     ("b0_mix", "gdn:conv", "remat")),
    ("jit(train_step)/transpose(jvp(b0_mix))/transpose(jvp(gdn:conv))/mul",
     ("b0_mix", "gdn:conv", "bwd")),
    ("jit(train_step)/transpose(jvp(b0_mix))/jvp()/mul",
     ("b0_mix", None, "remat")),
    ("jit(train_step)/transpose(jvp(b0_mix))/transpose(jvp())/mul",
     ("b0_mix", None, "bwd")),
    ("jit(train_step)/transpose(jvp(b0_mix))/optimization_barrier",
     ("b0_mix", None, "bwd")),
    # a custom_vjp inside the rebuild: its backward, and its own rebuild
    ("jit(train_step)/transpose(jvp(b0_mix))/transpose(transpose(jvp(b0_mix)))"
     "/jvp(gdn:chunk_local)/transpose(jvp())/dot_general",
     ("b0_mix", "gdn:chunk_local", "bwd")),
    ("jit(train_step)/transpose(jvp(b0_mix))/transpose(transpose(jvp(b0_mix)))"
     "/jvp(gdn:chunk_local)/jvp()/dot_general",
     ("b0_mix", "gdn:chunk_local", "remat")),
    # a kernel's backward rule met inside the rebuild: the levels after the
    # one transposed twice are the forward's record, not a rebuild
    ("jit(train_step)/transpose(jvp(b0_ssm))/transpose(transpose(jvp(b0_ssm)))"
     "/jvp(ssm:scan)/transpose(transpose(jvp(b0_ssm)))/jvp(ssm:scan)/"
     "jvp(jit(_run_bwd))/selective_scan_bwd/pallas_call",
     ("b0_ssm", "ssm:scan", "bwd")),
    ("jit(train_step)/transpose(jvp(b0_ssm))/jvp(ssm:scan)/"
     "jvp(jit(_run_fwd))/selective_scan_fwd/pallas_call",
     ("b0_ssm", "ssm:scan", "remat")),
    ("jit(train_step)/transpose(jvp(b3_mix))/transpose(transpose(jvp(b3_mix)))"
     "/jvp(flash_attention_dkv)/pallas_call", ("b3_mix", None, "bwd")),
    ("jit(train_step)/transpose(jvp(b3_mix))/jvp(flash_attention_fwd)/"
     "pallas_call", ("b3_mix", None, "remat")),
    # the backward kernels of a call site whose pair the node kept (PR 38)
    # carry the names they had: no forward kernel stands before them
    ("jit(train_step)/transpose(jvp(b3_mix))/transpose(transpose(jvp(b3_mix)))"
     "/jvp(flash_attention_dq)/pallas_call", ("b3_mix", None, "bwd")),
    ("jit(train_step)/transpose(jvp(b0_mix))/transpose(transpose(jvp(b0_mix)))"
     "/jvp(flash_attention_dkv)/while/body/dynamic_slice",
     ("b0_mix", None, "bwd")),
    # a scope inside a loop's body, and the loop itself
    ("jit(step)/jvp(b0_mix)/closed_call/while/body/closed_call/"
     "gdn:chunk_scan/sin", ("b0_mix", "gdn:chunk_scan", "fwd")),
    ("jit(train_step)/transpose(jvp(b0_mix))/transpose(jvp(gdn:chunk_scan))"
     "/while/body/closed_call/bhck,bhvk->bhcv/dot_general",
     ("b0_mix", "gdn:chunk_scan", "bwd")),
    ("jit(train_step)/jvp(b0_mix)/gdn:chunk_scan/while",
     ("b0_mix", "gdn:chunk_scan", "fwd")),
    # jax.checkpoint's own forms: rebuilt under both, so tested first
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "b0_mix/gdn:conv/tanh", ("b0_mix", "gdn:conv", "remat")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/b0_mix/gdn:conv/mul",
     ("b0_mix", "gdn:conv", "bwd")),
    # the innermost scope wins; a conditional's branch is no node
    ("jit(train_step)/jvp(b1_idx)/dsa:index/cond/branch_1_fun/dsa:topk/"
     "while/body/lt", ("b1_idx", "dsa:topk", "fwd")),
    # the shell, with and without a scope
    ("jit(train_step)/train:update/sub", (None, "train:update", "fwd")),
    ("jit(train_step)/train:cast/convert_element_type",
     (None, "train:cast", "fwd")),
    ("jit(train_step)/jit(_where)/select_n", (None, None, "fwd")),
    ("jit(train_step)/transpose(jvp())/mul", (None, None, "bwd")),
    ("jit(train_step)/mul", (None, None, "fwd")),
    ("", (None, None, "fwd")),
    # merged instructions: the first speaks
    ("jit(train_step)/jvp(b0_mix)/gdn:chunk_local/broadcast_in_dim;"
     "jit(train_step)/jvp(b1_mix)/gdn:conv/mul",
     ("b0_mix", "gdn:chunk_local", "fwd")),
    # a node of the list container, and one whose name has a dot
    ("jit(train_step)/transpose(jvp(layer0))/dot_general",
     ("layer0", None, "bwd")),
    ("jit(train_step)/jvp(res2a.branch)/conv_general_dilated",
     ("res2a.branch", None, "fwd")),
])
def test_split(op_name, want):
    assert scopes.split(op_name) == want


@pytest.mark.parametrize("form", ["kept", "rebuilt_whole"])
def test_no_flash_forward_is_left_under_remat(registry, monkeypatch, form):
    """A node under remat keeps the flash kernel's output and logsumexp
    (``nn/remat.kept``), so its compiled step holds the forward kernel in
    the forward phase alone and the backward kernels under ``bwd``; the
    wrapper that rebuilt the node whole ran it again under ``remat``."""
    from deeplearning4j_tpu.nn import netcommon
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from remat_reference import rebuilt_whole
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    if form == "rebuilt_whole":
        monkeypatch.setattr(netcommon, "checkpoint_after_cotangent",
                            rebuilt_whole)
    g = (NeuralNetConfiguration.builder().seed(7).updater("nesterovs")
         .learning_rate(0.05).gradient_checkpointing().graph_builder()
         .add_inputs("in")
         .add_layer("b0_mix", SelfAttentionLayer(n_heads=2, head_dim=4,
                                                 causal=True), "in")
         .add_layer("out", RnnOutputLayer(n_out=C, activation="softmax"),
                    "b0_mix")
         .set_outputs("out").set_input_types(InputType.recurrent(F)))
    ComputationGraph(g.build()).init().fit(_data("tbptt"))
    phases = {part: {scopes.split(name) for name in
                     scopes.step_table(STEP).values()
                     if f"flash_attention_{part}" in name}
              for part in ("fwd", "dq", "dkv")}
    forward = {scopes.Scope("b0_mix", None, "fwd")}
    if form == "rebuilt_whole":
        forward.add(scopes.Scope("b0_mix", None, "remat"))
    assert phases == dict(fwd=forward,
                          dq={scopes.Scope("b0_mix", None, "bwd")},
                          dkv={scopes.Scope("b0_mix", None, "bwd")})


def test_by_scope_counts_no_time_twice_and_adds_up_to_the_busy_time():
    table = {
        "while.3": "jit(train_step)/jvp(b0_mix)/gdn:chunk_scan/while",
        "fusion.7": "jit(train_step)/jvp(b0_mix)/gdn:chunk_scan/while/body/"
                    "mul",
        "fusion.8": "jit(train_step)/train:update/sub",
        "custom-call.4": "jit(train_step)/transpose(jvp(b3_mix))/pallas_call",
        "copy.2": "",
    }
    us = 1000
    events = [
        # a loop of 100 us holding two turns of its body, 30 us each
        ["while.3", 0, 100 * us],
        ["fusion.7", 10 * us, 30 * us],
        ["fusion.7", 50 * us, 30 * us],
        # a gap of 20 us, then a kernel whose event carries its target
        ["custom-call.4 tpu_custom_call flash_attention_fwd", 120 * us,
         40 * us],
        ["fusion.8", 160 * us, 25 * us],
        ["copy.2", 185 * us, 5 * us],
        ["fusion.99", 190 * us, 10 * us],       # another program's
    ]
    scoped, unknown, unlabelled = scopes.by_scope(events, table)
    assert scoped == pytest.approx({
        ("b0_mix", "gdn:chunk_scan", "fwd"): 100e-6,   # 40 its own, 60 inside
        ("b3_mix", None, "bwd"): 40e-6,
        (None, "train:update", "fwd"): 25e-6})
    assert unknown == pytest.approx(10e-6)
    assert unlabelled == pytest.approx(5e-6)
    busy = 100 + 40 + 25 + 5 + 10               # the union of the intervals
    assert sum(scoped.values()) + unknown + unlabelled == pytest.approx(
        busy * 1e-6)
    own = scopes.self_seconds(events)
    assert own["while.3"] == pytest.approx(40e-6)
    assert own["fusion.7"] == pytest.approx(60e-6)
    # a child that runs past its container is held to it
    own = scopes.self_seconds([["a", 0, 10], ["b", 5, 10]])
    assert own == {"a": pytest.approx(5e-9), "b": pytest.approx(5e-9)}


def test_step_for_v5e_tells_the_same_program_under_other_labels(tmp_path):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "step_for_v5e", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "step_for_v5e.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    a, b, c = (str(tmp_path / n) for n in ("a.txt", "b.txt", "c.txt"))
    with open(a, "w") as f:
        f.write(TEXT)
    with open(b, "w") as f:     # other labels, another source line
        f.write(TEXT.replace("jit(train_step)/train:update/sub",
                             "jit(train_step)/sub")
                .replace("stack_frame_id=3", "stack_frame_id=4"))
    with open(c, "w") as f:     # another program
        f.write(TEXT.replace("tanh(", "exp("))
    assert tool.same(a, b) == 1
    assert tool.same(a, b, names=False) == 0
    assert tool.same(a, c, names=False) == 1
