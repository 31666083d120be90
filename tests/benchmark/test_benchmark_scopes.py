"""The ten readers of a step's device time by the program's own scopes
(``benchmark/metrics/step_scopes.py``), each on a synthetic trace and table;
what they give with an empty registry and with a stale table; and their
entries in the manifest, by name and membership."""

import importlib
import os
import types

import pytest

from benchmark import manifest, trace
from benchmark.metrics import step_scopes
from deeplearning4j_tpu.profiling import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RESNET, OLMO, PHI4, KEYE = (
    "resnet50_train_1chip", "olmo_hybrid_train_seq8k_1chip",
    "phi4_flash_train_seq8k_1chip", "keye_vl2_train_seq8k_1chip")
# name -> (unit, better, layer, cells)
TEN = {
    "step_scope_coverage": ("%", "higher", "train_step_program",
                            [RESNET, OLMO, PHI4, KEYE]),
    "optimizer_update_ms": ("ms", "lower", "train_step_program",
                            [OLMO, PHI4, KEYE]),
    "moe_layout_ms": ("ms", "lower", "experts", [KEYE]),
    "sparse_select_ms": ("ms", "lower", "sparse_attention", [KEYE]),
    "attn_rope_ms": ("ms", "lower", "sparse_attention", [KEYE]),
    "gdn_elementwise_ms": ("ms", "lower", "linear_attention", [OLMO]),
    "gdn_relayout_ms": ("ms", "lower", "linear_attention", [OLMO]),
    "ssm_elementwise_ms": ("ms", "lower", "state_space", [PHI4]),
    "ssm_scan_outside_ms": ("ms", "lower", "state_space", [PHI4]),
    "conv_backward_ms": ("ms", "lower", "train_step_program", [RESNET]),
}

J = "jit(train_step)/"
# one step's instructions: name -> (op_name, HLO opcode, microseconds)
STEP = {
    "fusion.1": (J + "train:cast/convert_element_type", "fusion", 10),
    "fusion.2": (J + "jvp(b0_mix)/gdn:conv/mul", "fusion", 20),
    "fusion.3": (J + "transpose(jvp(b0_mix))/jvp(gdn:gate_norm)/mul",
                 "fusion", 30),
    "fusion.4": (J + "jvp(b0_mix)/gdn:chunk_local/transpose", "fusion", 40),
    "custom-call.5": (J + "jvp(b0_mix)/gdn:chunk_local/jit(_run_fwd)/"
                      "gdn_chunk_local_fwd/pallas_call", "custom-call", 50),
    "while.6": (J + "jvp(b0_mix)/gdn:chunk_scan/while", "while", 100),
    "fusion.7": (J + "jvp(b0_mix)/gdn:chunk_scan/while/body/mul", "fusion",
                 60),                               # inside while.6
    "fusion.8": (J + "jvp(b0_ssm)/ssm:in_conv/mul", "fusion", 70),
    "fusion.9": (J + "transpose(jvp(b0_ssm))/transpose(jvp(ssm:dt_bc))/mul",
                 "fusion", 80),
    "custom-call.10": (J + "jvp(b0_ssm)/ssm:scan/jit(_run_fwd)/"
                       "selective_scan_fwd/pallas_call", "custom-call", 90),
    "fusion.11": (J + "jvp(b0_ssm)/ssm:scan/mul", "fusion", 15),
    "fusion.12": (J + "jvp(b0_moe)/moe:dispatch/gather", "fusion", 25),
    "fusion.13": (J + "transpose(jvp(b0_moe))/jvp(moe:combine)/mul",
                  "fusion", 35),
    "fusion.14": (J + "jvp(b0_moe)/moe:experts/mul", "fusion", 45),
    "fusion.15": (J + "jvp(b0_index)/dsa:index/dot_general", "fusion", 55),
    "fusion.16": (J + "jvp(b0_index)/dsa:index/cond/branch_1_fun/dsa:topk/"
                  "while/body/lt", "fusion", 65),
    "fusion.17": (J + "jvp(b0_mix)/attn:rope/mul", "fusion", 75),
    "fusion.18": (J + "transpose(jvp(s0b0_a_conv))/conv_general_dilated",
                  "fusion", 85),
    "fusion.19": (J + "jvp(s0b0_a_conv)/conv_general_dilated", "fusion", 95),
    "fusion.20": (J + "train:update/add", "fusion", 105),
    "copy.21": ("", "copy", 5),
    # matrix products inside the elementwise scopes: left out of those two
    "fusion.22": (J + "jvp(b0_mix)/gdn:conv/dot_general", "fusion", 110),
    "convolution.23": (J + "transpose(jvp(b0_ssm))/transpose(jvp("
                       "ssm:in_conv))/dot_general", "convolution", 120),
}
PRODUCTS = {"fusion.22", "convolution.23"}
US = 1000
WANT_US = {                         # microseconds a step, read off STEP
    "optimizer_update_ms": 10 + 105,
    "moe_layout_ms": 25 + 35,
    "sparse_select_ms": 55 + 65,
    "attn_rope_ms": 75,
    "gdn_elementwise_ms": 20 + 30,  # not fusion.22, a product
    "gdn_relayout_ms": 40,          # the scope less its custom call
    "ssm_elementwise_ms": 70 + 80,  # not convolution.23
    "ssm_scan_outside_ms": 15,      # the scope less its custom call
    "conv_backward_ms": 85,
}


def _table(step=STEP):
    table = scopes.StepTable()
    for name, (op_name, opcode, _) in step.items():
        table[name] = op_name
        table.opcode[name] = opcode
    table.products.update(PRODUCTS & set(step))
    return table


def _run(table, extra=(), steps=2):
    """``steps`` whole steps of STEP's instructions one after another, the
    loop's body inside the loop, and a further step cut by the window's
    end; ``extra`` names run once more in every step."""
    ops, modules, t = [], [], 1000
    for i in range(steps + 1):
        start = t
        for name, (_, _, us) in STEP.items():
            if name == "fusion.7":
                continue
            ops.append([name, t, us * US])
            if name == "while.6":
                ops.append(["fusion.7", t + 10 * US, 60 * US])
            t += us * US
        for name in extra:
            ops.append([name, t, 400 * US])
            t += 400 * US
        modules.append([f"jit_train_step({i})", start, t - start])
        t += 50 * US
    window = modules[-1][1] + 100 * US      # cuts the last step
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.OPS_LINE, "events": ops},
            {"name": trace.MODULES_LINE, "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench:window", 0, window]]}]}]}
    return types.SimpleNamespace(trace=tr, measures={}, step_table=table)


def _reader(name):
    return importlib.import_module("benchmark.metrics." + name)


@pytest.mark.parametrize("name", sorted(WANT_US))
def test_a_scopes_milliseconds_a_step(name):
    assert _reader(name).read(_run(_table())) == pytest.approx(
        WANT_US[name] / 1e3)


def test_coverage_is_what_a_node_or_a_scope_names():
    step_us = sum(us for n, (_, _, us) in STEP.items() if n != "fusion.7")
    assert _reader("step_scope_coverage").read(_run(_table())) == \
        pytest.approx(100 * (step_us - 5) / step_us)
    read = step_scopes.steps(_run(_table()))
    assert read.steps == 2 and read.unknown == 0
    assert read.unlabelled == pytest.approx(2 * 5e-6)
    # the sum is the steps' busy time: nothing counted twice
    assert sum(read.seconds.values()) == pytest.approx(2 * step_us * 1e-6)


@pytest.mark.parametrize("name", sorted(TEN))
def test_nothing_to_read_reads_none(name):
    read = _reader(name).read
    scopes.clear()
    # an empty registry: a program that kept no step
    run = _run(None)
    assert read(run) is None
    # a stale table: over a hundredth of the steps under names it lacks
    assert read(_run(_table(), extra=["fusion.99"])) is None
    # no whole step in the trace
    run = _run(_table(), steps=0)
    assert read(run) is None
    if name != "step_scope_coverage":
        # a program without the scope reads nothing, not nought
        bare = {k: ("", code, us) for k, (_, code, us) in STEP.items()}
        assert step_scopes.steps(_run(_table(bare))) is not None
        assert read(_run(_table(bare))) is None


def test_a_program_from_before_the_registry_reads_none(monkeypatch):
    monkeypatch.setattr(step_scopes, "_scopes", lambda: None)
    for name in TEN:
        assert _reader(name).read(_run(_table())) is None


def test_a_little_unknown_time_is_tolerated_and_counted():
    table = _table()
    run = _run(table)
    run.trace["planes"][0]["lines"][0]["events"].append(
        ["fusion.99", 1000 + 5 * US, 2 * US])       # inside fusion.1's time
    read = step_scopes.steps(run)
    assert read.unknown == pytest.approx(2e-6)
    assert _reader("optimizer_update_ms").read(run) == pytest.approx(
        (115 - 1) / 1e3)                # fusion.1 less what ran inside it


def test_the_registrys_newest_step_is_the_table():
    scopes.clear()
    try:
        with scopes._lock:
            scopes._kept["jit_step"] = scopes.StepTable()
            scopes._kept["jit_train_step"] = _table(
                {"copy.21": STEP["copy.21"]})
            scopes._kept["jit_train_step"] = _table()   # takes its place
        run = _run(None)
        assert _reader("attn_rope_ms").read(run) == pytest.approx(0.075)
    finally:
        scopes.clear()


def test_the_loops_time_is_counted_once_under_its_scope():
    """What the retired twin of ``delta_rule_scan_ms`` read: the loop's own
    time and its body's, once."""
    assert step_scopes.scope_ms(_run(_table()), "gdn:chunk_scan") == \
        pytest.approx(0.100)


def test_update_work_fused_into_a_neighbour_goes_to_the_neighbour():
    """A fusion goes whole to its own ``op_name``: an update that XLA fused
    into a weight-gradient convolution reads as the convolution's, which is
    why ``optimizer_update_ms`` lists no cell of ResNet-50's."""
    step = dict(STEP)
    step["fusion.20"] = (J + "transpose(jvp(s0b0_a_conv))/"
                         "conv_general_dilated", "fusion", 105)
    run = _run(_table(step))
    assert _reader("optimizer_update_ms").read(run) == pytest.approx(0.010)
    assert _reader("conv_backward_ms").read(run) == pytest.approx(
        (85 + 105) / 1e3)
    m = manifest.load(ROOT)
    assert RESNET not in next(p for p in m["per_layer"] if p["name"] ==
                              "optimizer_update_ms")["workloads"]


def test_manifest_holds_the_ten_by_name_and_membership():
    m = manifest.load(ROOT)
    assert manifest.problems(m, ROOT) == []
    per_layer = {p["name"]: p for p in m["per_layer"]}
    layers = {p["layer"] for p in m["per_layer"] if p["name"] not in TEN}
    for name, (unit, better, layer, cells) in TEN.items():
        entry = per_layer[name]
        assert (entry["unit"], entry["better"], entry["layer"]) == (
            unit, better, layer), name
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_samples_per_s_chip"
        assert set(cells) <= set(entry["workloads"]), name
        assert layer in layers          # a layer the benchmark already names
        assert callable(_reader(name).read)
        for cell in cells:
            assert entry in manifest.metrics_of(m, "per_layer", cell)
    # the readers by kind stay until a benchmark issue retires them, and
    # the loops' has no twin by scope beside it
    assert {"delta_rule_scan_ms", "selective_scan_ms",
            "sparse_topk_ms"} <= set(per_layer)
    assert "gdn_chunk_scan_ms" not in per_layer
