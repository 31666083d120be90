"""What the SmallThinker cell adds to the benchmark: the parameter and
operation counts against numbers worked by hand, the readers of the two new
per-layer metrics on a trace and a table written by hand, the
configuration's published numbers, the manifest's new entries by name and
membership, and the cell's flow rehearsed on the CPU with its control and
its planted faults."""

import argparse
import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import compare, manifest, run, traffic
from benchmark.metrics import (
    flash_attention_roofline, train_step_mfu, window_attention_ms,
    window_attention_roofline)
from benchmark.reference import resnet50, smallthinker
from deeplearning4j_tpu.profiling import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "smallthinker_train_seq16k_1chip"
CONFIG = "smallthinker-21ba3b-instruct"


def _cfg(dry=False):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return traffic.with_dry(json.load(f), dry)


MIX = traffic.load(ROOT, "train_ids_seq16384_b1")

# ------------------------------------------------------------ operations

# W_q and W_o 2,560 x 3,584 each, W_k and W_v 2,560 x 512 each
ATTENTION = 2 * 9_175_040 + 2 * 1_310_720
ROUTER = 2560 * 64
EXPERT = 3 * 2560 * 768
HEAD = 18992 * 2560
# pairs a head: a full layer T (T + 1) / 2; a window layer 4,096 x 4,097 / 2
# while t < 4,096, then 4,096 each
FULL = 16384 * 16385 // 2
WINDOW = 4096 * 4097 // 2 + 12288 * 4096


def test_parameter_counts():
    """370.5 M: a layer's attention 20,971,520, its router 163,840, eight
    experts of 5,898,240 and two RMSNorms of 2,560; the embedding and the
    head 48,619,520 each and a last RMSNorm."""
    cfg = _cfg()
    layer = ATTENTION + ROUTER + 8 * EXPERT + 2 * 2560
    assert (ATTENTION, layer, HEAD) == (20_971_520, 68_326_400, 48_619_520)
    n = sum(int(np.prod(s)) for s in smallthinker.param_shapes(cfg).values())
    assert n == 4 * layer + 2 * HEAD + 2560 == 370_547_200
    assert smallthinker.held_experts(cfg) == (0, 8)


def test_train_flops_per_sample():
    """Forward, a sequence of 16,384 and a layer: 2 x 16,384 x (20,971,520
    + 163,840) of products, 1,536 held assignments (16,384 x 6 x 8 / 64) of
    2 x 5,898,240, and 28 heads of the layer's pairs, each a product over
    128 and a weight on 128; the head 2 x 16,384 x 48,619,520. Three times
    that with the backward."""
    cfg = _cfg()
    assert (FULL, WINDOW) == (134_225_920, 58_722_304)
    assert smallthinker.scores_seen(16384) == FULL
    assert smallthinker.scores_seen(16384, 4096) == WINDOW
    assert smallthinker.held_assignments(cfg, 16384) == 12288
    forward = (4 * (2 * 16384 * (ATTENTION + ROUTER) + 2 * 12288 * EXPERT)
               + 28 * (FULL + 3 * WINDOW) * 2 * 256 + 2 * 16384 * HEAD)
    assert smallthinker.trained_forward_flops(cfg, 16384) == forward
    assert smallthinker.train_flops_per_sample(cfg, MIX) == 3.0 * forward
    run_ = types.SimpleNamespace(
        measures={"samples": 30, "window_s": 20.0}, cfg=cfg, mix=MIX,
        chips=1, peaks={"flops_per_s": 197e12}, reference=smallthinker)
    assert train_step_mfu.read(run_) == pytest.approx(
        100 * 3 * forward * 1.5 / 197e12)


def test_attention_and_expert_costs():
    """The scores inside each layer's mask, whatever a kernel pads or skips;
    q 3,584, k and v 512 each and the output 3,584 numbers a token forward,
    those and the cotangent read and three gradients written backward:
    22,016 numbers a token a layer, bfloat16. The window layers' own share
    is three of the four layers' bytes and their pairs alone. ONE layer's
    experts: 12,288 rows through three products at the uniform router's
    load."""
    cfg = _cfg()
    token = 8192 + 11776 + 4608
    whole = smallthinker.flash_attention_cost(cfg, MIX)
    assert whole["flops"] == 3.0 * 28 * (FULL + 3 * WINDOW) * 2 * 256
    assert whole["bytes"] == 4 * 16384 * 2 * token
    window = smallthinker.window_attention_cost(cfg, MIX)
    assert window["flops"] == 3.0 * 28 * 3 * WINDOW * 2 * 256
    assert window["bytes"] == 3 * 16384 * 2 * token
    moe = smallthinker.moe_expert_cost(cfg, MIX)
    assert moe["flops"] == 3.0 * 12288 * 3 * 2 * 2560 * 768
    assert moe["bytes"] == 2 * (3 * 8 * EXPERT + 12288 * 6656 * 3)


# ------------------------------------------------- readers, by hand

J = "jit(train_step)/"
US = 1000
# one step's instructions: name -> (op_name, microseconds)
STEP = {
    "flash_attention_fwd.1": (J + "jvp(b0_mix)/flash_attention_fwd/"
                              "pallas_call", 300),
    "flash_attention_fwd.2": (J + "jvp(b1_mix)/attn:window/"
                              "flash_attention_fwd/pallas_call", 100),
    "jvp_flash_attention_dkv_.3": (
        J + "transpose(jvp(b1_mix))/transpose(transpose(jvp(b1_mix)))/"
        "jvp(attn:window)/flash_attention_dkv/pallas_call", 200),
    "fusion.4": (J + "transpose(jvp(b1_mix))/jvp(attn:window)/pad", 40),
    "fusion.5": (J + "jvp(b1_mix)/attn:rope/mul", 60),
    "fusion.6": (J + "train:update/add", 80),
}


def _run(step=STEP, steps=2, reference=smallthinker):
    """``steps`` whole steps of ``step``'s instructions one after another
    and a further one cut by the window's end, with its table."""
    table = scopes.StepTable()
    for name, (op_name, _) in step.items():
        table[name] = op_name
        table.opcode[name] = name.split(".")[0]
    ops, modules, t = [], [], 1000
    for i in range(steps + 1):
        start = t
        for name, (_, us) in step.items():
            ops.append([name, t, us * US])
            t += us * US
        modules.append([f"jit_train_step({i})", start, t - start])
        t += 50 * US
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ["bench:window", 0, modules[-1][1] + 100 * US]]}]}]}
    return types.SimpleNamespace(
        trace=tr, measures={}, cfg=_cfg(), mix=MIX, chips=1,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        reference=reference, step_table=table)


def test_window_attention_ms_and_roofline():
    run_ = _run()
    # everything under attn:window: two kernels and the pad round them
    assert window_attention_ms.read(run_) == pytest.approx(
        (100 + 200 + 40) / 1e3)
    cost = smallthinker.window_attention_cost(_cfg(), MIX)
    least_ms = 1e3 * max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    # the kernels alone, not the pad, and not the full layer's kernel
    assert window_attention_roofline.read(run_) == pytest.approx(
        100 * least_ms / 0.3)
    # the accepted roofline reads every flash kernel against every layer's
    whole = smallthinker.flash_attention_cost(_cfg(), MIX)
    assert flash_attention_roofline.read(run_) == pytest.approx(
        100 * 1e3 * max(whole["flops"] / 197e12, whole["bytes"] / 819e9)
        / 0.6)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the window's scope (one from before it), a trace
    without a whole step, a reference without the window's cost: None, and
    no exception."""
    bare = {k: v for k, v in STEP.items() if "attn:window" not in v[0]}
    for run_ in (_run(bare), _run(steps=0)):
        assert window_attention_ms.read(run_) is None
        assert window_attention_roofline.read(run_) is None
    assert window_attention_roofline.read(_run(reference=resnet50)) is None
    no_table = _run()
    no_table.step_table = None
    scopes.clear()
    assert window_attention_ms.read(no_table) is None


# -------------------------------------------------------------- manifest

SHARED = ["train_compiles_in_window", "train_step_gap_share",
          "train_step_mfu", "train_step_device_ms", "flash_attention_ms",
          "flash_attention_roofline", "kernel_gate_fallbacks",
          "train_tokens_per_s", "moe_layout_ms", "step_scope_coverage",
          "optimizer_update_ms", "attn_rope_ms"]
NEW = {"window_attention_ms": ("ms", "lower"),
       "window_attention_roofline": ("%", "higher")}


def test_manifest_has_the_configuration_the_cell_and_its_metrics():
    """Names and membership, not counts or last places: a later change
    appends to the same lists."""
    m = manifest.load(ROOT)
    assert manifest.problems(m, ROOT) == []
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_ids_seq16384_b1", 1)
    assert len(cell["why"]) <= 200
    entry = manifest.config_entry(m, CONFIG)
    assert entry["reduced"] == _cfg()["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == _cfg()["source"]
    st = {p["name"] for p in manifest.metrics_of(m, "per_layer", CELL)}
    assert set(SHARED) <= st
    assert {e["name"] for e in manifest.metrics_of(m, "end_to_end", CELL)} \
        == {"train_samples_per_s_chip", "setup_s"}
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name, (unit, better) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "kernels",
            "moves": "train_samples_per_s_chip", "workloads": [CELL]}
    # what reads another model's mechanism keeps its own cells
    for name in ("sparse_topk_ms", "sparse_select_ms", "delta_rule_scan_ms",
                 "selective_scan_ms"):
        assert name not in st
    with open(os.path.join(ROOT, "benchmark", "limits",
                           CELL + ".json")) as f:
        limits = json.load(f)
    numbers = {k for k in limits if not k.startswith("_") and k != "dry_cpu"}
    assert numbers and numbers == set(limits["_why"])


def test_configuration_keeps_the_published_numbers():
    """Every number of the catalog's row under its own key and the layouts
    whole; the three that are reduced give what is held here, with the
    published value beside; what the row does not give is listed under
    ``assumed``."""
    cfg = _cfg()
    period = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": period, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": period,
        "sliding_window_size": 4096, "tie_word_embeddings": False}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 8, 18992)
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts_routed"] == 64
    assert cfg["held_experts"]["count"] * cfg["held_experts"]["chips"] == 64
    for key in ("block", "attention", "positions", "window", "router",
                "experts", "dtype", "updater", "remat", "weights", "inputs"):
        assert key in cfg["assumed"], key
    dry = _cfg(True)
    assert (dry["hidden_size"], dry["num_experts_routed"],
            dry["moe_num_primary_experts"], dry["sliding_window_size"],
            dry["vocab_size"]) == (64, 8, 4, 8, 64)


def test_the_programs_builder_reads_the_files_keys():
    from benchmark import program
    cfg = _cfg(True)
    net = program.build_net(cfg, smallthinker.make_weights(cfg, 1))
    moe = net.conf.nodes["b0_moe"].layer
    assert (moe.n_experts, moe.first, moe.count, moe.top_k, moe.n_hidden,
            moe.activation, moe.route_from_side) == (
        8, 2, 4, 2, 32, "relu", True)
    layers = [net.conf.nodes[f"b{i}_mix"].layer for i in range(4)]
    assert [(x.window, x.rotate) for x in layers] == [
        (None, False), (8, True), (8, True), (8, True)]
    assert layers[1].rope_theta == 1500000 and not layers[1].qk_norm


# -------------------------------------------------------- the cell, rehearsed

def test_the_cells_flow_on_the_cpu_with_its_control_and_faults(monkeypatch):
    """The entry run in this process at the ``dry_cpu`` sizes, as
    ``tools/readings.py`` runs it: the program is correct under the file's
    dry limits, and the control in the precision below and the half
    sequence are not; the mechanisms' three faults, read against the
    reference as ``tools/planted_faults.py`` reads them, are not either."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    from deeplearning4j_tpu.profiling import CompileWatcher
    CompileWatcher().install()
    cfg = _cfg(True)
    mix = traffic.load(ROOT, "train_ids_seq16384_b1", True)
    ns = argparse.Namespace(seed=2**31 + 9, seconds=0.5, trace=0,
                            dry_cpu=True)
    ctx = run.Context(ns, {"name": CELL, "chips": 1}, cfg, mix, smallthinker)
    ctx.extra = ("control", "half_batch")
    entry = importlib.import_module("benchmark.entries.train_ids")
    out = entry.run(ctx)
    limits = compare.load_limits(ROOT, CELL, dry=True)
    ok, compared = compare.decide(out["numbers"], limits)
    assert ok and out["failed"] == 0 and out["attempted"] > 0, compared
    assert out["measures"]["compiles_in_window"] == 0
    for name in ("control", "half_batch"):
        bad, compared = compare.decide(out["extras"][name], limits)
        assert not bad, (name, compared)
    batches = entry.id_batches(cfg, mix, ctx.seed)[:mix["check_steps"]]
    ref = smallthinker.train_steps(
        cfg, smallthinker.make_weights(cfg, ctx.seed), batches)
    for fault in smallthinker.FAULTS:
        bad = smallthinker.train_steps(
            cfg, smallthinker.make_weights(cfg, ctx.seed), batches,
            fault=fault)
        ok, compared = compare.decide(compare.training_numbers(bad, ref),
                                      limits)
        assert not ok, (fault, compared)
