"""What the sparse-attention mixture-of-experts cell adds to the benchmark:
the parameter and operation counts against numbers worked by hand, the
readers of the three new per-layer metrics on a trace written by hand, the
configuration's published numbers, the manifest's new entries by name, and
the cell's flow rehearsed on the CPU with its control and its planted
fault."""

import argparse
import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import compare, manifest, run, traffic
from benchmark.metrics import (
    flash_attention_roofline, moe_expert_ms, moe_expert_roofline,
    sparse_topk_ms, train_step_mfu)
from benchmark.reference import keye_vl2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye_vl2_train_seq8k_1chip"
CONFIG = "keye-vl-2.0-30b-a3b"


def _cfg(dry=False):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return traffic.with_dry(json.load(f), dry)


MIX = traffic.load(ROOT, "train_ids_seq8192_b1")

# ------------------------------------------------------------ operations

# W_q and W_o 2,048 x 4,096 each, W_k and W_v 2,048 x 512 each
ATTENTION = 2 * 8_388_608 + 2 * 1_048_576
# W_qI 2,048 x 1,024, W_kI 2,048 x 64, W_w 2,048 x 16
INDEXER = 2_097_152 + 131_072 + 32_768
ROUTER = 2048 * 128
EXPERT = 3 * 2048 * 768
HEAD = 18992 * 2048


def test_parameter_counts():
    """853.0 M: a layer's attention 18,874,368 and two gains of 128, its
    indexer 2,260,992 and a gain and a shift of 64, its router 262,144,
    sixteen experts of 4,718,592, two RMSNorms of 2,048; the embedding and
    the head 38,895,616 each and a last RMSNorm."""
    cfg = _cfg()
    assert (ATTENTION, INDEXER, ROUTER, EXPERT, HEAD) == (
        18_874_368, 2_260_992, 262_144, 4_718_592, 38_895_616)
    layer = ATTENTION + 256 + INDEXER + 128 + ROUTER + 16 * EXPERT + 4096
    assert layer == 96_899_456
    n = sum(int(np.prod(s)) for s in keye_vl2.param_shapes(cfg).values())
    assert n == 8 * layer + 2 * HEAD + 2048 == 852_988_928
    frozen = sum(int(np.prod(s)) for k, s in keye_vl2.param_shapes(
        cfg).items() if not keye_vl2.trained(k))
    assert frozen == 8 * (INDEXER + 128)
    assert keye_vl2.held_experts(cfg) == (0, 16)


# selected pairs a head: 2,048 x 2,049 / 2 while t < 2,048, then 2,048 each
SELECTED = 2_098_176 + 6_144 * 2_048
CAUSAL = 8192 * 8193 // 2


def test_train_flops_per_sample():
    """Forward, a sequence of 8,192 and a layer: 2 x 8,192 x (18,874,368 +
    262,144) of products, 8,192 held assignments (8,192 x 8 x 16 / 128) of
    2 x 4,718,592, and 32 heads of 14,681,088 selected pairs, each a
    product over 128 and a weight on 128; the head 2 x 8,192 x 38,895,616.
    Three times that with the backward. The frozen indexer forward alone:
    its products and 16 heads of 33,558,528 pairs, a product over 64 and a
    weight each. The rebuilt forward is not counted."""
    cfg = _cfg()
    assert (keye_vl2.scores_seen(8192, 2048), keye_vl2.scores_seen(8192),
            keye_vl2.scores_seen(100, 2048)) == (SELECTED, CAUSAL, 5050)
    assert SELECTED == 14_681_088
    assert keye_vl2.held_assignments(cfg, 8192) == 8192
    layer = (2 * 8192 * (ATTENTION + ROUTER) + 2 * 8192 * EXPERT
             + 32 * SELECTED * 2 * 256)
    forward = 8 * layer + 2 * 8192 * HEAD
    assert keye_vl2.trained_forward_flops(cfg, 8192) == forward
    indexer = 8 * (2 * 8192 * INDEXER + 16 * CAUSAL * 130)
    assert keye_vl2.indexer_forward_flops(cfg, 8192) == indexer
    assert keye_vl2.train_flops_per_sample(cfg, MIX) == 3.0 * forward \
        + indexer
    run_ = types.SimpleNamespace(
        measures={"samples": 30, "window_s": 20.0}, cfg=cfg, mix=MIX,
        chips=1, peaks={"flops_per_s": 197e12}, reference=keye_vl2)
    assert train_step_mfu.read(run_) == pytest.approx(
        100 * (3 * forward + indexer) * 1.5 / 197e12)


def test_flash_attention_and_expert_costs():
    """The selected scores whatever a kernel pads, skips or rebuilds; q
    4,096, k and v 512 each and the output 4,096 numbers a token forward,
    those and the cotangent read and three gradients written backward:
    27,648 numbers a token a layer, bfloat16, eight layers. ONE layer's
    experts: 8,192 rows through three products, forward and twice
    backward; sixteen experts' matrices read twice and their gradients
    written, a row's 2 x 2,048 + 2 x 768 numbers forward and twice that
    backward."""
    cfg = _cfg()
    cost = keye_vl2.flash_attention_cost(cfg, MIX)
    assert cost["flops"] == 3.0 * 8 * 32 * SELECTED * 2 * 256
    assert cost["bytes"] == 8 * 8192 * 2 * (9216 + 13312 + 5120)
    moe = keye_vl2.moe_expert_cost(cfg, MIX)
    assert moe["flops"] == 3.0 * 8192 * 3 * 2 * 2048 * 768
    assert moe["bytes"] == 2 * (3 * 16 * EXPERT + 8192 * 5632 * 3)
    # the operations bound it, 1.18 ms a layer at the peak; the bytes, most
    # of them the experts' matrices read twice and their gradients, 0.89
    assert moe["flops"] / 197e12 == pytest.approx(1.1773e-3, rel=1e-4)
    assert moe["bytes"] / 819e9 == pytest.approx(0.8911e-3, rel=1e-4)


# ------------------------------------------------- readers, by hand

def _call(name, t, d):
    return [f"%{name}.{t} = bf16[1] custom-call(), custom_call_target="
            f'"tpu_custom_call", op_name="jit(train_step)/b0_moe/{name}"',
            t, d]


# One device; a window of 10,000 ns holding two whole steps, [1000, 4000)
# and [5000, 8000), and a third cut by the window's end. Step 1: grouped
# products 200 + 100 and a metadata call 20, sorts 300 + 50; step 2: 300 +
# 300 + 40 and 500 + 70. What lies between the steps, the cut step's, a
# fusion and a flash kernel are left out.
OPS = [["fusion.1", 1000, 100], ["ragged-dot-none.3", 1200, 200],
       ["ragged-dot-metadata", 1150, 20], ["ragged-dot-none", 1500, 100],
       ["sort.4", 2000, 300], ["sort", 2400, 50],
       ["sort.4", 4500, 100], ["ragged-dot-none.3", 4600, 100],
       ["ragged-dot-none.3", 5100, 300], ["ragged-dot-none", 5500, 300],
       ["ragged-dot-metadata", 5050, 40], ["sort.4", 6000, 500],
       ["sort", 6600, 70], ["sort_fusion", 6700, 10],
       ["ragged-dot-none.3", 9100, 300], ["sort.4", 9500, 100]]


def _run(ops, steps=((1000, 3000), (5000, 3000), (9000, 3000)),
         reference=keye_vl2, cfg=None):
    from benchmark import trace
    t = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                [trace.short_name(e[0]), e[1], e[2]] for e in ops]},
            {"name": "XLA Modules", "events": [
                ["jit_train_step(1)", t0, d] for t0, d in steps]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench:window", 0, 10000]]}]}]}
    return types.SimpleNamespace(
        trace=t, measures={}, cfg=cfg or _cfg(), mix=MIX, chips=1,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        reference=reference)


def test_moe_expert_ms_roofline_and_sparse_topk_ms():
    run_ = _run(OPS)
    assert moe_expert_ms.read(run_) == pytest.approx((320 + 640) / 2 * 1e-6)
    assert sparse_topk_ms.read(run_) == pytest.approx((350 + 570) / 2 * 1e-6)
    moe = keye_vl2.moe_expert_cost(_cfg(), MIX)
    least_ms = 1e3 * 8 * moe["flops"] / 197e12
    assert moe_expert_roofline.read(run_) == pytest.approx(
        100 * least_ms / 480e-6)


def test_experts_at_their_operations_time_read_one_hundred():
    moe = keye_vl2.moe_expert_cost(_cfg(), MIX)
    each = round(8 * moe["flops"] / 197e12 * 1e9 / 8)
    ops = [[f"ragged-dot-none.{i}", 1000 + i * (each + 10), each]
           for i in range(8)]
    run_ = _run(ops, steps=((1000, 10 * each),))
    run_.trace["planes"][1]["lines"][0]["events"] = [
        ["bench:window", 0, 12 * each]]
    assert moe_expert_roofline.read(run_) == pytest.approx(100, rel=1e-4)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the experts and the selection (the parent of the
    PR that brought them), a trace without a whole step, and a reference
    that counts no expert: None, and no exception."""
    bare = [["fusion.1", 1000, 100], ["while.3", 1200, 200]]
    for run_ in (_run(bare), _run(OPS, steps=())):
        assert moe_expert_ms.read(run_) is None
        assert moe_expert_roofline.read(run_) is None
        assert sparse_topk_ms.read(run_) is None
    from benchmark.reference import olmo_hybrid
    assert moe_expert_roofline.read(_run(OPS, reference=olmo_hybrid)) is None


def test_the_accepted_flash_roofline_reads_this_references_cost():
    kernel = lambda name, t, d: [
        f'%custom-call.{t} = bf16[1] custom-call(), op_name="jit(train_step)'
        f'/{name}", backend_config={{kernel_name: "{name}"}}', t, d]
    run_ = _run([kernel("flash_attention_fwd", 1100, 300),
                 kernel("flash_attention_dkv", 5100, 500)])
    cost = keye_vl2.flash_attention_cost(_cfg(), MIX)
    assert flash_attention_roofline.read(run_) == pytest.approx(
        100 * 1e3 * cost["flops"] / 197e12 / 400e-6)


# -------------------------------------------------------------- manifest

SHARED = ["train_compiles_in_window", "train_step_gap_share",
          "train_step_mfu", "train_step_device_ms", "flash_attention_ms",
          "flash_attention_roofline", "kernel_gate_fallbacks",
          "train_tokens_per_s"]
NEW = {"moe_expert_ms": ("ms", "lower", "experts"),
       "moe_expert_roofline": ("%", "higher", "experts"),
       "sparse_topk_ms": ("ms", "lower", "sparse_attention")}


def test_manifest_has_the_configuration_the_cell_and_its_metrics():
    """Names and membership, not counts or last places: a later PR appends
    to the same lists."""
    m = manifest.load(ROOT)
    assert manifest.problems(m, ROOT) == []
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_ids_seq8192_b1", 1)
    assert len(cell["why"]) <= 200
    entry = manifest.config_entry(m, CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == _cfg()["source"]
    per_layer = {p["name"]: p for p in manifest.metrics_of(
        m, "per_layer", CELL)}
    assert set(SHARED) | set(NEW) <= set(per_layer)
    for name in SHARED:
        for other in ("olmo_hybrid_train_seq8k_1chip",
                      "phi4_flash_train_seq8k_1chip", CELL):
            assert other in per_layer[name]["workloads"], (name, other)
    for name, (unit, better, layer) in NEW.items():
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "train_samples_per_s_chip", "workloads": [CELL]}
    assert {e["name"] for e in manifest.metrics_of(m, "end_to_end", CELL)} \
        == {"train_samples_per_s_chip", "setup_s"}
    # what reads another model's mechanism keeps its own cells
    for name in ("delta_rule_scan_ms", "gdn_chunk_local_ms",
                 "selective_scan_ms", "selective_scan_roofline"):
        assert name not in per_layer
    with open(os.path.join(ROOT, "benchmark", "limits",
                           CELL + ".json")) as f:
        limits = json.load(f)
    numbers = {k for k in limits if not k.startswith("_") and k != "dry_cpu"}
    assert numbers and numbers == set(limits["_why"])


def test_configuration_keeps_the_published_numbers():
    """Every number of the catalog's row under its own key and the nested
    groups whole; the three that are reduced give what is held here, with
    the published value beside; what the row does not give is listed under
    ``assumed``."""
    cfg = _cfg()
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 16, 18992)
    # the source's twin of num_experts is not reduced, so it does not change
    assert cfg["num_local_experts"] == 128
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    # the router keeps its 128 outputs; the held range is stated
    assert cfg["num_experts_routed"] == cfg["published"]["num_experts"]
    assert cfg["held_experts"]["first"] == cfg["first_expert"] == 0
    assert cfg["held_experts"]["count"] == cfg["num_experts"]
    assert cfg["held_experts"]["chips"] * cfg["num_experts"] == 128
    for key in ("block", "attention", "positions", "indexer", "chunks",
                "frozen_indexer", "experts", "dtype", "updater", "remat",
                "weights", "inputs"):
        assert key in cfg["assumed"], key
    assert "alignment loss" in cfg["assumed"]["frozen_indexer"]
    dry = _cfg(True)
    assert (dry["hidden_size"], dry["num_attention_heads"],
            dry["num_key_value_heads"], dry["head_dim"],
            dry["num_experts_routed"], dry["num_experts"],
            dry["first_expert"], dry["sa_config"]["topk"],
            dry["vocab_size"]) == (64, 4, 2, 16, 8, 4, 2, 24, 64)


def test_the_programs_builder_reads_the_files_keys():
    """The builder's arguments come from the file's own keys, so the
    reference and the program read one configuration."""
    from benchmark import program
    cfg = _cfg(True)
    net = program.build_net(cfg, keye_vl2.make_weights(cfg, 1))
    moe = net.conf.nodes["b0_moe"].layer
    assert (moe.n_experts, moe.first, moe.count, moe.top_k, moe.n_hidden) \
        == (8, 2, 4, 2, 32)
    idx = net.conf.nodes["b1_index"].layer
    assert (idx.n_heads, idx.head_dim, idx.topk, idx.query_chunk,
            idx.frozen) == (2, 8, 24, 32, True)
    mix = net.conf.nodes["b1_mix"].layer
    assert (mix.n_heads, mix.n_kv_heads, mix.head_dim, mix.rope_theta) == (
        4, 2, 16, 10000000)


# ------------------------------------------------------- the cell, rehearsed

def test_the_cells_flow_on_the_cpu_with_its_control_and_fault(monkeypatch):
    """The entry run in this process at the ``dry_cpu`` sizes, as
    ``tools/readings.py`` runs it: the program is correct under the file's
    dry limits, and the control in the precision below and the half
    sequence are not."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    from deeplearning4j_tpu.profiling import CompileWatcher
    CompileWatcher().install()
    cfg = _cfg(True)
    mix = traffic.load(ROOT, "train_ids_seq8192_b1", True)
    ns = argparse.Namespace(seed=2**31 + 5, seconds=0.5, trace=0,
                            dry_cpu=True)
    ctx = run.Context(ns, {"name": CELL, "chips": 1}, cfg, mix, keye_vl2)
    ctx.extra = ("control", "half_batch")
    entry = importlib.import_module("benchmark.entries.train_ids")
    fallbacks = entry.gate_fallbacks()      # the process's, tests before us
    out = entry.run(ctx)
    limits = compare.load_limits(ROOT, CELL, dry=True)
    ok, compared = compare.decide(out["numbers"], limits)
    assert ok and out["failed"] == 0 and out["attempted"] > 0, compared
    assert out["measures"]["compiles_in_window"] == 0
    assert out["measures"]["gate_fallbacks"] == fallbacks
    for name in ("control", "half_batch"):
        bad, compared = compare.decide(out["extras"][name], limits)
        assert not bad, (name, compared)
