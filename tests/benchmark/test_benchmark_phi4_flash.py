"""What the SambaY decoder's cell adds to the benchmark: the parameter and
operation counts against numbers worked by hand, the readers of the two
new per-layer metrics on a trace written by hand, the configuration's
published numbers, and the manifest's new entries, by name."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import manifest, traffic
from benchmark.metrics import (
    flash_attention_roofline, selective_scan_ms, selective_scan_roofline,
    train_step_mfu)
from benchmark.reference import phi4_flash

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "phi4_flash_train_seq8k_1chip"
CONFIG = "phi-4-mini-flash-reasoning"
KINDS = ["mamba", "window", "mamba", "window", "mamba", "full", "gmu",
         "cross"]


def _cfg(dry=False):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return traffic.with_dry(json.load(f), dry)


MIX = traffic.load(ROOT, "train_ids_seq8192_b1")


# ------------------------------------------------------------ operations

# a Mamba mixer's products: W_in's x half 2,560 x 5,120, W_x 5,120 x (160 +
# 16 + 16), W_dt 160 x 5,120, and the gate's W_z and W_out, 2 x 2,560 x 5,120
MAMBA = 13_107_200 + 983_040 + 819_200 + 26_214_400
# keys and values 2,560 x (2 x 20 x 64), queries and output 2 x 2,560^2
ATTENTION = 6_553_600 + 13_107_200
FFN = 3 * 2560 * 10240
HEAD = 25008 * 2560


def test_parameter_and_matmul_counts():
    """915.2 M. Beside its products a Mamba mixer holds 4 x 5,120 filter
    taps, a bias, a step's bias and a ``D`` of 5,120 each and 16 x 5,120
    rates (41,241,600 in all: the published model's 41.24 M); an attention
    mixer three biases of 2,560, four ``l_*`` of 64 and a gain of 128
    (19,668,864: 19.66 M; a cross layer 13,112,704); a memory unit nothing
    (26,214,400); each block two LayerNorms of 2 x 2,560, and one at the
    end; the embedding is the head."""
    cfg = _cfg()
    assert phi4_flash.layer_kinds(cfg) == KINDS
    assert (MAMBA, ATTENTION, FFN, HEAD) == (
        41_123_840, 19_660_800, 78_643_200, 64_020_480)
    matmul = 3 * MAMBA + 3 * ATTENTION + 26_214_400 + 13_107_200 \
        + 8 * FFN + HEAD
    assert matmul == 914_841_600 == phi4_flash.matmul_params(cfg)
    small = (3 * (4 * 5120 + 3 * 5120 + 16 * 5120)
             + 3 * (3 * 2560 + 4 * 64 + 128) + (2 * 2560 + 4 * 64 + 128)
             + 8 * 4 * 2560 + 2 * 2560)
    n = sum(int(np.prod(s)) for s in phi4_flash.param_shapes(cfg).values())
    assert n == matmul + small == 915_311_616
    mixer = lambda b: sum(
        int(np.prod(s)) for k, s in phi4_flash.param_shapes(cfg).items()
        if k.startswith((f"b{b}_ssm/", f"b{b}_mix/", f"b{b}_kv/")))
    assert [mixer(b) for b in (0, 1, 16, 17, 18, 19)] == [
        41_241_600, 19_668_864, 41_241_600, 19_668_864, 26_214_400,
        13_112_704]


# scores inside the mask, a map: causal 8,192 x 8,193 / 2; with the window
# 512 x 513 / 2 for the first 512 queries and 512 each for the other 7,680
FULL, WINDOW = 33_558_528, 131_328 + 7_680 * 512
# forty maps a layer, each score a product over 64 and a weight on 128
ATTENTION_FORWARD = 2 * 40 * 2 * (64 + 128) * (FULL + WINDOW)


def test_train_flops_per_sample():
    """Forward, a sequence of 8,192: 2 x 914,841,600 x 8,192 of products;
    two window layers and two that see the whole sequence (the full and the
    cross layer) 1,155,748,331,520; three scans of 4 x 8,192 x 5,120 x 16.
    Three times that with the backward; the rebuilt forward is not
    counted."""
    assert (phi4_flash.scores_seen(8192), phi4_flash.scores_seen(8192, 512),
            phi4_flash.scores_seen(100, 512)) == (FULL, WINDOW, 5050)
    assert ATTENTION_FORWARD == 1_155_748_331_520
    forward = 2 * 914_841_600 * 8192 + ATTENTION_FORWARD \
        + 3 * 2_684_354_560
    assert forward == 16_152_566_169_600
    assert phi4_flash.train_flops_per_sample(_cfg(), MIX) == 3.0 * forward
    # at 1.5 samples/s on one v5e: 36.9 % of the peak
    run = types.SimpleNamespace(
        measures={"samples": 30, "window_s": 20.0}, cfg=_cfg(), mix=MIX,
        chips=1, peaks={"flops_per_s": 197e12}, reference=phi4_flash)
    assert train_step_mfu.read(run) == pytest.approx(
        100 * 3 * forward * 1.5 / 197e12)


def test_flash_attention_cost():
    """What the algebra needs whatever a kernel pads, skips or rebuilds:
    forward and twice backward of the scores inside the masks; q 2,560, k
    and v 1,280 each and the pairs' outputs 2,560 numbers a token forward,
    those and the cotangent read and three gradients written backward:
    23,040 numbers a token a layer, bfloat16, four layers."""
    cost = phi4_flash.flash_attention_cost(_cfg(), MIX)
    assert cost["flops"] == 3.0 * ATTENTION_FORWARD
    assert cost["bytes"] == 4 * 8192 * 2 * (7680 + 10240 + 5120) \
        == 1_509_949_440
    # the operations bound it: 17.6 ms at the peak, 1.84 ms of bytes
    assert cost["flops"] / 197e12 > 9 * cost["bytes"] / 819e9


def test_selective_scan_cost():
    """ONE state-space layer: a token reads x (2 bytes a channel), Delta
    (4) and B, C (2 x 16 x 4) and writes s (2): 41,088 bytes forward;
    backward those, the cotangent in (2) and dx (2), dDelta (4), dB, dC out:
    82,176. The update and the read-out are a multiply-add each a state."""
    cost = phi4_flash.selective_scan_cost(_cfg(), MIX)
    assert cost["bytes"] == 8192 * (41_088 + 82_176) == 1_009_778_688
    assert cost["flops"] == 3.0 * 4 * 8192 * 5120 * 16
    # the bytes bound it: 1.233 ms a layer, 0.041 ms of operations
    assert cost["bytes"] / 819e9 == pytest.approx(1.2329e-3, rel=1e-4)


# ------------------------------------------------- readers, by hand

# One device; a window of 10,000 ns holding two whole steps, [1000, 4000)
# and [5000, 8000), and a third cut by the window's end. Step 1: loops 200
# + 100 + 300; step 2: 300 + 300 + 600. A loop between the steps (4,500),
# the cut step's, a fusion named like a loop's body and a flash kernel are
# left out. So: 900 ns a step, three loops a step.
def _kernel(name, t, d):
    return [f"%custom-call.{t} = bf16[1] custom-call(), custom_call_target="
            f'"tpu_custom_call", op_name="jit(train_step)/{name}", '
            f'backend_config={{kernel_name: "{name}"}}', t, d]


LOOPS = [["fusion.1", 1000, 100], ["while.3", 1200, 200],
         ["fusion.2", 1250, 50], ["while", 1500, 100],
         _kernel("flash_attention_fwd", 2000, 300), ["while.12", 3000, 300],
         ["while.9", 4500, 100],
         ["while.3", 5100, 300], ["while", 5500, 300],
         ["while_body_fusion", 5550, 10], ["while.12", 6000, 600],
         ["while.3", 9100, 300]]
# the same steps with a kernel for the scan: forward twice, backward once
KERNELS = [_kernel("selective_scan_fwd", 1200, 200),
           _kernel("selective_scan_fwd", 1500, 100),
           _kernel("selective_scan_bwd", 3000, 300),
           _kernel("selective_scan_fwd", 5100, 300),
           _kernel("selective_scan_fwd", 5500, 300),
           _kernel("selective_scan_bwd", 6000, 600)]


def _run(ops, steps=((1000, 3000), (5000, 3000), (9000, 3000)),
         reference=phi4_flash):
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
        trace=t, measures={}, cfg=_cfg(), mix=MIX, chips=1,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        reference=reference)


@pytest.mark.parametrize("ops", [LOOPS, KERNELS], ids=["loops", "kernels"])
def test_selective_scan_ms_and_roofline(ops):
    run = _run(ops)
    assert selective_scan_ms.read(run) == pytest.approx(900e-6)
    # three layers at 1,009,778,688 bytes each over 819 GB/s: 3.699 ms,
    # over the 900 ns of this toy trace
    least_ms = 1e3 * 3 * 1_009_778_688 / 819e9
    assert selective_scan_roofline.read(run) == pytest.approx(
        100 * least_ms / 900e-6)


def test_scans_at_their_bytes_time_read_one_hundred():
    """One step whose nine loops take the floor's 3.699 ms between them."""
    each = round(3 * 1_009_778_688 / 819e9 * 1e9 / 9)
    ops = [[f"while.{i}", 1000 + i * (each + 10), each] for i in range(9)]
    run = _run(ops, steps=((1000, 10 * each),))
    run.trace["planes"][1]["lines"][0]["events"] = [
        ["bench:window", 0, 12 * each]]
    assert selective_scan_roofline.read(run) == pytest.approx(100, rel=1e-4)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the scans (the parent of the PR that brought
    them), a trace without a whole step, and a reference that counts no
    scan: None, and no exception."""
    bare = [["fusion.1", 1000, 100], _kernel("flash_attention_fwd", 2000, 300)]
    for run in (_run(bare), _run(LOOPS, steps=())):
        assert selective_scan_ms.read(run) is None
        assert selective_scan_roofline.read(run) is None
    from benchmark.reference import olmo_hybrid
    assert selective_scan_roofline.read(
        _run(LOOPS, reference=olmo_hybrid)) is None


def test_the_accepted_flash_roofline_reads_this_references_cost():
    run = _run([_kernel("flash_attention_fwd", 1100, 300),
                _kernel("flash_attention_dkv", 5100, 500)])
    least_ms = 1e3 * 3 * ATTENTION_FORWARD / 197e12
    assert flash_attention_roofline.read(run) == pytest.approx(
        100 * least_ms / 400e-6)


# -------------------------------------------------------------- manifest

SHARED = ["train_compiles_in_window", "train_step_gap_share",
          "train_step_mfu", "train_step_device_ms", "flash_attention_ms",
          "flash_attention_roofline", "kernel_gate_fallbacks",
          "train_tokens_per_s"]


def test_manifest_has_the_configuration_the_cell_and_its_metrics():
    m = manifest.load(ROOT)
    assert manifest.problems(m, ROOT) == []
    assert len(m["configs"]) == len(m["workloads"]) == 3
    assert all(w["chips"] == 1 for w in m["workloads"])
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_ids_seq8192_b1", 1)
    entry = manifest.config_entry(m, CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == _cfg()["source"]
    per_layer = {p["name"]: p for p in manifest.metrics_of(
        m, "per_layer", CELL)}
    assert set(per_layer) == set(SHARED) | {
        "selective_scan_ms", "selective_scan_roofline"}
    for name in SHARED:         # appended to, nothing else changed
        assert per_layer[name]["workloads"][-1] == CELL
        assert "olmo_hybrid_train_seq8k_1chip" in per_layer[name]["workloads"]
    for name, unit, better in (("selective_scan_ms", "ms", "lower"),
                               ("selective_scan_roofline", "%", "higher")):
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "state_space",
            "moves": "train_samples_per_s_chip", "workloads": [CELL]}
    assert [e["name"] for e in manifest.metrics_of(m, "end_to_end", CELL)] \
        == ["train_samples_per_s_chip", "setup_s"]
    # what reads another model's mechanism keeps its one cell
    for name in ("delta_rule_scan_ms", "gdn_chunk_local_ms",
                 "gdn_chunk_local_roofline"):
        assert name not in per_layer
    limits = json.load(open(os.path.join(
        ROOT, "benchmark", "limits", CELL + ".json")))
    numbers = {k for k in limits if not k.startswith("_") and k != "dry_cpu"}
    assert numbers and numbers == set(limits["_why"])


def test_configuration_keeps_the_published_numbers():
    """Every number of the catalog's row under its own key; the two that
    are reduced give what is held here, with the published value beside;
    what the row does not give is listed under ``assumed``."""
    cfg = _cfg()
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (8, 25008)
    assert cfg["published"] == {"num_hidden_layers": 32, "vocab_size": 200064}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["layers"] == [0, 1, 2, 3, 16, 17, 18, 19]
    assert len(cfg["layers"]) == cfg["num_hidden_layers"]
    assert len(cfg["layer_types"]) == 32
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_d_inner"], cfg["mamba_dt_rank"]) == (
                16, 4, 2, 2 * 2560, 2560 // 16)
    for key in ("layer_rule", "state_space", "differential_attention",
                "window", "updater", "weights", "gated_memory_unit"):
        assert key in cfg["assumed"], key
    dry = _cfg(True)
    assert dry["layers"] == [0, 1, 2, 3, 16, 17, 18, 19, 20, 21, 22, 23]
    assert (dry["hidden_size"], dry["num_attention_heads"],
            dry["num_key_value_heads"], dry["mamba_d_inner"],
            dry["mamba_d_state"], dry["mamba_dt_rank"],
            dry["intermediate_size"], dry["sliding_window"],
            dry["vocab_size"]) == (64, 4, 2, 128, 4, 4, 128, 24, 64)


def test_the_layer_rule_is_the_programs():
    """The file's ``layer_types`` is the rule the program's builder
    states, so the reference and the program read one list."""
    from deeplearning4j_tpu.models.phi4_flash import layer_kinds
    cfg = _cfg()
    assert cfg["layer_types"] == layer_kinds(32, cfg["mb_per_layer"])
