"""What the hybrid decoder's cell adds to the benchmark: the operation
counts against numbers worked by hand, the token-id batches from the seed,
the new per-layer readers on a trace written by hand, and the manifest's
new entries."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import manifest, traffic
from benchmark.entries import train_ids
from benchmark.metrics import (
    delta_rule_scan_ms, flash_attention_ms, flash_attention_roofline,
    kernel_gate_fallbacks, step_ops, train_tokens_per_s)
from benchmark.reference import olmo_hybrid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmo_hybrid_train_seq8k_1chip"


def _cfg(dry=False):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        return traffic.with_dry(json.load(f), dry)


MIX = traffic.load(ROOT, "train_ids_seq8192_b1")


# ------------------------------------------------------------ operations

def test_parameter_and_matmul_counts():
    """A linear layer's five projections 3,840 x (2,880 + 2,880 + 5,760 +
    5,760) + 5,760 x 3,840 = 88,473,600 and its two gates 2 x 3,840 x 30 =
    230,400; a full layer 4 x 3,840^2 = 58,982,400; a feed-forward 3 x 3,840
    x 11,008 = 126,812,160; the head 3,840 x 12,544 = 48,168,960. Three
    linear layers, one full, four feed-forwards and the head: 880,512,000
    weights meet every token in a product. All parameters: those, the
    embedding's 48,168,960, and per linear layer 4 x (2,880 + 2,880 + 5,760)
    filter taps, 30 + 30 decays and steps and 192 gains, 2 x 3,840 gains in
    the full layer, 2 x 3,840 in each block and 3,840 at the end."""
    cfg = _cfg()
    linear = 3840 * (2880 + 2880 + 5760 + 5760) + 5760 * 3840 + 2 * 3840 * 30
    assert linear == 88_473_600 + 230_400
    matmul = 3 * linear + 58_982_400 + 4 * 126_812_160 + 48_168_960
    assert matmul == 880_512_000
    assert olmo_hybrid.matmul_params(cfg) == matmul
    small = 3 * (4 * 11_520 + 60 + 192) + 2 * 3840 + 4 * 2 * 3840 + 3840
    n = sum(int(np.prod(s)) for s in olmo_hybrid.param_shapes(cfg).values())
    assert n == matmul + 48_168_960 + small == 928_862_196
    assert olmo_hybrid.layer_kinds(cfg) == ["linear_attention"] * 3 + [
        "full_attention"]


def test_train_flops_per_sample():
    """Forward, a sequence of 8,192: 2 x 880,512,000 x 8,192 =
    14,426,308,608,000 in the products; the one full layer scores 8,192 x
    8,193 / 2 = 33,558,528 pairs a head, 4 x 128 operations each over 30
    heads: 515,458,990,080; a recurrence 6 x 96 x 192 x 30 x 8,192 =
    27,179,089,920, three of them. Three times that a trained sample:
    45.07 TFLOP."""
    cfg = _cfg()
    products = 2 * 880_512_000 * 8192
    scores = 4 * 128 * 30 * (8192 * 8193 // 2)
    recurrence = 6 * 96 * 192 * 30 * 8192
    assert products == 14_426_308_608_000
    assert scores == 515_458_990_080 and recurrence == 27_179_089_920
    assert olmo_hybrid.attention_forward_flops(cfg, 8192) == scores
    assert olmo_hybrid.recurrence_forward_flops(cfg, 8192) == recurrence
    want = 3 * (products + scores + 3 * recurrence)
    assert olmo_hybrid.train_flops_per_sample(cfg, MIX) == pytest.approx(
        want, rel=1e-12)
    assert want == pytest.approx(45.07e12, rel=1e-3)


def test_flash_attention_cost():
    """Forward and backward of the one full layer at batch 1: three times
    the forward's 515,458,990,080 operations; q, k, v, o forward and q, k,
    v, o, do, dq, dk, dv backward are 12 arrays of 8,192 x 3,840 bfloat16:
    754,974,720 bytes. At 197 TFLOP/s and 819 GB/s the operations bound it:
    7.85 ms against 0.92."""
    cost = olmo_hybrid.flash_attention_cost(_cfg(), MIX)
    assert cost["flops"] == 3 * 515_458_990_080
    assert cost["bytes"] == 12 * 8192 * 3840 * 2 == 754_974_720
    assert cost["flops"] / 197e12 == pytest.approx(7.85e-3, rel=1e-3)


# --------------------------------------------------------------- traffic

def test_id_batches_are_made_from_the_seed():
    cfg, mix = _cfg(True), traffic.load(ROOT, "train_ids_seq8192_b1", True)
    big = 2**31 + 12345
    a, b, c = (train_ids.id_batches(cfg, mix, s) for s in (big, big, big + 1))
    assert len(a) == mix["host_batches"]
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert xa.dtype == ya.dtype == np.int32
        assert xa.shape == ya.shape == (mix["batch"], mix["seq_len"])
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert not np.array_equal(xa, xc)
        assert np.array_equal(xa[:, 1:], ya[:, :-1])    # shifted by one
        assert xa.min() >= 0 and xa.max() < cfg["vocab_size"]
    full = traffic.load(ROOT, "train_ids_seq8192_b1")
    assert (full["batch"], full["seq_len"], full["host_batches"],
            full["check_steps"]) == (1, 8192, 8, 3)
    assert 4 * full["batch"] * full["seq_len"] == 32768    # bytes a batch


# ------------------------------------------------- readers, by hand

# One device; a window of 10,000 ns holding two whole steps, [1000, 4000)
# and [5000, 8000), and a third cut by the window's end, [9000, 12000).
# Step 1: flash forward 300 + 300 (run twice under remat), dq 400, dkv 500
# = 1,500; loops 200 + 100 = 300. Step 2: 250 + 250 + 400 + 600 = 1,500;
# loops 300 + 300 = 600. The cut step's kernel (9,100, 300 ns) and an
# operation outside every step (a loop at 4,500) are left out.
# So: flash 1,500 ns a step, 4 kernels a step; loops 450 ns a step.
def _kernel(name, t, d):
    return [f"%custom-call.{t} = bf16[1] custom-call(), custom_call_target="
            f'"tpu_custom_call", op_name="jit(train_step)/{name}", '
            f'backend_config={{kernel_name: "{name}"}}', t, d]


HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["fusion.1", 1000, 100],
            _kernel("flash_attention_fwd", 1100, 300),
            ["while.3", 1500, 200], ["fusion.2", 1550, 50],
            _kernel("flash_attention_fwd", 2000, 300),
            _kernel("flash_attention_dq", 2400, 400),
            _kernel("flash_attention_dkv", 2900, 500),
            ["while", 3500, 100],
            ["while.9", 4500, 100],
            _kernel("flash_attention_fwd", 5100, 250),
            ["while.3", 5400, 300],
            _kernel("flash_attention_fwd", 5800, 250),
            _kernel("flash_attention_dq", 6100, 400),
            _kernel("flash_attention_dkv", 6600, 600),
            ["while", 7300, 300], ["while_body_fusion", 7350, 10],
            _kernel("flash_attention_fwd", 9100, 300)]},
        {"name": "XLA Modules", "events": [
            ["jit_train_step(1)", 1000, 3000],
            ["jit_train_step(1)", 5000, 3000],
            ["jit__narrow_floats(2)", 4200, 50],
            ["jit_train_step(1)", 9000, 3000]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [["bench:window", 0, 10000]]}]},
]}


def _run(measures=None, trace_=HAND):
    from benchmark import trace
    t = {"planes": [
        {"name": p["name"], "lines": [
            {"name": l["name"], "events": [
                [trace.short_name(e[0]), e[1], e[2]] for e in l["events"]]}
            for l in p["lines"]]} for p in trace_["planes"]]}
    return types.SimpleNamespace(
        trace=t, measures=measures or {}, cfg=_cfg(), mix=MIX,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        reference=olmo_hybrid, chips=1)


def test_operations_are_counted_by_the_whole_steps_in_the_window():
    seconds, count = step_ops.seconds_per_step(
        _run().trace, flash_attention_ms.PATTERN)
    assert seconds == pytest.approx(1500e-9) and count == 4
    assert step_ops.seconds_per_step(_run().trace, r"^nothing$") == (0.0, 0.0)


def test_flash_attention_ms_and_roofline():
    assert flash_attention_ms.read(_run()) == pytest.approx(1500e-6)
    # least time 3 x 515,458,990,080 / 197e12 = 7.8497 ms (operations bound
    # it), over the 1,500 ns of this toy trace
    least_ms = 1e3 * 3 * 515_458_990_080 / 197e12
    assert flash_attention_roofline.read(_run()) == pytest.approx(
        100 * least_ms / 1500e-6)


def test_delta_rule_scan_ms_reads_the_steps_loops_and_nothing_else():
    # (200 + 100 + 300 + 300) / 2 steps; not the loop between the steps,
    # not "while_body_fusion"
    assert delta_rule_scan_ms.read(_run()) == pytest.approx(450e-6)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the kernel, the loops or the counter (the parent
    of the PR that brought them): None, and no exception."""
    bare = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 1000, 100]]},
            {"name": "XLA Modules", "events": [
                ["jit_train_step(1)", 1000, 3000]]}]}]}
    run = _run(trace_=bare)
    for reader in (flash_attention_ms, flash_attention_roofline,
                   delta_rule_scan_ms, kernel_gate_fallbacks,
                   train_tokens_per_s):
        assert reader.read(run) is None
    no_steps = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["fusion.1", 1000, 100]]}]}]}
    assert flash_attention_ms.read(_run(trace_=no_steps)) is None


def test_counter_readers():
    run = _run({"tokens": 8192.0 * 30, "window_s": 20.0, "gate_fallbacks": 0.0,
                "samples": 30})
    assert train_tokens_per_s.read(run) == pytest.approx(12288.0)
    assert kernel_gate_fallbacks.read(run) == 0.0
    # 8,192 tokens a sample: the counter's rate is the samples' rate
    assert train_tokens_per_s.read(run) == pytest.approx(
        8192 * run.measures["samples"] / run.measures["window_s"])


# -------------------------------------------------------------- manifest

NEW = {"flash_attention_ms": ("ms", "device_trace", "kernels"),
       "flash_attention_roofline": ("%", "device_trace", "kernels"),
       "delta_rule_scan_ms": ("ms", "device_trace", "linear_attention"),
       "kernel_gate_fallbacks": ("count", "program_counter", "kernels"),
       "train_tokens_per_s": ("tok/s", "program_counter", "entry_training")}


def test_manifest_has_the_cell_its_metrics_and_the_eight_span_metrics():
    m = manifest.load(ROOT)
    assert manifest.problems(m, ROOT) == []
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b", "train_ids_seq8192_b1", 1)
    entry = manifest.config_entry(m, "olmo-hybrid-7b")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    per_layer = {p["name"]: p for p in manifest.metrics_of(
        m, "per_layer", CELL)}
    assert set(per_layer) == set(NEW) | {
        "train_compiles_in_window", "train_step_gap_share", "train_step_mfu",
        "train_step_device_ms"}
    for name, (unit, source, layer) in NEW.items():
        p = per_layer[name]
        assert (p["unit"], p["source"], p["layer"], p["moves"],
                p["workloads"]) == (unit, source, layer,
                                    "train_samples_per_s_chip", [CELL])
    assert [e["name"] for e in manifest.metrics_of(m, "end_to_end", CELL)] \
        == ["train_samples_per_s_chip", "setup_s"]
    # the eight span metrics are as PR 25 left them, in its order, and of
    # the one cell that can report them
    spans = [p for p in m["per_layer"] if p["source"] == "program_span"]
    assert [p["name"] for p in spans] == [
        "fit_loop_self_ms", "fit_dispatch_ms", "feed_host_work_ms",
        "feed_h2d_ms", "feed_backpressure_share", "input_ready_share",
        "idle_in_input_wait_share", "idle_in_dispatch_share"]
    assert all(p["workloads"] == ["resnet50_train_1chip"] for p in spans)


def test_configuration_keeps_the_published_numbers():
    """Every number of the catalog's row under its own key; the two that
    are reduced give what is held here, with the published value beside."""
    cfg = _cfg()
    published = {
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "attention_bias": False, "tie_word_embeddings": False,
        "hidden_act": "silu", "model_type": "olmo_hybrid"}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 8
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (4, 12544)
    assert cfg["published"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]


# ------------------------------------------------------ the control's rounding

@pytest.mark.parametrize("kind,dtype,top", [
    ("e4m3", "float8_e4m3fn", 448.0), ("e5m2", "float8_e5m2", 57344.0)])
def test_round_fp8_is_lowprecs_rounding_bit_for_bit(kind, dtype, top):
    """The control rounds by float32 arithmetic (the v5e's own convert gave
    NaN inside one fused layer of this model); on the CPU it gives the bits
    ``lowprec._fp8`` gives through ml_dtypes: normal values, values over 25
    octaves (the format's subnormals and what flushes to nought), a ramp
    through every tie, and all zeros."""
    import jax.numpy as jnp
    from benchmark.reference.lowprec import _fp8
    rng = np.random.default_rng(0)
    for a in (rng.standard_normal(100_000),
              rng.standard_normal(100_000) * np.exp(rng.uniform(-25, 0, 100_000)),
              np.linspace(-1, 1, 100_001), np.zeros(5)):
        a = jnp.asarray(a, jnp.float32)
        want = np.asarray(_fp8(a, getattr(jnp, dtype), top))
        got = np.asarray(olmo_hybrid.round_fp8(a, kind))
        assert np.array_equal(got, want)


def test_control_rounders_pass_the_gradient_as_lowprecs_do():
    import jax
    import jax.numpy as jnp
    from benchmark.reference import lowprec
    x = jnp.asarray(np.random.default_rng(1).standard_normal((64, 32)),
                    jnp.float32)
    for mine, theirs in zip(olmo_hybrid.rounders("fp8"),
                            lowprec.rounders("fp8")):
        f = lambda fn: jax.value_and_grad(lambda a: jnp.sum(fn(a) ** 3))(x)
        (va, ga), (vb, gb) = f(mine), f(theirs)
        assert np.array_equal(np.asarray(va), np.asarray(vb))
        assert np.array_equal(np.asarray(ga), np.asarray(gb))
    same, also = olmo_hybrid.rounders("float32")
    assert same(x) is x and also(x) is x
    with pytest.raises(ValueError):
        olmo_hybrid.rounders("bfloat16")
