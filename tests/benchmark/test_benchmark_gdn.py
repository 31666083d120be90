"""The two readers PR 32 brings for the delta rule's chunk-local kernels
(``gdn_chunk_local_ms``, ``gdn_chunk_local_roofline``): a trace written by
hand with and without the kernels' names, the roofline's floor against
bytes and operations reckoned by hand at the hybrid cell's shape, and the
manifest's two new entries."""

import json
import os
import types

import pytest

from benchmark import manifest, trace, traffic
from benchmark.metrics import gdn_chunk_local_ms, gdn_chunk_local_roofline
from benchmark.reference import olmo_hybrid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmo_hybrid_train_seq8k_1chip"
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
MIX = traffic.load(ROOT, "train_ids_seq8192_b1")
with open(os.path.join(ROOT, "benchmark", "configs",
                       "olmo-hybrid-7b.json")) as f:
    CFG = json.load(f)

# at the cell's shape, a linear layer: 8,192 tokens x 30 heads
TOKENS = 8192 * 30
FWD_BYTES = TOKENS * (2 * 96 * 4 + 192 * 2 + 2 * 4        # q, k, v, gates
                      + 3 * 96 * 2 + 192 * 4 + 64 * 2)    # w q_in k_out u0 attn
BWD_BYTES = TOKENS * (2 * (2 * 96 * 4 + 192 * 2 + 2 * 4)  # inputs, their cts
                      + 3 * 96 * 2 + 192 * 4 + 64 * 2)    # the outputs' cts


def _kernel(name, t, d, n=1):
    return [f"%{name}.{n} = (bf16[128,1,30,64,96]) custom-call(), "
            f'custom_call_target="tpu_custom_call", '
            f'op_name="jit(train_step)/jvp(b0_mix)/gdn:chunk_local/{name}"',
            t, d]


def _run(ops, steps=((1000, 3000), (5000, 3000), (9000, 3000)),
         window=10_000):
    """Two whole steps in a window of 10 us, and a third cut by its end."""
    t = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                [trace.short_name(e[0]), e[1], e[2]] for e in ops]},
            {"name": "XLA Modules", "events": [
                [f"jit_train_step({i})", s, d]
                for i, (s, d) in enumerate(steps)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench:window", 0, window]]}]}]}
    return types.SimpleNamespace(trace=t, cfg=CFG, mix=MIX, peaks=PEAKS,
                                 measures={}, chips=1, reference=olmo_hybrid)


BOTH = [["fusion.1", 1000, 100],
        _kernel("gdn_chunk_local_fwd", 1100, 200, 6),
        _kernel("gdn_chunk_local_fwd", 1400, 200, 9),
        _kernel("gdn_chunk_local_bwd", 2000, 400, 3),
        ["while.3", 2500, 100],
        _kernel("gdn_chunk_local_fwd", 5100, 300, 6),
        _kernel("gdn_chunk_local_fwd", 5500, 300, 9),
        _kernel("gdn_chunk_local_bwd", 6000, 600, 3),
        _kernel("gdn_chunk_local_fwd", 9100, 300, 6)]       # a cut step's


def test_hand_reckoned_bytes_at_the_cells_shape():
    """647 MB a layer forward (the issue's arithmetic) and 932 backward; the
    four forward products 2 x 64 x (3 x 96 + 192) operations a token and
    head, the inverse 2 x 64 x 64 / 3."""
    assert FWD_BYTES == 646_840_320 and BWD_BYTES == 931_921_920
    cost = gdn_chunk_local_roofline.chunk_local_cost(CFG, MIX)
    assert set(cost) == set(gdn_chunk_local_ms.KERNELS)
    assert cost["gdn_chunk_local_fwd"]["bytes"] == FWD_BYTES
    assert cost["gdn_chunk_local_bwd"]["bytes"] == BWD_BYTES
    fwd = TOKENS * 2 * 64 * (3 * 96 + 192) + TOKENS * 2 * 64 * 64 / 3
    assert cost["gdn_chunk_local_fwd"]["flops"] == pytest.approx(fwd)
    assert fwd == pytest.approx(15.77e9, rel=1e-3)
    bwd = TOKENS * 2 * 64 * (2 * 96 + 2 * (96 + 192) + 4 * 96 + 2 * 64) \
        + TOKENS * 2 * 64 * 64 / 3
    assert cost["gdn_chunk_local_bwd"]["flops"] == pytest.approx(bwd)
    # both are bound by their bytes, ten and five times over
    for c in cost.values():
        assert c["bytes"] / 819e9 > 5 * c["flops"] / 197e12
    # a length that is no multiple of the chunk is counted in whole chunks
    longer = gdn_chunk_local_roofline.chunk_local_cost(
        CFG, dict(MIX, seq_len=8193))
    assert longer["gdn_chunk_local_fwd"]["bytes"] == FWD_BYTES * 129 // 128


def test_ms_and_roofline_read_the_kernels_of_whole_steps():
    run = _run(BOTH)
    # (200 + 200 + 400 + 300 + 300 + 600) ns over two steps
    assert gdn_chunk_local_ms.read(run) == pytest.approx(1000e-6)
    least = 3 * (FWD_BYTES + BWD_BYTES) / 819e9         # three linear layers
    assert gdn_chunk_local_roofline.read(run) == pytest.approx(
        100 * least / 1000e-9)


def test_a_kernel_at_its_bytes_time_reads_one_hundred():
    """Three layers' forward and backward at exactly the bandwidth's time,
    the forward once: 100 %. Under remat the forward runs twice, so the
    cell's share starts from under 71 %."""
    fwd_ns = 3 * FWD_BYTES / 819e9 * 1e9
    bwd_ns = 3 * BWD_BYTES / 819e9 * 1e9
    steps = ((0, 8_000_000), (10_000_000, 8_000_000))
    ops = []
    for s, _ in steps:
        ops += [_kernel("gdn_chunk_local_fwd", s, fwd_ns),
                _kernel("gdn_chunk_local_bwd", s + 3_000_000, bwd_ns)]
    run = _run(ops, steps, window=20_000_000)
    assert gdn_chunk_local_roofline.read(run) == pytest.approx(100.0)
    twice = 100 * (FWD_BYTES + BWD_BYTES) / (2 * FWD_BYTES + BWD_BYTES)
    assert twice < 71


def test_readers_return_nothing_without_the_kernels():
    """The parent of the PR that brought the kernels, and a trace with no
    whole step: None, and no exception."""
    bare = [["fusion.1", 1000, 100], ["while.3", 1200, 100],
            ["%custom-call.7 = bf16[1] custom-call(), custom_call_target="
             '"tpu_custom_call", op_name="jit(train_step)/flash_attention_fwd'
             '"', 1400, 100]]
    for run in (_run(bare), _run(BOTH, steps=())):
        assert gdn_chunk_local_ms.read(run) is None
        assert gdn_chunk_local_roofline.read(run) is None


def test_manifest_names_the_hybrid_cell_alone_for_both():
    m = manifest.load(ROOT)
    assert manifest.problems(m, ROOT) == []
    assert [p["name"] for p in m["per_layer"][-2:]] == [
        "gdn_chunk_local_ms", "gdn_chunk_local_roofline"]
    for p, unit, better in zip(m["per_layer"][-2:], ("ms", "%"),
                               ("lower", "higher")):
        assert p == {"name": p["name"], "unit": unit, "better": better,
                     "source": "device_trace", "layer": "linear_attention",
                     "moves": "train_samples_per_s_chip",
                     "workloads": [CELL]}
    names = [p["name"] for p in manifest.metrics_of(m, "per_layer", CELL)]
    assert names[-2:] == ["gdn_chunk_local_ms", "gdn_chunk_local_roofline"]
    other = [p["name"] for p in manifest.metrics_of(
        m, "per_layer", "resnet50_train_1chip")]
    assert not any(n.startswith("gdn_") for n in other)
