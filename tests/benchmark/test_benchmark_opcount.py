"""The operation and byte counts against values worked by hand."""

import json
import os

import pytest

from benchmark.reference import gpt2, resnet50

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet50_forward_flops():
    """3.86 G multiply-adds a 224x224 image (the paper's 3.8e9), so 7.7
    GFLOP forward and 23.1 a trained sample. Stem alone: 112*112*49*3*64."""
    cfg = _cfg("resnet50")
    f = resnet50.forward_flops_per_sample(cfg)
    assert f == pytest.approx(7.716e9, rel=1e-3)
    assert resnet50.train_flops_per_sample(cfg, {}) == pytest.approx(3 * f)
    stem = 2 * 112 * 112 * 49 * 3 * 64
    # the first bottleneck at 56x56: 64->64 1x1, 3x3, 64->256, and 64->256 proj
    block = 2 * 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    tiny = dict(cfg, height=224, width=224)
    assert f > stem + block
    n_convs = len(resnet50.conv_specs(tiny))
    assert n_convs == 53                # 1 + 3*16 + 4 projections


def test_resnet50_parameter_count():
    cfg = _cfg("resnet50")
    n = sum(k * k * ci * co + 2 * co
            for _, k, ci, co, _ in resnet50.conv_specs(cfg))
    n += 2048 * 1000 + 1000
    assert n == pytest.approx(25.56e6, rel=2e-3)


def test_gpt2_small_per_token():
    """Per layer 4*768^2 + 2*768*3072 = 7,077,888 weights; twelve layers
    84,934,656; the tied head 50257*768 = 38,597,376; 123,532,032 in all,
    so 247.06 MFLOP a token before attention, which adds 4*768*12 = 36,864
    FLOP for each position attended to."""
    cfg = _cfg("gpt2-small")
    assert gpt2.matmul_params(cfg) == 123_532_032
    assert gpt2.forward_flops_per_token(cfg, 0) == 2 * 123_532_032
    assert (gpt2.forward_flops_per_token(cfg, 100)
            - gpt2.forward_flops_per_token(cfg, 0)) == 100 * 36_864
    train = gpt2.train_flops_per_sample(cfg, {"seq_len": 1024})
    assert train == pytest.approx(
        3 * 1024 * (247_064_064 + 36_864 * 512.5), rel=1e-9)


def test_decode_roofline():
    cfg = _cfg("gpt2-small")
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    dec = gpt2.decode_step_roofline(cfg, rows=64, context=500, peaks=peaks)
    bytes_ = 4 * (123_532_032 + 64 * 500 * 12 * 2 * 768)
    assert dec["bound"] == "bandwidth"
    assert dec["seconds"] == pytest.approx(bytes_ / 819e9)
