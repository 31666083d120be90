"""What decides ``correct``, at a size a test run can hold: the plain
reference follows the program; the control (the reference in the precision
below the configuration's, put in the program's place) comes out not
correct; and a run with the timed path broken underneath comes out not
correct, once for each fault a cell can have. These drive the entries as
``run.py`` does, skipping only its look for a chip."""

import argparse
import importlib

import jax
import jax.numpy as jnp
import pytest

from benchmark import compare, manifest, run, traffic

ROOT = run.ROOT
# limits for the tiny float32 sizes of these tests (the cells' own limits,
# benchmark/limits/, are set from readings at the cells' sizes on the chip)
TRAIN_LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 1e-4,
                "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3}
SERVE_LIMITS = {"served_logit_gap": 1e-5, "sample_missing": 0}


CELLS = {   # config and traffic files, at their ``dry_cpu`` sizes
    "resnet50_train_1chip": ("resnet50", "train_b128"),
    # not cells of the manifest (PERF.md, Open questions, says what each
    # waits on), but the serving entry's tests and the training entry's
    # quickest exact test
    "gpt2s_serve_offline": ("gpt2-small", "serve_offline"),
    "gpt2s_train_1chip": ("gpt2-small", "train_seq1024_b4"),
}


def context(cell_name, seed=5, extra=(), cfg_over=None, mix_over=None,
            seconds=0.5):
    config, mix_name = CELLS[cell_name]
    cell = {"name": cell_name, "chips": 1}
    cfg = traffic.with_dry(run.load_json(
        "benchmark", "configs", config + ".json"), True)
    cfg.update(cfg_over or {})
    mix = traffic.load(ROOT, mix_name, True)
    mix.update(mix_over or {})
    ns = argparse.Namespace(seed=seed, seconds=seconds, trace=0, dry_cpu=True)
    ctx = run.Context(ns, cell, cfg, mix, importlib.import_module(
        f"benchmark.reference.{cfg['reference']}"))
    ctx.extra = tuple(extra)
    return ctx, importlib.import_module(f"benchmark.entries.{mix['entry']}")


@pytest.fixture(autouse=True)
def _kernels_interpreted(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS", "interpret")
    from deeplearning4j_tpu.profiling import CompileWatcher
    CompileWatcher().install()


def decide(out, limits):
    ok, compared = compare.decide(out["numbers"], limits)
    return ok and out["failed"] == 0 and out["attempted"] > 0, compared


# ------------------------------------------------------------- sound runs

def test_gpt2_training_follows_the_reference_and_the_control_fails():
    ctx, entry = context("gpt2s_train_1chip", extra=("control", "half_batch"))
    out = entry.run(ctx)
    ok, compared = decide(out, TRAIN_LIMITS)
    assert ok, compared
    assert out["measures"]["compiles_in_window"] == 0
    assert out["measures"]["steps"] > 0
    for planted in ("control", "half_batch"):
        bad, compared = compare.decide(out["extras"][planted], TRAIN_LIMITS)
        assert not bad, (planted, compared)


def test_resnet50_follows_the_reference_and_control_and_fault_fail():
    """Under the cell's own limits (``benchmark/limits``), the ones its runs
    on the chip are held to. bfloat16 compute at 32x32 and batch 4 is all
    rounding, so the tiny size runs the same program in float32 (at 64x64,
    or the last stage's batch norm has 8 values a channel and amplifies
    rounding over the three steps); the fp8 control and half a batch left
    out, each in the program's place, come out not correct."""
    ctx, entry = context("resnet50_train_1chip",
                         extra=("control", "half_batch"),
                         cfg_over={"compute_precision": "fp32",
                                   "height": 64, "width": 64},
                         mix_over={"feed_dtype": None, "batch": 8})
    with jax.default_matmul_precision("highest"):
        out = entry.run(ctx)
    limits = compare.load_limits(ROOT, "resnet50_train_1chip")
    ok, compared = decide(out, limits)
    assert ok, compared
    for planted in ("control", "half_batch"):
        bad, compared = compare.decide(out["extras"][planted], limits)
        assert not bad, (planted, compared)


def test_serving_follows_the_reference_and_the_control_fails():
    """The whole-bfloat16 control puts another token first only at a near
    tie, about one token in 500 at this size, so the sample is of some
    thousands of tokens: 32 clients, every request that finished. How many
    do depends on the machine, so ``sample_missing`` is not held here."""
    ctx, entry = context(
        "gpt2s_serve_offline", extra=("control",),
        cfg_over={"vocab_size": 8192, "n_positions": 128},
        mix_over={"check_requests": 96, "pool_requests": 256, "clients": 32,
                  "server": {"max_concurrency": 32, "queue_depth": 64,
                             "max_batch": 32},
                  "output_tokens": {"min": 60, "max": 80}}, seconds=8.0)
    out = entry.run(ctx)
    limits = {"served_logit_gap": SERVE_LIMITS["served_logit_gap"]}
    ok, compared = decide(out, limits)
    assert ok, compared
    assert out["numbers"]["sample_tokens"] > 2500
    assert out["measures"]["out_tokens"] > 0
    bad, compared = compare.decide(out["extras"]["control"], limits)
    assert not bad, compared


# ------------------------------------------------------------ broken runs

def _patch_train_step(monkeypatch, wrap):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    real_build = ComputationGraph._build_train_step

    def build(self):
        return wrap(real_build(self))
    monkeypatch.setattr(ComputationGraph, "_build_train_step", build)


def test_fault_a_step_that_returns_its_state_unchanged(monkeypatch):
    copy = lambda t: jax.tree.map(jnp.copy, t)

    def wrap(step):
        def unchanged(params, opt_state, states, *rest):
            out = step(copy(params), copy(opt_state), copy(states), *rest)
            return (params, opt_state, states) + tuple(out[3:])
        return unchanged
    _patch_train_step(monkeypatch, wrap)
    ctx, entry = context("gpt2s_train_1chip")
    ok, compared = decide(entry.run(ctx), TRAIN_LIMITS)
    assert not ok
    assert compared["delta_norm_gap"][0] == pytest.approx(1.0)
    assert compared["grad_norm_gap"][0] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(monkeypatch):
    half = lambda d: {k: v[: v.shape[0] // 2] for k, v in d.items()}

    def wrap(step):
        def halved(params, opt_state, states, inputs, labels, *rest):
            return step(params, opt_state, states, half(inputs), half(labels),
                        *rest)
        return halved
    _patch_train_step(monkeypatch, wrap)
    ctx, entry = context("gpt2s_train_1chip")
    ok, compared = decide(entry.run(ctx), TRAIN_LIMITS)
    assert not ok
    assert compared["grad_norm_gap"][0] > 10 * TRAIN_LIMITS["grad_norm_gap"]


def test_fault_a_token_altered_where_it_is_produced(monkeypatch):
    from deeplearning4j_tpu.keras import generation
    real = generation._Engine._select

    def altered(self, req, probs_vec):
        return (real(self, req, probs_vec) + 1) % self.vocab
    monkeypatch.setattr(generation._Engine, "_select", altered)
    ctx, entry = context("gpt2s_serve_offline")
    ok, compared = decide(entry.run(ctx), SERVE_LIMITS)
    assert not ok
    assert compared["served_logit_gap"][0] > SERVE_LIMITS["served_logit_gap"]


def test_a_failed_request_is_not_correct(monkeypatch):
    """A run in which a request comes back as an error is not correct,
    whatever the reference says of the others."""
    from deeplearning4j_tpu.keras import generation
    real = generation.GenerationScheduler.submit
    calls = {"n": 0}

    def flaky(self, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 12:
            raise RuntimeError("planted failure")
        return real(self, *a, **kw)
    monkeypatch.setattr(generation.GenerationScheduler, "submit", flaky)
    ctx, entry = context("gpt2s_serve_offline")
    out = entry.run(ctx)
    if out["failed"]:
        assert not decide(out, SERVE_LIMITS)[0]
    else:       # the planted failure fell outside the window
        assert calls["n"] >= 12


# -------------------------------------------------------------- the parts

def test_worst_leaf_is_a_gap_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 10.0, "c": 1e-9}
    got = {"a": 1.1, "b": 10.0, "c": 1e-3}
    gap, leaf = compare.worst_leaf_gap(got, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    # c's own norm is all but nought: it is measured against the median leaf
    assert abs(1e-3 - 1e-9) / 1.0 < gap
    assert compare.worst_leaf_gap({"a": float("nan")}, {"a": 1.0})[1] == "a"


def test_leaves_that_rounding_alone_moves_are_left_out():
    grads = {"w": 1.0, "v": 2.0, "u": 3.0, "bias_under_softmax": 1e-7}
    assert compare.moved_leaves(grads) == ["u", "v", "w"]


def test_decide_fails_a_missing_or_nan_number():
    ok, compared = compare.decide({"x": 0.1}, {"x": 0.2, "y": 0.2})
    assert not ok and compared["x"] == [0.1, 0.2]
    assert not compare.decide({"x": float("nan")}, {"x": 0.2})[0]
    assert compare.decide({"x": 0.1}, {"x": 0.2})[0]


def test_every_cell_has_limits_set_for_numbers_it_compares():
    m = manifest.load(ROOT)
    for w in m["workloads"]:
        limits = compare.load_limits(ROOT, w["name"])
        assert limits, w["name"]
        assert all(isinstance(v, (int, float)) for v in limits.values())
