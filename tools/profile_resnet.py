"""ResNet-50 MFU ablation ladder (VERDICT r3 #1b: find the other 88%).

Runs a sequence of timed ablations on the real chip and prints one JSON
line per experiment, so a hang can never erase earlier results (the
bench.py banking lesson). Experiments:

  peak        8192^3 bf16 matmul — the chip's *achievable* peak, the MFU
              denominator sanity check
  conv_micro  the three dominant conv shapes fwd+bwd standalone
  fwd         ResNet-50 b64@224 inference forward
  train       ResNet-50 b64@224 full train step (bench 'full' rung)
  train_bnbf16   same with BatchNormalization statistics kept in bf16
              (ablates the f32-upcast HBM traffic around every conv)
  train_nobn  same with BN layers removed (upper bound of all BN cost)
  train_b128 / train_b256   batch scaling (MXU occupancy)

Usage (idempotent, safe to rerun):  python tools/profile_resnet.py
Env: PROFILE_STEPS=10 PROFILE_SKIP=train_b256,... to trim.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

STEPS = int(os.environ.get("PROFILE_STEPS", "10"))
SKIP = set(filter(None, os.environ.get("PROFILE_SKIP", "").split(",")))
# PROFILE_SMOKE=1: tiny shapes so the whole ladder runs in ~a minute on
# CPU — validates the harness (patching, timing, emission) before the
# chip run spends its window on it
SMOKE = os.environ.get("PROFILE_SMOKE") == "1"


def stamp(msg):
    print(f"[profile {time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.perf_counter()


def emit(rec):
    print(json.dumps(rec), flush=True)


def timed(fn, *args, steps=STEPS, warmup=2):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def main():
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.profiling.cost import peak_flops
    from deeplearning4j_tpu.util.compile_cache import use_compile_cache

    stamp(f"compile cache: {use_compile_cache()}")
    devs = jax.devices()
    kind = str(devs[0].device_kind)
    stamp(f"backend: {len(devs)}x {kind}")
    peak = peak_flops(kind)

    # ---------------------------------------------------------------- peak
    if "peak" not in SKIP:
        n = 512 if SMOKE else 8192
        a = jnp.ones((n, n), jnp.bfloat16)
        b = jnp.ones((n, n), jnp.bfloat16)
        f = jax.jit(lambda x, y: x @ y)
        dt = timed(f, a, b)
        tf = 2 * n ** 3 / dt / 1e12
        emit({"exp": "peak", "tflops": round(tf, 1), "device": kind,
              "frac_of_spec": round(tf / (peak / 1e12), 3) if peak else None})

    # ---------------------------------------------------------- conv micro
    if "conv_micro" not in SKIP:
        from jax import lax
        shapes = [
            ("stem7x7", (64, 224, 224, 3), (7, 7, 3, 64), 2),
            ("s2_3x3", (64, 56, 56, 64), (3, 3, 64, 64), 1),
            ("s4_3x3", (64, 14, 14, 256), (3, 3, 256, 256), 1),
        ] if not SMOKE else [
            ("stem7x7", (4, 32, 32, 3), (7, 7, 3, 8), 2),
            ("s2_3x3", (4, 8, 8, 8), (3, 3, 8, 8), 1),
        ]
        for name, xs, ks, stride in shapes:
            x = jnp.ones(xs, jnp.bfloat16)
            k = jnp.ones(ks, jnp.bfloat16)

            def conv(x, k, _s=stride):
                return lax.conv_general_dilated(
                    x, k, (_s, _s), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))

            def fwd_bwd(x, k, _c=conv):
                loss, g = jax.value_and_grad(
                    lambda kk: (_c(x, kk) ** 2).sum())(k)
                return g

            dt = timed(jax.jit(fwd_bwd), x, k)
            out_hw = (xs[1] // stride) * (xs[2] // stride)
            flops = 3 * 2 * xs[0] * out_hw * ks[0] * ks[1] * ks[2] * ks[3]
            emit({"exp": f"conv_{name}", "ms": round(dt * 1e3, 3),
                  "tflops": round(flops / dt / 1e12, 1),
                  "mfu": round(flops / dt / peak, 3) if peak else None})

    # ------------------------------------------------- flash attention
    if "attn" not in SKIP:
        from deeplearning4j_tpu.nn.layers.attention import (
            attention_reference)
        from deeplearning4j_tpu.ops.pallas_attention import (
            attention_mode, flash_attention)
        B, H, T, D = (2, 2, 256, 64) if SMOKE else (8, 8, 2048, 64)
        r = np.random.default_rng(1)
        q, k, v = (jnp.asarray(r.normal(size=(B, H, T, D))
                               .astype(np.float32)).astype(jnp.bfloat16)
                   for _ in range(3))
        interp = attention_mode() == "interpret"

        def train_like(fn):
            def f(q, k, v):
                return jnp.sum(fn(q, k, v) ** 2)
            return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

        flops = 4 * 2 * B * H * T * T * D  # fwd QK^T+PV, ~2x again bwd
        for name, fn in (
                ("attn_xla", lambda q, k, v: attention_reference(
                    q, k, v, causal=True)),
                ("attn_flash", lambda q, k, v: flash_attention(
                    q, k, v, causal=True, interpret=interp))):
            try:
                dt = timed(train_like(fn), q, k, v)
                emit({"exp": name, "B": B, "T": T, "ms": round(dt * 1e3, 2),
                      "tflops": round(flops / dt / 1e12, 1),
                      "mfu": (round(flops / dt / peak, 3)
                              if peak else None)})
            except Exception as e:  # noqa: BLE001 — never cost the ladder
                emit({"exp": name, "error": f"{type(e).__name__}: {e}"[:160]})

    # ------------------------------------------------------------- resnet
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import (
        DevicePrefetchIterator, ListDataSetIterator)
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    from deeplearning4j_tpu.nn.layers import normalization as nm
    _orig_bn_apply = nm.BatchNormalization.apply

    def _bn_apply_bf16(self, params, x, *, state, train, rng, mask=None):
        """BN with statistics in the activation dtype (bf16): ablates the
        f32 upcast traffic of the production impl."""
        axes = tuple(range(x.ndim - 1))
        if train and self.is_minibatch:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            new_state = {
                "mean": self.decay * state["mean"]
                + (1 - self.decay) * mean.astype(jnp.float32),
                "var": self.decay * state["var"]
                + (1 - self.decay) * var.astype(jnp.float32),
            }
        else:
            mean = state["mean"].astype(x.dtype)
            var = state["var"].astype(x.dtype)
            new_state = state
        inv = jax.lax.rsqrt(var + jnp.asarray(self.eps, x.dtype))
        out = (x - mean) * inv
        if not self.lock_gamma_beta:
            out = params["gamma"] * out + params["beta"]
        return out, new_state

    def _bn_apply_identity(self, params, x, *, state, train, rng,
                           mask=None):
        return x, state

    def run_train(tag, batch, bn_apply=None):
        if tag in SKIP:
            return
        stamp(f"{tag}: building (batch={batch})")
        # patch stays active through BOTH init and the fit-time trace
        if bn_apply is not None:
            nm.BatchNormalization.apply = bn_apply
        hw = 32 if SMOKE else 224
        try:
            net = ComputationGraph(
                resnet50(dtype="bfloat16", height=hw, width=hw)).init()
            jax.block_until_ready(net.params)
            rng = np.random.default_rng(0)
            xs = [DataSet(
                rng.normal(size=(batch, hw, hw, 3)).astype(np.float32),
                np.eye(1000, dtype=np.float32)[
                    rng.integers(0, 1000, batch)]) for _ in range(3)]
            staged = list(DevicePrefetchIterator(ListDataSetIterator(xs),
                                                 dtype="bfloat16"))
            jax.block_until_ready([d.features for d in staged])
            for i in range(2):
                net.fit_batch(staged[i % 3])
            jax.block_until_ready(net.params)
            t0 = time.perf_counter()
            for i in range(STEPS):
                net.fit_batch(staged[i % 3])
            jax.block_until_ready(net.params)
        finally:
            nm.BatchNormalization.apply = _orig_bn_apply
        dt = (time.perf_counter() - t0) / STEPS
        sps = batch / dt
        fwd_flops = 4.09e9 * (hw * hw) / (224 * 224)
        mfu = 3 * fwd_flops * sps / peak if peak else None
        emit({"exp": tag, "batch": batch, "step_ms": round(dt * 1e3, 2),
              "samples_per_sec": round(sps, 1),
              "mfu": round(mfu, 3) if mfu else None})

    if "fwd" not in SKIP:
        hw = 32 if SMOKE else 224
        fb = 8 if SMOKE else 64
        net = ComputationGraph(
            resnet50(dtype="bfloat16", height=hw, width=hw)).init()
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(fb, hw, hw, 3)).astype(np.float32)).astype(jnp.bfloat16)
        jax.block_until_ready(net.params)
        dt = timed(lambda xx: net.output({"in": xx}), x)
        sps = fb / dt
        ffl = 4.09e9 * (hw * hw) / (224 * 224)
        emit({"exp": "fwd", "step_ms": round(dt * 1e3, 2),
              "samples_per_sec": round(sps, 1),
              "mfu_fwd": round(ffl * sps / peak, 3) if peak else None})

    B = 8 if SMOKE else 64
    run_train("train", B)
    run_train("train_bnbf16", B, bn_apply=_bn_apply_bf16)
    run_train("train_nobn", B, bn_apply=_bn_apply_identity)
    run_train("train_b128", 2 * B)
    run_train("train_b256", 4 * B)
    stamp("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
