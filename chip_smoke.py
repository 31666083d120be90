"""chip_smoke.py — the quickest proof the system still starts on the chip.

One process, plain ``python chip_smoke.py`` from the root of a checkout, on
a machine with a TPU. It drives the two main paths once through the entry
points a user calls, at the full width of models the repo ships (random
weights from a seed), and checks what comes out by the repo's own means:

  P0 device     platform must be ``tpu``; versions, device_kind, peak table,
                compile-cache directory
  P1 trainer    ResNet-50 224x224 b64 bf16 through ``net.fit(iterator)``
  P2 server     the ``lm_serve`` GPT over the socket: KerasServer +
                KerasClient.generate against singleton ``greedy_generate``
  P3 kernels    char-LSTM and GPT trainers with the compiled Pallas kernels
                (Mosaic custom call present in the lowered step) and kernel
                vs XLA-reference parity at aligned and unaligned shapes;
                the delta rule's chunk-local kernels against its XLA path
                at the hybrid cell's shape, values and gradients; the flash
                kernels with a window against the written-out mask and the
                selective scan, by its kernels and by the chunked form,
                against the token-by-token one, at the SambaY cell's shapes
                in bfloat16 and at lengths that are no multiple of a block
                in float32; the selection by the counting threshold
                against the sort's at the sparse cell's shape, equal;
                SmallThinker's four layers trained past one window
  P4 multichip  ResNet-50 over ``ParallelTrainer`` on four chips, when the
                machine has them

Exit 0 only if every phase passed; the last line of stdout is then
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
With no accelerator the script exits non-zero and prints no result.

``--dry-cpu`` is for the CPU sandbox and the tier-1 test: tiny shapes,
Pallas kernels in interpret mode, every line prefixed ``[DRY-CPU]`` and no
result line. It is never the default and proves nothing about the chip.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

DRY = "--dry-cpu" in sys.argv[1:]
#: the selective scan's kernels in float32 against float64 on the host: they
#: chain all T decays, as the token-by-token form does, and the v5e's
#: float32 exp is biased by -1e-6 (1.6e-4 to 6.0e-4 read at T 1,100 for
#: both, PERF.md section 6, PRs 33 and 34); against the chip's own
#: token-by-token float32 they read 2e-7 and are held to 1e-5
SCAN_KERNEL_TOL = 2e-3
TAG = "[DRY-CPU] " if DRY else ""
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "chip_smoke")


def say(msg: str) -> None:
    print(f"{TAG}{msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# shared: train a few steps through the public fit(iterator)
# ---------------------------------------------------------------------------

def _compiles() -> float:
    from deeplearning4j_tpu.profiling.metrics import get_registry
    return get_registry().counter("jax_compile_total").value


def _fit_steps(net, batches, fit, warmup: int = 2) -> dict:
    """``fit(iterator)`` over ``warmup`` batches, then over the rest with
    the compile counter watched. Loss per step comes from a listener (a
    host read of the loss ends each step, so the times are whole steps)."""
    import jax

    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)

    scores = CollectScoresIterationListener()
    net.set_listeners(scores)
    t0 = time.perf_counter()
    fit(ListDataSetIterator(batches[:warmup]))
    jax.block_until_ready(net.params)
    warm_s = time.perf_counter() - t0
    compiles = _compiles()
    t0 = time.perf_counter()
    fit(ListDataSetIterator(batches[warmup:]))
    jax.block_until_ready(net.params)
    step_ms = 1e3 * (time.perf_counter() - t0) / (len(batches) - warmup)
    losses = [s for _, s in scores.scores]
    check(len(losses) == len(batches), f"{len(losses)} steps ran, "
          f"{len(batches)} batches fed")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(_compiles() == compiles,
          f"{_compiles() - compiles:.0f} compilation(s) after warm-up")
    return {"losses": [round(float(l), 4) for l in losses],
            "warmup_s": round(warm_s, 1), "step_ms": round(step_ms, 2)}


def _prefetched(net, dtype):
    """``net.fit`` fed through the device-prefetch iterator."""
    from deeplearning4j_tpu.datasets.iterator import DevicePrefetchIterator
    return lambda it: net.fit(DevicePrefetchIterator(it, dtype=dtype))


def _image_batches(n, batch, side, classes, dtype=np.float32, seed=0):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    out = [DataSet(
        rng.normal(size=(batch, side, side, 3)).astype(dtype),
        np.eye(classes, dtype=dtype)[rng.integers(0, classes, batch)])
        for _ in range(2)]
    return [out[i % 2] for i in range(n)]


def _char_batches(n, batch, seq_len, vocab, seed=0):
    """One-hot char windows with next-char targets."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    eye = np.eye(vocab, dtype=np.float32)
    out = []
    for _ in range(n):
        ids = rng.integers(0, vocab, (batch, seq_len + 1))
        out.append(DataSet(eye[ids[:, :-1]], eye[ids[:, 1:]]))
    return out


# ---------------------------------------------------------------------------
# P0 device
# ---------------------------------------------------------------------------

def p0_device() -> dict:
    if DRY:
        os.environ["DL4J_TPU_PALLAS"] = "interpret"
    elif "DL4J_TPU_PALLAS" in os.environ:
        raise SystemExit("chip_smoke: DL4J_TPU_PALLAS is set; the smoke "
                         "must pick the kernel path from the platform")
    from importlib import metadata

    import jax
    import jaxlib

    from deeplearning4j_tpu.native_loader import load_native
    from deeplearning4j_tpu.profiling import CompileWatcher
    from deeplearning4j_tpu.profiling.cost import peak_flops
    from deeplearning4j_tpu.util.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not DRY:
        raise SystemExit(f"chip_smoke: no TPU (jax found {len(devices)}x "
                         f"{dev.platform}); nothing was run")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    peak = peak_flops(dev.device_kind)   # unknown accelerator: raises
    CompileWatcher().install()
    native = "built" if load_native("dataloader") is not None else \
        "absent (pure-Python readers)"
    say(f"P0 device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} peak_flops={peak} "
        f"compile_cache={cache_dir} native_lib={native}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# P1 trainer: ResNet-50, the north-star path
# ---------------------------------------------------------------------------

def _resnet():
    import jax

    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    side = 32 if DRY else 224
    t0 = time.perf_counter()
    net = ComputationGraph(resnet50(
        height=side, width=side, dtype="bfloat16", updater="nesterovs",
        learning_rate=0.1)).init()
    jax.block_until_ready(net.params)
    return side, net, round(time.perf_counter() - t0, 1)


def p1_trainer() -> dict:
    import jax
    side, net, init_s = _resnet()
    batch = 2 if DRY else 64
    rec = _fit_steps(net, _image_batches(5, batch, side, 1000),
                     _prefetched(net, "bfloat16"))
    rec["init_s"] = init_s
    dev = jax.devices()[0]
    check(all(leaf.devices() == {dev}
              for leaf in jax.tree_util.tree_leaves(net.params)),
          f"parameters not resident on {dev}")
    rec["memory_stats"] = dev.memory_stats() or {}
    rec["peak_bytes_in_use"] = rec["memory_stats"].get("peak_bytes_in_use")
    check(DRY or rec["peak_bytes_in_use"], "device reports no peak memory")
    say(f"P1 trainer: ResNet-50 {side}x{side} b{batch} bf16 via net.fit — "
        f"losses {rec['losses']}, init {init_s}s, warm-up "
        f"{rec['warmup_s']}s, step "
        f"{rec['step_ms']} ms, 0 compiles after warm-up, params on {dev}, "
        f"peak_bytes_in_use={rec['peak_bytes_in_use']}")
    return rec


# ---------------------------------------------------------------------------
# P2 token server
# ---------------------------------------------------------------------------

def p2_server() -> dict:
    from deeplearning4j_tpu.analysis.memory import default_kv_page_len
    from deeplearning4j_tpu.keras.server import KerasClient, KerasServer
    from deeplearning4j_tpu.models.gpt import gpt_decoder, greedy_generate
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.profiling.metrics import get_registry
    from deeplearning4j_tpu.util.serializer import ModelSerializer

    V, L, width = (13, 16, dict(d_model=16, n_heads=2, n_layers=2)) if DRY \
        else (64, 128, dict(d_model=128, n_heads=4, n_layers=4))
    net = ComputationGraph(gpt_decoder(V, L, seed=11, **width)).init()
    max_new, page = L // 4, default_kv_page_len(L)
    rng = np.random.default_rng(9)
    draw = lambda n: rng.integers(0, V, n).tolist()
    prefix = draw(2 * page)              # two full KV pages, shared
    short, mid, tail = L // 8, L // 4 + L // 16, page // 4
    # six concurrent requests of three prompt lengths, two sharing the
    # page-aligned prefix, one sampled; then the two repeats
    prompts = [prefix + draw(tail), prefix + draw(tail), draw(short),
               draw(mid), draw(mid), draw(short)]
    sampling = {"temperature": 0.8, "seed": 7}
    wave1 = [(p, None) for p in prompts[:5]] + [(prompts[5], sampling)]
    wave2 = [(prompts[3], None), (prompts[5], sampling)]
    refs = [greedy_generate(net, p, max_new) for p in prompts[:5]]

    baseline = set(threading.enumerate())
    os.makedirs(OUT_DIR, exist_ok=True)
    model = os.path.join(OUT_DIR, "gpt_serve.zip")
    ModelSerializer.write_model(net, model)
    srv = KerasServer(max_concurrency=8, queue_depth=16,
                      max_batch=4 if DRY else 16)
    answers, errors = {}, []

    def ask(i, prompt, sampling, delay_s):
        time.sleep(delay_s)
        cli = KerasClient(srv.host, srv.port)
        try:
            kw = {"sampling": sampling} if sampling else {}
            answers[i] = cli.generate(prompt, max_new, model=model,
                                      **kw)["tokens"]
        except Exception as e:  # noqa: BLE001 — reported by the phase
            errors.append(f"request {i}: {type(e).__name__}: {e}")
        finally:
            cli.close()

    def wave(reqs, first, stagger_s):
        threads = [threading.Thread(
            target=ask, args=(first + k, p, s, stagger_s * k), daemon=True)
            for k, (p, s) in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600.0)
        check(not any(t.is_alive() for t in threads), "a client hung")

    try:
        # staggered, so the first prefix owner registers its pages
        # before its twin is admitted
        wave(wave1, 0, 0.2)
        prefills = srv._gen.stats()["prefill_steps"]
        wave(wave2, len(wave1), 0.0)
        stats = srv._gen.stats()
    finally:
        srv.drain(grace_s=5.0)
    check(not errors, "; ".join(errors))
    for i, ref in enumerate(refs):
        check(answers[i] == ref, f"request {i}: served {answers[i]} != "
              f"greedy_generate {ref}")
    check(answers[6] == refs[3], "the exact repeat answered differently")
    check(answers[5] == answers[7] and len(answers[5]) == max_new,
          f"sampled pair differs: {answers[5]} vs {answers[7]}")
    decode_steps = get_registry().counter("serving_decode_steps_total").value
    check(decode_steps > 0, "no decode step ran")
    check(stats["kv_pages_shared"] >= 2,
          f"kv_pages_shared={stats['kv_pages_shared']}")
    check(stats["prefill_steps"] == prefills,
          f"repeats prefilled again ({prefills} -> "
          f"{stats['prefill_steps']})")
    deadline = time.monotonic() + 10.0
    while set(threading.enumerate()) - baseline and \
            time.monotonic() < deadline:
        time.sleep(0.05)
    leaked = set(threading.enumerate()) - baseline
    check(not leaked,
          f"threads left after drain: {sorted(t.name for t in leaked)}")
    rec = {"requests": len(answers), "decode_steps": int(decode_steps),
           "kv_pages_shared": stats["kv_pages_shared"],
           "prefill_steps": stats["prefill_steps"],
           "compiles": stats["compiles"], "compile_s": stats["compile_s"]}
    say(f"P2 server: gpt_decoder({V}, {L}, {width}) over KerasServer — "
        f"8 requests, 5 greedy == greedy_generate, repeat identical, "
        f"sampled pair identical; {rec}; threads back to baseline")
    return rec


# ---------------------------------------------------------------------------
# P3 kernels: parity against the XLA reference, then the trainers
# ---------------------------------------------------------------------------

def _parity(name, kernel, reference, args, cot, tol) -> dict:
    """Kernel against its XLA reference, outputs and gradients of
    ``sum(first output * cot)`` w.r.t. every argument, max abs error.

    The reference runs at HIGHEST matmul precision. The kernel runs
    twice. Traced under ``default_matmul_precision("highest")`` its dots
    lower to Mosaic's fp32 contract precision, and it must meet ``tol``
    — the logic check: a real bug (gate order, stale carry, wrong mask)
    is O(0.1-1). At the default precision — what the trainers run —
    Mosaic's f32 dot is a bf16 MXU pass, exactly like XLA's own default
    on a TPU (measured on the v5e, jax 0.9.0: both drift ~1e-2 from the
    HIGHEST reference), so there the bound is relative: no worse than
    4x what XLA's default-precision run of the reference costs."""
    import jax
    import jax.numpy as jnp

    def run(fn, precision):
        loss = lambda *a: jnp.sum(fn(*a)[0] * cot)
        with jax.default_matmul_precision(precision):
            return (*jax.jit(fn)(*args), *jax.jit(
                jax.grad(loss, argnums=tuple(range(len(args)))))(*args))

    def err(got, ref):
        f32 = lambda x: x.astype(jnp.float32)
        return max(float(jnp.max(jnp.abs(f32(a) - f32(b))))
                   for a, b in zip(got, ref))

    ref = run(reference, "highest")
    rec = {"fp32": err(run(kernel, "highest"), ref),
           "default": err(run(kernel, "default"), ref),
           "xla_default": err(run(reference, "default"), ref)}
    check(rec["fp32"] < tol, f"{name}: max_abs_err={rec['fp32']:.3e} at "
          f"fp32 contract precision (tol {tol:.1e})")
    check(rec["default"] < 4 * rec["xla_default"] + tol,
          f"{name}: max_abs_err={rec['default']:.3e} at default precision "
          f"against {rec['xla_default']:.3e} for XLA's own default")
    return {k: float(f"{v:.2e}") for k, v in rec.items()}


def lstm_parity(B, T, F, H) -> dict:
    """Pallas fused LSTM (forward and custom-VJP backward) against a
    ``lax.scan`` of the same cell. Rounding drift accumulates along the
    recurrence, hence a T-proportional bound."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_kernels import fused_lstm

    rng = np.random.default_rng(7)
    shapes = ((B, T, F), (F, 4 * H), (H, 4 * H), (4 * H,), (B, H), (B, H))
    args = [jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.1)
            for s in shapes]
    cot = jnp.asarray(rng.normal(size=(B, T, H)).astype(np.float32) * 0.1)

    def kernel(x, w, rw, b, h0, c0):
        return fused_lstm(x, w, rw, b, None, h0, c0, forget_bias=1.0,
                          interpret=DRY)

    def scan_ref(x, w, rw, b, h0, c0):
        xz = (x.reshape(B * T, F) @ w + b).reshape(B, T, 4 * H)

        def step(carry, z_t):
            h, c = carry
            z = z_t + h @ rw
            i = jax.nn.sigmoid(z[:, :H])
            f = jax.nn.sigmoid(z[:, H:2 * H] + 1.0)
            g = jnp.tanh(z[:, 2 * H:3 * H])
            o = jax.nn.sigmoid(z[:, 3 * H:])
            c2 = f * c + i * g
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2

        (hT, cT), ys = jax.lax.scan(step, (h0, c0), jnp.swapaxes(xz, 0, 1))
        return jnp.swapaxes(ys, 0, 1), hT, cT

    return _parity(f"fused_lstm B={B} T={T} F={F} H={H}", kernel, scan_ref,
                   args, cot, tol=max(1e-3, 2.5e-4 * T))


def attention_parity(B, H, T, D, dtype="float32") -> dict:
    """Pallas flash attention (forward and FA2 backward, causal) against
    ``attention_reference`` in float32 on the same inputs. In bfloat16
    the kernel's products take bfloat16 operands and its outputs and
    gradients are bfloat16, so the bound is bfloat16's own on values of
    order one to ten, where a real bug is still O(0.1-1) on many of them
    at once."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import attention_reference
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    rng = np.random.default_rng(11)
    q, k, v, cot = (jnp.asarray(rng.normal(size=(B, H, T, D))
                                .astype(np.float32)) for _ in range(4))
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    return _parity(
        f"flash_attention B={B} H={H} T={T} D={D} {dtype}",
        lambda q, k, v: (flash_attention(q, k, v, causal=True,
                                         interpret=DRY),),
        lambda q, k, v: (attention_reference(
            *(x.astype(jnp.float32) for x in (q, k, v)), causal=True),),
        (q, k, v), cot, tol=5e-4 if dtype == "float32" else 6e-2)


def _rel_errors(names: str, got, ref) -> dict:
    """The largest error over the largest entry, for each of ``names``."""
    import jax.numpy as jnp
    rec = {}
    for what, a, b in zip(names.split(), got, ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        rec[what] = float(f"{err:.2e}")
    return rec


def delta_rule_parity(B, T, H, dk, dv, dtype="bfloat16") -> dict:
    """The gated delta rule with its chunk-local work in the Pallas kernels
    (forward and backward) against the same function on the XLA path, on
    the same inputs: the output and the gradients of all five inputs, the
    largest error over the largest entry. Both paths make the same
    products in ``dtype``, so what parts them is where each rounds:
    bfloat16's 2^-8 on a few entries, where a real bug (a mask, a decay's
    index, a missing term of a cotangent) is O(0.1-1) on many."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.linear_attention import (
        gated_delta_rule_chunked)
    from deeplearning4j_tpu.profiling.metrics import get_registry

    rng = np.random.default_rng(13)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    args = (f32(unit(rng.normal(size=(B, T, H, dk))) / np.sqrt(dk)),
            f32(unit(rng.normal(size=(B, T, H, dk)))),
            jnp.asarray(rng.normal(size=(B, T, H, dv)), dtype),
            f32(np.log(rng.uniform(0.9, 0.9999, (B, T, H)))),
            f32(rng.uniform(0.2, 2.0, (B, T, H))))
    cot = f32(rng.normal(size=(B, T, H, dv)))

    def run(pallas):
        before = os.environ.get("DL4J_TPU_PALLAS")
        os.environ["DL4J_TPU_PALLAS"] = pallas      # read once a trace
        try:
            fn = lambda *a: gated_delta_rule_chunked(*a, compute_dtype=dtype)
            loss = lambda *a: jnp.sum(fn(*a) * cot)
            return (jax.jit(fn)(*args), *jax.jit(
                jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))
        finally:
            os.environ.pop("DL4J_TPU_PALLAS")
            if before is not None:
                os.environ["DL4J_TPU_PALLAS"] = before

    traces = get_registry().labeled_counter("pallas_gdn_chunk_traces_total")
    ref = run("off")
    kernel_traces = traces.labels(path="kernel").value
    got = run("interpret" if DRY else "auto")
    kernel_traces = traces.labels(path="kernel").value - kernel_traces
    name = f"gated_delta_rule B={B} T={T} H={H} dk={dk} dv={dv} {dtype}"
    check(kernel_traces == 2, f"{name}: {kernel_traces:.0f} traces took the "
          "kernel path, of the output's and the gradient's two")
    rec = _rel_errors("o dq dk dv dlog_alpha dbeta", got, ref)
    tol = 2e-2 if dtype == "bfloat16" else 1e-3
    check(max(rec.values()) < tol, f"{name}: kernel against XLA path {rec} "
          f"(tol {tol:.0e} of the largest entry)")
    return rec


def window_attention_parity(B, H, T, D, Dv, window, dtype) -> dict:
    """The flash kernels with a window and a value wider than its key (as
    a differential attention layer calls them) against
    ``attention_reference`` in float32 with the mask written out: the
    output and ``dq, dk, dv``, the largest error over the largest entry."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import attention_reference
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    rng = np.random.default_rng(17)
    draw = lambda d: jnp.asarray(rng.normal(size=(B, H, T, d)), jnp.float32)
    q, k, v, cot = draw(D), draw(D), draw(Dv), draw(Dv)
    kernel = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=DRY)
    plain = lambda q, k, v: attention_reference(
        q, k, v, causal=True, window=window)

    def run(fn, args):
        loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot)
        return (jax.jit(fn)(*args),
                *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args))

    with jax.default_matmul_precision("highest"):
        ref = run(plain, (q, k, v))
        got = run(kernel, tuple(x.astype(dtype) for x in (q, k, v)))
    rec = _rel_errors("o dq dk dv", got, ref)
    tol = 3e-2 if dtype == "bfloat16" else 1e-3
    name = f"flash_attention window={window} T={T} D={D}/{Dv} {dtype}"
    check(max(rec.values()) < tol, f"{name}: kernel against the written-out "
          f"mask {rec} (tol {tol:.0e} of the largest entry)")
    return rec


def selected_attention_parity(B, H, T, D, topk, dtype) -> dict:
    """The flash kernels under a selection (``select=``: the ``topk`` keys
    ``s <= t`` of largest random score a query, as an indexer hands them)
    against a softmax over the mask written out in float32: the output and
    ``dq, dk, dv``, the largest error over the largest entry."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import top_keys
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    rng = np.random.default_rng(19)
    draw = lambda: jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    q, k, v, cot = draw(), draw(), draw(), draw()
    select = jax.jit(lambda s: top_keys(s, 0, topk))(
        jnp.asarray(rng.normal(size=(B, T, T)), jnp.float32))
    kept = float(jnp.sum(select.astype(jnp.float32)))
    want = B * sum(min(t + 1, topk) for t in range(T))
    check(kept == want, f"selection keeps {kept:.0f} pairs of {want}")
    kernel = lambda q, k, v: flash_attention(
        q, k, v, causal=True, select=select, interpret=DRY)

    def plain(q, k, v):
        seen = (select != 0)[:, None]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", maps, v)

    def run(fn, args):
        loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot)
        return (jax.jit(fn)(*args),
                *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args))

    with jax.default_matmul_precision("highest"):
        ref = run(plain, (q, k, v))
        got = run(kernel, tuple(x.astype(dtype) for x in (q, k, v)))
    rec = _rel_errors("o dq dk dv", got, ref)
    tol = 3e-2 if dtype == "bfloat16" else 1e-3
    name = f"flash_attention select top {topk} T={T} D={D} {dtype}"
    check(max(rec.values()) < tol, f"{name}: kernel against the written-out "
          f"mask {rec} (tol {tol:.0e} of the largest entry)")
    return rec


def selection_threshold_parity(Q, T, topk) -> dict:
    """``top_keys`` (its threshold by the counting search) against the
    selection whose threshold comes from ``lax.top_k``'s sort, written out
    here as ``top_keys`` stood before PR 36: the int8 masks of the last
    ``Q`` of ``T`` queries EQUAL entry for entry, on plain rows and on rows
    rounded to halves (hundreds of equal scores across the edge)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import top_keys

    first = T - Q

    def by_sort(scores):
        seen = jnp.arange(T)[None, :] <= first + jnp.arange(Q)[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        edge = jax.lax.top_k(scores, topk)[0][..., -1:]
        above, level = scores > edge, scores == edge
        wanted = topk - jnp.sum(above, axis=-1, keepdims=True)
        among = jnp.cumsum(level.astype(jnp.int32), axis=-1)
        return (seen & (above | (level & (among <= wanted)))).astype(jnp.int8)

    plain = jnp.asarray(np.random.default_rng(23).normal(size=(Q, T)),
                        jnp.float32)
    rec = {}
    for name, scores in (("plain", plain), ("halves", jnp.round(plain * 2) / 2)):
        want = jax.jit(by_sort)(scores)
        got = jax.jit(lambda s: top_keys(s, first, topk))(scores)
        differ = int(jnp.sum(got != want))
        check(differ == 0, f"the selection on {name} rows differs from the "
              f"sort's in {differ} entries")
        rec[name] = int(jnp.sum(got.astype(jnp.int32)))
    return rec


def routed_experts_parity(N, F, M, E, K, first, count, dtype) -> dict:
    """The expert layer (sorted assignments, grouped products, one
    scatter-add) against a loop over its held experts, each run on every
    token and weighted token by token, in float32: the output and the
    gradients of the input and the four parameters, the largest error over
    the largest entry; and no assignment to a held expert dropped."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.nn.layers import RoutedExpertsLayer

    layer = RoutedExpertsLayer(n_experts=E, top_k=K, n_hidden=M, first=first,
                               count=count, activation="silu")
    layer.set_n_in(InputType.recurrent(F, N))
    rng = np.random.default_rng(23)
    draw = lambda *s: jnp.asarray(0.05 * rng.normal(size=s), jnp.float32)
    params = {"W_r": draw(F, E), "W_gate": draw(count, F, M),
              "W_up": draw(count, F, M), "W_down": draw(count, M, F)}
    u, cot = 20 * draw(1, N, F), draw(1, N, F)

    def kernel(p, u):
        return layer.apply(p, u, state=layer.init_state(), train=True,
                           rng=None)

    def plain(p, u):
        prob = jax.nn.softmax(u @ p["W_r"], axis=-1)
        top = jax.lax.top_k(prob, K)[0]
        weight = jnp.where(prob >= top[..., -1:], prob, 0.0) / jnp.sum(
            top, axis=-1, keepdims=True)
        y = 0.0
        for e in range(count):
            out = (jax.nn.silu(u @ p["W_gate"][e]) * (u @ p["W_up"][e])
                   ) @ p["W_down"][e]
            y = y + weight[..., first + e, None] * out
        return y

    def run(fn, p, u):
        loss = lambda p, u: jnp.sum(fn(p, u).astype(jnp.float32) * cot)
        g = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, u)
        return (jax.jit(fn)(p, u), g[1], g[0]["W_r"], g[0]["W_gate"],
                g[0]["W_up"], g[0]["W_down"])

    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    # both sides route the same rounded numbers, so that a token changes
    # experts on neither
    params, u = jax.tree.map(lambda a: a.astype(dtype).astype(jnp.float32),
                             (params, u))
    with jax.default_matmul_precision("highest"):
        ref = run(plain, params, u)
        got = run(lambda p, u: kernel(p, u)[0], cast(params), cast(u))
        assigned = jax.jit(kernel)(cast(params), cast(u))[1]["assigned"]
        prob = jax.nn.softmax(u @ params["W_r"], axis=-1)
        held = jax.lax.top_k(prob, K)[1]
        want = int(jnp.sum((held >= first) & (held < first + count)))
    name = f"routed experts {count} of {E} held, {K} a token, N={N} {dtype}"
    check(int(assigned.sum()) == want, f"{name}: {int(assigned.sum())} "
          f"assignments multiplied of {want} made")
    rec = _rel_errors("y du dW_r dW_gate dW_up dW_down", got, ref)
    rec["assigned"] = int(assigned.sum())
    tol = 4e-2 if dtype == "bfloat16" else 1e-3
    check(max(v for k, v in rec.items() if k != "assigned") < tol,
          f"{name}: layer against the loop over experts {rec} (tol "
          f"{tol:.0e} of the largest entry)")
    return rec


def selective_scan_parity(B, T, D, N, dtype) -> dict:
    """The selective scan by both paths, the Pallas kernels and the chunked
    XLA form, against the token-by-token one on the same inputs (``x`` in
    ``dtype``, the rest float32): the output and the gradients of all five
    inputs, the largest error over the largest entry. Steps near 0 and
    decays down to ``exp(-30)`` a token. In float32 the token-by-token side
    runs in float64 on the host, so that the gap read is each form's own
    and not the rounding of a thousand chained float32 steps on the other
    side (PERF.md section 6, PR 33)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.state_space import (
        selective_scan_chunked, selective_scan_recurrent)
    from deeplearning4j_tpu.ops.pallas_selective_scan import (
        selective_scan, selective_scan_ok)

    rng = np.random.default_rng(19)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    args = (jnp.asarray(rng.normal(size=(B, T, D)), dtype),
            f32(np.exp(rng.uniform(np.log(1e-3), np.log(2.0), (B, T, D)))),
            f32(-np.exp(rng.uniform(np.log(1e-2), np.log(16.0), (N, D)))),
            f32(rng.normal(size=(B, T, N))), f32(rng.normal(size=(B, T, N))))
    cot = f32(rng.normal(size=(B, T, D)))
    check(selective_scan_ok(T, D, N, jnp.float32, dtype),
          f"the selective scan's gate refuses T={T} d_in={D} N={N} {dtype}")

    def run(fn, args, cot):
        loss = lambda *a: jnp.sum(fn(*a) * cot)
        return (jax.jit(fn)(*args), *jax.jit(
            jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))

    if dtype == "float32":
        host = jax.devices("cpu")[0]
        with jax.enable_x64(True):
            wide = [jax.device_put(np.asarray(a, np.float64), host)
                    for a in args + (cot,)]
            ref = [np.asarray(r) for r in run(
                selective_scan_recurrent, wide[:-1], wide[-1])]
    else:
        ref = run(selective_scan_recurrent, args, cot)
    # float32, chunked: 1.2e-5 to 3.2e-5 read on the v5e, whose float32 exp
    # is biased by -1e-6 and the chunked form chains sixteen steps, sixteen
    # runs and T / 256 blocks of it (2e-7 on the CPU)
    tols = {"chunked": 2e-2 if dtype == "bfloat16" else 1e-4,
            "kernel": 2e-2 if dtype == "bfloat16" else SCAN_KERNEL_TOL}
    got = {"chunked": run(selective_scan_chunked, args, cot),
           "kernel": run(functools.partial(selective_scan, interpret=DRY),
                         args, cot)}
    refs = {path: (ref, tol) for path, tol in tols.items()}
    if dtype == "float32":      # the same chain of the same exp
        got["kernel_same_device"] = got["kernel"]
        refs["kernel_same_device"] = (
            run(selective_scan_recurrent, args, cot), 1e-5)
    name = f"selective_scan B={B} T={T} d_in={D} N={N} {dtype}"
    out = {}
    for path, (want, tol) in refs.items():
        rec = _rel_errors("y dx ddelta da db dc", got[path], want)
        check(max(rec.values()) < tol, f"{name}: {path} against token by "
              f"token {rec} (tol {tol:.0e} of the largest entry)")
        out[path] = rec
    return out


def smallthinker_probe(T, vocab) -> dict:
    """SmallThinker's four-layer period at published widths (8 of 64
    experts held) trained four steps through ``net.fit`` at length ``T``,
    remat on, bfloat16 policy: the windowed and the full flash kernels in
    the step, no layer on the XLA side of a gate, finite losses; and how
    the tokens route, by layer, with the embedding's rows at the model's
    own 0.02 and scaled to 1.0 (the benchmark's): the largest share of one
    held expert among the held assignments, and the assignments held of
    the ``6 T`` made."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.smallthinker import (
        smallthinker, smallthinker_tiny)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.profiling.metrics import get_registry

    build = smallthinker_tiny if DRY else smallthinker
    kw = {} if DRY else dict(n_layers=4, held_experts=8)
    conf = build(vocab, T, remat=True, precision="bf16",
                 learning_rate=2e-5, **kw)
    rng = np.random.default_rng(31)
    ids = rng.integers(0, vocab, (4, 1, T + 1), dtype=np.int32)
    batches = [DataSet(i[:, :-1], i[:, 1:]) for i in ids]
    gated = get_registry().labeled_counter("pallas_gate_fallbacks_total")
    before = gated.value
    rec = {}
    for std in (0.02, 1.0):
        net = ComputationGraph(conf).init()
        net.params["embed"]["W"] = net.params["embed"]["W"] * (std / 0.02)
        run = _fit_steps(net, batches, _prefetched(net, None))
        spread = {}
        for node, state in net.states.items():
            if "assigned" in state:
                a = np.asarray(jax.device_get(state["assigned"]))
                spread[node] = [round(float(a.max() / max(a.sum(), 1)), 3),
                                int(a.sum())]
        run["routing"] = spread
        rec[f"embedding_std {std}"] = run
    calls = _lowered_step_text(net, batches[0]).count("tpu_custom_call")
    check(DRY or calls > 0, "SmallThinker: no Mosaic custom call in the "
          "lowered train step")
    check(gated.value == before, f"SmallThinker: a shape gate sent "
          f"{gated.value - before:.0f} layer(s) to the XLA path")
    rec["tpu_custom_calls"] = calls
    return rec


def _lowered_step_text(net, batch) -> str:
    from deeplearning4j_tpu.profiling.cost import step_example_args
    return net._train_step_fn.lower(
        *step_example_args(net, batch)).as_text()


def _kernel_trainer(name, net, batches) -> dict:
    from deeplearning4j_tpu.profiling.metrics import get_registry
    rec = _fit_steps(net, batches, _prefetched(net, None))
    calls = _lowered_step_text(net, batches[0]).count("tpu_custom_call")
    check(DRY or calls > 0, f"{name}: no Mosaic custom call in the lowered "
          "train step — the XLA path stood in for the kernel")
    gated = get_registry().labeled_counter(
        "pallas_gate_fallbacks_total").value
    check(gated == 0, f"{name}: a shape gate sent {gated:.0f} layer(s) to "
          "the XLA path")
    rec["tpu_custom_calls"] = calls
    say(f"P3 kernels: {name} via net.fit — losses {rec['losses']}, step "
        f"{rec['step_ms']} ms, {calls} tpu_custom_call in the lowered "
        "train step" + (" (interpret mode: none expected)" if DRY else ""))
    return rec


def p3_kernels() -> dict:
    import jax

    from deeplearning4j_tpu import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.models.gpt import gpt_decoder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    # (B, T, F, H) and (B, H, T, D): tile-aligned, then the pad path
    parity = {f"fused_lstm{shape}": lstm_parity(*shape)
              for shape in ((8, 16, 128, 128), (6, 16, 72, 200))}
    parity.update({f"flash_attention{shape}": attention_parity(*shape)
                   for shape in ((2, 2, 256, 128), (2, 2, 40, 24))})
    # bfloat16 operands, and two blocks of 512 a head
    parity["flash_attention(2, 2, 1024, 128) bfloat16"] = attention_parity(
        2, 2, 1024, 128, dtype="bfloat16")
    rec = {"parity_max_abs_err": parity}
    say("P3 kernels: parity vs HIGHEST-precision XLA reference, outputs "
        f"and gradients, max abs err {parity}")
    # the hybrid cell's shape (a padded length at the tiny size); a
    # float32 one whose length is no multiple of the chunk and, on the
    # chip, runs in blocks of 4 chunks (33 as 36); one in blocks of 8
    shapes = ((1, 200, 2, 8, 16), (2, 100, 2, 8, 16), (1, 320, 2, 8, 16)) \
        if DRY else ((1, 8192, 30, 96, 192), (2, 2100, 4, 96, 192),
                     (1, 4200, 4, 96, 192))
    with jax.default_matmul_precision("highest"):
        wide = delta_rule_parity(*shapes[1], dtype="float32")
    rec["delta_rule_rel_err"] = {
        f"{shapes[0]} bfloat16": delta_rule_parity(*shapes[0]),
        f"{shapes[1]} float32": wide,
        f"{shapes[2]} bfloat16": delta_rule_parity(*shapes[2])}
    say("P3 kernels: gated delta rule, kernels against the XLA path, error "
        f"over the largest entry {rec['delta_rule_rel_err']}")

    # the SambaY cell's shapes (four of its forty kernel heads: the plain
    # reference keeps every score), then lengths that are no multiple of a
    # block, in float32
    shapes = ((1, 2, 300, 16, 32, 24), (2, 2, 100, 16, 32, 40)) if DRY else (
        (1, 4, 8192, 64, 128, 512), (2, 2, 1100, 64, 128, 512))
    rec["window_attention_rel_err"] = {
        f"{shapes[0]} bfloat16": window_attention_parity(
            *shapes[0], dtype="bfloat16"),
        f"{shapes[1]} float32": window_attention_parity(
            *shapes[1], dtype="float32")}
    say("P3 kernels: flash kernels with a window against the written-out "
        "mask, error over the largest entry "
        f"{rec['window_attention_rel_err']}")
    shapes = ((1, 300, 128, 8), (2, 100, 128, 8)) if DRY else (
        (1, 8192, 5120, 16), (2, 1100, 1024, 16))
    rec["selective_scan_rel_err"] = {
        f"{shapes[0]} bfloat16": selective_scan_parity(
            *shapes[0], dtype="bfloat16"),
        f"{shapes[1]} float32": selective_scan_parity(
            *shapes[1], dtype="float32")}
    say("P3 kernels: selective scan, kernels and chunked form against token "
        f"by token, error over the largest entry "
        f"{rec['selective_scan_rel_err']}")

    # the sparse cell's shapes (four of its 32 heads: the plain side keeps
    # every score; its sixteen held of 128 experts at the cell's widths),
    # then a padded length and a range that starts further on, in float32
    shapes = ((1, 2, 300, 16, 40), (2, 2, 100, 16, 24)) if DRY else (
        (1, 4, 8192, 128, 2048), (2, 2, 1100, 128, 512))
    rec["selected_attention_rel_err"] = {
        f"{shapes[0]} bfloat16": selected_attention_parity(
            *shapes[0], dtype="bfloat16"),
        f"{shapes[1]} float32": selected_attention_parity(
            *shapes[1], dtype="float32")}
    say("P3 kernels: flash kernels under a selection against the "
        "written-out mask, error over the largest entry "
        f"{rec['selected_attention_rel_err']}")
    shape = (64, 256, 40) if DRY else (512, 8192, 2048)
    rec["selection_threshold_kept"] = selection_threshold_parity(*shape)
    say(f"P3 kernels: the selection of {shape[2]} of {shape[1]} keys by the "
        "counting threshold equals the sort's entry for entry, kept "
        f"{rec['selection_threshold_kept']}")
    shapes = ((64, 32, 16, 16, 4, 0, 4), (50, 32, 16, 16, 4, 8, 4)) \
        if DRY else ((8192, 2048, 768, 128, 8, 0, 16),
                     (1100, 256, 128, 128, 8, 48, 16))
    rec["routed_experts_rel_err"] = {
        f"{shapes[0]} bfloat16": routed_experts_parity(
            *shapes[0], dtype="bfloat16"),
        f"{shapes[1]} float32": routed_experts_parity(
            *shapes[1], dtype="float32")}
    say("P3 kernels: routed experts, grouped products against the loop "
        "over experts, error over the largest entry "
        f"{rec['routed_experts_rel_err']}")

    # SmallThinker's period past one window of 4,096 keys (a window of 8
    # at the tiny size)
    T, vocab = (40, 64) if DRY else (6144, 4096)
    rec["smallthinker"] = smallthinker_probe(T, vocab)
    say(f"P3 kernels: SmallThinker's four layers at T={T} via net.fit, "
        f"losses and routing by the embedding's std {rec['smallthinker']}")

    hidden, T, K, B = (32, 8, 16, 4) if DRY else (256, 64, 96, 32)
    lstm = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(7)
        .updater("rmsprop", learning_rate=1e-3).weight_init("xavier").list()
        .layer(GravesLSTM(n_out=hidden, activation="tanh"))
        .layer(GravesLSTM(n_out=hidden, activation="tanh"))
        .layer(RnnOutputLayer(n_out=K, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(K, T)).build()).init()
    rec["char_lstm"] = _kernel_trainer(
        f"char-LSTM 2xGravesLSTM({hidden}) T={T} b{B} f32", lstm,
        _char_batches(5, B, T, K))

    V, T, B, width = (16, 8, 4, dict(d_model=32, n_heads=2, n_layers=2)) \
        if DRY else (96, 128, 32, dict(d_model=256, n_heads=8, n_layers=4))
    gpt = ComputationGraph(gpt_decoder(V, T, seed=7, **width)).init()
    rec["gpt"] = _kernel_trainer(
        f"gpt_decoder({V}, {T}, {width}) b{B} f32", gpt,
        _char_batches(5, B, T, V))
    return rec


# ---------------------------------------------------------------------------
# P4 four chips: data parallelism over ICI
# ---------------------------------------------------------------------------

def p4_multichip() -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.mesh import MeshContext
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    devices = jax.devices()
    if len(devices) < 4:
        say(f"multichip: not run, {len(devices)} device")
        return {"run": False}
    side, net, _ = _resnet()
    trainer = ParallelTrainer(net, MeshContext.create(n_data=4))
    batch = 8 if DRY else 256
    batches = _image_batches(5, batch, side, 1000, dtype=jnp.bfloat16)
    rec = _fit_steps(net, batches, trainer.fit)
    feats = trainer.mesh.shard_batch(jnp.asarray(batches[0].features))
    check(feats.sharding.spec[0] == "data"
          and len({s.device for s in feats.addressable_shards}) == 4
          and feats.addressable_shards[0].data.shape[0] == batch // 4,
          f"feature batch not sharded over 'data': {feats.sharding}")
    four = set(devices[:4])
    for leaf in jax.tree_util.tree_leaves(net.params):
        check({s.device for s in leaf.addressable_shards} == four,
              f"a parameter has no live shard on every chip: {leaf.sharding}")
    in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
              for d in devices[:4]}
    check(DRY or all(in_use.values()), f"idle chip: {in_use}")
    rec["bytes_in_use"] = in_use
    say(f"P4 multichip: ResNet-50 {side}x{side} global b{batch} bf16 via "
        f"ParallelTrainer(n_data=4) — losses {rec['losses']}, step "
        f"{rec['step_ms']} ms, batch sharded over 'data' "
        f"({batch // 4}/chip), parameter shards live on 4 chips, "
        f"bytes_in_use {in_use}")
    return rec


# ---------------------------------------------------------------------------

PHASES = (("P1 trainer", p1_trainer), ("P2 server", p2_server),
          ("P3 kernels", p3_kernels), ("P4 multichip", p4_multichip))


def main() -> int:
    from deeplearning4j_tpu.profiling.metrics import get_registry

    t_start = time.perf_counter()
    device = p0_device()          # no TPU: SystemExit, nothing printed after
    summary = {"device": device}
    failed = []
    for name, phase in PHASES:
        t0 = time.perf_counter()
        try:
            summary[name] = phase()
        except Exception:  # noqa: BLE001 — a failed phase fails the run
            traceback.print_exc()
            last = traceback.format_exc().strip().splitlines()[-1]
            say(f"{name}: FAILED ({last[:300]})")
            failed.append(name)
        say(f"{name}: {time.perf_counter() - t0:.1f}s")
    reg = get_registry()
    summary["compile"] = {
        "compiles": reg.counter("jax_compile_total").value,
        "compile_s": round(reg.counter("jax_compile_seconds_total").value, 1),
        "cache_hits": reg.counter("jax_compile_cache_hits_total").value,
        "cache_misses": reg.counter("jax_compile_cache_misses_total").value,
    }
    summary["wall_s"] = round(time.perf_counter() - t_start, 1)
    summary["failed"] = failed
    say(f"compile: {summary['compile']}; wall {summary['wall_s']}s")
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
        f.write("\n")
    if failed:
        say(f"chip_smoke FAILED: {failed}")
        return 1
    if DRY:
        say("dry run passed on the CPU; this says nothing about the chip")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
