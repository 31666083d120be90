"""Benchmark: training and serving throughput ladder on one accelerator.

North-star metric (BASELINE.json): samples/sec/chip, ResNet-50 ImageNet,
``fit()`` equivalent. The reference publishes no numbers (BASELINE.md), so
``vs_baseline`` is the ratio against the first recorded value of the same
metric (BENCH_HISTORY below; 1.0 on the first successful run).

One process: ``python bench.py`` initializes the backend, climbs the rung
ladder in the process it was started in and prints one JSON record per
rung on stdout, each naming its ``platform`` and ``device_kind``. A chip
belongs to one process at a time, so nothing here starts a child.

- The run needs an accelerator. With none it exits non-zero before any
  rung, unless ``BENCH_SMOKE=1`` asks for the CPU smoke (tiny shapes;
  every metric name carries the ``_SMOKE`` suffix so a CPU number can
  never be read as a device number).
- A rung that raises prints a failure record (``failed: true``, with the
  tracer's open-span stack naming the phase in flight and the flight-
  recorder tail), the ladder goes on to the next rung, and the process
  exits non-zero at the end. Exit 0 means every rung ran.
- Every phase is stamped to stderr; the per-rung watchdog
  (BENCH_RUNG_WALL, default 600s, report-only) prints a timeout record
  and dumps a diagnostic bundle if a rung wedges.
- Accelerator records are also merged into ``chiprun_out/bench/
  BENCH_BANKED.json`` as they are measured. Nothing tracked by git is
  written: output, bundles and the compile cache (``.jax_cache`` unless
  ``JAX_COMPILATION_CACHE_DIR`` places it) are all ignored directories.
- Profiling: every rung runs inside spans of the process-global tracer
  and its record carries ``flops_per_step`` / ``analytic_mfu`` /
  ``compile_s`` from XLA's compiled-step cost analysis (BENCH_COST=0
  skips; a CPU has no MFU). BENCH_TRACE=<path> exports the Perfetto
  timeline.

Kernel-vs-XLA parity of the two Pallas kernels is ``chip_smoke.py``'s job
(phase P3), where a mismatch is fatal.

Model init is one jitted program (nn/graph.py ``init``): eager per-tensor
init would compile and dispatch hundreds of tiny programs.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback

import numpy as np

# stdlib-only imports (no jax at module load): the process-global span
# tracer every rung emits into (failure/timeout records carry its open-
# span stack), the flight recorder + stall watchdog (ISSUE 17: a wedged
# rung leaves a diagnostic bundle on disk, not silence), and the single
# peak-FLOPs table both MFU fields are computed against.
from deeplearning4j_tpu.profiling import (StallWatchdog, get_flightrec,
                                          get_tracer, peak_flops)
from deeplearning4j_tpu.profiling.flightrec import record as flight_record

# First-EVER recorded value per metric — the fixed vs_baseline
# denominator. Do NOT update on later improvements (that would hide the
# cumulative speedup); metrics still None here take their baseline from
# the first value banked into BENCH_BANKED.json.
BENCH_HISTORY = {
    # First real-TPU numbers, banked r03 (v5e-1, this harness): LeNet
    # 28811.7, ResNet-50 b64@224 1904.97 samples/s/chip. The small/xl
    # rungs' r03 probe values were corrupted by a warmup=1 recompile
    # (uncommitted-vs-committed sharding cache miss, since fixed in
    # DevicePrefetchIterator) and are not baselines.
    "resnet50_b64_bf16_samples_per_sec_per_chip": 1904.97,
    "resnet50_96px_b16_bf16_samples_per_sec_per_chip": None,
    "lenet_mnist_b128_samples_per_sec_per_chip": 28811.7,
    "resnet50_b128_bf16_samples_per_sec_per_chip": None,
    "charlstm_b32_t64_samples_per_sec_per_chip": None,
    "vgg16_cifar10_b128_bf16_samples_per_sec_per_chip": None,
    # serving rung (ISSUE 6): requests/sec inside the latency SLO
    # through the continuous-batching KerasServer
    "keras_serve_requests_per_sec": None,
    # lm_serve rung (ISSUE 15): generated tokens/sec inside the latency
    # SLO through the TOKEN-level continuous-batching gateway (KV
    # caches + prefill/decode AOT buckets); the record also carries the
    # whole-predict baseline on the same workload
    "lm_serve_tokens_per_sec_at_slo": None,
    # input rung (ISSUE 7): samples/sec through the sharded streaming
    # input pipeline ALONE (read+decode+h2d, no training step) —
    # CPU-runnable, so input-pipeline PRs are measurable off-TPU too
    "input_pipeline_samples_per_sec": None,
}

# Peak FLOP/s per chip: ONE table for both MFU fields (the hand-model
# `mfu` and the cost-analysis `analytic_mfu`) — profiling/cost.py's
# PEAK_FLOPS_PER_CHIP, via peak_flops(). A second copy here would let
# the two numbers silently disagree when a chip generation is added.

T0 = time.perf_counter()


# Everything an accelerator run writes lands here (ignored by git): the
# bank of measured records and the watchdog's diagnostic bundles.
_OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chiprun_out", "bench")

# Durable perf record: every successful accelerator rung is merged into
# this file the moment it is measured, so a later hang or timeout cannot
# erase the run's earlier evidence.
_BANK_PATH = os.path.join(_OUT_DIR, "BENCH_BANKED.json")


def _bank_record(rec: dict, amend: bool = False) -> None:
    """Merge one rung record into the bank at ``_BANK_PATH`` (atomic
    replace).

    ``records`` keeps the best value per metric; ``runs`` the measurement
    log (most recent last, capped); ``baselines`` the first-ever value per
    metric (never evicted — the stable vs_baseline denominator).
    ``amend=True`` replaces the newest run entry of the same metric
    instead of appending (used to attach the parity verdict post-hoc
    without duplicating the run). Smoke/CPU records are the caller's
    responsibility to exclude.
    """
    try:
        if os.path.exists(_BANK_PATH):
            with open(_BANK_PATH) as f:
                data = json.load(f)
        else:
            data = {"records": {}, "runs": []}
    except Exception:  # noqa: BLE001 — a corrupt bank must not stop banking
        data = {"records": {}, "runs": []}
    rec = dict(rec,
               banked_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    runs = data.setdefault("runs", [])
    if amend:
        for i in range(len(runs) - 1, -1, -1):
            if runs[i].get("metric") == rec["metric"]:
                runs[i] = rec
                break
        else:
            runs.append(rec)
    else:
        runs.append(rec)
    data["runs"] = runs[-200:]
    if rec.get("value"):
        data.setdefault("baselines", {}).setdefault(rec["metric"],
                                                    rec["value"])
    # records[] keeps the BEST value per metric. Direction comes from the
    # record itself (rec["direction"]: "max"|"min"); default "max" because
    # every current banked metric is a throughput. A lower-is-better metric
    # (step_ms, latency) MUST set direction="min" or it would bank
    # regressions as best.
    cur = data.setdefault("records", {}).get(rec["metric"])
    direction = rec.get("direction") or (cur or {}).get("direction", "max")
    if cur is None:
        better = True
    elif direction == "min":
        better = rec.get("value", float("inf")) <= cur.get("value",
                                                           float("inf"))
    else:
        better = rec.get("value", 0) >= cur.get("value", 0)
    if better:
        # persist the resolved direction so a later direction-less call
        # can't flip a min-metric back to max-is-better
        data["records"][rec["metric"]] = dict(rec, direction=direction)
    os.makedirs(os.path.dirname(_BANK_PATH), exist_ok=True)
    tmp = _BANK_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, _BANK_PATH)
    _stamp(f"banked {rec['metric']}={rec.get('value')} -> {_BANK_PATH}")


def _banked_baseline(metric: str):
    """vs_baseline denominator for ``metric``: the BENCH_HISTORY literal
    (the authoritative first-ever measurement — do NOT update it on later
    improvements) when set, else the first value ever banked into the
    bank's ``baselines``."""
    lit = BENCH_HISTORY.get(metric)
    if lit is not None:
        return lit
    try:
        with open(_BANK_PATH) as f:
            return json.load(f).get("baselines", {}).get(metric)
    except Exception:  # noqa: BLE001
        return None


def _stamp(msg: str) -> None:
    """Phase-progress line on stderr, flushed immediately, so a timeout is
    attributable to the phase after the last stamp."""
    print(f"[bench {time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _precision_fields(default: str = "float32") -> dict:
    """``compute_dtype`` / ``params_dtype`` — fields EVERY rung record
    carries (ISSUE 10) so a ladder entry names the matmul precision it
    ran at next to its throughput. ``BENCH_PRECISION`` (fp32|bf16|fp16
    or a dtype name, the ``nn.updater.PrecisionPolicy`` presets)
    overrides; ``default`` is the rung's own dtype choice."""
    from deeplearning4j_tpu.nn.updater import PrecisionPolicy
    pol = PrecisionPolicy.parse(
        os.environ.get("BENCH_PRECISION") or default)
    return {"compute_dtype": pol.compute_dtype,
            "params_dtype": pol.params_dtype}


def _tuned_precision_fields(tuned) -> dict:
    """compute/params dtypes of a BENCH_AUTOTUNE run — what the tuned
    trainer ACTUALLY ran at. Unlike :func:`_precision_fields`, the
    BENCH_PRECISION env knob does NOT apply: the tuner chose the
    policy, and the record must name what ran."""
    from deeplearning4j_tpu.nn.updater import PrecisionPolicy
    pol = PrecisionPolicy.parse(tuned.precision)
    return {"compute_dtype": pol.compute_dtype,
            "params_dtype": pol.params_dtype}


def _failure_record(metric: str, detail: str, open_spans, kind: str,
                    bundle_path: str = None) -> dict:
    """A rung failure as a first-class JSON record: value 0, marked
    ``failed``, the open/error span stack naming the phase that hung or raised, the
    flight-recorder tail (the last structured events every subsystem
    emitted before the failure), and the resilience counters
    (retries/rollbacks/skipped batches/injected faults — plus the
    ``elastic_*`` family: resizes, elections, scale-ups, fences,
    barrier timeouts) so the record carries the run's fault history
    next to its diagnosis. ``bundle_path`` names the on-disk
    diagnostic bundle when the stall watchdog wrote one."""
    from deeplearning4j_tpu.profiling.metrics import get_registry
    reg = get_registry()
    err = {"kind": kind, "detail": detail,
           "open_spans": list(open_spans),
           "flight_tail": get_flightrec().tail(32),
           "resilience": {**reg.snapshot("resilience_"),
                          **reg.snapshot("elastic_")}}
    if bundle_path:
        err["bundle"] = bundle_path
    return {"metric": metric, "value": 0.0, "unit": "samples/sec/chip",
            "vs_baseline": 0.0, "failed": True, "error": err}


class _RungWatchdog:
    """Report-only per-rung timer: if the rung outlives ``wall_s`` the
    watchdog prints a timeout failure record naming the tracer's open
    spans to stdout IMMEDIATELY — it never kills anything (a hung XLA
    call is not interruptible anyway), but the record is already on
    stdout when an outer time limit kills the run, so the hang arrives
    diagnosed instead of silent. ``wall_s <= 0`` disables."""

    def __init__(self, metric: str, wall_s: float, tracer,
                 emit=None, stall_watchdog=None):
        self.metric = metric
        self.wall_s = wall_s
        self.tracer = tracer
        self.emit = emit or (lambda line: print(line, flush=True))
        self.stall_watchdog = stall_watchdog
        self.fired = False
        self._timer = None

    def _fire(self):
        self.fired = True
        spans = self.tracer.open_span_stack()
        bundle_path = None
        if self.stall_watchdog is not None:
            # full black box on disk: thread stacks, per-thread open
            # spans, heartbeat ages, metrics, flight tail
            try:
                bundle_path = self.stall_watchdog.dump(
                    reason=f"rung_timeout_{self.metric}")
            except Exception:  # noqa: BLE001 — diagnosis must not kill
                pass
        rec = _failure_record(
            self.metric,
            f"rung exceeded {self.wall_s:.0f}s (BENCH_RUNG_WALL); "
            "still running — open spans name the phase in flight",
            spans, kind="timeout", bundle_path=bundle_path)
        self.emit(json.dumps(rec))
        _stamp(f"RUNG WATCHDOG: {self.metric} over budget; open spans: "
               f"{' > '.join(spans) or '(none)'}"
               + (f"; bundle -> {bundle_path}" if bundle_path else ""))

    def __enter__(self):
        if self.wall_s > 0:
            self._timer = threading.Timer(self.wall_s, self._fire)
            self._timer.daemon = True
            self._timer.start()
        return self

    def __exit__(self, *exc):
        if self._timer is not None:
            self._timer.cancel()
        return False


def _make_stall_watchdog() -> StallWatchdog:
    """The run's stall watchdog: bundles land in BENCH_BUNDLE_DIR
    (default ``chiprun_out/bench/bundles``) so a wedged run leaves its
    black box in a predictable place; a catchable external kill
    (SIGTERM/atexit) still writes one."""
    bundle_dir = (os.environ.get("BENCH_BUNDLE_DIR")
                  or os.path.join(_OUT_DIR, "bundles"))
    return StallWatchdog(bundle_dir, interval_s=5.0, exit_dump=True)


# ---------------------------------------------------------------------------
# rung configurations
# ---------------------------------------------------------------------------

_RUNGS = ("lenet", "small", "full", "vgg", "lstm", "lm", "xl", "input",
          "serve", "lm_serve", "fleet")


def _rung_config(rung: str, smoke: bool):
    if rung == "lenet":
        return dict(model="lenet", height=28, width=28, channels=1,
                    classes=10, batch=8 if smoke else 128,
                    steps=3 if smoke else 20, warmup=2,
                    dtype="float32",
                    metric="lenet_mnist_b128_samples_per_sec_per_chip")
    if rung == "small":
        # warmup=2 everywhere: warmup=1 put a second full compile inside
        # the r03 timed region (sharding-signature cache miss; root cause
        # fixed in DevicePrefetchIterator, this is belt-and-braces)
        return dict(model="resnet50", height=32 if smoke else 96,
                    width=32 if smoke else 96, channels=3, classes=1000,
                    batch=2 if smoke else 16, steps=2 if smoke else 5,
                    warmup=2, dtype="bfloat16",
                    metric="resnet50_96px_b16_bf16_samples_per_sec_per_chip")
    if rung == "full":
        return dict(model="resnet50", height=32 if smoke else 224,
                    width=32 if smoke else 224, channels=3, classes=1000,
                    batch=2 if smoke else 64, steps=2 if smoke else 20,
                    warmup=2, dtype="bfloat16",
                    metric="resnet50_b64_bf16_samples_per_sec_per_chip")
    if rung == "xl":
        # same model/shape as 'full' at 2x batch: better MXU utilization
        # if HBM allows. Runs late in the ladder: an OOM here fails
        # the run but the b64 record is already printed and banked.
        return dict(model="resnet50", height=32 if smoke else 224,
                    width=32 if smoke else 224, channels=3, classes=1000,
                    batch=2 if smoke else 128, steps=2 if smoke else 20,
                    warmup=2, dtype="bfloat16",
                    metric="resnet50_b128_bf16_samples_per_sec_per_chip")
    if rung == "vgg":
        # BASELINE config #2: VGG-16 on CIFAR-10 (MultiLayerNetwork).
        return dict(model="vgg16", height=32, width=32, channels=3,
                    classes=10, batch=8 if smoke else 128,
                    steps=2 if smoke else 20, warmup=2, dtype="bfloat16",
                    metric="vgg16_cifar10_b128_bf16_samples_per_sec_per_chip")
    if rung == "lstm":
        # BASELINE config #4: GravesLSTM char-RNN. H=256 keeps the Pallas
        # H%128 gate satisfied so TPU runs exercise the compiled kernel.
        return dict(model="charlstm", height=0, width=0,
                    channels=8 if smoke else 64,      # timesteps
                    classes=16 if smoke else 96,      # charset
                    batch=4 if smoke else 32, steps=2 if smoke else 20,
                    warmup=2, dtype="float32",
                    metric="charlstm_b32_t64_samples_per_sec_per_chip")
    if rung == "lm":
        # ISSUE 14: the GPT decoder LM — the composition workload
        # (attention + LayerNorm + residual graph + tied head). channels
        # carries the sequence length, classes the char vocab (the
        # charlstm convention); the record's headline converts to
        # tokens/sec/chip and carries seq_len + analytic MFU.
        return dict(model="gpt", height=0, width=0,
                    channels=8 if smoke else 128,     # seq_len
                    classes=16 if smoke else 96,      # charset
                    d_model=32 if smoke else 256,
                    n_heads=2 if smoke else 8,
                    n_layers=2 if smoke else 4,
                    batch=4 if smoke else 32, steps=2 if smoke else 20,
                    warmup=2, dtype="float32",
                    metric="gpt_char_b32_t128_tokens_per_sec_per_chip")
    if rung == "input":
        # input-pipeline throughput, no training step: N sources decode
        # into MNIST-shaped minibatches through the staged pipeline
        # (parallel read/decode + ordered emission + device staging);
        # the headline is samples/sec INTO device memory
        return dict(model="input_pipeline",
                    sources=3 if smoke else 8,
                    batches_per_source=2 if smoke else 6,
                    batch=8 if smoke else 128,
                    height=28, width=28, channels=1, classes=10,
                    reader_workers=2, decode_workers=2,
                    metric="input_pipeline_samples_per_sec")
    if rung == "serve":
        # serving throughput: C concurrent clients firing N predicts at
        # the continuous-batching gateway; the headline is requests/sec
        # INSIDE the latency SLO (a number that only improves when
        # batching actually works — raw rps would reward queue-and-stall)
        return dict(model="serve_mlp", clients=4 if smoke else 12,
                    requests=48 if smoke else 240,
                    slo_ms=2000 if smoke else 250,
                    max_batch=8 if smoke else 16,
                    max_wait_ms=5.0, features=32, classes=8,
                    metric="keras_serve_requests_per_sec")
    if rung == "lm_serve":
        # ISSUE 15: token-level LM serving — C concurrent clients fire
        # mixed-length generations at the continuous-batching decode
        # gateway. Headline = generated tokens/sec INSIDE the SLO; the
        # record carries TTFT p50/p99 and the PR 6 whole-predict
        # baseline measured on the same workload (vs_whole_predict must
        # exceed 1.0 or the KV-cache path is mis-wired).
        return dict(model="gpt_serve",
                    vocab=13 if smoke else 64,
                    seq_len=16 if smoke else 128,
                    d_model=16 if smoke else 128,
                    n_heads=2 if smoke else 4,
                    n_layers=2 if smoke else 4,
                    clients=3 if smoke else 8,
                    requests=6 if smoke else 48,
                    max_new_tokens=6 if smoke else 32,
                    slo_ms=30_000 if smoke else 2_000,
                    max_rows=4 if smoke else 16,
                    metric="lm_serve_tokens_per_sec_at_slo")
    if rung == "fleet":
        # ISSUE 18: the multi-replica serving fleet — the serve rung's
        # workload dispatched across R in-process replicas through the
        # FleetRouter. Headline = aggregate requests/sec INSIDE the SLO;
        # the record carries the single-server number measured on the
        # same workload (vs_single_server — the scale-out ratio the
        # fleet must eventually justify; not gated in smoke, where R
        # replicas on one CPU just share it).
        return dict(model="fleet_mlp", replicas=3,
                    clients=4 if smoke else 12,
                    requests=48 if smoke else 240,
                    slo_ms=4000 if smoke else 250,
                    max_batch=8 if smoke else 16,
                    max_wait_ms=5.0, features=32, classes=8,
                    metric="fleet_requests_per_sec_at_slo")
    raise ValueError(f"unknown rung {rung!r}; valid: {_RUNGS}")


# ---------------------------------------------------------------------------
# the ladder: one JSON record per completed rung
# ---------------------------------------------------------------------------

def _run_rung(jax, rung: str, smoke: bool, on_accel: bool, device_kind: str,
              platform: str):
    cfg = _rung_config(rung, smoke)
    batch, steps, warmup = cfg["batch"], cfg["steps"], cfg["warmup"]
    height, width = cfg["height"], cfg["width"]
    _stamp(f"rung '{rung}': {cfg}")
    tracer = get_tracer()

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import (
        DevicePrefetchIterator, ListDataSetIterator)

    t = time.perf_counter()
    with tracer.span("build_model", model=cfg["model"]):
        if cfg["model"] == "lenet":
            from deeplearning4j_tpu.models.lenet import lenet_mnist
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
            net = MultiLayerNetwork(lenet_mnist(
                height=height, width=width, updater="nesterovs",
                learning_rate=0.01)).init()
        elif cfg["model"] == "vgg16":
            from deeplearning4j_tpu.models.vgg import vgg16_cifar10
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
            net = MultiLayerNetwork(vgg16_cifar10(
                height=height, width=width, dtype=cfg["dtype"],
                updater="nesterovs", learning_rate=0.01)).init()
        elif cfg["model"] == "charlstm":
            from deeplearning4j_tpu import (InputType,
                                            NeuralNetConfiguration)
            from deeplearning4j_tpu.nn.layers import (GravesLSTM,
                                                      RnnOutputLayer)
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
            T, K = cfg["channels"], cfg["classes"]
            net = MultiLayerNetwork(
                NeuralNetConfiguration.builder().seed(7)
                .updater("rmsprop", learning_rate=1e-3).weight_init("xavier")
                .list()
                .layer(GravesLSTM(n_out=256, activation="tanh"))
                .layer(GravesLSTM(n_out=256, activation="tanh"))
                .layer(RnnOutputLayer(n_out=K, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(K, T)).build()).init()
        elif cfg["model"] == "gpt":
            from deeplearning4j_tpu.models.gpt import gpt_decoder
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            net = ComputationGraph(gpt_decoder(
                vocab_size=cfg["classes"], seq_len=cfg["channels"],
                d_model=cfg["d_model"], n_heads=cfg["n_heads"],
                n_layers=cfg["n_layers"], seed=7,
                dtype=cfg["dtype"])).init()
        else:
            from deeplearning4j_tpu.models.resnet import resnet50
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            net = ComputationGraph(resnet50(
                height=height, width=width, dtype=cfg["dtype"],
                updater="nesterovs", learning_rate=0.1)).init()
        jax.block_until_ready(net.params)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(net.params))
    _stamp(f"model built, init'd on device in {time.perf_counter() - t:.1f}s "
           f"({n_params / 1e6:.1f}M params)")

    rng = np.random.default_rng(0)
    C, K = cfg["channels"], cfg["classes"]

    def batches(n):
        out = []
        for _ in range(n):
            if cfg["model"] in ("charlstm", "gpt"):
                # one-hot char sequences, next-char targets (C = T)
                ids = rng.integers(0, K, (batch, C + 1))
                eye = np.eye(K, dtype=np.float32)
                out.append(DataSet(eye[ids[:, :-1]], eye[ids[:, 1:]]))
                continue
            x = rng.normal(size=(batch, height, width, C)).astype(np.float32)
            y = np.eye(K, dtype=np.float32)[rng.integers(0, K, batch)]
            out.append(DataSet(x, y))
        return out

    # BENCH_AUTOTUNE=1 (ISSUE 13): hand this rung's configuration to the
    # autotuner — search, prune, probe — then train THROUGH the chosen
    # TunedConfig. The record carries the prediction and the per-config
    # calibration gap next to the measured number (the same surface
    # tools/autotune_smoke.py and SC007 read).
    tuned = trainer = None
    if os.environ.get("BENCH_AUTOTUNE", "0") == "1":
        t = time.perf_counter()
        from deeplearning4j_tpu.autotune import autotune as _autotune
        with tracer.span("autotune"):
            tuned = _autotune(
                net, global_batch=batch, batch=batches(1)[0],
                top_k=int(os.environ.get("BENCH_AUTOTUNE_TOPK", "2")),
                probe_steps=2)
            trainer = tuned.trainer(net)
        gap = tuned.measured_vs_predicted_gap
        _stamp(f"autotune in {time.perf_counter() - t:.1f}s: "
               f"{tuned.candidate.slug()} "
               f"(predicted {tuned.predicted_step_s:.2e}s/step, "
               f"gap {f'{gap:.1f}x' if gap is not None else 'n/a'}, "
               f"{tuned.search})")
    fit_batch = trainer.fit_batch if trainer is not None else net.fit_batch
    fit_scan = (trainer.fit_batches_scan if trainer is not None
                else net.fit_batches_scan)

    # Stage a small rotation of distinct batches in DEVICE memory once
    # (bf16 on TPU, narrowed on the device by DevicePrefetchIterator: the
    # native MXU dtype), then time the training step cycling through
    # them: MLPerf-style synthetic-input measurement of samples/sec/chip,
    # independent of the host link.
    t = time.perf_counter()
    n_stage = 2 if smoke else 4
    with tracer.span("stage_batches", n=n_stage):
        staged = list(DevicePrefetchIterator(
            ListDataSetIterator(batches(n_stage)),
            dtype="bfloat16" if on_accel and cfg["dtype"] == "bfloat16"
            else None))
        jax.block_until_ready([d.features for d in staged])
    mb = sum(d.features.nbytes + d.labels.nbytes for d in staged) / 1e6
    _stamp(f"{n_stage} batches staged on device in "
           f"{time.perf_counter() - t:.1f}s ({mb:.1f}MB)")

    t = time.perf_counter()
    with tracer.span("warmup", steps=warmup):
        for i in range(warmup):
            loss = fit_batch(staged[i % len(staged)])
            jax.block_until_ready(net.params)
            _stamp(f"warmup step {i + 1}/{warmup} done "
                   f"(+{time.perf_counter() - t:.1f}s, "
                   f"loss={float(loss):.3f})")
    compile_s = time.perf_counter() - t

    # timed region A (loop): pure async dispatch + ONE final sync — any
    # stamp or block_until_ready inside would serialize the pipeline (a
    # host round-trip per step) and bias low.
    # The per-step next()-wait is accumulated as input_stall_s (ISSUE 7:
    # every rung record carries it) — two perf_counter calls per step,
    # no device sync, so the headline stays unbiased; pre-staged batches
    # should report ~0, and a nonzero value here means the harness
    # itself went host-bound.
    _stamp(f"timing {steps} steps (loop)...")
    with tracer.span("timed_loop", steps=steps):
        feed = iter([staged[i % len(staged)] for i in range(steps)])
        input_stall = 0.0
        t0 = time.perf_counter()
        for i in range(steps):
            t_next = time.perf_counter()
            b = next(feed)
            input_stall += time.perf_counter() - t_next
            fit_batch(b)
        jax.block_until_ready(net.params)
        dt_loop = time.perf_counter() - t0
    sps_loop = batch * steps / dt_loop
    _stamp(f"loop: {steps} steps in {dt_loop:.2f}s -> "
           f"{sps_loop:.1f} samples/s")

    # timed region B (scan): the same `steps` optimization steps as ONE
    # jitted lax.scan program (netcommon.make_scan_fit) — no per-step
    # host dispatch at all. Where the loop number is dispatch-bound the
    # scan number is the chip's actual training throughput. The headline
    # value takes the better of the two.
    # Compiling the scan program roughly doubles a rung's compile cost,
    # so only the rungs where the number matters pay for it (override
    # with BENCH_SCAN_RUNGS=all / comma-list / none).
    scan_rungs = os.environ.get("BENCH_SCAN_RUNGS", "lenet,full,xl,lstm")
    scan_this = (scan_rungs == "all"
                 or rung in [r.strip() for r in scan_rungs.split(",")])
    sps = sps_loop
    dt, timing_mode = dt_loop, "loop"
    if scan_this:
        with tracer.span("timed_scan", steps=steps):
            window = [staged[i % len(staged)] for i in range(steps)]
            t0 = time.perf_counter()
            fit_scan(window)  # warmup: compiles the program
            jax.block_until_ready(net.params)
            _stamp(f"scan program compiled+warm in "
                   f"{time.perf_counter() - t0:.1f}s; timing...")
            t0 = time.perf_counter()
            fit_scan(window)
            jax.block_until_ready(net.params)
            dt_scan = time.perf_counter() - t0
        sps_scan = batch * steps / dt_scan
        _stamp(f"scan: {steps} steps in {dt_scan:.2f}s -> "
               f"{sps_scan:.1f} samples/s")
        if sps_scan > sps:
            sps, dt, timing_mode = sps_scan, dt_scan, f"scan{steps}"
    else:
        _stamp(f"scan timing skipped for rung '{rung}' "
               f"(BENCH_SCAN_RUNGS={scan_rungs})")

    # Phase breakdown (ref
    # ParameterAveragingTrainingMasterStats): a SHORT separately-timed
    # pass — per-step sync inside the headline regions would serialize
    # the dispatch pipeline and bias the number low. data_wait = host
    # batch synthesis, shard = host->device transfer, step = synced
    # device step.
    from deeplearning4j_tpu.optimize.training_stats import TrainingStats
    phase_breakdown = None
    try:
        with tracer.span("phase_breakdown"):
            stats = TrainingStats()
            n_phase = 2 if smoke else 6
            for i in range(n_phase):
                with stats.phase("data_wait"):
                    fresh = batches(1)
                with stats.phase("shard"):
                    put = list(DevicePrefetchIterator(
                        ListDataSetIterator(fresh),
                        dtype="bfloat16"
                        if on_accel and cfg["dtype"] == "bfloat16"
                        else None))
                    jax.block_until_ready([d.features for d in put])
                with stats.phase("step"):
                    fit_batch(staged[i % len(staged)])
                    jax.block_until_ready(net.params)
            phase_breakdown = {
                name: round(p["mean_s"], 4)
                for name, p in stats.export()["phases"].items()}
        _stamp(f"phase breakdown (s/step over {n_phase}): {phase_breakdown}")
    except Exception:  # noqa: BLE001 — telemetry must never cost the rung
        _stamp("phase breakdown FAILED (headline number stands):\n"
               + traceback.format_exc(limit=10))

    # XLA cost analysis of the REAL compiled train step (profiling/cost):
    # FLOPs + bytes per step and the analytic MFU — platform-independent
    # compile-time numbers (the same fields a CPU smoke run reports).
    # Runs AFTER the timed regions (it pays one AOT recompile) and can
    # never cost the rung. BENCH_COST=0 skips.
    flops_per_step = bytes_accessed = analytic = comm_bytes_hlo = None
    if os.environ.get("BENCH_COST", "1") == "1":
        t = time.perf_counter()
        try:
            with tracer.span("cost_analysis"):
                if trainer is not None:
                    # the program that actually ran is the TUNED
                    # trainer's sharded step — cost-analyze IT, not the
                    # untuned net's own single-device step (the record
                    # must name what actually ran; same invariant as
                    # the wus fields above)
                    from deeplearning4j_tpu.analysis.shardcheck import (
                        hlo_comm_bytes)
                    program = trainer.step_program(staged[0])
                    pcost = dict(program.cost)
                    cost = {"flops_per_step": pcost.get("flops"),
                            "bytes_accessed": pcost.get("bytes_accessed"),
                            "comm_bytes_hlo": hlo_comm_bytes(program),
                            "peak_flops_per_chip": peak_flops(device_kind)}
                else:
                    cost = net.cost_analysis(staged[0])
            flops_per_step = cost.get("flops_per_step")
            bytes_accessed = cost.get("bytes_accessed")
            # shardcheck's SC007 surface: the MEASURED program's actual
            # per-chip collective bytes (ring model over the compiled
            # HLO) — 0 for a single-device step; on a sharded run the
            # number `comm_bytes_per_step` (the analytic model) is
            # calibrated against
            comm_bytes_hlo = cost.get("comm_bytes_hlo")
            peak = cost.get("peak_flops_per_chip")
            if flops_per_step and peak and sps > 0:
                from deeplearning4j_tpu.profiling.cost import analytic_mfu
                analytic = round(
                    analytic_mfu(flops_per_step, batch / sps, peak), 4)
            _stamp(f"cost analysis in {time.perf_counter() - t:.1f}s: "
                   f"{(flops_per_step or 0):.3e} FLOPs/step, "
                   f"analytic_mfu={analytic}")
        except Exception:  # noqa: BLE001 — telemetry must never cost it
            _stamp("cost analysis FAILED (headline number stands):\n"
                   + traceback.format_exc(limit=10))

    # Weight-update layout cost (ISSUE 5 + 10): analytic per-update
    # comm bytes + per-chip updater-state/gradient HBM at this device
    # count, for the layout under test (BENCH_WUS=off|zero1|zero2,
    # BENCH_ACCUM=k) — the fields a real-TPU ladder compares against
    # the replicated baseline to attribute an MFU delta to the layout.
    # under BENCH_AUTOTUNE the layout under test is the TUNED one, not
    # the env knobs — the record must name what actually ran
    wus_mode = (tuned.weight_update_sharding if tuned is not None
                else os.environ.get("BENCH_WUS", "off"))
    comm_bytes = updater_hbm = gradient_hbm = None
    try:
        from deeplearning4j_tpu.profiling.cost import weight_update_cost
        wuc = weight_update_cost(
            net,
            dp=tuned.dp if tuned is not None else jax.device_count(),
            gradient_accumulation=(
                tuned.gradient_accumulation if tuned is not None
                else int(os.environ.get("BENCH_ACCUM", "1"))),
            weight_update_sharding=wus_mode)
        comm_bytes = wuc["comm_bytes_per_step"]
        updater_hbm = wuc["updater_hbm_bytes"]
        gradient_hbm = wuc["gradient_hbm_bytes"]
    except Exception:  # noqa: BLE001 — telemetry must never cost it
        _stamp("weight-update cost model FAILED (headline stands):\n"
               + traceback.format_exc(limit=10))

    # MFU estimate: analytic fwd FLOPs x3 (fwd+bwd) over chip peak.
    # ResNet-50 @224 fwd ~= 4.09e9 FLOPs/image, scaled by area; LeNet is
    # too small for a meaningful MFU.
    mfu = None
    if cfg["model"] in ("resnet50", "vgg16"):
        # analytic fwd FLOPs/image at 224^2, scaled by actual area (conv
        # towers dominate both; VGG's CIFAR fc head is negligible)
        fwd224 = 4.09e9 if cfg["model"] == "resnet50" else 15.47e9
        fwd = fwd224 * (height * width) / (224 * 224)
        # on_accel gate: the shared table has a nominal CPU entry (for
        # analytic_mfu off-chip); the hand-model `mfu` stays a real-
        # hardware-only field as before
        peak = peak_flops(device_kind) if on_accel else None
        if peak:
            mfu = round(3.0 * fwd * sps / peak, 4)

    # baselines are real-TPU numbers; comparing a CPU/smoke run against
    # them would report a meaningless ratio
    base = (_banked_baseline(cfg["metric"])
            if on_accel and not smoke else None)
    rec = {
        "metric": cfg["metric"] + ("" if on_accel and not smoke
                                   else "_SMOKE"),
        "value": round(sps, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps / base, 3) if base else 1.0,
        "mfu": mfu,
        "device_kind": device_kind,
        "platform": platform,
        "rung": rung,
        "batch": batch,
        "steps": steps,
        "step_ms": round(1000 * dt / steps, 2),
        "input_stall_s": round(input_stall, 4),
        "timing_mode": timing_mode,
        "loop_samples_per_sec": round(sps_loop, 2),
        "compile_s": round(compile_s, 1),
        "warmup_compile_s": round(compile_s, 1),  # legacy alias
        "flops_per_step": flops_per_step,
        "bytes_accessed_per_step": bytes_accessed,
        "analytic_mfu": analytic,
        "weight_update_sharding": wus_mode,
        "comm_bytes_per_step": comm_bytes,
        "comm_bytes_hlo": comm_bytes_hlo,
        "updater_hbm_bytes": updater_hbm,
        "gradient_hbm_bytes": gradient_hbm,
        # ISSUE 13: the autotune calibration surface — present on every
        # record (schema-checked in run_checks.sh); populated when
        # BENCH_AUTOTUNE=1 ran the rung at the tuner's chosen config
        "autotuned": tuned is not None,
        "predicted_step_s": (tuned.predicted_step_s
                             if tuned is not None else None),
        "measured_vs_predicted_gap": (tuned.measured_vs_predicted_gap
                                      if tuned is not None else None),
        "phase_breakdown_s_per_step": phase_breakdown,
        **(_tuned_precision_fields(tuned) if tuned is not None
           else _precision_fields(
               "bfloat16" if on_accel and cfg["dtype"] == "bfloat16"
               else "float32")),
    }
    if rung == "lm":
        # the LM rung's headline is token throughput: every sample is a
        # seq_len-token window, so tokens/sec/chip = samples/sec x T
        # (schema-checked in run_checks.sh: tokens_per_sec_per_chip,
        # seq_len, and a finite analytic_mfu must be present)
        seq_len = cfg["channels"]
        rec["seq_len"] = seq_len
        rec["tokens_per_sec_per_chip"] = round(sps * seq_len, 2)
        rec["unit"] = "tokens/sec/chip"
        rec["value"] = rec["tokens_per_sec_per_chip"]
        rec["samples_per_sec_per_chip"] = round(sps, 2)
        # the banked baseline stores the HEADLINE (tokens/sec) — the
        # ratio must compare like with like, not samples vs tokens
        rec["vs_baseline"] = (round(rec["value"] / base, 3)
                              if base else 1.0)
    return rec


def _run_input_rung(jax, smoke: bool, on_accel: bool, device_kind: str,
                    platform: str) -> dict:
    """The `input` rung (ISSUE 7): samples/sec through the sharded
    streaming input pipeline ALONE — parallel source decode, ordered
    emission, batches staged into device memory — with no training step
    consuming them. The record's
    ``input_stall_s`` here is the consumer's total wait, i.e. ~the
    whole wall (nothing hides the pipeline behind compute); the stage
    seconds (read/decode/h2d) ride along from the metrics registry."""
    cfg = _rung_config("input", smoke)
    _stamp(f"rung 'input': {cfg}")
    tracer = get_tracer()

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.pipeline import StreamingInputPipeline
    from deeplearning4j_tpu.profiling.metrics import get_registry

    batch, per_src = cfg["batch"], cfg["batches_per_source"]
    H, W, C, K = cfg["height"], cfg["width"], cfg["channels"], cfg["classes"]

    def make_source(seed):
        def synth():
            r = np.random.default_rng(seed)
            out = []
            for _ in range(per_src):
                x = r.normal(size=(batch, H, W, C)).astype(np.float32)
                y = np.eye(K, dtype=np.float32)[r.integers(0, K, batch)]
                out.append(DataSet(x, y))
            return out
        return synth

    sources = [make_source(s) for s in range(cfg["sources"])]
    reg0 = dict(get_registry().snapshot("input_"))
    with tracer.span("input_pipeline", sources=len(sources)):
        pipe = StreamingInputPipeline(
            sources, num_shards=1, shard_index=0,
            reader_workers=cfg["reader_workers"],
            decode_workers=cfg["decode_workers"])
        t0 = time.perf_counter()
        n_samples = n_batches = 0
        for ds in pipe:
            jax.block_until_ready(ds.features)  # count ARRIVED batches
            n_batches += 1
            n_samples += ds.num_examples()
        wall = time.perf_counter() - t0
    sps = n_samples / wall if wall > 0 else 0.0
    reg1 = get_registry().snapshot("input_")
    stages = {k: round(reg1.get(k, 0.0) - reg0.get(k, 0.0), 4)
              for k in ("input_read_seconds_total",
                        "input_decode_seconds_total",
                        "input_h2d_seconds_total")}
    _stamp(f"input pipeline: {n_batches} batches / {n_samples} samples "
           f"in {wall:.2f}s -> {sps:.1f} samples/s "
           f"(stall {pipe.stall_s:.2f}s, stages {stages})")
    base = (_banked_baseline(cfg["metric"])
            if on_accel and not smoke else None)
    return {
        "metric": cfg["metric"] + ("" if on_accel and not smoke
                                   else "_SMOKE"),
        "value": round(sps, 2),
        "unit": "samples/sec",
        "vs_baseline": round(sps / base, 3) if base else 1.0,
        "device_kind": device_kind,
        "platform": platform,
        "rung": "input",
        "batch": batch,
        # schema uniformity: the pipeline-alone rung compiles no step,
        # so there is no program to derive collective bytes from
        "comm_bytes_hlo": None,
        "sources": cfg["sources"],
        "batches": n_batches,
        "input_stall_s": round(pipe.stall_s, 4),
        "input_stage_seconds": stages,
        "reader_workers": cfg["reader_workers"],
        "decode_workers": cfg["decode_workers"],
        # schema uniformity (ISSUE 13): the pipeline-alone rung trains
        # no step, so there is nothing for the autotuner to choose
        "autotuned": False,
        "predicted_step_s": None,
        "measured_vs_predicted_gap": None,
        **_precision_fields(),
    }


def _run_serve_rung(jax, smoke: bool, on_accel: bool, device_kind: str,
                    platform: str) -> dict:
    """The `serve` rung (ISSUE 6): requests/sec at a latency SLO through
    the continuous-batching KerasServer. C concurrent clients fire N
    predicts (mixed row counts) at an in-process gateway; warmup
    AOT-compiles every power-of-two bucket first, so the timed storm
    runs with zero recompiles. The record carries p50/p99 latency, the
    achieved batch-size mix, and the scheduler's `compile_s` — the
    fields every future serving PR reports against."""
    import tempfile
    import threading as _threading

    cfg = _rung_config("serve", smoke)
    _stamp(f"rung 'serve': {cfg}")
    tracer = get_tracer()

    from deeplearning4j_tpu import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.keras.server import KerasClient, KerasServer
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.serializer import ModelSerializer

    F, K = cfg["features"], cfg["classes"]
    t = time.perf_counter()
    with tracer.span("serve_build_model"):
        net = MultiLayerNetwork(
            NeuralNetConfiguration.builder().updater("adam")
            .learning_rate(0.01).seed(7).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(OutputLayer(n_out=K, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(F)).build()).init()
    _stamp(f"serve model built in {time.perf_counter() - t:.1f}s")

    rng = np.random.default_rng(3)
    clients, n_requests = cfg["clients"], cfg["requests"]
    slo_s = cfg["slo_ms"] / 1000.0
    with tempfile.TemporaryDirectory() as d:
        model = os.path.join(d, "serve.zip")
        ModelSerializer.write_model(net, model)
        # mixed request sizes: every power-of-two bucket the storm can
        # hit gets a feature file (and a warmup predict below)
        row_choices = [r for r in (1, 2, 4, 8, 16)
                       if r <= cfg["max_batch"]]
        files = []
        for rows in row_choices:
            p = os.path.join(d, f"x{rows}.npy")
            np.save(p, rng.normal(size=(rows, F)).astype(np.float32))
            files.append(p)
        srv = KerasServer(max_concurrency=clients,
                          queue_depth=2 * clients,
                          max_batch=cfg["max_batch"],
                          max_wait_ms=cfg["max_wait_ms"])
        try:
            t = time.perf_counter()
            with tracer.span("serve_warmup"):
                warm = KerasClient(srv.host, srv.port)
                for p in files:  # one AOT compile per bucket
                    warm.predict(p, model=model)
                warm.close()
            _stamp(f"serve warmup ({len(files)} buckets) in "
                   f"{time.perf_counter() - t:.1f}s")

            latencies, errors = [], []
            lock = _threading.Lock()
            start = _threading.Barrier(clients + 1)
            per_client = n_requests // clients

            def client(idx: int) -> None:
                cli = KerasClient(srv.host, srv.port)
                start.wait(30.0)
                for k in range(per_client):
                    p = files[(idx + k) % len(files)]
                    t0 = time.perf_counter()
                    try:
                        cli.request(op="predict", features=p,
                                    model=model)
                        with lock:
                            latencies.append(time.perf_counter() - t0)
                    except Exception as e:  # noqa: BLE001 — recorded
                        with lock:
                            errors.append(f"{type(e).__name__}: {e}")
                cli.close()

            threads = [_threading.Thread(target=client, args=(i,),
                                         daemon=True)
                       for i in range(clients)]
            for th in threads:
                th.start()
            with tracer.span("serve_storm", clients=clients,
                             requests=per_client * clients):
                start.wait(30.0)
                t0 = time.perf_counter()
                for th in threads:
                    th.join(300.0)
                wall = time.perf_counter() - t0
            stats = srv._batcher.stats()
        finally:
            srv.drain(grace_s=5.0)

    from deeplearning4j_tpu.keras.batching import quantile
    n_done = len(latencies)
    n_slo = sum(1 for s in latencies if s <= slo_s)
    rps_slo = n_slo / wall if wall > 0 else 0.0
    ordered = sorted(latencies) or [0.0]
    p50, p99 = quantile(ordered, 0.5), quantile(ordered, 0.99)
    _stamp(f"serve storm: {n_done}/{per_client * clients} served in "
           f"{wall:.2f}s -> {n_done / wall:.1f} rps "
           f"({rps_slo:.1f} inside {cfg['slo_ms']}ms SLO), "
           f"p50={p50 * 1e3:.1f}ms p99={p99 * 1e3:.1f}ms, "
           f"mix={stats['batch_size_mix']}, {len(errors)} errors")
    base = (_banked_baseline(cfg["metric"])
            if on_accel and not smoke else None)
    return {
        "metric": cfg["metric"] + ("" if on_accel and not smoke
                                   else "_SMOKE"),
        "value": round(rps_slo, 2),
        "unit": "requests/sec",
        "vs_baseline": round(rps_slo / base, 3) if base else 1.0,
        "device_kind": device_kind,
        "platform": platform,
        "rung": "serve",
        # schema uniformity: the serve rung's AOT infer buckets are not
        # collective-analyzed (inference ships no gradient collectives)
        "comm_bytes_hlo": None,
        "clients": clients,
        "requests": n_done,
        "request_errors": errors[:5],
        "slo_ms": cfg["slo_ms"],
        # no training input feeds the serve rung; the field is carried
        # so every rung record shares the same schema (ISSUE 7)
        "input_stall_s": 0.0,
        "slo_attained": round(n_slo / max(1, n_done), 4),
        "p50_ms": round(p50 * 1e3, 2),
        "p99_ms": round(p99 * 1e3, 2),
        "max_batch": cfg["max_batch"],
        "max_wait_ms": cfg["max_wait_ms"],
        "batch_size_mix": stats["batch_size_mix"],
        "compile_s": stats["compile_s"],
        # schema uniformity (ISSUE 13): the serve rung's bucket ladder
        # is fixed by the rung config, not chosen by the autotuner
        "autotuned": False,
        "predicted_step_s": None,
        "measured_vs_predicted_gap": None,
        **_precision_fields(),
    }


def _run_fleet_rung(jax, smoke: bool, on_accel: bool, device_kind: str,
                    platform: str) -> dict:
    """The `fleet` rung (ISSUE 18): the serve rung's predict storm
    dispatched across R in-process KerasServer replicas through the
    FleetRouter (lease membership, power-of-two routing). The same
    workload is first measured against ONE KerasServer so the record
    carries the scale-out ratio (`vs_single_server`) alongside the
    aggregate requests/sec-inside-SLO headline."""
    import tempfile
    import threading as _threading

    cfg = _rung_config("fleet", smoke)
    _stamp(f"rung 'fleet': {cfg}")
    tracer = get_tracer()

    from deeplearning4j_tpu import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.keras.batching import quantile
    from deeplearning4j_tpu.keras.fleet import FleetReplica, FleetRouter
    from deeplearning4j_tpu.keras.server import KerasClient, KerasServer
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.serializer import ModelSerializer

    F, K = cfg["features"], cfg["classes"]
    t = time.perf_counter()
    with tracer.span("fleet_build_model"):
        net = MultiLayerNetwork(
            NeuralNetConfiguration.builder().updater("adam")
            .learning_rate(0.01).seed(7).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(OutputLayer(n_out=K, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(F)).build()).init()
    _stamp(f"fleet model built in {time.perf_counter() - t:.1f}s")

    rng = np.random.default_rng(3)
    clients, n_requests = cfg["clients"], cfg["requests"]
    slo_s = cfg["slo_ms"] / 1000.0
    per_client = n_requests // clients

    def storm(host, port, files, model):
        """C clients, N requests, against whatever serves (host, port).
        Returns (latencies, errors, wall_s)."""
        latencies, errors = [], []
        lock = _threading.Lock()
        start = _threading.Barrier(clients + 1)

        def client(idx: int) -> None:
            cli = KerasClient(host, port)
            start.wait(30.0)
            for k in range(per_client):
                p = files[(idx + k) % len(files)]
                t0 = time.perf_counter()
                try:
                    cli.request(op="predict", features=p, model=model)
                    with lock:
                        latencies.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 — recorded
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
            cli.close()

        threads = [_threading.Thread(target=client, args=(i,),
                                     daemon=True)
                   for i in range(clients)]
        for th in threads:
            th.start()
        start.wait(30.0)
        t0 = time.perf_counter()
        for th in threads:
            th.join(300.0)
        return latencies, errors, time.perf_counter() - t0

    def rps_slo(latencies, wall):
        return (sum(1 for s in latencies if s <= slo_s) / wall
                if wall > 0 else 0.0)

    with tempfile.TemporaryDirectory() as d:
        model = os.path.join(d, "fleet.zip")
        ModelSerializer.write_model(net, model)
        row_choices = [r for r in (1, 2, 4, 8, 16)
                       if r <= cfg["max_batch"]]
        files = []
        for rows in row_choices:
            p = os.path.join(d, f"x{rows}.npy")
            np.save(p, rng.normal(size=(rows, F)).astype(np.float32))
            files.append(p)

        # ---- single-server baseline on the identical workload
        srv = KerasServer(max_concurrency=clients,
                          queue_depth=2 * clients,
                          max_batch=cfg["max_batch"],
                          max_wait_ms=cfg["max_wait_ms"])
        try:
            with tracer.span("fleet_single_warmup"):
                warm = KerasClient(srv.host, srv.port)
                for p in files:
                    warm.predict(p, model=model)
                warm.close()
            with tracer.span("fleet_single_storm"):
                lat1, err1, wall1 = storm(srv.host, srv.port, files,
                                          model)
        finally:
            srv.drain(grace_s=5.0)
        single_rps = rps_slo(lat1, wall1)
        _stamp(f"fleet baseline: single server {len(lat1)} served in "
               f"{wall1:.2f}s -> {single_rps:.1f} rps inside SLO, "
               f"{len(err1)} errors")

        # ---- the fleet: R replicas behind the router, same storm
        fdir = os.path.join(d, "members")
        router = FleetRouter(fdir, poll_s=0.1,
                             max_concurrency=2 * clients,
                             queue_depth=4 * clients,
                             metrics_port=None)
        reps = []
        try:
            with tracer.span("fleet_form",
                             replicas=cfg["replicas"]):
                reps = [FleetReplica(fdir, r, model=model,
                                     max_concurrency=clients,
                                     queue_depth=2 * clients,
                                     max_batch=cfg["max_batch"],
                                     max_wait_ms=cfg["max_wait_ms"])
                        for r in range(cfg["replicas"])]
                if not router.wait_for_replicas(cfg["replicas"],
                                                timeout_s=60.0):
                    raise RuntimeError(
                        f"fleet never formed: {router.replicas()} of "
                        f"{cfg['replicas']} admitted")
            with tracer.span("fleet_warmup"):
                warm = KerasClient(router.host, router.port)
                for p in files:  # per-replica buckets prewarm on load
                    warm.predict(p, model=model)
                warm.close()
            with tracer.span("fleet_storm", clients=clients,
                             requests=per_client * clients):
                lat, errors, wall = storm(router.host, router.port,
                                          files, model)
            epoch = router.epoch
        finally:
            router.close()
            for rep in reps:
                rep.drain(grace_s=5.0)

    fleet_rps = rps_slo(lat, wall)
    n_done = len(lat)
    ordered = sorted(lat) or [0.0]
    p50, p99 = quantile(ordered, 0.5), quantile(ordered, 0.99)
    vs_single = fleet_rps / single_rps if single_rps > 0 else 0.0
    _stamp(f"fleet storm: {n_done}/{per_client * clients} served in "
           f"{wall:.2f}s -> {fleet_rps:.1f} rps inside SLO "
           f"({vs_single:.2f}x single server), p50={p50 * 1e3:.1f}ms "
           f"p99={p99 * 1e3:.1f}ms, {len(errors)} errors")
    base = (_banked_baseline(cfg["metric"])
            if on_accel and not smoke else None)
    return {
        "metric": cfg["metric"] + ("" if on_accel and not smoke
                                   else "_SMOKE"),
        "value": round(fleet_rps, 2),
        "unit": "requests/sec",
        "vs_baseline": round(fleet_rps / base, 3) if base else 1.0,
        "device_kind": device_kind,
        "platform": platform,
        "rung": "fleet",
        # schema uniformity: inference buckets carry no gradient
        # collectives to analyze
        "comm_bytes_hlo": None,
        "replicas": cfg["replicas"],
        "epoch": epoch,
        "clients": clients,
        "requests": n_done,
        "request_errors": errors[:5],
        "slo_ms": cfg["slo_ms"],
        # no training input feeds the fleet rung (schema, ISSUE 7)
        "input_stall_s": 0.0,
        "slo_attained": round(
            sum(1 for s in lat if s <= slo_s) / max(1, n_done), 4),
        "p50_ms": round(p50 * 1e3, 2),
        "p99_ms": round(p99 * 1e3, 2),
        "single_server_rps": round(single_rps, 2),
        "vs_single_server": round(vs_single, 3),
        "max_batch": cfg["max_batch"],
        # schema uniformity (ISSUE 13): the fleet's bucket ladder is
        # fixed by the rung config, not autotuned
        "autotuned": False,
        "predicted_step_s": None,
        "measured_vs_predicted_gap": None,
        **_precision_fields(),
    }


def _run_lm_serve_rung(jax, smoke: bool, on_accel: bool,
                       device_kind: str, platform: str) -> dict:
    """The `lm_serve` rung (ISSUE 15): token-level continuous batching
    through the gateway. C concurrent clients fire mixed-length
    generations; requests join/leave the decode batch every step. The
    headline is generated tokens/sec INSIDE the SLO; the record carries
    TTFT p50/p99 and the PR 6 whole-predict baseline (each token
    re-runs the full padded window as an ordinary batched predict) on
    the same workload — the number token-level scheduling must beat."""
    import tempfile
    import threading as _threading

    cfg = _rung_config("lm_serve", smoke)
    _stamp(f"rung 'lm_serve': {cfg}")
    tracer = get_tracer()

    from deeplearning4j_tpu.keras.server import KerasClient, KerasServer
    from deeplearning4j_tpu.models.gpt import gpt_decoder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.util.serializer import ModelSerializer

    V, L = cfg["vocab"], cfg["seq_len"]
    t = time.perf_counter()
    with tracer.span("lm_serve_build_model"):
        net = ComputationGraph(gpt_decoder(
            V, L, d_model=cfg["d_model"], n_heads=cfg["n_heads"],
            n_layers=cfg["n_layers"], seed=11)).init()
    _stamp(f"lm_serve model built in {time.perf_counter() - t:.1f}s")

    rng = np.random.default_rng(9)
    clients, n_requests = cfg["clients"], cfg["requests"]
    max_new, slo_s = cfg["max_new_tokens"], cfg["slo_ms"] / 1000.0
    per_client = max(1, n_requests // clients)
    # mixed prompt lengths spanning several pow2 prefill buckets, all
    # opening with the SAME page-aligned system prefix (ISSUE 20): the
    # paged engine dedupes that page's KV across the fleet and repeat
    # prompts hit the full-prompt registry — the record reports the
    # resulting prefix_cache_hit_rate / kv_pages_shared
    from deeplearning4j_tpu.analysis.memory import default_kv_page_len
    page_len = default_kv_page_len(L)
    sys_prefix = rng.integers(0, V, page_len).tolist()
    lengths = [max(1, L // 8), max(2, L // 4), max(3, L // 2 - 1)]

    def _prompt(target: int) -> list:
        if target <= page_len:
            return sys_prefix[:target]
        return sys_prefix + rng.integers(0, V,
                                         target - page_len).tolist()

    prompts = [_prompt(lengths[k % len(lengths)])
               for k in range(per_client * clients)]

    with tempfile.TemporaryDirectory() as d:
        model = os.path.join(d, "gpt_serve.zip")
        ModelSerializer.write_model(net, model)
        srv = KerasServer(max_concurrency=clients,
                          queue_depth=2 * clients,
                          max_batch=cfg["max_rows"])
        try:
            def storm(timed: bool):
                done, lock = [], _threading.Lock()
                start = _threading.Barrier(clients + 1)

                def client(idx: int) -> None:
                    cli = KerasClient(srv.host, srv.port)
                    start.wait(60.0)
                    for k in range(per_client):
                        p = prompts[idx * per_client + k]
                        t0 = time.perf_counter()
                        try:
                            r = cli.generate(p, max_new, model=model)
                            with lock:
                                done.append((
                                    time.perf_counter() - t0,
                                    len(r["tokens"]), r["ttft_ms"]))
                        except Exception as e:  # noqa: BLE001 — recorded
                            with lock:
                                done.append((None, 0,
                                             f"{type(e).__name__}: {e}"))
                    cli.close()

                threads = [_threading.Thread(target=client, args=(i,),
                                             daemon=True)
                           for i in range(clients)]
                for th in threads:
                    th.start()
                with tracer.span("lm_serve_storm", timed=timed):
                    start.wait(60.0)
                    t0 = time.perf_counter()
                    for th in threads:
                        th.join(600.0)
                    return done, time.perf_counter() - t0

            # warmup wave: compiles every prefill/decode bucket the
            # timed wave will hit — the timed storm runs zero-recompile
            t = time.perf_counter()
            storm(timed=False)
            compile_s = srv._gen.stats()["compile_s"]
            compiles_after_warm = srv._gen.stats()["compiles"]
            _stamp(f"lm_serve warmup wave in {time.perf_counter() - t:.1f}s "
                   f"({compiles_after_warm} bucket compiles, "
                   f"{compile_s:.1f}s compiling)")
            done, wall = storm(timed=True)
            recompiles = srv._gen.stats()["compiles"] - compiles_after_warm

            # whole-predict baseline: each token re-runs the FULL padded
            # window through the PR 6 predict scheduler (fixed [1, L, V]
            # shape — the sane way to serve an LM without a KV cache)
            base_per_client = max(1, per_client // 2) if not smoke \
                else per_client
            eye = np.eye(V, dtype=np.float32)

            def baseline_client(idx: int, files_dir: str, out: list,
                                lock) -> None:
                cli = KerasClient(srv.host, srv.port)
                for k in range(base_per_client):
                    p = list(prompts[idx * per_client + k])
                    n_gen = 0
                    for step in range(max_new):
                        x = np.zeros((1, L, V), np.float32)
                        x[0, :len(p)] = eye[np.asarray(p)]
                        fp = os.path.join(files_dir,
                                          f"b{idx}_{k}_{step}.npy")
                        np.save(fp, x)
                        try:
                            y = cli.predict(fp, model=model)
                        except Exception:  # noqa: BLE001
                            break
                        p.append(int(np.asarray(y)[0, len(p) - 1]
                                     .argmax()))
                        n_gen += 1
                        if len(p) >= L:
                            break
                    with lock:
                        out.append(n_gen)
                cli.close()

            base_out, base_lock = [], _threading.Lock()
            # warm EVERY predict bucket the baseline storm can
            # coalesce into ([r, L, V] for pow2 r up to the client
            # count) — the token-level side got an untimed warmup
            # wave, so the baseline must not pay compiles in its
            # timed window either
            warm = KerasClient(srv.host, srv.port)
            from deeplearning4j_tpu.util.math_utils import next_pow_of_2
            top_bucket = min(next_pow_of_2(clients), cfg["max_rows"])
            r = 1
            while r <= top_bucket:   # incl. the padded non-pow2 case
                xw = np.zeros((r, L, V), np.float32)
                xw[:, 0, 0] = 1.0
                fp = os.path.join(d, f"warm{r}.npy")
                np.save(fp, xw)
                warm.predict(fp, model=model)
                r <<= 1
            warm.close()
            threads = [_threading.Thread(
                target=baseline_client, args=(i, d, base_out, base_lock),
                daemon=True) for i in range(clients)]
            with tracer.span("lm_serve_whole_predict_baseline"):
                t0 = time.perf_counter()
                for th in threads:
                    th.start()
                for th in threads:
                    th.join(600.0)
                base_wall = time.perf_counter() - t0
            base_tokens = sum(base_out)
            stats = srv._gen.stats()
        finally:
            srv.drain(grace_s=5.0)

    from deeplearning4j_tpu.keras.batching import quantile
    ok = [(lat, n, ttft) for lat, n, ttft in done if lat is not None]
    errors = [ttft for lat, _, ttft in done if lat is None]
    tokens_total = sum(n for _, n, _ in ok)
    tokens_slo = sum(n for lat, n, _ in ok if lat <= slo_s)
    tps = tokens_total / wall if wall > 0 else 0.0
    tps_slo = tokens_slo / wall if wall > 0 else 0.0
    base_tps = base_tokens / base_wall if base_wall > 0 else 0.0
    ttfts = sorted(t for _, _, t in ok if isinstance(t, (int, float)))
    ttft_p50 = quantile(ttfts, 0.5) if ttfts else None
    ttft_p99 = quantile(ttfts, 0.99) if ttfts else None
    _stamp(f"lm_serve storm: {tokens_total} tokens in {wall:.2f}s -> "
           f"{tps:.1f} tok/s ({tps_slo:.1f} inside {cfg['slo_ms']}ms "
           f"SLO), ttft p50={ttft_p50}ms p99={ttft_p99}ms, "
           f"whole-predict baseline {base_tps:.1f} tok/s "
           f"(x{tps / base_tps if base_tps else float('inf'):.1f}), "
           f"{recompiles} recompiles in timed wave, "
           f"{len(errors)} errors")
    base = (_banked_baseline(cfg["metric"])
            if on_accel and not smoke else None)
    return {
        "metric": cfg["metric"] + ("" if on_accel and not smoke
                                   else "_SMOKE"),
        "value": round(tps_slo, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(tps_slo / base, 3) if base else 1.0,
        "device_kind": device_kind,
        "platform": platform,
        "rung": "lm_serve",
        "comm_bytes_hlo": None,   # inference: no gradient collectives
        "clients": clients,
        "requests": len(ok),
        "request_errors": errors[:5],
        "slo_ms": cfg["slo_ms"],
        "input_stall_s": 0.0,     # schema uniformity (ISSUE 7)
        "seq_len": L,
        "max_new_tokens": max_new,
        "tokens_per_sec": round(tps, 2),
        "tokens_per_sec_at_slo": round(tps_slo, 2),
        "ttft_p50_ms": ttft_p50,
        "ttft_p99_ms": ttft_p99,
        "whole_predict_tokens_per_sec": round(base_tps, 2),
        "vs_whole_predict": (round(tps / base_tps, 3) if base_tps
                             else None),
        "decode_recompiles_timed_wave": recompiles,
        "max_rows": cfg["max_rows"],
        "bucket_mix": stats["bucket_mix"],
        "compile_s": stats["compile_s"],
        # block-paged KV pool (ISSUE 20): how much of the workload's
        # prefill the prefix caches absorbed, and the pool census
        "prefix_cache_hit_rate": stats["prefix_cache_hit_rate"],
        "kv_pages_total": stats["kv_pages_total"],
        "kv_pages_shared": stats["kv_pages_shared"],
        # schema uniformity (ISSUE 13): the decode bucket ladder is
        # fixed by the rung config, not chosen by the autotuner
        "autotuned": False,
        "predicted_step_s": None,
        "measured_vs_predicted_gap": None,
        **_precision_fields(),
    }


def main() -> int:
    smoke = os.environ.get("BENCH_SMOKE", os.environ.get("BENCH_SMALL",
                                                         "0")) == "1"
    only = os.environ.get("BENCH_RUNGS", "")
    rungs = [r for r in (only.split(",") if only else _RUNGS) if r]
    if smoke and not only:
        # smoke shrinks every rung to the same tiny shapes, making 'xl'
        # a byte-identical duplicate of 'full' — skip the recompile
        rungs = [r for r in rungs if r != "xl"]
    _stamp(f"ladder {rungs}; importing jax + initializing backend")

    t = time.perf_counter()
    import jax

    from deeplearning4j_tpu.profiling import CompileWatcher
    from deeplearning4j_tpu.util.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    device_kind = str(devices[0].device_kind)
    _stamp(f"backend up in {time.perf_counter() - t:.1f}s: "
           f"{len(devices)}x {device_kind} ({platform}); compile cache "
           f"{cache_dir}")
    on_accel = platform != "cpu"
    if not on_accel and not smoke:
        _stamp("no accelerator: bench.py measures the chip and does not "
               "fall back to the CPU (BENCH_SMOKE=1 runs the CPU smoke)")
        return 1
    # count + time every jit trace/lower/compile of the ladder into the
    # metrics registry and mirror compiles into the trace timeline
    # (BENCH_TRACE) — a surprise recompile should self-report
    CompileWatcher().install()

    # tiny sanity op: separates "backend dead" from "model too big"
    t = time.perf_counter()
    val = float(jax.jit(lambda a: (a @ a.T).sum())(
        jax.numpy.ones((8, 128))).block_until_ready())
    _stamp(f"tiny matmul compile+run {time.perf_counter() - t:.1f}s "
           f"(= {val:.0f})")

    done, failed = [], []
    tracer = get_tracer()
    rung_wall = float(os.environ.get("BENCH_RUNG_WALL", "600"))
    # per-rung timeouts dump a full bundle through the stall watchdog,
    # subsystem heartbeats (elastic step, input wait, decode loop) are
    # monitored against the rung wall, and a catchable external kill
    # still leaves a black box
    stall_wd = _make_stall_watchdog()
    for rung in rungs:
        metric = f"{rung}_samples_per_sec_per_chip"  # fallback name
        try:
            metric = _rung_config(rung, smoke)["metric"] + (
                "" if on_accel and not smoke else "_SMOKE")
            stall_wd.watch("bench_rung", deadline_s=rung_wall)
            flight_record("bench", "rung_started", rung=rung,
                          metric=metric)
            with _RungWatchdog(metric, rung_wall, tracer,
                               stall_watchdog=stall_wd), \
                    tracer.span(f"rung:{rung}"):
                if rung == "serve":
                    rec = _run_serve_rung(jax, smoke, on_accel,
                                          device_kind, platform)
                elif rung == "lm_serve":
                    rec = _run_lm_serve_rung(jax, smoke, on_accel,
                                             device_kind, platform)
                elif rung == "fleet":
                    rec = _run_fleet_rung(jax, smoke, on_accel,
                                          device_kind, platform)
                elif rung == "input":
                    rec = _run_input_rung(jax, smoke, on_accel,
                                          device_kind, platform)
                else:
                    rec = _run_rung(jax, rung, smoke, on_accel,
                                    device_kind, platform)
            print(json.dumps(rec), flush=True)
            done.append(rung)
            if on_accel and not smoke:
                _bank_record(rec)  # durable: survives any later failure
        except Exception:  # noqa: BLE001 — recorded; the run exits non-zero
            tb = traceback.format_exc(limit=20)
            _stamp(f"rung '{rung}' FAILED:\n" + tb)
            failed.append(rung)
            # failure record with the span stack the exception unwound
            # through PLUS any spans still open (other threads / async
            # work). Concatenate, not `or`: the outer rung span always
            # populates the error stack, which must not mask open spans.
            err = tracer.error_span_stack()
            spans = err + [s for s in tracer.open_span_stack()
                           if s not in err]
            print(json.dumps(_failure_record(
                metric, tb.strip().splitlines()[-1][:300], spans,
                kind="exception")), flush=True)
    stall_wd.unwatch("bench_rung")
    stall_wd.close()
    _stamp(f"ladder done: {len(done)}/{len(rungs)} rungs ran"
           + (f"; FAILED: {failed}" if failed else ""))
    trace_path = os.environ.get("BENCH_TRACE")
    if trace_path:
        tracer.save(trace_path)
        _stamp(f"chrome trace ({tracer.event_count()} events) -> "
               f"{trace_path}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
