#!/usr/bin/env python
"""Input-pipeline smoke stage (tools/run_checks.sh): the same LeNet fit
twice on CPU — once through the plain sync iterator, once through the
sharded streaming input pipeline — over a deliberately SLOWED source
(50ms of sleepy decode per batch, the host-bound profile the pipeline
exists to hide). Gates, per ISSUE 7's acceptance criteria:

1. **Loss parity** — the pipeline preserves batch order, so the two
   runs' loss trajectories (and final params) must be BITWISE equal:
   the pipeline is an execution change, never an algorithm change.
2. **Stall strictly lower** — the sync run eats every decode sleep in
   ``next()`` (``input_stall_s`` ~= batches x delay); the pipeline's
   parallel decode + double-buffered device staging must overlap that
   work with the step, so its measured ``input_stall_s`` is STRICTLY
   below the sync baseline's.
3. The ``input_*`` stage counters actually accumulated on the metrics
   registry (the /api/metrics wiring).
4. **Shuffle-on resume parity (ISSUE 12)** — with the windowed shuffle
   enabled, a run broken after 3 batches and resumed through a FRESH
   pipeline restored from ``cursor_state()`` must be BITWISE identical
   (per-step losses and final params) to the unbroken shuffled run:
   the shuffle RNG + window cursor replay the exact same emission
   order, the consumed prefix exactly once skipped, the tail exactly
   once trained, nothing re-randomized.

Exit 0 = the input pipeline is wired end to end, measurably faster
than the sync feed on a slow source, and shuffled-yet-resumable.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

DP = 2
BATCHES = 6
BATCH = 8
DECODE_DELAY_S = 0.05


def main() -> int:
    import jax
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", DP)
    if len(jax.devices()) < DP:
        print(f"input_smoke: FAIL need {DP} cpu devices, "
              f"have {jax.devices()}")
        return 1

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.datasets.pipeline import StreamingInputPipeline
    from deeplearning4j_tpu.models.lenet import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer
    from deeplearning4j_tpu.profiling.metrics import get_registry

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(BATCHES):
        x = rng.normal(size=(BATCH, 28, 28, 1)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, BATCH)]
        batches.append(DataSet(x, y))

    def build():
        return MultiLayerNetwork(lenet_mnist(
            updater="nesterovs", learning_rate=0.01, seed=12345)).init()

    class SleepyIterator(ListDataSetIterator):
        """The slowed source, sync shape: every next() pays the decode
        delay serially on the consumer thread."""

        def next(self):
            time.sleep(DECODE_DELAY_S)
            return super().next()

        def async_supported(self):
            return False  # the SYNC baseline: no prefetch thread

    def sleepy_source(b):
        def synth():
            time.sleep(DECODE_DELAY_S)  # the same delay, decode-stage side
            return b
        return synth

    # -- sync baseline ------------------------------------------------------
    net_sync = build()
    tr_sync = ParallelTrainer(net_sync, MeshContext.create(n_data=DP,
                                                           n_model=1),
                              collect_training_stats=True)
    tr_sync.fit(SleepyIterator(list(batches)), use_async=False)
    stall_sync = tr_sync.training_stats.input_stall_s()

    # -- pipeline -----------------------------------------------------------
    net_pipe = build()
    tr_pipe = ParallelTrainer(net_pipe, MeshContext.create(n_data=DP,
                                                           n_model=1),
                              collect_training_stats=True)
    pipe = StreamingInputPipeline([sleepy_source(b) for b in batches],
                                  num_shards=1, shard_index=0,
                                  reader_workers=2, decode_workers=2)
    tr_pipe.fit(pipe)
    stall_pipe = tr_pipe.training_stats.input_stall_s()

    # -- gates --------------------------------------------------------------
    ls = float(np.asarray(net_sync.score_value))
    lp = float(np.asarray(net_pipe.score_value))
    if np.float32(ls).tobytes() != np.float32(lp).tobytes():
        print(f"input_smoke: FAIL loss parity broken — sync {ls!r} vs "
              f"pipeline {lp!r} (batch order must be identical)")
        return 1
    ps = np.asarray(net_sync.params_flat())
    pp = np.asarray(net_pipe.params_flat())
    if ps.tobytes() != pp.tobytes():
        print("input_smoke: FAIL params diverged bitwise between the "
              "sync and pipeline runs")
        return 1
    if not stall_pipe < stall_sync:
        print(f"input_smoke: FAIL pipeline stall {stall_pipe:.3f}s is not "
              f"strictly below the sync baseline's {stall_sync:.3f}s — "
              "the staged decode is not overlapping the step")
        return 1
    snap = get_registry().snapshot("input_")
    missing = [k for k in ("input_batches_total", "input_stall_seconds_total",
                           "input_decode_seconds_total",
                           "input_h2d_seconds_total") if not snap.get(k)]
    if missing:
        print(f"input_smoke: FAIL input_* metrics never accumulated: "
              f"{missing} (have {sorted(snap)})")
        return 1

    # -- shuffle-on resume parity (ISSUE 12) --------------------------------
    SHUF = {"shuffle_window": 4, "shuffle_seed": 17,
            "num_shards": 1, "shard_index": 0}
    BREAK_AT = 3

    def run_shuffled(resume: bool):
        net = build()
        tr = ParallelTrainer(net, MeshContext.create(n_data=DP, n_model=1))
        losses = []

        def consume(pipe, upto=None):
            while (upto is None or len(losses) < upto) and pipe.has_next():
                losses.append(float(tr.fit_batch(pipe.next())))

        pipe = StreamingInputPipeline(list(batches), **SHUF)
        if not resume:
            consume(pipe)
        else:
            consume(pipe, upto=BREAK_AT)
            state = pipe.cursor_state()
            pipe.close()                      # the "crash"
            pipe = StreamingInputPipeline(list(batches), **SHUF)
            pipe.restore_cursor(state)        # fresh pipeline, same order
            consume(pipe)
        return losses, np.asarray(net.params_flat())

    unbroken_losses, unbroken_params = run_shuffled(resume=False)
    resumed_losses, resumed_params = run_shuffled(resume=True)
    if len(unbroken_losses) != BATCHES:
        print(f"input_smoke: FAIL shuffled run consumed "
              f"{len(unbroken_losses)} batches, wanted {BATCHES}")
        return 1
    if np.float64(unbroken_losses).tobytes() \
            != np.float64(resumed_losses).tobytes():
        print(f"input_smoke: FAIL shuffled resume re-randomized the "
              f"order — unbroken {unbroken_losses} vs resumed "
              f"{resumed_losses}")
        return 1
    if unbroken_params.tobytes() != resumed_params.tobytes():
        print("input_smoke: FAIL shuffled resumed params diverged "
              "bitwise from the unbroken run")
        return 1
    print(f"input_smoke: OK — {BATCHES} LeNet steps bitwise loss-equal, "
          f"input_stall_s {stall_pipe:.3f}s (pipeline) < "
          f"{stall_sync:.3f}s (sync, {DECODE_DELAY_S * 1e3:.0f}ms sleepy "
          f"decode/batch), {stall_pipe / max(stall_sync, 1e-9):.2f}x; "
          f"shuffled resume@{BREAK_AT} bitwise == unbroken shuffled run")
    return 0


if __name__ == "__main__":
    sys.exit(main())
