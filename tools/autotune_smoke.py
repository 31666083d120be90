#!/usr/bin/env python
"""autotune smoke stage (tools/run_checks.sh): on a dp=2 CPU mesh,
search a LeNet-sized configuration space end to end and gate the
ISSUE-13 acceptance criteria:

1. the whole search — enumerate, graphcheck-prune, rank, probe —
   completes in under 60 seconds;
2. the winner's MEASURED probe step time is no slower than the naive
   default config's (MeshContext.create()'s all-devices dp, fp32,
   replicated update) — the tuner can speed you up or leave you where
   you were, never slow you down;
3. every probed config recorded a finite ``measured_vs_predicted_gap``
   and the ``autotune_*`` calibration metrics landed in the process
   registry (the same objects ``/api/metrics`` serves);
4. probe parity: training at the chosen config through the
   ``TunedConfig`` (``tuned=``) is BITWISE identical — losses and final
   params — to hand-building the same trainer, so autotuning changes
   *which* config runs but never the math of a given config.

Exit 0 = the self-driving configuration loop is wired end to end.
"""

import math
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
BATCH = 16
SEARCH_BUDGET_S = 60.0


def main() -> int:
    import jax
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", DP)
    if len(jax.devices()) < DP:
        print(f"autotune_smoke: FAIL need {DP} cpu devices, "
              f"have {jax.devices()}")
        return 1

    from deeplearning4j_tpu.autotune import autotune, default_candidate
    from deeplearning4j_tpu.models.lenet import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer
    from deeplearning4j_tpu.profiling.metrics import get_registry

    net = MultiLayerNetwork(lenet_mnist()).init()

    # ---- 1. the search completes inside the budget
    t0 = time.perf_counter()
    tuned = autotune(net, devices=DP, global_batch=BATCH, top_k=2,
                     probe_steps=2)
    elapsed = time.perf_counter() - t0
    print(tuned.summary())
    if elapsed >= SEARCH_BUDGET_S:
        print(f"autotune_smoke: FAIL search took {elapsed:.1f}s "
              f"(budget {SEARCH_BUDGET_S:.0f}s)")
        return 1

    # ---- 2. the winner measures no slower than the naive default
    default = default_candidate(DP, BATCH)
    by_cfg = {p.config: p for p in tuned.probes}
    if default.slug() not in by_cfg:
        print(f"autotune_smoke: FAIL default config {default.slug()} "
              f"was not probed (probes: {sorted(by_cfg)})")
        return 1
    default_s = by_cfg[default.slug()].measured_step_s
    if tuned.measured_step_s is None \
            or tuned.measured_step_s > default_s:
        print(f"autotune_smoke: FAIL winner measured "
              f"{tuned.measured_step_s}s/step, slower than the default "
              f"config's {default_s}s/step")
        return 1

    # ---- 3. finite calibration gaps, exported as autotune_* metrics
    bad = [p.config for p in tuned.probes
           if not math.isfinite(p.measured_vs_predicted_gap)
           or p.measured_vs_predicted_gap <= 0]
    if not tuned.probes or bad:
        print(f"autotune_smoke: FAIL probes without a finite positive "
              f"gap: {bad or '(no probes ran)'}")
        return 1
    snap = get_registry().snapshot("autotune_")
    want = ("autotune_searches_total", "autotune_probes_total",
            "autotune_best_measured_step_s",
            "autotune_measured_vs_predicted_gap")
    missing = [k for k in want if not snap.get(k)]
    if missing:
        print(f"autotune_smoke: FAIL autotune_* metrics missing/zero: "
              f"{missing} (have {sorted(snap)})")
        return 1
    gap_gauges = [k for k in snap if k.startswith("autotune_gap_")]
    if len(gap_gauges) < len(tuned.probes):
        print(f"autotune_smoke: FAIL per-config gap gauges missing: "
              f"{gap_gauges} for {len(tuned.probes)} probes")
        return 1

    # ---- 4. probe parity: tuned= vs hand-built, bitwise
    from deeplearning4j_tpu.autotune.probe import synthesize_batch
    ds = synthesize_batch(net.conf, BATCH)

    def run(build_trainer, steps=3):
        fresh = MultiLayerNetwork(lenet_mnist()).init()
        trainer = build_trainer(fresh)
        losses = [np.float32(np.asarray(trainer.fit_batch(ds)))
                  for _ in range(steps)]
        return losses, np.asarray(fresh.params_flat())

    losses_t, params_t = run(lambda n: tuned.trainer(n))
    losses_h, params_h = run(lambda n: ParallelTrainer(
        n, MeshContext.create(n_data=tuned.dp, n_model=tuned.tp,
                              n_seq=tuned.sp),
        **tuned.trainer_kwargs()))
    if any(a.tobytes() != b.tobytes() for a, b in zip(losses_t, losses_h)):
        print(f"autotune_smoke: FAIL tuned-vs-hand loss sequences "
              f"differ\n  tuned: {losses_t}\n  hand:  {losses_h}")
        return 1
    if params_t.tobytes() != params_h.tobytes():
        print("autotune_smoke: FAIL tuned-vs-hand params diverged")
        return 1

    print(f"autotune_smoke: OK — {tuned.candidate.slug()} in "
          f"{elapsed:.1f}s ({tuned.search.get('candidates')} candidates, "
          f"{tuned.search.get('pruned_illegal')} illegal, "
          f"{tuned.search.get('pruned_hbm')} over-budget, "
          f"{len(tuned.probes)} probed), winner "
          f"{tuned.measured_step_s:.4f}s/step <= default "
          f"{default_s:.4f}s/step, gaps finite, tuned==hand bitwise")
    return 0


if __name__ == "__main__":
    sys.exit(main())
