#!/usr/bin/env python
"""zero2 parity smoke stage (tools/run_checks.sh): on a dp=2 CPU mesh,
train the same seeded MLP under the replicated and the ZeRO-2
weight-update layouts — with ``gradient_accumulation=4`` and a label
mask — and require (1) the fp32 loss sequences AND final params to be
BITWISE equal (zero2, like zero1, is an execution-layout change, not an
algorithm change), (2) the optax state leaves to live as (2, chunk)
views sharded over 'data' (1/2 per replica), (3) the analytic cost
model to report zero2 per-update comm <= zero1's and gradient HBM
divided by dp (``profiling/cost.py``), and (4) the bf16 mixed-precision
policy to compose: a bf16 zero2 run trains finitely while the fp32
master weights stay float32. Exit 0 = the zero2 + precision path is
wired end to end.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

DP = 2
STEPS = 4
ACCUM = 4


def main() -> int:
    import jax
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", DP)
    if len(jax.devices()) < DP:
        print(f"zero2_smoke: FAIL need {DP} cpu devices, "
              f"have {jax.devices()}")
        return 1

    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer
    from deeplearning4j_tpu.profiling.cost import (dp_comm_bytes_per_update,
                                                   dp_gradient_hbm_bytes)

    def build():
        conf = (NeuralNetConfiguration.builder()
                .seed(12345).updater("adam", learning_rate=0.05)
                .weight_init("xavier")
                .list()
                .layer(DenseLayer(n_out=17, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(6))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    ds = DataSet(x, y)
    ds.labels_mask = (rng.random(16) > 0.25).astype(np.float32)

    def run(mode, precision=None):
        net = build()
        trainer = ParallelTrainer(
            net, MeshContext.create(n_data=DP, n_model=1),
            gradient_accumulation=ACCUM, weight_update_sharding=mode,
            precision=precision)
        losses = [np.float32(np.asarray(trainer.fit_batch(ds)))
                  for _ in range(STEPS)]
        return net, losses

    net_rep, losses_rep = run("off")
    net_z, losses_z = run("zero2")

    if any(a.tobytes() != b.tobytes()
           for a, b in zip(losses_rep, losses_z)):
        print(f"zero2_smoke: FAIL loss sequences differ\n"
              f"  replicated: {losses_rep}\n  zero2:      {losses_z}")
        return 1
    pr = np.asarray(net_rep.params_flat())
    pz = np.asarray(net_z.params_flat())
    if pr.tobytes() != pz.tobytes():
        print("zero2_smoke: FAIL params diverged bitwise")
        return 1

    sharded = [l for l in jax.tree_util.tree_leaves(net_z.opt_state)
               if getattr(l, "ndim", 0) >= 1]
    bad = [l for l in sharded
           if l.shape[0] != DP
           or str(getattr(l.sharding, "spec", "")) != "PartitionSpec('data',)"]
    if not sharded or bad:
        print(f"zero2_smoke: FAIL updater state not (dp, chunk)-sharded "
              f"over 'data': {[(l.shape, str(l.sharding)) for l in bad]}")
        return 1

    p = pr.size
    z1_bytes = dp_comm_bytes_per_update(p, DP, 4, ACCUM, "zero1")
    z2_bytes = dp_comm_bytes_per_update(p, DP, 4, ACCUM, "zero2")
    if not z2_bytes <= z1_bytes:
        print(f"zero2_smoke: FAIL comm model: zero2 {z2_bytes} > "
              f"zero1 {z1_bytes} bytes/update at accum={ACCUM}")
        return 1
    g_full = dp_gradient_hbm_bytes(p, DP, 4, "zero1")
    g_z2 = dp_gradient_hbm_bytes(p, DP, 4, "zero2")
    if not (g_z2 < g_full and g_z2 == -(-g_full // DP)):
        print(f"zero2_smoke: FAIL gradient HBM model: zero2 {g_z2} vs "
              f"zero1 {g_full} (want exactly 1/{DP})")
        return 1

    # bf16 policy composes with zero2: finite losses, fp32 masters
    net_bf, losses_bf = run("zero2", precision="bf16")
    if not all(np.isfinite(losses_bf)):
        print(f"zero2_smoke: FAIL bf16 zero2 run went non-finite: "
              f"{losses_bf}")
        return 1
    master_dtypes = {str(l.dtype)
                     for l in jax.tree_util.tree_leaves(net_bf.params)}
    if master_dtypes != {"float32"}:
        print(f"zero2_smoke: FAIL bf16 master weights not fp32: "
              f"{master_dtypes}")
        return 1

    print(f"zero2_smoke: OK — {STEPS} steps bitwise loss-equal "
          f"(accum={ACCUM}, masked), updater state 1/{DP} per replica, "
          f"comm/update {z2_bytes} <= zero1 {z1_bytes} bytes, gradient "
          f"HBM {g_z2} = zero1 {g_full} / {DP}, bf16 masters fp32")
    return 0


if __name__ == "__main__":
    sys.exit(main())
