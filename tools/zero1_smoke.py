#!/usr/bin/env python
"""zero1 parity smoke stage (tools/run_checks.sh): on a dp=2 CPU mesh,
train the same seeded MLP under the replicated and the ZeRO-1
weight-update layouts — with ``gradient_accumulation=4`` and a label
mask — and require (1) the fp32 loss sequences to be BITWISE equal (the
tentpole's exact-parity guarantee: zero1 is an execution-layout change,
not an algorithm change), (2) the optax state leaves to actually live
as (2, chunk) views sharded over 'data' (1/2 per replica), and (3) the
analytic per-update comm bytes reported by ``profiling/cost.py`` to
drop vs the replicated layout at that accumulation depth. Exit 0 = the
weight-update sharding path is wired end to end.
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
        print(f"zero1_smoke: FAIL need {DP} cpu devices, "
              f"have {jax.devices()}")
        return 1

    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer
    from deeplearning4j_tpu.profiling.cost import dp_comm_bytes_per_update

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

    def run(mode):
        net = build()
        trainer = ParallelTrainer(
            net, MeshContext.create(n_data=DP, n_model=1),
            gradient_accumulation=ACCUM, weight_update_sharding=mode)
        losses = [np.float32(np.asarray(trainer.fit_batch(ds)))
                  for _ in range(STEPS)]
        return net, losses

    net_rep, losses_rep = run("off")
    net_z, losses_z = run("zero1")

    if any(a.tobytes() != b.tobytes()
           for a, b in zip(losses_rep, losses_z)):
        print(f"zero1_smoke: FAIL loss sequences differ\n"
              f"  replicated: {losses_rep}\n  zero1:      {losses_z}")
        return 1
    pr = np.asarray(net_rep.params_flat())
    pz = np.asarray(net_z.params_flat())
    if pr.tobytes() != pz.tobytes():
        print("zero1_smoke: FAIL params diverged bitwise")
        return 1

    sharded = [l for l in jax.tree_util.tree_leaves(net_z.opt_state)
               if getattr(l, "ndim", 0) >= 1]
    bad = [l for l in sharded
           if l.shape[0] != DP
           or str(getattr(l.sharding, "spec", "")) != "PartitionSpec('data',)"]
    if not sharded or bad:
        print(f"zero1_smoke: FAIL updater state not (dp, chunk)-sharded "
              f"over 'data': {[(l.shape, str(l.sharding)) for l in bad]}")
        return 1
    full = sum(l.size for l in sharded)
    local = sum(s.data.size for l in sharded
                for s in l.addressable_shards
                if s.device == jax.devices()[0])
    if local * DP != full:
        print(f"zero1_smoke: FAIL device 0 holds {local} of {full} "
              f"updater elements (want 1/{DP})")
        return 1

    p = pr.size
    rep_bytes = dp_comm_bytes_per_update(p, DP, 4, ACCUM, "off")
    z_bytes = dp_comm_bytes_per_update(p, DP, 4, ACCUM, "zero1")
    if not z_bytes < rep_bytes:
        print(f"zero1_smoke: FAIL comm model: zero1 {z_bytes} >= "
              f"replicated {rep_bytes} bytes/update at accum={ACCUM}")
        return 1

    print(f"zero1_smoke: OK — {STEPS} steps bitwise loss-equal "
          f"(accum={ACCUM}, masked), updater state 1/{DP} per replica, "
          f"comm/update {z_bytes} vs {rep_bytes} bytes "
          f"({z_bytes / rep_bytes:.2f}x)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
