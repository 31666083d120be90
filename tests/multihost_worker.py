"""Worker script for the 2-process multi-host test (run by
test_multihost.py in two subprocesses).

Each process: join the distributed runtime, build a GLOBAL mesh over both
processes' CPU devices, train a small net on process-LOCAL batch shards,
print the per-step losses. The parent asserts both processes print
identical losses (the SPMD program is deterministic and synchronized) and
that they match the single-process run on the full batch.
"""

import os
import sys

proc_id = int(sys.argv[1])
num_procs = int(sys.argv[2])
port = sys.argv[3]
#: "spmd" (default) = the synchronous-parity phases below;
#: "elastic" = ElasticTrainer chaos run (1 device/process, kill_host /
#: slow_host / kill_coordinator / rejoin_host armed via env, prints
#: TRAJ/METRICS — and RESTART when the run ends in a group re-form);
#: "elastic_rank0" = the elastic run with the fault armed on RANK 0
#: (the coordinator): the survivor must ELECT itself (ISSUE 12);
#: "elastic_rejoin" = single-process elastic run with a rejoin_host
#: fault: a replacement announces itself mid-epoch and the epoch
#: boundary must ADMIT it (scale-up restart request);
#: "elastic_ref" = single-process clean dp=1 restart from a specific
#: checkpoint of a previous elastic run (the bitwise reference)
mode = sys.argv[4] if len(sys.argv) > 4 else "spmd"
if mode == "elastic_rank0":
    os.environ.setdefault("ELASTIC_FAULT_RANK", "0")
    os.environ.setdefault("ELASTIC_FAULT_KIND", "kill_coordinator")
if mode == "elastic_rejoin":
    os.environ.setdefault("ELASTIC_FAULT_KIND", "rejoin_host")
    os.environ.setdefault("ELASTIC_EPOCHS", "2")

os.environ["JAX_PLATFORMS"] = "cpu"
_DEVS = 1 if mode.startswith("elastic") else 4
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + f" --xla_force_host_platform_device_count={_DEVS}")

import numpy as np  # noqa: E402

import jax  # noqa: E402

# Workers are CPU processes whatever the parent's environment says
# (cf. tests/conftest.py).
jax.config.update("jax_platforms", "cpu")

from deeplearning4j_tpu.parallel import multihost  # noqa: E402

import faulthandler  # noqa: E402

faulthandler.dump_traceback_later(120, exit=False)


def _elastic_factory():
    """Same seeded net on every process / every (re)build — Adam state
    so the zero1 cross-width reshard has real (m, v) leaves to move."""
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(99)
        .updater("adam").learning_rate(0.05)
        .list()
        .layer(DenseLayer(n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(6)).build()).init()


def _elastic_batches():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(0)  # same GLOBAL data on every process
    return [DataSet(rng.normal(size=(8, 6)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])
            for _ in range(6)]


def _run_elastic() -> None:
    """The preemption/coordination chaos phase: every process trains
    under ElasticTrainer; env arms a kill_host / kill_coordinator /
    slow_host / rejoin_host fault on ``ELASTIC_FAULT_RANK``. Survivors
    must finish (or request a group re-form — printed as RESTART) and
    print the exactly-once record + elastic counters."""
    import json

    from deeplearning4j_tpu.profiling.metrics import get_registry
    from deeplearning4j_tpu.resilience import faultinject
    from deeplearning4j_tpu.resilience.elastic import (
        ElasticRestartRequired, ElasticTrainer)
    from deeplearning4j_tpu.resilience.faultinject import (Fault,
                                                           FaultSchedule)

    print(f"worker {proc_id}: initializing elastic runtime", flush=True)
    # ELASTIC_EXTERNAL_SERVICE=1: the driver runs the coordination
    # service as a sidecar (rank-0-survivable mode) — no training
    # process hosts it, so killing ANY rank leaves the service (and
    # the survivors' error-poll streams) up
    multihost.initialize(
        coordinator=f"localhost:{port}",
        num_processes=num_procs, process_id=proc_id, elastic=True,
        host_service=(False if os.environ.get("ELASTIC_EXTERNAL_SERVICE")
                      else None))
    fault_step = int(os.environ.get("ELASTIC_FAULT_STEP", "0"))
    victim = int(os.environ.get("ELASTIC_FAULT_RANK", "1"))
    if fault_step and proc_id == victim:
        faultinject.set_schedule(FaultSchedule([Fault(
            kind=os.environ.get("ELASTIC_FAULT_KIND", "kill_host"),
            step=fault_step,
            duration=float(os.environ.get("ELASTIC_FAULT_S", "6.0")),
            rank=int(os.environ.get("ELASTIC_JOIN_RANK", "-1")))]))
    trainer = ElasticTrainer(
        _elastic_factory, os.environ["ELASTIC_CKPT"],
        weight_update_sharding="zero1", checkpoint_every=1, keep_last=50,
        step_timeout_s=2.0, heartbeat_timeout_s=3.0, commit_timeout_s=30.0)
    try:
        trainer.fit(_elastic_batches(),
                    epochs=int(os.environ.get("ELASTIC_EPOCHS", "1")))
    except ElasticRestartRequired as e:
        # the group must re-form (election with >1 survivor, or a
        # scale-up admission): hand the lease record to the driver
        print("RESTART " + json.dumps(
            {"survivors": e.survivors, "coordinator": e.coordinator,
             "epoch": e.epoch, "grow": e.grow}), flush=True)
    print("TRAJ " + json.dumps(trainer.trajectory), flush=True)
    print("WORLD " + json.dumps(trainer.world), flush=True)
    reg = get_registry()
    print("METRICS " + json.dumps(
        reg.snapshot("elastic_") | reg.snapshot("resilience_host")),
        flush=True)
    trainer.close()


def _run_elastic_ref() -> None:
    """Clean dp=1 restart from checkpoint ELASTIC_RESUME_STEP of a
    finished chaos run: restore (cross-width reshard), fit the
    unconsumed tail, print the losses the survivor must have matched
    bitwise."""
    from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer
    from deeplearning4j_tpu.resilience.manager import CheckpointManager

    net = _elastic_factory()
    mesh = MeshContext.create(n_data=1)
    mgr = CheckpointManager(os.environ["ELASTIC_CKPT"], sharded=True,
                            mesh_ctx=mesh)
    step = int(os.environ["ELASTIC_RESUME_STEP"])
    info = next(i for i in mgr.checkpoints() if i.step == step)
    cursor = mgr.restore(net, info, reshard=True)
    trainer = ParallelTrainer(net, mesh)
    batches = _elastic_batches()
    losses = [float(trainer.fit_batch(batches[i]))
              for i in range(cursor.data_position, len(batches))]
    print("REFLOSSES " + " ".join(f"{l:.17g}" for l in losses), flush=True)


if mode in ("elastic", "elastic_rank0", "elastic_rejoin"):
    _run_elastic()
    sys.exit(0)
if mode == "elastic_ref":
    _run_elastic_ref()
    sys.exit(0)

print(f"worker {proc_id}: initializing distributed", flush=True)
multihost.initialize(coordinator=f"localhost:{port}",
                     num_processes=num_procs, process_id=proc_id)

print(f"worker {proc_id}: devices {len(jax.devices())}", flush=True)
assert jax.process_count() == num_procs, jax.process_count()
assert len(jax.devices()) == 4 * num_procs, jax.devices()

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,  # noqa: E402
                                NeuralNetConfiguration)
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer  # noqa: E402
from deeplearning4j_tpu.parallel import MeshContext, ParallelTrainer  # noqa: E402

net = MultiLayerNetwork(
    NeuralNetConfiguration.builder().seed(99)
    .updater("sgd").learning_rate(0.1)
    .list()
    .layer(DenseLayer(n_out=16, activation="relu"))
    .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
    .set_input_type(InputType.feed_forward(10)).build()).init()

ctx = MeshContext.create(n_data=4 * num_procs, n_model=1)
trainer = ParallelTrainer(net, ctx)

GLOBAL_BATCH = 16
rng = np.random.default_rng(0)  # same data on every process
x = rng.normal(size=(GLOBAL_BATCH, 10)).astype(np.float32)
y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, GLOBAL_BATCH)]

sl = multihost.local_batch_slice(GLOBAL_BATCH)
losses = []
for _ in range(3):
    # each process feeds only ITS slice of the global batch
    losses.append(trainer.fit_batch(DataSet(x[sl], y[sl])))
    # Serialize steps on the gloo CPU-collectives path: async dispatch
    # lets step N+1's collectives launch while step N's are still in
    # flight, and consecutive runs of one executable reuse the same
    # collective tags — two same-tag ops of different byte sizes then
    # collide on one TCP pair and gloo aborts the whole process
    # (EnforceNotMet: op.preamble.length <= op.nbytes).
    jax.block_until_ready((net.params, net.opt_state))
print("LOSSES", " ".join(f"{l:.8f}" for l in losses), flush=True)

# ---- phase 2: delayed-sync DP (the DP-2/DCN tier) over the same mesh ----
from deeplearning4j_tpu.parallel import DelayedSyncTrainer  # noqa: E402

net2 = MultiLayerNetwork(
    NeuralNetConfiguration.builder().seed(99)
    .updater("sgd").learning_rate(0.1)
    .list()
    .layer(DenseLayer(n_out=16, activation="relu"))
    .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
    .set_input_type(InputType.feed_forward(10)).build()).init()
ctx2 = MeshContext.create(n_data=4 * num_procs, n_model=1)
dtrainer = DelayedSyncTrainer(net2, ctx2, sync_frequency=2)
dlosses = []
for _ in range(4):
    dlosses.append(float(dtrainer.fit_batch(DataSet(x[sl], y[sl]))))
    jax.block_until_ready((net2.params, net2.opt_state))  # see phase 1
print("DLOSSES", " ".join(f"{l:.8f}" for l in dlosses), flush=True)

# ---- phase 3: zero1 weight-update sharding over the global mesh ----------
# Same seed/net/data as phase 1, dp = every chip of every process, optax
# state sharded 1/dp globally; the loss sequence must be BITWISE the
# replicated phase-1 sequence (the exact-parity guarantee, ISSUE 5).
net3 = MultiLayerNetwork(
    NeuralNetConfiguration.builder().seed(99)
    .updater("sgd").learning_rate(0.1)
    .list()
    .layer(DenseLayer(n_out=16, activation="relu"))
    .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
    .set_input_type(InputType.feed_forward(10)).build()).init()
ztrainer = multihost.data_parallel_trainer(net3,
                                           weight_update_sharding="zero1")
zlosses = []
for _ in range(3):
    zlosses.append(ztrainer.fit_batch(DataSet(x[sl], y[sl])))
    jax.block_until_ready((net3.params, net3.opt_state))  # see phase 1
np.testing.assert_array_equal(np.float32(zlosses), np.float32(losses))
# each process addresses only its slice of the sharded updater state
opt_leaves = [l for l in jax.tree_util.tree_leaves(net3.opt_state)
              if getattr(l, "ndim", 0) >= 1]
for leaf in opt_leaves:
    local = sum(s.data.size for s in leaf.addressable_shards)
    assert local * num_procs == leaf.size, (local, leaf.size)
print("ZLOSSES", " ".join(f"{float(l):.8f}" for l in zlosses), flush=True)
