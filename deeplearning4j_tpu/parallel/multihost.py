"""Multi-host (multi-process) SPMD support.

The reference's multi-node tier is Spark parameter averaging
(ref: spark/dl4j-spark/.../paramavg/ParameterAveragingTrainingMaster.java:
358-420 — driver splits the RDD, executors fit, tree-aggregate averages).
TPU-native, the cluster program IS the single jitted step: every host runs
the same program, `jax.distributed` wires the processes into one global
device mesh, per-host input pipelines feed process-local batch shards, and
XLA's collectives ride ICI within a slice / DCN across slices.

Usage (one call per process, before any jax computation):

    from deeplearning4j_tpu.parallel import multihost
    multihost.initialize(coordinator="host0:1234",
                         num_processes=8, process_id=k)   # TPU pods: no-op
    ctx = MeshContext.create()          # global mesh over all processes
    trainer = ParallelTrainer(net, ctx) # feed process-LOCAL batches

On TPU pods jax.distributed auto-detects everything, so ``initialize()``
with no args is correct there too.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import List, Optional, Tuple

import jax
import numpy as np

logger = logging.getLogger(__name__)

_initialized = False

#: runtime liveness windows used in ELASTIC mode. The coordination
#: service's own health checking is all-or-nothing: a missed heartbeat
#: propagates a fatal error to every task (jax's default callback
#: terminates the process — the opposite of surviving a preemption).
#: Elastic mode therefore dials the runtime's windows up to "never"
#: and supplies its own liveness layer (resilience/elastic.py heartbeat
#: files + step-barrier timeouts), which can tell a slow host from a
#: dead one and react without killing the fleet.
_ELASTIC_HEARTBEAT_TIMEOUT_S = 3_600_000

#: statuses delivered to the benign missed-heartbeat callback (elastic
#: mode); resilience/elastic.py reads these as one more failure signal
_runtime_faults: List[str] = []
_runtime_faults_lock = threading.Lock()


def _on_runtime_fault(status) -> None:
    # replaces jax's default callback (which LOG(FATAL)s the process)
    with _runtime_faults_lock:
        _runtime_faults.append(str(status))
    logger.warning("distributed runtime fault (benign in elastic mode): %s",
                   status)


def runtime_fault_count() -> int:
    """Distributed-runtime faults seen by the elastic client's benign
    missed-heartbeat callback (0 outside elastic mode)."""
    with _runtime_faults_lock:
        return len(_runtime_faults)


def _ensure_cpu_collectives() -> None:
    """On the CPU platform, cross-process computations need a real
    collectives backend — without one XLA rejects every multi-process
    program ("Multiprocess computations aren't implemented on the CPU
    backend"). Select gloo before the backend initializes; harmless on
    TPU/GPU (flag only consulted by the CPU client factory)."""
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") or \
            str(jax.config.jax_platforms or "").startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # NOTE: do NOT disable XLA's thunk runtime here to dodge the
        # gloo slot race (see gloo_collectives_active): the legacy CPU
        # runtime turns a gloo all-reduce failing on a dead peer into a
        # FATAL check — the SURVIVOR aborts with its killed peer, which
        # breaks elastic recovery. The thunk runtime leaves that
        # collective hanging, which the elastic layer's abandonable
        # step thread + bounded barrier waits are built to detect.


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None,
               elastic: bool = False,
               host_service: Optional[bool] = None) -> None:
    """Bring this process into the global runtime
    (wraps jax.distributed.initialize; safe to call once per process).

    The Spark-era analog is the driver/executor bootstrap; here every
    process is a peer and process 0 hosts the coordination service.

    ``elastic=True`` builds the distributed runtime for preemption
    tolerance (the contract ``resilience/elastic.py`` needs): the
    coordination client is constructed with a benign missed-heartbeat
    callback instead of jax's default process-terminating one, with
    ``shutdown_on_destruction`` off (a survivor must not run the
    shutdown barrier against dead peers at exit), and with liveness
    windows long enough that the runtime never declares a peer dead on
    its own — host-failure detection belongs to the elastic layer's
    heartbeat files + step-barrier timeouts, which can actually react.
    Elastic mode requires explicit coordinator/num_processes/process_id
    (no TPU-pod auto-detection yet).

    ``host_service`` (elastic mode only) controls whether THIS process
    hosts the runtime's coordination service. Default (None): process 0
    hosts it, the classic wiring — sufficient when rank 0's loss is
    handled by restart. Pass ``host_service=False`` on every process
    and run the service EXTERNALLY (``serve_coordination`` /
    ``python -m deeplearning4j_tpu.parallel.multihost serve <port>
    <n>``) for full rank-0 survivability: jaxlib's coordination client
    polls the service for errors from a background thread, and losing
    the service mid-poll ABORTS the surviving client process
    (observed: ``coordination_service_agent ... Polled an error`` ->
    ``std::bad_cast`` terminate) — no Python-level knob can catch it,
    so the service must simply outlive every training host. An
    external service owned by the scheduler/driver does exactly that;
    after it, losing ANY training host — rank 0 included — is
    detected and survived by the elastic layer's own lease/heartbeat
    protocol.
    """
    global _initialized
    if _initialized:
        return
    _ensure_cpu_collectives()
    if host_service is not None and not elastic:
        raise ValueError(
            "host_service is an elastic-mode knob (external coordination "
            "service); without elastic=True jax.distributed.initialize "
            "would still make process 0 host its own service and the two "
            "would fight over the coordinator port — pass elastic=True, "
            "or drop host_service")
    if elastic:
        if coordinator is None or num_processes is None or process_id is None:
            raise ValueError(
                "elastic initialize needs explicit coordinator, "
                "num_processes and process_id (auto-detection would hand "
                "the runtime back its fatal health checking)")
        if local_device_ids is not None:
            raise ValueError(
                "local_device_ids is not supported with elastic=True "
                "(the direct client bootstrap does not thread device "
                "visibility); pin devices via CUDA_VISIBLE_DEVICES / "
                "JAX flags instead")
        _initialize_elastic(coordinator, num_processes, process_id,
                            host_service=host_service)
        _initialized = True
        return
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)
    _initialized = True


def _initialize_elastic(coordinator: str, num_processes: int,
                        process_id: int,
                        host_service: Optional[bool] = None) -> None:
    """The preemption-tolerant bootstrap: same wiring as
    jax.distributed.initialize, but the client is built directly so the
    failure-handling knobs jax does not expose can be set. Process 0
    hosts the runtime's coordination service, but that service is NOT
    the liveness authority: with the benign callback + hour-scale
    windows below, a peer losing the service-hosting process (rank 0
    included) keeps running — its stuck collectives are detected by the
    elastic layer's own heartbeat files + bounded step-barrier waits,
    and the lease-based rendezvous protocol (resilience/elastic.py)
    elects the lowest surviving rank as the new coordinator. After a
    restart the outer scheduler renumbers survivors, so whichever
    process is the NEW rank 0 hosts a fresh service — the service
    follows the lease, never the other way around."""
    from jax._src import distributed as jdist
    from jax._src import xla_bridge
    from jax._src.lib import _jax

    if xla_bridge.backends_are_initialized():
        raise RuntimeError("multihost.initialize(elastic=True) must be "
                           "called before any JAX computation")
    gs = jdist.global_state
    if gs.client is not None:
        raise RuntimeError("distributed runtime already initialized")
    if host_service is None:
        host_service = process_id == 0
    if host_service:
        port = coordinator.rsplit(":", 1)[1]
        gs.service = _jax.get_distributed_runtime_service(
            f"[::]:{port}", num_processes,
            heartbeat_timeout=_ELASTIC_HEARTBEAT_TIMEOUT_S)
    gs.client = _jax.get_distributed_runtime_client(
        coordinator, process_id, init_timeout=300,
        heartbeat_timeout=_ELASTIC_HEARTBEAT_TIMEOUT_S,
        missed_heartbeat_callback=_on_runtime_fault,
        shutdown_on_destruction=False, use_compression=True)
    gs.client.connect()
    gs.process_id = process_id
    gs.num_processes = num_processes
    gs.coordinator_address = coordinator


# ---------------------------------------------------------------------------
# effective topology — the resize seam
# ---------------------------------------------------------------------------
# After an elastic resize the surviving world differs from what
# jax.process_count() reports (the runtime's view is frozen at
# initialize time). Everything that reasons about the per-host data/
# checkpoint contract — local_batch_slice, shard_sources, the sharded
# checkpoint writer — goes through these accessors so the elastic layer
# can install the post-resize world without re-initializing jax.

_topology_override: Optional[Tuple[int, int]] = None  # (count, index)

#: the current rendezvous epoch (resilience/elastic.py's lease-based
#: group-membership counter: +1 per resize, shrink OR grow). Stamped
#: into every checkpoint cursor/manifest via CheckpointManager.topology
#: so a restore can tell which incarnation of the fleet cut it; 0
#: outside elastic runs.
_rendezvous_epoch: int = 0


def set_rendezvous_epoch(epoch: int) -> None:
    """Install the current rendezvous epoch (called by ElasticTrainer
    at bootstrap and on every lease transition — election or scale-up
    admission). Checkpoint topology records pick it up from here."""
    global _rendezvous_epoch
    _rendezvous_epoch = int(epoch)


def rendezvous_epoch() -> int:
    """The lease-based coordination layer's current epoch (0 when not
    training elastically)."""
    return _rendezvous_epoch


def set_topology_override(count: int, index: int) -> None:
    """Install the post-resize world: ``count`` surviving processes,
    this one at rank ``index``. Called by ElasticTrainer after a host
    loss; also useful for tests. ``clear_topology_override`` restores
    the runtime's own view."""
    global _topology_override
    if not 0 <= index < count:
        raise ValueError(f"rank {index} outside world of {count}")
    _topology_override = (int(count), int(index))


def clear_topology_override() -> None:
    global _topology_override
    _topology_override = None


def effective_process_count() -> int:
    """Surviving-world process count (== jax.process_count() until an
    elastic resize installs an override)."""
    if _topology_override is not None:
        return _topology_override[0]
    return jax.process_count()


def gloo_collectives_active() -> bool:
    """True when cross-process collectives run over the gloo CPU
    backend (the path ``_ensure_cpu_collectives`` selects).

    Gloo reuses one set of per-executable collective tags, so two
    async in-flight runs of the SAME compiled step — jax dispatch
    returns before the param-update all-reduce lands — can collide on
    a TCP pair and abort the whole process
    (``gloo::EnforceNotMet: op.preamble.length <= op.nbytes``).
    Callers stepping in a loop on this path must drain each step
    (``jax.block_until_ready`` on params + updater state) before
    dispatching the next; on TPU/GPU this is unnecessary and the
    helper returns False so pipelining is preserved."""
    if effective_process_count() <= 1:
        return False
    return (os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
            or str(jax.config.jax_platforms or "").startswith("cpu"))


def effective_process_index() -> int:
    """This process's rank in the surviving world."""
    if _topology_override is not None:
        return _topology_override[1]
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def local_batch_slice(global_batch: int) -> slice:
    """This host's slice of a [0, global_batch) range — the per-host input
    shard (the reference's RDD split -> executor partition mapping).
    Honors the elastic topology override: after a resize the survivors
    split the same global batch among themselves."""
    n = effective_process_count()
    if global_batch % n != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by process count {n}")
    per = global_batch // n
    k = effective_process_index()
    return slice(k * per, (k + 1) * per)


def global_array(local_data, sharding):
    """Assemble a GLOBAL jax.Array from this process's LOCAL batch shard
    (jax.make_array_from_process_local_data) — the host-boundary crossing
    the Spark tier did with broadcast/collect, done zero-copy per host."""
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_data))


def shard_sources(sources):
    """THIS host's disjoint strided shard of a dataset source list —
    shard ``effective_process_index()`` of ``effective_process_count()``
    (the per-host input contract: no two hosts ever read the same
    bytes; after an elastic resize the survivors re-partition the same
    source list). Single-process: identity."""
    from deeplearning4j_tpu.datasets.pipeline import (
        shard_sources as _shard)
    return _shard(sources, effective_process_count(),
                  effective_process_index())


def input_pipeline(sources, mesh=None, **kwargs):
    """Per-host sharded :class:`~deeplearning4j_tpu.datasets.pipeline.
    StreamingInputPipeline`: this process reads source shard
    ``process_index()`` of ``process_count()`` and — when ``mesh`` is a
    ``MeshContext`` (or left None and the pipeline is handed to
    ``ParallelTrainer.fit``, which attaches its own) — stages each batch
    as this host's slice of the GLOBAL sharded batch array
    (``make_array_from_process_local_data``). Feed the result to
    ``data_parallel_trainer(...).fit`` as-is; every host runs the same
    call on the same source list."""
    from deeplearning4j_tpu.datasets.pipeline import StreamingInputPipeline
    kwargs.setdefault("num_shards", effective_process_count())
    kwargs.setdefault("shard_index", effective_process_index())
    return StreamingInputPipeline(sources, mesh=mesh, **kwargs)


def serve_coordination(port: int, num_processes: int) -> None:
    """Run the distributed runtime's coordination service in a process
    of its OWN (no training, no devices): the external-service half of
    rank-0-survivable elastic training. Every training process then
    calls ``initialize(..., elastic=True, host_service=False)`` —
    whichever training host dies, the service (and with it the
    surviving clients' error-poll streams) stays up, so survival is
    decided entirely by the lease/heartbeat protocol. Liveness windows
    are the elastic ones (effectively never), because host-failure
    detection belongs to resilience/elastic.py. Prints ``READY`` once
    listening; blocks until terminated (the scheduler/driver owns the
    lifecycle and kills it after the job)."""
    import sys
    import time as _time

    from jax._src.lib import _jax
    service = _jax.get_distributed_runtime_service(
        f"[::]:{int(port)}", int(num_processes),
        heartbeat_timeout=_ELASTIC_HEARTBEAT_TIMEOUT_S)
    print(f"READY coordination service on port {port} for "
          f"{num_processes} processes", flush=True)
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        service.shutdown()
        print("coordination service shut down", file=sys.stderr, flush=True)


def data_parallel_trainer(net, n_model: int = 1,
                          gradient_accumulation: int = 1,
                          weight_update_sharding=None,
                          precision=None, tuned=None, **kwargs):
    """One-call multihost trainer: build the global mesh over every
    process's devices and wrap ``net`` in a ``ParallelTrainer``.

    ``weight_update_sharding="zero1"`` shards the weight update and the
    optax state 1/dp across the WHOLE data axis (all chips of all
    processes): each process's addressable shard of Adam's m+v is only
    ``local_devices/global_devices`` of the replicated footprint, and
    the sharded checkpoint format persists exactly those addressable
    shards per process — updater-state writes scale out with the pod
    instead of funneling through one host. ``"zero2"`` additionally
    keeps the GRADIENTS in that 1/dp layout from the reduce-scatter
    onward (no full-size reduced gradient per replica), so gradient
    HBM scales out with the pod too.

    ``precision="bf16"`` (or a ``PrecisionPolicy``) runs every
    process's forward/backward in bfloat16 against fp32 master weights
    — same cast seams as ``ParallelTrainer``; composes with every
    weight-update-sharding mode.

    ``tuned`` (a ``TunedConfig`` from ``deeplearning4j_tpu.autotune``):
    run at the autotuner's chosen configuration — supplies
    ``n_model`` (its tp width) plus the accumulation / sharding /
    precision knobs left at their defaults, over the GLOBAL device
    mesh. Explicit kwargs win, exactly as on ``ParallelTrainer``.

    Call ``initialize()`` first (TPU pods: with no args). Every process
    then feeds process-LOCAL batch shards to ``fit_batch`` as usual.
    """
    from deeplearning4j_tpu.parallel.mesh import MeshContext
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer
    if tuned is not None:
        if tuned.pp > 1:
            # the flat dp x tp (x sp) mesh this helper builds cannot
            # carry a pipeline schedule — running anyway would silently
            # train a DIFFERENT layout than the TunedConfig promises
            raise ValueError(
                f"TunedConfig plans pp={tuned.pp}; "
                "multihost.data_parallel_trainer builds a flat mesh — "
                "build a PipelineTrainer from tuned.candidate instead")
        if n_model == 1:
            n_model = tuned.tp
    ctx = MeshContext.create(n_model=n_model,
                             n_seq=tuned.sp if tuned is not None else 1)
    if tuned is not None and len(ctx.mesh.devices.flat) \
            != tuned.device_count:
        logger.warning(
            "TunedConfig was searched for %d device(s) but the global "
            "mesh has %d — the tuned knobs still apply, but re-running "
            "autotune() at this fleet size may choose differently",
            tuned.device_count, len(ctx.mesh.devices.flat))
    return ParallelTrainer(
        net, ctx, gradient_accumulation=gradient_accumulation,
        weight_update_sharding=weight_update_sharding,
        precision=precision, tuned=tuned, **kwargs)


if __name__ == "__main__":   # pragma: no cover — thin sidecar CLI
    # python -m deeplearning4j_tpu.parallel.multihost serve <port> <nprocs>
    import sys as _sys
    if len(_sys.argv) == 4 and _sys.argv[1] == "serve":
        serve_coordination(int(_sys.argv[2]), int(_sys.argv[3]))
    else:
        _sys.exit("usage: python -m deeplearning4j_tpu.parallel.multihost "
                  "serve <port> <num_processes>")
