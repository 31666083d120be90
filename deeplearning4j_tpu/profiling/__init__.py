"""Unified profiling subsystem: span tracing, metrics, cost analysis.

The reference ships its telemetry in three disconnected places —
``ParameterAveragingTrainingMasterStats`` (phase timings),
``PerformanceListener`` (throughput lines), and the UI's system tab
(memory polls). Here they are one subsystem with three legs, designed
for the failure mode the bench rounds actually hit (hangs with zero
diagnostics) and for the question a TPU port actually asks (where did
88% of the FLOPs go):

- ``tracer`` — thread-safe span tracer exporting Chrome trace-event
  JSON (open the file in Perfetto / chrome://tracing). A process-global
  default tracer (``get_tracer()``) is emitted into by the containers
  and all three parallel trainers; its *open-span stack*
  names the phase in flight when something hangs.
- ``metrics`` — process-global registry of counters / gauges /
  fixed-bucket histograms, exposed as JSON and Prometheus text on the
  ui server (``/api/metrics.json``, ``/api/metrics``), fed by the
  ``CompileWatcher`` (jit trace/lower/compile counts + seconds,
  shape-change recompile warnings) and the ``DeviceMemoryWatermark``
  sampler (``memory_stats()`` probe).
- ``cost`` — ``lowered.compile().cost_analysis()`` over a container's
  real train step: FLOPs + bytes-accessed per optimization step and an
  **analytic MFU** against a peak-FLOPs table — computable on CPU,
  no chip required (the µ-cuDNN cost-model-before-device-time idea).
- ``flightrec`` / ``watchdog`` — the black box: a bounded ring of
  structured events the subsystems emit at their seams, and a
  heartbeat-fed stall watchdog that turns a hang (or an external kill)
  into an atomic diagnostic bundle on disk — thread stacks, open
  spans, metrics snapshot, flight tail. ``tools/postmortem.py`` reads
  one back.

- ``scopes`` — the compiled train step's own table ``{instruction:
  op_name}``, kept by the fit loop once a compiled step, and device time
  by (node, scope, phase) from a profile's number-named operations
  (``fusion.14``): the join a builder used to make by hand.

No jax import at module load: the tracer/metrics/flightrec/watchdog/scopes
legs are pure stdlib and must stay importable from the lint tooling.
"""

from deeplearning4j_tpu.profiling.tracer import (  # noqa: F401
    Tracer, get_tracer, self_times, set_tracer,
)
from deeplearning4j_tpu.profiling.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, get_registry, set_registry,
)
from deeplearning4j_tpu.profiling.flightrec import (  # noqa: F401
    FlightRecorder, get_flightrec, set_flightrec,
)
from deeplearning4j_tpu.profiling.watchdog import (  # noqa: F401
    StallWatchdog, assemble_bundle, beat, heartbeat_ages,
)
from deeplearning4j_tpu.profiling.watchers import (  # noqa: F401
    CompileWatcher, DeviceMemoryWatermark, device_memory_stats,
)
from deeplearning4j_tpu.profiling.cost import (  # noqa: F401
    PEAK_FLOPS_PER_CHIP, analytic_mfu, peak_flops, train_step_cost,
)

__all__ = [
    "Tracer", "get_tracer", "self_times", "set_tracer",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "set_registry",
    "FlightRecorder", "get_flightrec", "set_flightrec",
    "StallWatchdog", "assemble_bundle", "beat", "heartbeat_ages",
    "CompileWatcher", "DeviceMemoryWatermark", "device_memory_stats",
    "PEAK_FLOPS_PER_CHIP", "analytic_mfu", "peak_flops", "train_step_cost",
]
