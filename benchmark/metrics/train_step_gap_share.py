"""The share of the measured (untraced) window in which the step's program
was not running on the device: 1 less the window's steps a second (host
clock) times the device seconds of one run of the step's program (from the
traced stretch, where it reads the same in every run). A reckoning, not a
reading of the device: uploads, casts and any other program on the chip
count as gap, and it holds only while the step's device time is the same
with and without the profiler. The device's idle share of a training cell
(``device_idle_share.train``) waits for a profiler that does not make the
step run in bursts (``PERF.md``, for the tracing issue)."""

from benchmark import trace
from benchmark.metrics import train_step_device_ms


def read(run):
    runs = trace.module_runs(run.trace, train_step_device_ms.PATTERN)
    m = run.measures
    if not runs or not m.get("steps"):
        return None
    busy = m["steps"] * (sum(runs) / len(runs)) / m["window_s"]
    return 100.0 * (1.0 - busy)
