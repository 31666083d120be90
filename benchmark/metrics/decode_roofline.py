"""Least time of a decode step (``reference/gpt2.decode_step_roofline``: the
weights read once and each row's K and V read once, against the step's
operations) at the window's mean rows and context, over the measured
device time of a step."""

from benchmark.metrics import decode_rows_per_step, decode_step_device_ms


def read(run):
    step_ms = decode_step_device_ms.read(run)
    rows = decode_rows_per_step.read(run)
    m = run.measures
    if not step_ms or not rows or not m.get("out_tokens"):
        return None
    context = m["context_tokens"] / m["out_tokens"]
    least = run.reference.decode_step_roofline(run.cfg, rows, context,
                                               run.peaks)
    return 100.0 * least["seconds"] * 1e3 / step_ms
