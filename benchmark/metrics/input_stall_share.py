"""Share of the window that the fit loop spent waiting inside the feed's
``has_next``/``next``, by the benchmark's proxy round the program's
``DevicePrefetchIterator`` (host clock)."""


def read(run):
    m = run.measures
    if "stall_s" not in m:
        return None
    return 100.0 * m["stall_s"] / m["window_s"]
