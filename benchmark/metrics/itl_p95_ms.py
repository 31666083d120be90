"""95th percentile of all gaps between successive streamed tokens that
closed in the window, stamped at the client (host clock)."""


def read(run):
    return run.measures.get("itl_p95_ms")
