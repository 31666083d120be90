"""95th percentile, over the requests sent in the window, of the time from
send to the first streamed token at the client (host clock). In a closed
loop that saturates the server it swings with how many requests end in the
window, so it stands here and not among the end-to-end metrics."""


def read(run):
    return run.measures.get("ttft_p95_ms")
